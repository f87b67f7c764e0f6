"""Every operation of the fused step program lies under one of the step's
named scopes (``models/transformer.py:STEP_SCOPES``): a refactor that drops a
scope fails here, on the CPU, not in the benchmark's ``unscoped_device_ms`` on
the chip."""

import functools
import operator
import re

import jax
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import TransformerLM, get_preset
from deepspeed_tpu.models.transformer import STEP_SCOPES
from deepspeed_tpu.observability import steplog
from deepspeed_tpu.parallel import build_mesh

CASES = {
    "dense": ({}, 1),
    "moe": ({"num_experts": 4, "top_k": 2}, 1),
    "dense_tiled_loss_ga2": ({"loss_tiling": 4}, 2),
    "dense_unrolled_layers": ({"scan_layers": False}, 1),
    # four passes over shared weights under recomputation, sandwich norms,
    # the exit gate and the expected-exit loss: the pass loop's own
    # operations and the summing of the shared weights' gradients included
    "looped": ({"num_passes": 4, "sandwich_norm": True, "exit_loss_beta": 0.1,
                "tie_embeddings": False, "remat_policy": "full"}, 1),
    "looped_ga2": ({"num_passes": 2, "sandwich_norm": True,
                    "exit_loss_beta": 0.1}, 2),
    # window and full layers in turn under recomputation, a rope by kind, a
    # held share of grouped experts: the kind's scope nests inside attn, the
    # router, the dispatch and the products inside moe
    "pattern_share": ({"num_layers": 4, "sliding_window": 8,
                       "attn_pattern": ("window", "full"),
                       "rope_by_kind": {"full": {
                           "rope_type": "yarn", "rope_theta": 1e4,
                           "factor": 4.0,
                           "original_max_position_embeddings": 16}},
                       "num_experts": 8, "top_k": 2,
                       "moe_dispatch": "grouped", "moe_intermediate_size": 32,
                       "moe_experts_held": 4, "moe_first_expert": 4,
                       "tie_embeddings": False, "remat_policy": "full"}, 1),
    # state-space layers and an attention layer in turn under recomputation,
    # the four multipliers, no rope: the mixer's parts nest inside attn (the
    # projections, the convolution, the scan, the gated norm), the attention
    # layer keeps attn_full
    "hybrid": ({"num_layers": 4, "attn_pattern": ("ssm", "ssm", "full", "ssm"),
                "use_rope": False, "ssm_heads": 8, "ssm_head_dim": 16,
                "ssm_state": 16, "ssm_groups": 2, "ssm_chunk": 8,
                "attention_multiplier": 1 / 64, "embedding_multiplier": 12.0,
                "residual_multiplier": 0.22, "logits_scaling": 8.0,
                "remat_policy": "full"}, 1),
    # latent attention under recomputation, a dense layer then routed ones
    # with a sigmoid router, its selection bias moved by rule in the
    # optimizer's scope, a held share and shared experts: the mixer's parts
    # nest inside attn/attn_mla, the shared experts inside moe, and the
    # dense layer's FFN keeps mlp
    "mla_moe": ({"num_layers": 3, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
                 "qk_rope_head_dim": 8, "v_head_dim": 16,
                 "rope_interleave": True, "first_k_dense": 1,
                 "num_experts": 8, "top_k": 2, "moe_dispatch": "grouped",
                 "moe_intermediate_size": 32, "moe_experts_held": 4,
                 "moe_scoring": "sigmoid", "moe_routed_scale": 2.448,
                 "moe_shared_experts": 2, "moe_bias_rate": 1e-3,
                 "moe_bias_init": 0.1, "tie_embeddings": False,
                 "remat_policy": "full"}, 1),
    # gated delta-rule layers and a full layer in turn under recomputation,
    # a norm after each branch and none before, q/k norms, no rope, a held
    # share of the heads, an untied head: the delta mixer's parts nest
    # inside attn (the projections, the convolutions, the rule with its
    # triangular inverse and chunk loop, the gated norm), the attention
    # layer keeps attn_full
    "delta_hybrid": ({"num_layers": 4,
                      "attn_pattern": ("delta", "delta", "delta", "full"),
                      "use_rope": False, "norm_placement": "post",
                      "qk_norm": "width", "heads_held": 2, "delta_heads": 4,
                      "delta_key_dim": 8, "delta_value_dim": 16,
                      "delta_neg_eigval": True,
                      "tie_embeddings": False, "remat_policy": "full"}, 1),
    # the Qwen3-Next cell's scopes: gated delta-rule layers whose two key
    # heads serve four value heads and a full layer in turn under
    # recomputation, zero-centred norms, head norms on q and k, a quarter of
    # a head under the rope, the gate a channel from wq's second half, a held
    # share of softmax-routed experts beside a gated shared one: the delta
    # mixer's parts nest inside attn, the channel gate under
    # attn/attn_full/attn_gate, the shared expert and its gate under
    # moe/moe_shared
    "gdn_moe": ({"num_layers": 2, "attn_pattern": ("delta", "full"),
                 "num_kv_heads": 2, "qk_norm": "head", "rope_pct": 0.25,
                 "norm_zero_centred": True, "attn_channel_gate": True,
                 "delta_heads": 4, "delta_key_heads": 2, "delta_key_dim": 8,
                 "delta_value_dim": 16, "num_experts": 8, "top_k": 3,
                 "moe_dispatch": "grouped", "moe_intermediate_size": 32,
                 "moe_experts_held": 4, "moe_shared_experts": 1,
                 "moe_shared_gate": True, "tie_embeddings": False,
                 "remat_policy": "full"}, 1),
    # gated short convolutions and an attention layer whose q and k are
    # normed per head before the rope, under recomputation; a dense FFN then
    # routed ones (a sigmoid router, a held share) under the pattern of
    # mixer kinds, three runs: the conv mixer's parts nest inside attn (the
    # projections; the gates and the convolution), the attention layer keeps
    # attn_full, the dense layer's FFN keeps mlp
    "conv_moe": ({"num_layers": 5,
                  "attn_pattern": ("conv", "full", "conv", "conv", "conv"),
                  "qk_norm": "head", "first_k_dense": 1, "num_experts": 8,
                  "top_k": 2, "moe_dispatch": "grouped",
                  "moe_intermediate_size": 32, "moe_experts_held": 4,
                  "moe_scoring": "sigmoid", "moe_bias_rate": 1e-3,
                  "moe_bias_init": 0.1, "remat_policy": "full"}, 1),
    # KDA layers (the delta rule with a decay a key channel) and a
    # latent-attention layer in one pattern under recomputation, both on a
    # held share of the heads under a head-wise gate; a dense FFN then routed
    # ones with a shared expert, chosen under a group limit: every operation
    # of the KDA mixer under one of its four scopes inside attn, the
    # latent-attention layer keeps attn/attn_mla, the group selection lies
    # under moe_router
    "kda_moe": ({"num_layers": 4,
                 "attn_pattern": ("kda", "kda", "mla", "kda"),
                 "delta_key_dim": 16, "delta_value_dim": 16,
                 "kv_lora_rank": 32, "qk_nope_head_dim": 16,
                 "qk_rope_head_dim": 8, "v_head_dim": 16,
                 "rope_interleave": True, "mla_head_gate": True,
                 "heads_held": 2, "first_k_dense": 1, "num_experts": 8,
                 "top_k": 2, "moe_dispatch": "grouped",
                 "moe_intermediate_size": 32, "moe_experts_held": 4,
                 "moe_scoring": "sigmoid", "moe_routed_scale": 2.5,
                 "moe_shared_experts": 1, "moe_n_group": 4,
                 "moe_topk_group": 2, "moe_bias_rate": 1e-3,
                 "moe_bias_init": 0.1, "tie_embeddings": False,
                 "remat_policy": "full"}, 1),
    # attention over the keys a learned indexer picks (two query tiles, the
    # second past the topk-th key) under recomputation, head norms on q and
    # k, a rope over three position axes, a held share of grouped experts:
    # the indexer's projections and scores, the threshold and the set, the
    # attention over the set and the indexer's loss with its gradient each
    # under their scope inside attn/attn_dsa, forward and backward
    "dsa_moe": ({"attn_pattern": ("dsa",), "num_kv_heads": 2,
                 "qk_norm": "head", "mrope_section": (2, 2, 4),
                 "dsa_index_heads": 4, "dsa_index_head_dim": 8,
                 "dsa_topk": 8, "dsa_q_chunk": 16, "dsa_kv_chunk": 16,
                 "num_experts": 8, "top_k": 2, "moe_dispatch": "grouped",
                 "moe_intermediate_size": 32, "moe_experts_held": 4,
                 "tie_embeddings": False, "remat_policy": "full"}, 1),
    # window and full layers that differ in their query heads (a stack of
    # leaves a kind), half a head turned under yarn on the full ones, every
    # head under the sigmoid gate, on a held share of the heads, under
    # recomputation; a dense FFN then routed ones with a shared expert: the
    # gate's operations lie under attn_gate inside the kind's scope, forward
    # and backward
    "heads_moe": ({"num_layers": 5, "num_heads": 4, "num_kv_heads": 2,
                   "head_dim_override": 16, "sliding_window": 8,
                   "attn_pattern": ("full", "window", "window", "window",
                                    "full"),
                   "heads_by_kind": {"window": 6}, "heads_held": 2,
                   "mla_head_gate": True,
                   "rope_by_kind": {
                       "full": {"rope_theta": 500000.0, "rope_type": "yarn",
                                "factor": 8.0,
                                "original_max_position_embeddings": 16,
                                "attention_factor": 1.4852,
                                "partial_rotary_factor": 0.5},
                       "window": {"rope_theta": 10000.0,
                                  "rope_type": "default",
                                  "partial_rotary_factor": 1}},
                   "first_k_dense": 1, "num_experts": 8, "top_k": 2,
                   "moe_dispatch": "grouped", "moe_intermediate_size": 32,
                   "moe_experts_held": 4, "moe_scoring": "sigmoid",
                   "moe_routed_scale": 2.5, "moe_shared_experts": 1,
                   "moe_bias_rate": 1e-3, "moe_bias_init": 0.1,
                   "tie_embeddings": False, "remat_policy": "full"}, 1),
    # block diffusion: the [noised ; clean] row through the flash kernels
    # under the rounded diagonal (interpreted here), the noised half's call
    # with its own block as a second key source, head norms on q and k, the
    # repeated positions through the rope, a held share of grouped experts
    # over both halves, the weighted loss over the noised half, under
    # recomputation: the kernels' operations lie under bd_cross, forward,
    # recomputed and backward
    "bd_moe": ({"diffusion_block": 4, "mask_token_id": 255,
                "num_kv_heads": 2, "qk_norm": "head",
                "num_experts": 8, "top_k": 2, "moe_dispatch": "grouped",
                "moe_intermediate_size": 32, "moe_experts_held": 4,
                "tie_embeddings": False, "remat_policy": "full",
                "attention_impl": "flash_pallas"}, 1),
}
NESTED = {"attn_window": "attn", "attn_full": "attn", "moe_router": "moe",
          "moe_dispatch": "moe", "moe_experts": "moe"}
NESTED_HYBRID = {"attn_full": "attn", "ssm_proj": "attn", "ssm_conv": "attn",
                 "ssm_scan": "attn", "ssm_gate": "attn"}
NESTED_DELTA = {"attn_full": "attn", "delta_proj": "attn",
                "delta_conv": "attn", "delta_scan": "attn",
                "delta_gate": "attn"}
# the cases that run a mixer's kernels interpreted keep one layer of each
# kind (a period of two): what is asked of them is where a kernel's
# operations lie, and a second and third layer of the kind lie where the
# first does
ONE_OF_EACH = {"hybrid": {"num_layers": 2, "attn_pattern": ("ssm", "full")},
               "delta_hybrid": {"num_layers": 2,
                                "attn_pattern": ("delta", "full")},
               "conv_moe": {"num_layers": 2,
                            "attn_pattern": ("conv", "full")}}
NESTED_CONV = {"attn_full": "attn", "sconv_proj": "attn",
               "sconv_conv": "attn", "moe_router": "moe",
               "moe_dispatch": "moe", "moe_experts": "moe"}
NESTED_MLA = {"attn_mla": "attn", "mla_proj": "attn_mla",
              "mla_rope": "attn_mla", "moe_router": "moe",
              "moe_dispatch": "moe", "moe_experts": "moe",
              "moe_shared": "moe"}
NESTED_KDA = {**NESTED_MLA, "kda_proj": "attn", "kda_conv": "attn",
              "kda_scan": "attn", "kda_gate": "attn"}
NESTED_HEADS = {"attn_window": "attn", "attn_full": "attn",
                "moe_router": "moe", "moe_dispatch": "moe",
                "moe_experts": "moe", "moe_shared": "moe"}
NESTED_DSA = {"attn_dsa": "attn", "dsa_indexer": "attn_dsa",
              "dsa_select": "attn_dsa", "dsa_attend": "attn_dsa",
              "dsa_loss": "attn_dsa", "moe_router": "moe",
              "moe_dispatch": "moe", "moe_experts": "moe"}
NESTED_GDN = {**NESTED_DELTA, "attn_gate": "attn_full", "moe_router": "moe",
              "moe_dispatch": "moe", "moe_experts": "moe",
              "moe_shared": "moe"}
NESTED_BD = {"attn_full": "attn", "bd_cross": "attn_full",
             "moe_router": "moe",
             "moe_dispatch": "moe", "moe_experts": "moe"}


def _batch(overrides, rows):
    ids = np.zeros((rows, 32), np.int32)
    if "diffusion_block" not in overrides:
        return {"input_ids": ids}
    from deepspeed_tpu.runtime.data_pipeline import noise_batch

    return noise_batch({"input_ids": ids}, seed=0, t_min=0.5,
                       block=overrides["diffusion_block"],
                       mask_token_id=overrides["mask_token_id"])


def _op_names(overrides, ga):
    eng, *_ = ds.initialize(
        model=TransformerLM(get_preset("tiny", **overrides)),
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": ga,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True}, "steps_per_print": 10 ** 9,
                "zero_optimization": {"stage": 0}},
        mesh=build_mesh(devices=jax.devices()[:1]))
    eng.fused_train_step(_batch(overrides, 2 * ga))
    row = steplog.programs()[-1]
    assert row.name == "ds_train_step" and row.key == str(ga)
    text = row.hlo_text()
    assert "jit_ds_train_step" in text
    # the program's own operations carry "jit(ds_train_step)/..."; the
    # bodies of reducers and scatters carry the bare primitive's name
    return [n for n in re.findall(r'op_name="([^"]*)"', text)
            if n.startswith("jit(")]


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_operation_carries_a_step_scope(case):
    names = _op_names(*CASES[case])
    assert len(names) > 200
    loose = sorted({n for n in names
                    if not set(re.split(r"[/()]", n)) & set(STEP_SCOPES)})
    assert loose == []
    found = {p for n in names for p in re.split(r"[/()]", n)} \
        & set(STEP_SCOPES)
    ffn = "moe" if case in ("moe", "pattern_share", "mla_moe",
                            "conv_moe", "kda_moe", "dsa_moe",
                            "heads_moe", "bd_moe", "gdn_moe") else "mlp"
    want = {"embed", "layers", "attn", ffn, "final_norm", "loss", "optimizer"}
    if case in ("mla_moe", "conv_moe", "kda_moe", "heads_moe"):
        want.add("mlp")         # the dense layer's
    nested = {"pattern_share": NESTED, "hybrid": NESTED_HYBRID,
              "mla_moe": NESTED_MLA, "delta_hybrid": NESTED_DELTA,
              "conv_moe": NESTED_CONV, "kda_moe": NESTED_KDA,
              "dsa_moe": NESTED_DSA, "heads_moe": NESTED_HEADS,
              "bd_moe": NESTED_BD, "gdn_moe": NESTED_GDN}.get(case)
    if nested:
        want |= set(nested)
        for inner, outer in nested.items():
            ops = [n for n in names if inner in re.split(r"[/()]", n)]
            if case == "dsa_moe":
                # (a constant the compiler lifts out of the recomputed
                # region's loops keeps its innermost scope alone)
                ops = [n for n in ops if n.rsplit("/", 1)[-1] not in (
                    "iota", "broadcast_in_dim")]
            assert ops and all(outer in re.split(r"[/()]", n) for n in ops)
    if CASES[case][0].get("loss_tiling", 0) <= 1:
        want.add("lm_head")
    if CASES[case][1] > 1:
        want.add("grad_accum")
    else:
        # with one micro-batch the compiler folds 0 + g; the lift of the
        # carried copy's bf16 cotangents to float32 is left under the scope
        # where it stays an instruction of its own
        found.discard("grad_accum")
    if case == "kda_moe":
        # every operation of a KDA mixer lies under one of its four scopes:
        # what lies under attn and outside them (and outside attn_mla) is
        # the block's own norm before the mixer and the mean square of its
        # output, elementwise: no product, no loop, no exponential
        own = {re.sub(r".*/attn/", "", n).split("/")[0] for n in names
               if "/attn/" in n} - set(NESTED_KDA)
        assert own <= {"add", "add_any", "broadcast_in_dim", "mul", "div",
                       "convert_element_type", "reduce_sum", "rsqrt",
                       "square"}, own
        # the groups are chosen inside the router: two sorts more than the
        # plain top k has
        assert any("moe_router" in n and "top_k" in n for n in names)
    if case == "heads_moe":
        # the gate lies under either kind's scope, forward, recomputed and
        # backward, and holds the sigmoid and the product with wg alone
        want.add("attn_gate")
        gate = [n for n in names if "attn_gate" in re.split(r"[/()]", n)]
        for kind in ("attn_window", "attn_full"):
            mine = [n for n in gate if f"/attn/{kind}/" in n]
            assert any("transpose(" in n for n in mine), kind
            assert any("rematted_computation" in n for n in mine), kind
        assert all("/attn/attn_window/" in n or "/attn/attn_full/" in n
                   for n in gate)
        assert {n.rsplit("/", 1)[-1] for n in gate} >= {"exp", "dot_general",
                                                        "mul"}
        row = steplog.programs()[-1]
        assert row.attn_heads_per_step == 2 * 2 + 3 * 3
        assert row.heads_held == {"full": (2, 4), "window": (3, 6)}
    if case == "gdn_moe":
        # the gate a channel holds the sigmoid and the product alone (its
        # columns come out of wq's product, under attn_full), forward,
        # recomputed and backward; the shared expert's gate lies with it
        gate = [n for n in names if "attn_gate" in re.split(r"[/()]", n)]
        assert any("transpose(" in n for n in gate)
        assert any("rematted_computation" in n for n in gate)
        assert {n.rsplit("/", 1)[-1] for n in gate} >= {"exp", "mul"}
        assert not any(n.endswith("dot_general") for n in gate)
        shared = {n.rsplit("/", 1)[-1] for n in names
                  if "moe_shared" in re.split(r"[/()]", n)}
        assert shared >= {"exp", "dot_general"}
        row = steplog.programs()[-1]
        assert row.delta_heads == (2, 4)
        assert row.delta_rule_lowering == {0: "xla"}
        assert row.delta_qk_rows == {"xla": 2 * 2 * 32 * 4}
    if case == "dsa_moe":
        # every exponential, logarithm and branch of the mixer lies under
        # one of its four scopes: what lies under attn_dsa and outside them
        # is the main projections, the head norms, the rope and the loops
        # over rows and query tiles themselves
        own = {n.rsplit("/", 1)[-1] for n in names
               if "attn_dsa" in re.split(r"[/()]", n)
               and not set(re.split(r"[/()]", n)) & {
                   "dsa_indexer", "dsa_select", "dsa_attend", "dsa_loss"}}
        assert not own & {"exp", "sort", "top_k", "cond", "log"}, own
        assert steplog.programs()[-1].dsa_lowerings == {"jnp": 2}
    if case == "bd_moe":
        # the two calls run forward, again under recomputation, and
        # backward; every product over (query, key) pairs and every
        # exponential of the mixer lies under their scope (what lies under
        # attn_full and outside it is the projections, the head norms, the
        # rope over the repeated positions, the joining of the halves and
        # the early positions' mean square)
        mine = [n for n in names if "bd_cross" in re.split(r"[/()]", n)]
        assert any("transpose(" in n for n in mine)
        assert any("rematted_computation" in n for n in mine)
        own = {n.rsplit("/", 1)[-1] for n in names
               if "attn_full" in re.split(r"[/()]", n)
               and "bd_cross" not in re.split(r"[/()]", n)}
        assert not own & {"exp", "exp2", "log", "while"}, own
        row = steplog.programs()[-1]
        assert (row.diffusion_block, row.positions_per_token,
                row.head_rows) == (4, 2, 32)
        assert set(row.flash_bwd_tiles) == {"diag4", "diag4_strict",
                                            "diag4_own"}
        # the noised call's second key source, and the clean call without
        assert set(row.flash_own_keys_lowerings) == {"operand", "none"}
    if case.startswith("looped"):
        want.add("exit_gate")
        # the gate's operations nest inside the loss: loss/exit_gate/...
        gate = [n for n in names if "exit_gate" in re.split(r"[/()]", n)]
        assert gate and all("loss" in re.split(r"[/()]", n) for n in gate)
    assert found == want


def test_the_scan_kernels_keep_the_scan_scope(monkeypatch):
    """The hybrid case at shapes the state-space scan's Pallas kernels take
    (interpreted here): what the forward kernel, its recomputation and the
    backward kernel lower to lies under ``attn/ssm_scan`` with the jitted
    kernel's name in the path, which is how the benchmark's scope readers
    find the Mosaic calls on the chip; nothing is left without a scope."""
    import functools

    from deepspeed_tpu.models import mamba

    monkeypatch.setattr(mamba, "ssd_scan", functools.partial(
        mamba.ssd_scan, interpret=True))
    over = dict(CASES["hybrid"][0], **ONE_OF_EACH["hybrid"], ssm_heads=2,
                ssm_head_dim=64, ssm_state=128, ssm_groups=1, ssm_chunk=128)
    names = _op_names(over, 1)
    # the one scan and its backward
    assert steplog.programs()[-1].ssm_scan_lowerings == {"pallas": 2}
    parts = [set(re.split(r"[/()]", n)) for n in names]
    assert all(p & set(STEP_SCOPES) for p in parts)
    fwd = [n for n in names if "/ssm_scan/jit(ssd_fwd)/" in n]
    bwd = [n for n in names if "/ssm_scan/jit(ssd_bwd)/" in n]
    assert fwd and bwd and all("/attn/" in n for n in fwd + bwd)
    assert all("transpose(" in n for n in bwd)
    assert any("rematted_computation" in n for n in fwd)
    assert any("transpose(" not in n for n in fwd)


@pytest.mark.parametrize("policy", ("dots_saveable", "attn_saveable", "full"))
def test_the_rule_kernels_keep_the_scan_scope(monkeypatch, policy):
    """The delta case at widths the rule's Pallas kernels take (interpreted
    here), under the policies of the two benchmark cells that run the rule
    (``dots_saveable``: Olmo-Hybrid; ``attn_saveable``: Qwen3-Next): the
    forward and the backward kernel lower under ``attn/delta_scan`` with the
    jitted kernel's name in the path, which is how the benchmark's scope
    readers find the Mosaic calls on the chip; both policies keep what the
    forward rule named (its output and the chunks' states), so the recomputed
    region holds no second forward and the row's ``recomputed_kernels`` does
    not list the scope; nothing is left without a scope. ``full`` runs it
    again, and the row says so."""
    import functools

    from deepspeed_tpu.models import gated_delta

    monkeypatch.setattr(gated_delta, "chunked_delta_rule", functools.partial(
        gated_delta.chunked_delta_rule, interpret=True))
    over = dict(CASES["delta_hybrid"][0], **ONE_OF_EACH["delta_hybrid"],
                delta_key_dim=32, delta_value_dim=64, remat_policy=policy)
    names = _op_names(over, 1)
    row = steplog.programs()[-1]
    # the one rule and its backward
    assert row.delta_scan_lowerings == {"pallas": 2}
    parts = [set(re.split(r"[/()]", n)) for n in names]
    assert all(p & set(STEP_SCOPES) for p in parts)
    fwd = [n for n in names if "/delta_scan/jit(rule_fwd)/" in n]
    bwd = [n for n in names if "/delta_scan/jit(rule_bwd)/" in n]
    assert fwd and bwd and all("/attn/" in n for n in fwd + bwd)
    assert all("transpose(" in n for n in bwd)
    first = [n for n in fwd if "rematted_computation" not in n]
    assert first and not any("transpose(" in n for n in first)
    again = policy == "full"
    assert (len(first) < len(fwd)) == again
    assert ("delta_scan" in row.recomputed_kernels()) == again


# (case, the mixer's module, the convolution's scope, the scan's, what the
# trace counts: a convolution and its backward for the one state-space layer
# kept of the hybrid case, three convolutions and the backward of each for
# the one delta layer kept of the delta case, the convolution without an
# activation and its backward for the one conv layer kept of the conv case,
# which has no scan: its kernels do not lie among the projections), and the
# name the mixer calls the op by
CONV_KERNELS = {
    "hybrid": ("mamba", "ssm_conv", "ssm_scan", 2, "full",
               "causal_conv_silu"),
    "delta_hybrid": ("gated_delta", "delta_conv", "delta_scan", 6,
                     "dots_saveable", "causal_conv_silu"),
    "conv_moe": ("short_conv", "sconv_conv", "sconv_proj", 2, "full",
                 "causal_conv_act"),
}


@pytest.mark.parametrize("case", sorted(CONV_KERNELS))
def test_the_conv_kernels_keep_the_conv_scope(monkeypatch, case):
    """The mixers' convolution as its Pallas kernels (interpreted here), each
    case under its benchmark cell's policy: the forward kernel, its
    recomputation (``dots_saveable`` keeps products, and a kernel is none)
    and the backward kernel lower under ``attn/<mixer>_conv`` with the jitted
    kernel's name in the path, which is how the benchmark's scope readers
    find the Mosaic calls on the chip, and never under the scan's scope,
    where the delta cell's reader would take a recomputed kernel for a second
    forward of the rule; no padded copy is left under the scope; nothing is
    left without a scope; the row says which lowering the program took, on
    the chip's kind of program and on a CPU's."""
    import functools
    import importlib

    module, conv, scan, counted, policy, op = CONV_KERNELS[case]
    over = dict(CASES[case][0], **ONE_OF_EACH[case], remat_policy=policy)
    if case == "hybrid":        # x, B and C of whole lane tiles
        over.update(ssm_heads=2, ssm_head_dim=64, ssm_state=128, ssm_groups=1)
    _op_names(over, 1)
    assert steplog.programs()[-1].conv_lowerings == {"xla": counted // 2}
    mixer = importlib.import_module(f"deepspeed_tpu.models.{module}")
    monkeypatch.setattr(mixer, op, functools.partial(
        getattr(mixer, op), interpret=True))
    names = _op_names(over, 1)
    assert steplog.programs()[-1].conv_lowerings == {"pallas": counted}
    parts = [set(re.split(r"[/()]", n)) for n in names]
    assert all(p & set(STEP_SCOPES) for p in parts)
    fwd = [n for n in names if f"/{conv}/jit(conv_fwd)/" in n]
    bwd = [n for n in names if f"/{conv}/jit(conv_bwd)/" in n]
    assert fwd and bwd and all("/attn/" in n for n in fwd + bwd)
    assert all("transpose(" in n for n in bwd)
    assert any("rematted_computation" in n for n in fwd)
    assert any("transpose(" not in n for n in fwd)
    assert not any(scan in n for n in fwd + bwd)
    assert not any("conv_fwd" in n or "conv_bwd" in n for n in names
                   if f"/{conv}/" not in n)
    under = [n for n in names if conv in re.split(r"[/()]", n)]
    assert not any(n.endswith("/pad") for n in under)


def test_the_flash_kernels_in_parts_stay_under_attn_mla(monkeypatch):
    """The latent-attention case through the flash kernels (interpreted
    here), q and k in the parts the products write and the rope on q as its
    own kernel: every flash lowering, forward, recomputed forward and
    backward, takes the rope columns as operands and lies directly under
    ``attn/attn_mla``, outside ``mla_rope`` and ``mla_proj``, which is where
    the compiled call takes the name the benchmark's rooflines look for; the
    rope kernel lies under ``mla_rope`` with its own name in the path, where
    no roofline pattern finds it; nothing is left without a scope. A dense
    model's flash lowerings take no such operand."""
    import deepspeed_tpu.ops as ops

    monkeypatch.setattr(ops, "mosaic_runs_whole", lambda: True)
    names = _op_names(dict(CASES["mla_moe"][0],
                           attention_impl="flash_pallas"), 1)
    row = steplog.programs()[-1]
    # a dense run and a routed run, each traced once: the primal, the forward
    # rule and the backward rule of the kernels' custom_vjp
    assert row.flash_rope_operand_lowerings == {"operand": 6}
    assert row.flash_bwd_lowerings == {"fused": 2}
    parts = [re.split(r"[/()]", n) for n in names]
    assert all(set(p) & set(STEP_SCOPES) for p in parts)
    # interpreted, a kernel is a loop over its grid
    flash = [n for n in names if re.search(r"/attn_mla/while\b", n)]
    rope = [n for n in names if "/mla_rope/jit(_rotate)/" in n]
    for ops_, inside in ((flash, "attn_mla"), (rope, "mla_rope")):
        assert any("transpose(" in n for n in ops_)           # the backward
        assert any("rematted_computation" in n for n in ops_)
        assert any("transpose(" not in n for n in ops_)
        assert all("/attn/attn_mla/" in n for n in ops_)
    assert not any("mla_rope" in n or "mla_proj" in n for n in flash)
    # what the block does itself to q, k and v (products, the rope, and,
    # for an attention that takes no parts, putting them together) carries
    # mla_proj or mla_rope: bare under attn_mla are the first norm, the
    # kernels and what feeds them (layouts, the backward's row sums)
    block = [n for n in names if "attn_mla" in re.split(r"[/()]", n)]
    bare = {re.sub(r".*/attn_mla/", "", n).split("/")[0] for n in block
            if "mla_rope" not in n and "mla_proj" not in n}
    assert "while" in bare
    assert not bare & {"concatenate", "pad", "dot_general", "cos", "sin",
                       "gather", "dynamic_slice"}, bare

    _op_names(dict(CASES["dense"][0], attention_impl="flash_pallas"), 1)
    assert steplog.programs()[-1].flash_rope_operand_lowerings == {"none": 3}


@pytest.mark.parametrize("policy,again", [
    ("dots_saveable", {"sconv_conv": 1}),
    ("full", {"sconv_conv": 1, "attn_full": 1})])
def test_the_row_counts_the_kernels_run_again(monkeypatch, policy, again):
    """A conv layer and an attention layer through their kernels
    (interpreted here: a kernel is a loop over its grid) under a dense FFN:
    the step-program row counts, by innermost scope, the kernel calls in the
    backward's recomputed region. ``dots_saveable`` keeps what the flash
    forward rule named, so only the convolution's forward (a kernel is no
    dot, and it names nothing) runs again; under ``full`` both do."""
    import functools

    from deepspeed_tpu.models import short_conv

    monkeypatch.setattr(short_conv, "causal_conv_act", functools.partial(
        short_conv.causal_conv_act, interpret=True))
    _op_names({"num_layers": 2, "attn_pattern": ("conv", "full"),
               "qk_norm": "head", "attention_impl": "flash_pallas",
               "remat_policy": policy}, 1)
    row = steplog.programs()[-1]
    assert row.conv_lowerings == {"pallas": 2}
    assert row.recomputed_kernels() == again
    calls = steplog.kernel_calls(row.hlo_text(), "attn_full")
    assert len(calls) == 2 + ("attn_full" in again)
    assert sum("transpose(" in n for n in calls) == 1 + ("attn_full" in again)


def test_backward_operations_keep_their_scope():
    names = _op_names(*CASES["dense"])
    back = [n for n in names if "transpose(" in n]
    assert any("/attn/" in n for n in back) and any("/mlp/" in n for n in back)


def _casts_to(jaxpr, dtype, stack=""):
    """``(operand shape, name stack)`` of every float32 -> ``dtype`` convert
    of ``jaxpr`` and of the jaxprs its equations call (a scan's body, a
    recomputed block), the stack from the outermost scope down."""
    import jax.numpy as jnp

    for eqn in jaxpr.eqns:
        here = f"{stack}/{eqn.source_info.name_stack}"
        if (eqn.primitive.name == "convert_element_type"
                and eqn.invars[0].aval.dtype == jnp.float32
                and eqn.params["new_dtype"] == dtype):
            yield tuple(eqn.invars[0].aval.shape), here
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _casts_to(sub, dtype, here)


#: case -> (model overrides, the master leaves that stay out of the copy and
#: are still cast in the step)
CARRIED = {
    "untied": ({"tie_embeddings": False}, ()),
    # gathered from and projected with: cast at both sites, as before
    "tied_table": ({}, (("embed", "tokens"),)),
    # the head under the exit gate is projected with once a pass
    "looped": (CASES["looped"][0], (("lm_head",),)),
    # a rule-moved float32 leaf beside the experts' stacks
    "mla_moe": (CASES["mla_moe"][0], ()),
}


@pytest.mark.parametrize("case", sorted(CARRIED))
def test_the_step_program_casts_no_carried_weight(case):
    """The traced ``ds_train_step`` holds no float32 -> compute-dtype convert
    of a weight-shaped value outside the ``optimizer`` scope but of the
    leaves the model keeps out of the working copy; inside it, one for each
    leaf of the copy (AdamW's new master, rounded beside it). The program's
    row says the same: what its trace counted at the model's cast sites, and
    the bytes of the copy it carries, 2 B a cast parameter."""
    import jax.numpy as jnp

    over, in_step = CARRIED[case]
    eng, *_ = ds.initialize(
        model=TransformerLM(get_preset("tiny", **over)),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True}, "steps_per_print": 10 ** 9,
                "zero_optimization": {"stage": 0}},
        mesh=build_mesh(devices=jax.devices()[:1]))
    batch = {"input_ids": np.zeros((2, 32), np.int32)}
    eng.fused_train_step(batch)
    row = steplog.programs()[-1]
    copy = jax.tree_util.tree_leaves(eng._work)
    in_step_shapes = {functools.reduce(operator.getitem, path,
                                       eng.params).shape for path in in_step}
    weight_shapes = {x.shape for x in jax.tree_util.tree_leaves(eng.params)}
    (step,) = eng._fused_step_cache.values()
    jaxpr = jax.make_jaxpr(step)(eng.params, eng._work, eng.opt_state, batch,
                                 eng.scaler_state)
    casts = [(shape, stack) for shape, stack in _casts_to(
        jaxpr.jaxpr, jnp.bfloat16) if shape in weight_shapes]
    inside = [shape for shape, stack in casts
              if "optimizer" in stack.split("/")]
    # (of the matrices: a vector's shape is also an activation's)
    outside = [shape for shape, stack in casts
               if "optimizer" not in stack.split("/") and len(shape) > 1]
    assert sorted(inside) == sorted(x.shape for x in copy)
    assert set(outside) == in_step_shapes
    counted = row.counted["weight_cast"]
    assert counted["carried"] >= len(copy)
    assert ("in_step" in counted) == bool(in_step)
    cast_parameters = sum(x.size for x in copy)
    assert row.working_copy_bytes == 2 * cast_parameters
    whole = sum(x.size for x in jax.tree_util.tree_leaves(eng.params))
    kept = whole - cast_parameters
    if not in_step:     # all but the float32-read leaves: norms, biases
        assert kept < 0.01 * whole
