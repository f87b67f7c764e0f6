"""Layers of one branch each, a Mamba-2 mixer whose gated norm is by group
beside routed experts, and LatentMoE (experts of two products round relu^2 in
a latent, a sigmoid router with a selection bias, a shared expert, a held
share): the program against the plain reference
(``benchmarks/reference_nemotron_h.py``: the tests import it from there, a
reference is held once) on seeded random weights, the shares adding up to the
whole layer, the plan of the layer loop, what refuses the model, the published
config's mapping, and the faults the benchmark cell's check has to see."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import modelcfg_nemotron_h as modelcfg
from benchmarks import reference_nemotron_h as ref
from benchmarks.runners.train_hybrid import compare
from benchmarks.runners.train_mla_moe import compare_biases
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.models import transformer as tf

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL_CONFIG = os.path.join(ROOT, "benchmarks", "configs",
                           "nemotron3_super_120b_train_d11h16e8v8.json")
ALPHA, GAMMA = 1e-2, 1e-3
PATTERN = "MEMEMEM*EME"


def hf_config(**over):
    """A small file of the cell's keys: hidden 64, 8 Mamba heads of 16 in 2
    groups, 4 query heads on 2 key-value heads, 4 of 16 experts held from
    the 4th on, 4 a token, a latent of 32."""
    hf = {"model_type": "nemotron_h", "hidden_size": 64,
          "num_hidden_layers": len(PATTERN),
          "hybrid_override_pattern": PATTERN * 8, "vocab_size": 256,
          "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
          "intermediate_size": 96, "layer_norm_epsilon": 1e-5,
          "mamba_num_heads": 8, "mamba_head_dim": 16, "ssm_state_size": 16,
          "n_groups": 2, "conv_kernel": 4, "chunk_size": 8,
          "n_routed_experts": 4, "router_width": 16, "first_expert": 4,
          "num_experts_per_tok": 4, "routed_scaling_factor": 5.0,
          "moe_latent_size": 32, "moe_intermediate_size": 48,
          "moe_shared_expert_intermediate_size": 96, "n_group": 1,
          "norm_topk_prob": True, "use_conv_bias": True,
          "mlp_hidden_act": "relu2", "tie_word_embeddings": False,
          "num_nextn_predict_layers": 0,
          "deployment": {"local_pairs_factor": 4.0, "bias_update_rate": GAMMA,
                         "bias_init": 0.1, "balance_coef": ALPHA,
                         "remat_policy": "none", "embed_init_std": 1.0}}
    hf.update(over)
    return hf


def model_for(hf, dtype="float32", **over):
    return TransformerLM(modelcfg.transformer_config(
        hf, max_seq_len=64, param_dtype="float32", dtype=dtype,
        attention_impl="xla", **over))


def init(model, seed=0, router_gain=4.0):
    params = jax.jit(model.init)(jax.random.key(seed))     # one program, not an op at a time
    # a router that prefers some experts, so that the top k is not a toss-up
    moe = params["layers"]["mlp_moe"]
    moe["router"] = moe["router"] * router_gain
    return params


ROWS = np.random.default_rng(0).integers(0, 256, (2, 24)).astype(np.int32)


@pytest.fixture(scope="module")
def small(run_memo):
    hf = hf_config()
    model = model_for(hf)
    params = init(model)
    want, grads = run_memo("nemotron_h_small", lambda: ref.batch_loss_and_grads(
        hf, modelcfg.weights_getter(params, hf), list(ROWS), ALPHA))
    return hf, model, params, want, grads


def _bf16(params):
    """bf16-rounded weights, the leaves the program keeps in float32 as they
    are."""
    def cast(path, w):
        keep = getattr(path[-1], "key", None) in tf._KEEP_FP32
        return w if keep else w.astype(jnp.bfloat16).astype(jnp.float32)
    return jax.tree_util.tree_map_with_path(cast, params)


# ---- the program against the reference ------------------------------------

@pytest.mark.parametrize("dtype, loss_tol, ms_tol, pairs_tol, grad_tol", [
    # float32: the same function, up to the order of sums
    ("float32", 2e-5, 1e-4, 0, 1e-4),
    # bf16 activations on the same bf16-rounded weights: a rounding of every
    # branch's output (2**-9 relative each), a few near-tied choices flipped
    # (one of 48 tokens x 4 choices at weights of up to 5 moves an expert
    # layer's mean square by a few hundredths, and gives or takes one of the
    # dozen tokens a held expert's gradient is summed over)
    ("bfloat16", 0.03, 0.08, 3, 0.6)])
def test_loss_branch_outputs_counts_and_every_gradient_match_the_reference(
        small, dtype, loss_tol, ms_tol, pairs_tol, grad_tol):
    hf, model, params, want, grads = small
    if dtype != "float32":
        model, params = model_for(hf, dtype), _bf16(params)
        want, grads = ref.batch_loss_and_grads(
            hf, modelcfg.weights_getter(params, hf), list(ROWS), ALPHA)
    (loss, parts), got = jax.jit(jax.value_and_grad(
        model.loss_and_parts, has_aux=True))(params, {"input_ids": ROWS})
    assert parts["mix_out_ms"].shape == (len(PATTERN),)
    assert parts["router_counts"].shape == (PATTERN.count("E"), 16)
    assert abs(float(loss) - float(want["loss"])) <= loss_tol
    assert abs(float(parts["lb_loss"]) - float(want["lb_loss"])) \
        <= max(loss_tol, 0.02 * float(want["lb_loss"]) * (dtype != "float32"))
    np.testing.assert_allclose(parts["mix_out_ms"], want["mix_out_ms"],
                               rtol=ms_tol)
    assert np.abs(np.asarray(parts["expert_pairs"])
                  - np.asarray(want["expert_pairs"])).max() <= pairs_tol
    assert int(np.sum(parts["pairs_dropped"])) == 0
    get = modelcfg.weights_getter(got, hf)
    assert len(grads) == 3 + 9 * 5 + 5 + 8 * 5     # every leaf but the bias
    for (name, layer), g in grads.items():
        mine, g = np.asarray(get(name, layer)), np.asarray(g)
        assert np.linalg.norm(mine - g) <= grad_tol * np.linalg.norm(g), \
            (name, layer)
    # the selection bias picks and gets no gradient
    assert not np.any(np.asarray(got["layers"]["mlp_moe"]["router_bias"]))


def test_the_layer_at_a_time_gradient_is_the_whole_models(small):
    hf, _, params, want, grads = small
    get = modelcfg.weights_getter(params, hf)
    names = [("embed", None), ("final_norm", None), ("lm_head", None)] + [
        (n, i) for i, c in enumerate(PATTERN) for n in ref.TENSORS[c]]
    loss, whole = ref.loss_and_grads(
        hf, {k: jnp.asarray(get(*k), jnp.float32) for k in names},
        list(ROWS), ALPHA)
    assert float(loss) == pytest.approx(float(want["loss"]), rel=1e-6)
    for key, g in grads.items():
        np.testing.assert_allclose(g, whole[key], rtol=2e-4, atol=1e-7)


# ---- the shares add up ----------------------------------------------------

UNCUT = dict(hidden_size=64, mamba_num_heads=16, mamba_head_dim=8,
             ssm_state_size=16, n_groups=8, num_attention_heads=16,
             num_key_value_heads=2, head_dim=8, n_routed_experts=64,
             router_width=64, first_expert=0, num_experts_per_tok=6)


def _mamba_share(w, hf, s, n=8):
    """Share ``s`` of ``n`` of a Mamba layer's tensors: its heads' z, x and
    dt columns of ``in_proj`` and its groups' B and C, the convolution's
    channels to match, its rows of ``out_proj``."""
    H, P = hf["mamba_num_heads"], hf["mamba_head_dim"]
    G, N = hf["n_groups"], hf["ssm_state_size"]
    inner = H * P
    ch = np.arange(s * inner // n, (s + 1) * inner // n)
    gr = np.arange(s * G * N // n, (s + 1) * G * N // n)
    hd = np.arange(s * H // n, (s + 1) * H // n)
    conv = np.concatenate([ch, inner + gr, inner + G * N + gr])
    cols = np.concatenate([ch, inner + conv, 2 * inner + 2 * G * N + hd])
    return {"in_proj": w["in_proj"][:, cols], "conv_w": w["conv_w"][:, conv],
            "conv_b": w["conv_b"][conv], "dt_bias": w["dt_bias"][hd],
            "A_log": w["A_log"][hd], "D": w["D"][hd], "norm": w["norm"][ch],
            "out_proj": w["out_proj"][ch]}


def test_the_eight_head_shares_of_a_mamba_layer_are_the_uncut_layer():
    """Eight models that each hold 2 of the 16 heads and 1 of the 8 groups
    (what the cell's configuration holds of 128 and 8): their mixers'
    outputs add up to the reference's uncut layer, whose gated norm is by
    group."""
    from deepspeed_tpu.models import mamba

    hf = hf_config(**UNCUT)
    whole = model_for(hf).cfg
    w = jax.tree_util.tree_map(lambda a: a[0], mamba.init(
        jax.random.key(1), whole, 1, jnp.float32))
    u = jax.random.normal(jax.random.key(2), (24, 64), jnp.float32)
    names = {"gate_norm": "norm"}
    want = jax.jit(lambda u, w: ref.mamba(u, w, hf))(
        u, {n: w[names.get(n, n)] for n in ref.TENSORS["M"] if n != "norm"})
    share = dataclasses.replace(whole, ssm_heads=2, ssm_groups=1)
    ssm_block = jax.jit(mamba.ssm_block, static_argnums=2)    # a program a cfg
    total = sum(ssm_block(u[None], _mamba_share(w, hf, s), share)[0]
                for s in range(8))
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)
    # and the uncut layer in one piece, its norm over 8 groups apart
    np.testing.assert_allclose(ssm_block(u[None], w, whole)[0], want,
                               rtol=2e-4, atol=2e-5)
    one_group = dataclasses.replace(whole, ssm_group_norm=False)
    assert float(jnp.abs(ssm_block(u[None], w, one_group)[0]
                         - want).max()) > 1e-2


def test_the_eight_head_shares_of_an_attention_layer_are_the_uncut_layer():
    hf = hf_config(**UNCUT)
    whole = model_for(hf)
    w = jax.tree_util.tree_map(lambda a: a[0] * 2.0, whole.init(
        jax.random.key(3))["layers"]["attn"])
    u = jax.random.normal(jax.random.key(4), (24, 64), jnp.float32)
    want = jax.jit(lambda u, w: ref.attention_layer(u, w, hf))(u, w)
    cut = dataclasses.replace(whole.cfg.kind_cfg("full:none"), num_heads=2,
                              num_kv_heads=1)
    attention_block = jax.jit(tf.attention_block, static_argnums=(2, 3, 4))
    total = 0.0
    for s in range(8):
        q = np.arange(16 * s, 16 * (s + 1))         # 2 heads of 8
        kv = np.arange(8 * (s // 4), 8 * (s // 4 + 1))
        ws = {"wq": w["wq"][:, q], "wk": w["wk"][:, kv],
              "wv": w["wv"][:, kv], "wo": w["wo"][q]}
        total = total + attention_block(u[None], ws, cut, None,
                                        tf.xla_attention)[0]
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


def test_the_expert_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """Eight models that each hold 8 of the 64 experts: their routed parts
    (each projected up out of the latent on its own) and the shared expert
    counted once add up to the reference's uncut layer."""
    from deepspeed_tpu.moe.sharded_moe import grouped_moe_mlp_block

    hf = hf_config(**UNCUT)
    whole = model_for(hf)
    w = jax.tree_util.tree_map(lambda a: a[0], whole.init(
        jax.random.key(5))["layers"]["mlp_moe"])
    w["router"] = w["router"] * 4.0
    u = jax.random.normal(jax.random.key(6), (2, 12, 64), jnp.float32)
    names = modelcfg._WHERE["E"]

    def leaf(tree, path):
        for key in path[1:]:
            tree = tree[key]
        return tree

    rw = {n: leaf(w, names[n]) for n in ref.TENSORS["E"] if n != "norm"}
    want = jax.jit(jax.vmap(
        lambda row: ref.experts(row, rw, hf, held=range(64))[0]))(u)
    shared = jnp.stack([
        ref.relu2(row @ rw["shared_w1"]) @ rw["shared_w2"] for row in u])
    block = jax.jit(grouped_moe_mlp_block, static_argnums=2)
    routed = 0.0
    for s in range(8):
        cut = dataclasses.replace(whole.cfg, moe_experts_held=8,
                                  moe_first_expert=8 * s,
                                  moe_ep_capacity_factor=8.0)
        ws = {**w, "w_up": w["w_up"][8 * s:8 * s + 8],
              "w_down": w["w_down"][8 * s:8 * s + 8]}
        out, aux = block(u, ws, cut)
        assert int(aux["pairs_dropped"]) == 0
        routed = routed + (out - shared)
    np.testing.assert_allclose(routed + shared, want, rtol=2e-4, atol=2e-5)


# ---- the layer loop -------------------------------------------------------

def test_eleven_alternating_layers_trace_three_block_bodies(small,
                                                            monkeypatch):
    """One scan over the period of 11 layers whose body calls one jitted
    block a kind: the forward and its gradient trace ``branch_block`` three
    times, each kind's stack has a row a layer of that kind and the norms a
    row a layer, and cutting the list into runs of one kind instead gives
    the same numbers."""
    hf, model, params, want, _ = small
    assert model.cfg.layer_kinds[:3] == ("ssm:none", "none:moe", "ssm:none")
    assert model._layer_plan() == [(0, 11, model.cfg.layer_kinds)]
    layers = params["layers"]
    assert sorted(layers) == ["attn", "ln1", "mlp_moe", "ssm"]
    assert layers["ssm"]["in_proj"].shape[0] == 5
    assert layers["attn"]["wq"].shape[0] == 1
    assert layers["mlp_moe"]["w_up"].shape[:2] == (5, 4)
    assert layers["mlp_moe"]["latent_down"].shape == (5, 64, 32)
    assert layers["ln1"]["scale"].shape == (11, 64)
    seen = []
    block = tf.branch_block
    monkeypatch.setattr(tf, "branch_block", lambda *a: (
        seen.append(a[-1]), block(*a))[1])
    fresh = model_for(hf, remat_policy="full")
    (loss, parts), _ = jax.jit(jax.value_and_grad(
        fresh.loss_and_parts, has_aux=True))(params, {"input_ids": ROWS})
    assert seen == ["ssm:none", "none:moe", "full:none"]
    monkeypatch.setattr(tf, "_MAX_PERIOD", 4)       # 11 > 2 x 4: runs
    runs = model_for(hf)
    assert len(runs._layer_plan()) == 11
    loss2, parts2 = jax.jit(runs.loss_and_parts)(params, {"input_ids": ROWS})
    assert float(loss2) == pytest.approx(float(loss), rel=1e-6)
    np.testing.assert_allclose(parts2["mix_out_ms"], parts["mix_out_ms"],
                               rtol=1e-5)
    np.testing.assert_array_equal(parts2["router_counts"],
                                  parts["router_counts"])
    unrolled = model_for(hf, scan_layers=False)
    assert float(jax.jit(unrolled.loss_fn)(params, {"input_ids": ROWS})) \
        == pytest.approx(float(loss), rel=1e-6)


def test_num_params_estimate_and_param_specs_follow_init(small):
    hf, model, params, _, _ = small
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert model.cfg.num_params_estimate() == n
    specs = model.param_specs()
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda x: 0, params)) \
        == jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda x: 0, specs, is_leaf=lambda x: not isinstance(x, dict)))
    facts = model.step_program_facts((2, 24))
    assert facts["layer_applications"] == 11
    assert facts["ssm_chunks_per_step"] == 5 * 2 * 3
    assert facts["experts_held"] == (4, 4, 16)
    assert facts["moe_scoring"] == "sigmoid"


def test_the_cells_parameter_count_is_the_programs():
    """The cell's configuration at its published widths, as shapes: the
    program's leaves are the 700,865,520 the file states."""
    with open(CELL_CONFIG) as f:
        cfg = json.load(f)
    model = TransformerLM(modelcfg.transformer_config(
        cfg, max_seq_len=8192, param_dtype="float32"))
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == model.cfg.num_params_estimate() == 700_865_520
    assert n == cfg["deployment"]["parameters"]
    assert shapes["layers"]["mlp_moe"]["w_up"].shape == (5, 8, 1024, 2688)
    assert shapes["layers"]["mlp_moe"]["router"].shape == (5, 4096, 512)
    assert shapes["layers"]["ssm"]["in_proj"].shape == (5, 4096, 2320)


# ---- the engine's step ----------------------------------------------------

def test_two_fused_steps_carry_both_records_and_move_the_bias_by_the_rule():
    """Through ``deepspeed_tpu.initialize`` -> ``fused_train_step``: the step
    record holds the router's counts by expert layer and every layer's
    branch-output mean square, the row the facts, and the selection bias
    moves by the rule on the step's own counts and by nothing else."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.observability import steplog
    from deepspeed_tpu.parallel import build_mesh

    hf = hf_config()
    engine, *_ = ds.initialize(
        model=model_for(hf, "bfloat16"), mesh=build_mesh(
            devices=jax.devices()[:1]),
        config={"train_micro_batch_size_per_gpu": 2, "seed": 3,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 0},
                "steps_per_print": 10 ** 9})
    for _ in range(2):
        before = np.array(modelcfg.biases(engine.params))
        engine.fused_train_step({"input_ids": ROWS})
        parts = steplog.get_steplog().parts(last=1)[-1]
        counts = np.asarray(parts["router_counts"], np.float64)
        np.testing.assert_allclose(
            np.array(modelcfg.biases(engine.params)),
            before + GAMMA * np.sign(counts.mean(-1, keepdims=True) - counts),
            atol=1e-7)
        assert parts["mix_out_ms"].shape == (11,)
        assert counts.shape == (5, 16) and counts.sum() == 5 * 48 * 4
        assert int(np.sum(parts["pairs_dropped"])) == 0
    row = [p for p in steplog.programs()
           if p.name.startswith("ds_train_step")][-1]
    assert row.layer_applications == 11 and row.ssm_chunks_per_step == 30
    assert row.experts_held == (4, 4, 16) and row.moe_scoring == "sigmoid"
    assert row.ssm_scan_lowerings and row.moe_grouped_lowerings


# ---- what refuses ---------------------------------------------------------

BASE = dict(vocab_size=64, hidden_size=32, num_layers=4, num_heads=2,
            use_rope=False, ssm_heads=4, ssm_head_dim=8, ssm_state=8,
            ssm_chunk=8, attn_pattern=("ssm", "dense", "full", "dense"),
            one_branch=True)


@pytest.mark.parametrize("what, kw, error", [
    ("num_passes", dict(num_passes=2), NotImplementedError),
    ("sandwich_norm", dict(sandwich_norm=True), NotImplementedError),
    ("parallel_block", dict(parallel_block=True), NotImplementedError),
    ("loss_tiling", dict(loss_tiling=2), NotImplementedError),
    ("fpdt", dict(attention_impl="fpdt"), NotImplementedError),
    ("norm_placement", dict(norm_placement="post"), NotImplementedError),
    ("first_k_dense", dict(first_k_dense=1, num_experts=4,
                           moe_dispatch="grouped",
                           attn_pattern=("ssm", "moe", "full", "moe")),
     NotImplementedError),
    ("'moe' where and only where", dict(num_experts=4,
                                        moe_dispatch="grouped"), ValueError),
    ("'moe' / 'dense'", dict(attn_pattern=("ssm", "full")), ValueError),
    ("relu2", dict(activation="relu2", num_experts=4,
                   attn_pattern=("ssm", "moe", "full", "moe")), ValueError),
    ("moe_latent_size", dict(moe_latent_size=16), ValueError),
])
def test_what_a_one_branch_model_does_not_run_refuses_by_name(what, kw, error):
    with pytest.raises(error, match=what):
        TransformerConfig(**{**BASE, **kw})


def test_ffn_kinds_in_the_pattern_need_one_branch():
    with pytest.raises(ValueError, match="one_branch"):
        TransformerConfig(**{**BASE, "one_branch": False})


def test_state_space_layers_beside_experts_keep_their_other_refusals():
    """The refusal of ``num_experts > 1`` beside state-space layers is
    lifted for the plain fused step; a looped stack, parallel_block, the
    tiled loss and FPDT still refuse by name, and so do the serving paths,
    the pipeline's helpers and the step paths that do not carry the rule."""
    two_branch = dict(vocab_size=64, hidden_size=32, num_layers=2,
                      num_heads=2, use_rope=False, ssm_heads=4,
                      ssm_head_dim=8, ssm_state=8, ssm_chunk=8,
                      attn_pattern=("ssm", "full"), num_experts=4,
                      moe_dispatch="grouped")
    model = TransformerLM(TransformerConfig(**two_branch))
    loss, parts = jax.jit(model.loss_and_parts)(
        model.init(jax.random.key(0)),
        {"input_ids": jnp.zeros((1, 8), jnp.int32)})
    assert np.isfinite(float(loss)) and parts["mix_out_ms"].shape == (2,)
    for kw in (dict(num_passes=2), dict(parallel_block=True),
               dict(loss_tiling=2), dict(attention_impl="fpdt")):
        with pytest.raises(NotImplementedError, match="state-space"):
            TransformerConfig(**{**two_branch, **kw})
    hf = hf_config()
    model = model_for(hf)
    for call in (lambda: model.init_kv_cache(1),
                 lambda: model.init_paged_kv_cache(4),
                 lambda: model.set_random_ltd(4),
                 lambda: model.set_pld_depth(2)):
        with pytest.raises(NotImplementedError):
            call()


def test_step_paths_that_do_not_carry_the_rule_refuse_the_model():
    import deepspeed_tpu as ds
    from deepspeed_tpu.parallel import build_mesh

    engine, *_ = ds.initialize(
        model=model_for(hf_config()), mesh=build_mesh(
            devices=jax.devices()[:1]),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
                "zero_optimization": {"stage": 0},
                "steps_per_print": 10 ** 9})
    batch = {"input_ids": ROWS}
    engine.forward(batch)
    engine.backward()
    with pytest.raises(NotImplementedError, match="rule"):
        engine.step()
    for attr in ("_offload", "_onebit", "_zpp"):
        setattr(engine, attr, object())
        with pytest.raises(NotImplementedError, match="router_bias"):
            engine.fused_train_step(batch)
        setattr(engine, attr, None)


# ---- the published config -------------------------------------------------

def test_the_published_config_maps_onto_the_model(tmp_path):
    """``config_from_hf`` on the catalog's keys: layers by the pattern's
    letters, the Mamba sizes, LatentMoE; the multi-token keys are noted and
    not read; group-limited routing is refused by name."""
    import logging

    from deepspeed_tpu.models.hf import config_from_hf
    from deepspeed_tpu.utils.logging import logger

    with open(CELL_CONFIG) as f:
        cell = json.load(f)
    hf = {k: v for k, v in cell.items() if k not in (
        "reduced", "assumed", "modules", "deployment", "check")}
    hf.update({k: v["published"] for k, v in cell["reduced"].items()})
    seen = []
    handler = logging.Handler()
    handler.emit = lambda record: seen.append(record.getMessage())
    logger.addHandler(handler)
    try:
        cfg = config_from_hf(hf, max_seq_len=128)
    finally:
        logger.removeHandler(handler)
    assert any("num_nextn_predict_layers=1" in m and "not implemented" in m
               for m in seen)
    kinds = cfg.layer_kinds
    assert len(kinds) == 88 and cfg.one_branch and not cfg.use_rope
    assert (kinds.count("ssm:none"), kinds.count("full:none"),
            kinds.count("none:moe")) == (40, 8, 40)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_conv, cfg.ssm_chunk, cfg.ssm_group_norm) \
        == (128, 64, 128, 8, 4, 128, True)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 2, 128)
    assert (cfg.num_experts, cfg.top_k, cfg.moe_intermediate_size,
            cfg.moe_latent_size, cfg.moe_shared_experts, cfg.moe_scoring,
            cfg.moe_routed_scale, cfg.activation) \
        == (512, 22, 2688, 1024, 2, "sigmoid", 5.0, "relu2")
    from benchmarks import opcount_nemotron_h as opcount
    assert cfg.num_params_estimate() == opcount.whole_model_params(cell)
    with pytest.raises(ValueError, match="n_group"):
        config_from_hf({**hf, "n_group": 2})
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        config_from_hf({**hf, "hybrid_override_pattern": "MX"})
    with pytest.raises(NotImplementedError, match="num_nextn_predict"):
        modelcfg.transformer_config({**cell, "num_nextn_predict_layers": 1},
                                    max_seq_len=64, param_dtype="float32")


# ---- the faults the cell's check has to see -------------------------------

def _mamba_norm_over_all_channels(u, w, cfg, norm_groups=None):
    return _MAMBA(u, w, cfg, norm_groups=1)


_MAMBA = ref.mamba


def _route_variant(latent=False, bias_in_weights=False, normalised=True):
    def route(x, router, bias, k, scale):
        s = jax.nn.sigmoid(x @ router)
        _, top_e = jax.lax.top_k(s + bias, k)
        top_s = jnp.take_along_axis(s + (bias if bias_in_weights else 0.0),
                                    top_e, axis=-1)
        if normalised:
            top_s = top_s / jnp.sum(top_s, -1, keepdims=True)
        return s, top_e, scale * top_s
    return route


def _experts_variant(router_reads_latent=False, shared_reads_latent=False,
                     weights_before_experts=False):
    def experts(x, w, cfg, held=None, shared=True):
        held = list(ref.held_experts(cfg) if held is None else held)
        k, E = int(cfg["num_experts_per_tok"]), int(cfg["router_width"])
        z = x @ w["latent_down"]
        if router_reads_latent:     # through the first rows of the router
            s, top_e, top_w = ref.route(
                z, w["router"][:z.shape[1]], w["router_bias"], k,
                float(cfg["routed_scaling_factor"]))
        else:
            s, top_e, top_w = ref.route(
                x, w["router"], w["router_bias"], k,
                float(cfg["routed_scaling_factor"]))
        mixed = jnp.zeros_like(z)
        for j, e in enumerate(held):
            weight = jnp.sum(jnp.where(top_e == e, top_w, 0.0), -1)[:, None]
            if weights_before_experts:
                mixed = mixed + ref.relu2((weight * z) @ w["w1"][j]) \
                    @ w["w2"][j]
            else:
                mixed = mixed + weight * (ref.relu2(z @ w["w1"][j])
                                          @ w["w2"][j])
        out = mixed @ w["latent_up"]
        if shared_reads_latent:     # through the first rows of its matrix
            out = out + ref.relu2(z @ w["shared_w1"][:z.shape[1]]) \
                @ w["shared_w2"]
        else:
            out = out + ref.relu2(x @ w["shared_w1"]) @ w["shared_w2"]
        counts = jnp.sum(jax.nn.one_hot(top_e, E, dtype=jnp.float32), (0, 1))
        f = counts * (E / (k * x.shape[0]))
        p = jnp.mean(s / jnp.sum(s, -1, keepdims=True), axis=0)
        return out, counts, jnp.sum(f * p)
    return experts


def _rope_applied(u, w, cfg):
    H, K = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d, T = int(cfg["head_dim"]), u.shape[0]
    freqs = tf.rope_frequencies(d, T, 10000.0)
    q = tf.apply_rope((u @ w["wq"]).reshape(1, T, H, d), freqs)[0]
    k = tf.apply_rope((u @ w["wk"]).reshape(1, T, K, d), freqs)[0]
    o = ref.attention(q, k, (u @ w["wv"]).reshape(T, K, d))
    return o.reshape(T, H * d) @ w["wo"]


def _two_branches_in_one_layer(x, w, cfg, kind):
    """A Mamba layer that also runs the next expert layer's FFN on its own
    output, inside the one layer (the tree's two-branch block)."""
    y, ms, counts, term = _BLOCK(x, w, cfg, kind)
    if kind == "M":
        h = ref.rms_norm(y, w["norm"], float(cfg["layer_norm_epsilon"]))
        y = y + ref.relu2(h @ w["in_proj"][:, :64]) @ w["in_proj"][:, :64].T
    return y, ms, counts, term


_BLOCK = ref.block


def _fp8(params):
    return jax.tree_util.tree_map(
        lambda w: w.astype(jnp.float8_e4m3fn).astype(jnp.float32), params)


FAULTS = {
    "the gate's norm over all channels and not by group":
        dict(mamba=_mamba_norm_over_all_channels),
    "relu without the square": dict(relu2=lambda v: jnp.maximum(v, 0.0)),
    "a gate product added":
        dict(relu2=lambda v: jax.nn.silu(v) * jnp.square(jnp.maximum(v, 0.0))),
    "the router reading the latent":
        dict(experts=_experts_variant(router_reads_latent=True)),
    "weights taken with the bias":
        dict(route=_route_variant(bias_in_weights=True)),
    "no division by the sum": dict(route=_route_variant(normalised=False)),
    "the scaling 5 left out": dict(hf={"routed_scaling_factor": 1.0}),
    "the shared expert fed the latent":
        dict(experts=_experts_variant(shared_reads_latent=True)),
    # (W_up before or after the weighted sum is the same function: a linear
    # map; the weights before the experts' relu^2 are not)
    "the weights applied before the experts and not to their outputs":
        dict(experts=_experts_variant(weights_before_experts=True)),
    "a rope applied": dict(attention_layer=_rope_applied),
    "two branches in one layer": dict(block=_two_branches_in_one_layer),
    "fp8-rounded weights": dict(weights=_fp8),
}


@pytest.fixture(scope="module")
def cell_check(run_memo):
    """The cell's own tolerances on the forward's parts, and the reference
    at a small size (hidden 256, the 11 layers, 2 Mamba groups, 64-token
    rows) on bf16-rounded weights."""
    with open(CELL_CONFIG) as f:
        check = json.load(f)["check"]
    check["compared"] = [n for n in check["compared"]
                         if n not in ("grad_err", "param_change_err")]
    hf = hf_config(hidden_size=256, vocab_size=512, mamba_num_heads=16,
                   mamba_head_dim=16, n_groups=2, chunk_size=16,
                   n_routed_experts=16, first_expert=0, router_width=16,
                   num_experts_per_tok=4, moe_latent_size=64,
                   moe_intermediate_size=96,
                   moe_shared_expert_intermediate_size=192)
    params = init(model_for(hf), seed=5, router_gain=2.0)
    # queries and keys that prefer some positions, so that a rope moves what
    # is attended to
    attn = params["layers"]["attn"]
    attn["wq"], attn["wk"] = attn["wq"] * 3.0, attn["wk"] * 3.0
    params = _bf16(params)
    rows = list(np.random.default_rng(7).integers(0, 512, (2, 64))
                .astype(np.int32))
    return check, hf, params, rows, run_memo(
        "nemotron_h_cell_check", lambda: ref.batch_loss(
            hf, modelcfg.weights_getter(params, hf), rows, ALPHA))


def _judged(check, got, want, bias):
    """The runner's own rules (``runners/train_hybrid.py:compare``,
    ``runners/train_mla_moe.py:compare_biases``) on a stand-in's parts."""
    got = {k: np.asarray(v) for k, v in got.items()}
    problems, _ = compare(got, want, {**check, "first_loss_range": [0, 99]})
    if got["router_counts"].shape == np.shape(want["router_counts"]):
        more, _ = compare_biases(
            bias, ref.bias_after(bias, got["router_counts"], GAMMA), want,
            check, GAMMA, {"reference": ref})
        problems += more
    return problems


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_cells_check_sees_the_fault(cell_check, monkeypatch, fault):
    check, hf, params, rows, want = cell_check
    how = FAULTS[fault]
    for name in ("mamba", "relu2", "route", "experts", "attention_layer",
                 "block"):
        if name in how:
            monkeypatch.setattr(ref, name, how[name])
    bad = how.get("weights", lambda p: p)(params)
    got = ref.batch_loss({**hf, **how.get("hf", {})},
                         modelcfg.weights_getter(bad, hf), rows, ALPHA)
    bias = np.asarray(params["layers"]["mlp_moe"]["router_bias"])
    assert _judged(check, got, want, bias), fault
    monkeypatch.undo()
    assert not _judged(check, want, want, bias)
