"""Latent attention with keys wider than values, a leading dense layer, and
routed layers with a sigmoid router, a selection bias the step moves by
rule, a held share and shared experts (kanana-2): the program against the
plain reference (``kanana_reference.py``, a copy of
``benchmarks/reference_kanana2.py``) on seeded random weights, the flash
kernels at two widths, the shares adding up to the whole layer, the bias
rule through the engine, what refuses the model, and the faults the benchmark
cell's check has to see."""

import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_kanana2 as ref
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.models import transformer as tf
from deepspeed_tpu.ops import lowerings

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL_CONFIG = os.path.join(ROOT, "benchmarks", "configs",
                           "kanana2_30b_train_d5e16.json")
ALPHA, GAMMA = 1e-4, 1e-3


def hf_config(L=3, D=64, H=4, held=8, first=4, routed=16, k=3, V=128,
              **over):
    return {"hidden_size": D, "num_attention_heads": H,
            "num_hidden_layers": L, "kv_lora_rank": 32,
            "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
            "rope_theta": 10000.0, "rope_interleave": True,
            "rms_norm_eps": 1e-6, "first_k_dense_replace": 1,
            "intermediate_size": 96, "moe_intermediate_size": 32,
            "n_routed_experts": held, "router_width": routed,
            "first_expert": first, "num_experts_per_tok": k,
            "routed_scaling_factor": 2.448, "n_shared_experts": 2,
            "vocab_size": V, **over}


def model_for(hf, **over):
    held, routed = hf["n_routed_experts"], hf["router_width"]
    kw = dict(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        intermediate_size=hf["intermediate_size"], max_seq_len=64,
        tie_embeddings=False, rope_theta=hf["rope_theta"], norm_eps=1e-6,
        dtype="float32", attention_impl="xla",
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"], v_head_dim=hf["v_head_dim"],
        rope_interleave=hf["rope_interleave"],
        first_k_dense=hf["first_k_dense_replace"], num_experts=routed,
        top_k=hf["num_experts_per_tok"], moe_dispatch="grouped",
        moe_intermediate_size=hf["moe_intermediate_size"],
        moe_experts_held=None if held == routed else held,
        moe_first_expert=hf["first_expert"], moe_scoring="sigmoid",
        moe_routed_scale=hf["routed_scaling_factor"],
        moe_shared_experts=hf["n_shared_experts"], moe_bias_rate=GAMMA,
        moe_bias_init=0.1, moe_aux_loss_coef=ALPHA, embed_init_std=1.0)
    kw.update(over)
    return TransformerLM(TransformerConfig(**kw))


_SHARED = {"shared_gate": "w_gate", "shared_up": "w_up",
           "shared_down": "w_down"}


def getter(params, hf, dense_as_routed=False):
    """The reference's ``get`` over the program's tree (what
    ``benchmarks/modelcfg_kanana2.py:weights_getter`` is to the cell)."""
    layers, dense = params["layers"], hf["first_k_dense_replace"]

    def get(name, layer=None):
        if name == "embed":
            return params["embed"]["tokens"]
        if name == "final_norm":
            return params["final_norm"]["scale"]
        if name == "head":
            return params["lm_head"]
        if name in ("ln1", "ln2"):
            return layers[name]["scale"][layer]
        if name in ref.MLA_TENSORS:
            return layers["mla"][name][layer]
        if layer < dense and not dense_as_routed:
            return layers["mlp_dense"][name][layer]
        row = max(layer - dense, 0)
        if name in _SHARED:
            return layers["mlp_moe"]["shared"][_SHARED[name]][row]
        return layers["mlp_moe"][name][row]

    return get


def init(model, seed=0, router_gain=4.0):
    params = jax.jit(model.init)(jax.random.key(seed))     # one program, not an op at a time
    # a router that prefers some experts, so that the top k is not a toss-up
    moe = params["layers"]["mlp_moe"]
    moe["router"] = moe["router"] * router_gain
    return params


ROWS = np.random.default_rng(0).integers(0, 128, (2, 24)).astype(np.int32)


@pytest.fixture(scope="module")
def small(run_memo):
    hf = hf_config()
    model = model_for(hf)
    params = init(model)
    return hf, model, params, run_memo("kanana_small", lambda: ref.batch_loss(
        hf, getter(params, hf), ROWS, ALPHA))


def test_loss_balance_term_mixer_outputs_and_counts_match_the_reference(
        small):
    hf, model, params, want = small
    loss, parts = jax.jit(model.loss_and_parts)(params, {"input_ids": ROWS})
    np.testing.assert_allclose(loss, want["loss"], atol=2e-5)
    np.testing.assert_allclose(parts["lb_loss"], want["lb_loss"], rtol=1e-5)
    np.testing.assert_allclose(parts["mix_out_ms"], want["mix_out_ms"],
                               rtol=1e-4)
    assert parts["mix_out_ms"].shape == (3,)
    np.testing.assert_array_equal(parts["router_counts"],
                                  want["router_counts"])
    np.testing.assert_array_equal(parts["expert_pairs"],
                                  want["expert_pairs"])
    assert parts["expert_pairs"].shape == (2, 8)      # the routed layers'
    assert not np.asarray(parts["pairs_dropped"]).any()
    c = np.asarray(want["router_counts"])
    assert int(parts["bias_moved"]) == int(
        (c != c.mean(-1, keepdims=True)).sum())


def test_gradients_of_every_leaf_match_the_reference(small):
    hf, model, params, _ = small
    got = jax.jit(jax.grad(model.loss_fn))(params, {"input_ids": ROWS})
    get, got_of = getter(params, hf), getter(got, hf)
    weights = {(n, None): get(n) for n in ("embed", "final_norm", "head")}
    for i in range(hf["num_hidden_layers"]):
        names = ref.MLA_TENSORS + (ref.DENSE_TENSORS if ref.is_dense(hf, i)
                                   else ref.ROUTED_TENSORS)
        weights.update({(n, i): get(n, i) for n in names})
    _, want = ref.loss_and_grads(hf, weights, ROWS, ALPHA)
    seen = 0
    for (name, layer), g in want.items():
        scale = float(np.abs(g).max())
        np.testing.assert_allclose(
            got_of(name, layer), g, atol=2e-5 * max(scale, 1.0) + 1e-7,
            err_msg=f"{name} of layer {layer}")
        seen += np.size(g)
    assert seen == sum(np.size(x) for x in jax.tree_util.tree_leaves(params))
    # the selection bias only picks experts: no gradient
    assert not np.asarray(got["layers"]["mlp_moe"]["router_bias"]).any()


def test_the_eight_shares_and_the_shared_experts_once_are_the_uncut_layer():
    """model-configs section 4's test: the partial sums that the shares'
    routed experts give, added, with the shared experts counted once, are
    what the uncut reference gives for the layer."""
    from deepspeed_tpu.moe.sharded_moe import grouped_moe_mlp_block

    rng = np.random.default_rng(1)
    D, F, E, k, T = 64, 32, 16, 3, 40
    f32 = lambda a: jnp.asarray(a, jnp.float32)      # noqa: E731
    x = f32(rng.standard_normal((1, T, D)))
    w = {"router": f32(rng.standard_normal((D, E)) * 0.5),
         "router_bias": f32(rng.uniform(-0.1, 0.1, (E,))),
         "w_gate": f32(rng.standard_normal((E, D, F)) / 8),
         "w_up": f32(rng.standard_normal((E, D, F)) / 8),
         "w_down": f32(rng.standard_normal((E, F, D)) / 6)}
    shared = {"w_gate": f32(rng.standard_normal((D, 2 * F)) / 8),
              "w_up": f32(rng.standard_normal((D, 2 * F)) / 8),
              "w_down": f32(rng.standard_normal((2 * F, D)) / 8)}
    hf = hf_config(held=E, first=0, routed=E, k=k)
    whole, counts, _ = ref.experts(x[0], {
        **w, "shared_gate": shared["w_gate"], "shared_up": shared["w_up"],
        "shared_down": shared["w_down"]}, hf)
    total = jnp.zeros((T, D), jnp.float32)
    pairs = []
    for share in range(8):
        lo = 2 * share
        cfg = TransformerConfig(
            hidden_size=D, num_heads=4, num_experts=E, top_k=k,
            moe_dispatch="grouped", moe_intermediate_size=F,
            moe_experts_held=2, moe_first_expert=lo, moe_scoring="sigmoid",
            moe_routed_scale=2.448, dtype="float32")
        ws = {**w, **{n: w[n][lo:lo + 2]
                      for n in ("w_gate", "w_up", "w_down")}}
        if share == 3:      # whole on every chip: counted once
            ws["shared"] = shared
        out, aux = grouped_moe_mlp_block(x, ws, cfg)
        total = total + out[0]
        pairs.append(aux["expert_pairs"])
        np.testing.assert_array_equal(aux["router_counts"], counts)
    np.testing.assert_allclose(total, whole, atol=2e-5)
    np.testing.assert_array_equal(jnp.concatenate(pairs), counts)
    # a list of experts is a share too
    part, _, _ = ref.experts(x[0], {**w, **{n: w[n][jnp.asarray([5, 9])]
                                            for n in ("w_gate", "w_up",
                                                      "w_down")}},
                             hf, held=[5, 9], shared=False)
    assert float(jnp.abs(part).max()) > 0


# ---- the flash kernels at a key width and a value width --------------------

@pytest.mark.parametrize("T, d, dv, block", [(512, 192, 128, 128),
                                             (512, 24, 16, 128),
                                             (64, 24, 16, 16)])
def test_flash_kernels_at_two_widths_match_xla_attention(T, d, dv, block):
    """Forward, the fused backward (128-wide tiles) and the split backward
    (16-wide tiles), interpreted, at keys 192 / values 128 and at a toy
    pair."""
    from deepspeed_tpu.ops import flash_attention as fa

    rng = np.random.default_rng(T + d)
    B, H = 1, 2
    q = jnp.asarray(rng.standard_normal((B, T, H, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, H, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, H, dv)), jnp.float32)
    do = jnp.asarray(rng.standard_normal((B, T, H, dv)), jnp.float32)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, block_q=block,
                                  block_k=block, interpret=True)

    before = lowerings.snapshot()
    out, vjp = jax.vjp(flash, q, k, v)
    want, vjp_want = jax.vjp(
        lambda q, k, v: tf.xla_attention(q, k, v, causal=True), q, k, v)
    assert out.shape == (B, T, H, dv)
    np.testing.assert_allclose(out, want, atol=2e-5)
    for got, ref_g, name in zip(vjp(do), vjp_want(do), "qkv"):
        assert got.shape == ref_g.shape
        np.testing.assert_allclose(got, ref_g, atol=1e-4, err_msg="d" + name)
    assert lowerings.since(before)["flash_bwd"] == {
        "fused" if block == 128 else "split": 1}
    # the log-sum-exp variant takes the two widths too
    out2, lse = fa.flash_attention_lse(q, k, v, block_q=block, block_k=block,
                                       interpret=True)
    np.testing.assert_allclose(out2, want, atol=2e-5)
    assert lse.shape == (B, H, T, 1)


def test_the_fused_backward_budget_counts_both_widths():
    from deepspeed_tpu.ops import flash_attention as fa

    same = fa._fused_bwd_vmem_bytes(8192, 128, 1024, 1024, 2)
    assert same == fa._fused_bwd_vmem_bytes(8192, 128, 1024, 1024, 2, 128)
    wide = fa._fused_bwd_vmem_bytes(8192, 192, 1024, 1024, 2, 128)
    assert same < wide < fa._fused_bwd_vmem_bytes(8192, 256, 1024, 1024, 2)
    assert fa._bwd_takes_fused(8192, 192, 1024, 1024, 2, 128)
    assert fa._bwd_segments(8192, 192, 1024, 1024, 2, 128) == 1


# ---- q and k in the parts the projections write -----------------------------

# (T, dn, dr, dv, tile, keys and values in one array[, the sub-block's edge
# in place of the kernels' 512]): the fused backward at 128-wide tiles, the
# split pair at 16-wide ones, at 128 + 64 over 128 and at a toy pair; with
# the edge at 32 each diagonal tile is 16 sub-blocks, of which the fused
# backward skips 6, masks 4 and works 6 unmasked
IN_PARTS = {
    "192-fused": (256, 128, 64, 128, 128, False),
    "192-fused-kv-whole-sub-blocks": (256, 128, 64, 128, 128, True, 32),
    "192-fused-kv-whole": (256, 128, 64, 128, 128, True),
    "192-split-kv-whole": (32, 128, 64, 128, 16, True),
    "toy-fused-kv-whole": (256, 16, 8, 16, 128, True),
    "toy-split": (64, 16, 8, 16, 16, False),
    "toy-split-kv-whole": (64, 16, 8, 16, 16, True),
}


@pytest.mark.parametrize("case", sorted(IN_PARTS))
def test_flash_kernels_take_q_and_k_in_parts(case, monkeypatch):
    """The rope columns of q and the one rope key as operands of their own,
    keys and values an array each or side by side in one, interpreted,
    against ``xla_attention`` on q and k put together: the forward and every
    gradient, the rope key's as the sum over the heads."""
    from deepspeed_tpu.ops import flash_attention as fa

    T, dn, dr, dv, block, kv_whole, *sub = IN_PARTS[case]
    if sub:
        monkeypatch.setattr(fa, "_SUB", sub[0])
    rng = np.random.default_rng(T + dn + kv_whole)
    B, H = 1, 2
    q, qr, k, kr, v, do = (
        jnp.asarray(rng.standard_normal((B, T, h, w)), jnp.float32)
        for h, w in ((H, dn), (H, dr), (H, dn), (1, dr), (H, dv), (H, dv)))
    tiles = dict(block_q=block, block_k=block, interpret=True)

    def parts(k, v):
        return (jnp.concatenate([k, v], axis=-1), None) if kv_whole \
            else (k, v)

    def flash(q, qr, k, kr, v):
        return fa.flash_attention(q, *parts(k, v), q_rope=qr, k_rope=kr,
                                  **tiles)

    def whole(q, qr, k, kr, v):
        return tf.xla_attention(*fa.assembled(q, k, v, qr, kr), causal=True)

    before = lowerings.snapshot()
    out, vjp = jax.vjp(flash, q, qr, k, kr, v)
    want, vjp_want = jax.vjp(whole, q, qr, k, kr, v)
    assert out.shape == (B, T, H, dv)
    np.testing.assert_allclose(out, want, atol=2e-5)
    for got, ref_g, name in zip(vjp(do), vjp_want(do),
                                ("dq", "dq_rope", "dk", "dk_rope", "dv")):
        assert got.shape == ref_g.shape, name
        np.testing.assert_allclose(got, ref_g, atol=1e-4, err_msg=name)
    took = lowerings.since(before)
    assert took["flash_bwd"] == {"fused" if block == 128 else "split": 1}
    if sub:
        assert took["flash_bwd_tiles"] == {"causal": dict(
            masked=2, unmasked=1, dead=1, sub_live=20, sub_dead=12,
            sub_inside=12)}
    # a forward and a backward, each counted as taking the operands
    assert took["flash_rope_operand"] == {"operand": 2}
    # the log-sum-exp variant takes the parts too, gradients through both
    # results
    (out2, lse), vjp2 = jax.vjp(
        lambda q, qr, k, kr, v: fa.flash_attention_lse(
            q, *parts(k, v), q_rope=qr, k_rope=kr, **tiles), q, qr, k, kr, v)
    np.testing.assert_allclose(out2, want, atol=2e-5)
    assert lse.shape == (B, H, T, 1)
    scores = jnp.einsum("bthd,bshd->bhts", *fa.assembled(q, k, v, qr, kr)[:2])
    scores = jnp.where(jnp.tril(jnp.ones((T, T), bool)),
                       scores / math.sqrt(dn + dr), -jnp.inf)
    np.testing.assert_allclose(lse[..., 0],
                               jax.nn.logsumexp(scores, axis=-1), atol=2e-5)
    for got, ref_g, name in zip(vjp2((do, jnp.zeros_like(lse))),
                                vjp_want(do), "abcde"):
        np.testing.assert_allclose(got, ref_g, atol=1e-4, err_msg=name)


def test_parts_come_in_pairs_and_keys_count_in_blocks_of_values():
    from deepspeed_tpu.ops import flash_attention as fa

    x = jnp.zeros((1, 16, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="together"):
        fa.flash_attention(x, x, x, q_rope=x[..., :8], interpret=True)
    # values 12 wide after keys 16 wide: no column block names them
    with pytest.raises(ValueError, match="divide"):
        fa.flash_attention(x, jnp.zeros((1, 16, 2, 28), jnp.float32), None,
                           interpret=True)


def test_the_fused_backward_budget_counts_the_rope_operands():
    from deepspeed_tpu.ops import flash_attention as fa

    MiB = 2 ** 20
    whole = fa._fused_bwd_vmem_bytes(8192, 192, 1024, 1024, 2, 128)
    parts = fa._fused_bwd_vmem_bytes(8192, 128, 1024, 1024, 2, 128, 64)
    assert fa._fused_bwd_vmem_bytes(8192, 128, 1024, 1024, 2, 128, 0) \
        == fa._fused_bwd_vmem_bytes(8192, 128, 1024, 1024, 2)
    # dq and dk's accumulator stay 192 (256 lanes) wide; q's and k's tiles
    # and dk's result come in two parts of a lane tile each, where the whole
    # took two; a tile of q and of k is put together beside them
    assert parts == whole + (1024 + 1024) * 256 * 2
    assert 38 * MiB < whole < parts < 40 * MiB < fa._FUSED_VMEM_BUDGET
    assert fa._bwd_takes_fused(8192, 128, 1024, 1024, 2, 128, 64)
    assert fa._bwd_segments(8192, 128, 1024, 1024, 2, 128, 64) == 1
    # past the budget the head is worked in segments of rows whose dq fits:
    # at 32,768 four of 8,192 (two of 16,384 would hold 57.8 MB)
    assert fa._bwd_takes_fused(32768, 128, 1024, 1024, 2, 128, 64)
    assert fa._bwd_segments(32768, 128, 1024, 1024, 2, 128, 64) == 4
    assert fa._fused_bwd_vmem_bytes(16384, 128, 1024, 1024, 2, 128, 64) \
        > fa._FUSED_VMEM_BUDGET


def test_the_rope_kernel_is_apply_rope_on_heads_first_rows():
    """``ops/rope.py``, interpreted: the rotation's partner column through a
    signed permutation, bf16 to the bit, and its backward the same call with
    the sine negated."""
    from deepspeed_tpu.ops.rope import rope_heads_first

    rng = np.random.default_rng(5)
    freqs = tf.rope_frequencies(8, 64, 10000.0)
    for dtype, atol in ((jnp.bfloat16, 0.0), (jnp.float32, 1e-6)):
        x = jnp.asarray(rng.standard_normal((2, 3, 48, 8)), dtype)
        dy = jnp.asarray(rng.standard_normal(x.shape), dtype)
        got, vjp = jax.vjp(
            lambda x: rope_heads_first(x, freqs, interpret=True), x)
        want, vjp_want = jax.vjp(
            lambda x: tf.apply_rope(x.transpose(0, 2, 1, 3),
                                    freqs).transpose(0, 2, 1, 3), x)
        assert got.dtype == dtype and got.shape == x.shape
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=atol)
        np.testing.assert_allclose(np.asarray(vjp(dy)[0], np.float32),
                                   np.asarray(vjp_want(dy)[0], np.float32),
                                   atol=atol)


@pytest.mark.parametrize("impl, mosaic", [("flash_pallas", True),
                                          ("flash_pallas", False),
                                          ("ulysses", False)])
def test_the_block_in_parts_is_the_block_on_q_and_k_whole(monkeypatch, impl,
                                                          mosaic):
    """``mla_block`` through the flash kernels (the parts as operands; with
    and without the rope kernel), and through an attention that takes no
    parts, against the block through ``xla_attention``, which
    ``kanana_reference.py`` holds to its limits above: the output and every
    leaf's gradient."""
    import deepspeed_tpu.ops as ops
    from deepspeed_tpu.models import mla
    from deepspeed_tpu.ops import flash_attention as fa

    monkeypatch.setattr(ops, "mosaic_runs_whole", lambda: mosaic)
    hf = hf_config()
    cfg = model_for(hf).cfg
    B, T = 2, 32
    rng = np.random.default_rng(3)
    w = jax.tree_util.tree_map(
        lambda x: x[0], mla.init(jax.random.key(1), cfg, 1, jnp.float32))
    x = jnp.asarray(rng.standard_normal((B, T, cfg.hidden_size)),
                    jnp.float32)
    freqs = tf.rope_frequencies(cfg.qk_rope_head_dim, 64, cfg.rope_theta)
    do = jnp.asarray(rng.standard_normal(x.shape), jnp.float32)
    attn = tf.get_attention_impl(impl)
    if impl == "ulysses":       # one device: the impl's own XLA attention
        assert not tf._attn_takes(attn, "q_rope")

    def block(attn_fn):
        return jax.vjp(lambda x, w: mla.mla_block(x, w, cfg, freqs, attn_fn),
                       x, w)

    before = lowerings.snapshot()
    out, vjp = block(attn)
    dx, dw = vjp(do)
    assert lowerings.since(before).get("flash_rope_operand") == (
        {"operand": 2} if impl == "flash_pallas" else None)
    want, vjp_want = block(tf.xla_attention)
    dx_want, dw_want = vjp_want(do)
    np.testing.assert_allclose(out, want, atol=2e-5)
    np.testing.assert_allclose(dx, dx_want, atol=1e-4)
    assert sorted(dw) == sorted(dw_want) == sorted(
        set(ref.MLA_TENSORS) - {"ln1", "ln2"})
    for name in dw:
        np.testing.assert_allclose(dw[name], dw_want[name], atol=1e-4,
                                   err_msg=name)


# ---- the layer plan, the stacks, the count ---------------------------------

def test_a_dense_run_then_a_routed_run_each_with_its_own_stack(monkeypatch):
    hf = hf_config(L=6)
    model = model_for(hf)
    assert model.cfg.layer_kinds == ("mla:dense",) + ("mla:moe",) * 5
    assert model._layer_plan() == [(0, 1, ("mla:dense",)),
                                   (1, 6, ("mla:moe",))]
    whole = jax.eval_shape(model.init, jax.random.key(0))
    shapes = whole["layers"]
    assert "attn" not in shapes and "mlp" not in shapes
    assert shapes["mla"]["wq"].shape == (6, 64, 4 * 24)
    assert shapes["mlp_dense"]["w_up"].shape == (1, 64, 96)
    assert shapes["mlp_moe"]["w_up"].shape == (5, 8, 64, 32)
    assert shapes["mlp_moe"]["router_bias"].shape == (5, 16)
    assert shapes["mlp_moe"]["shared"]["w_down"].shape == (5, 64, 64)
    specs = model.param_specs()["layers"]
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda a: 0, shapes)) \
        == jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda a: 0, specs, is_leaf=lambda s: isinstance(
                s, jax.sharding.PartitionSpec)))
    traced = []
    real = tf.transformer_block
    monkeypatch.setattr(tf, "transformer_block", lambda *a, **kw: (
        traced.append(kw.get("kind")), real(*a, **kw))[1])
    jax.eval_shape(model.loss_fn, whole, {"input_ids": ROWS})
    assert traced == ["mla:dense", "mla:moe"]        # two bodies, six layers


@pytest.mark.parametrize("scan", [True, False])
def test_the_scanned_and_the_unrolled_stack_give_the_same_numbers(small,
                                                                  scan):
    hf, _, params, want = small
    model = model_for(hf, scan_layers=scan, remat_policy="full")
    loss, parts = jax.jit(model.loss_and_parts)(params, {"input_ids": ROWS})
    np.testing.assert_allclose(loss, want["loss"], atol=2e-5)
    np.testing.assert_array_equal(parts["expert_pairs"],
                                  want["expert_pairs"])


FAMILIES = {
    "llama": dict(hidden_size=64, num_heads=4, num_kv_heads=2, num_layers=2,
                  vocab_size=96, tie_embeddings=False),
    "gpt2": dict(arch="gpt2", hidden_size=64, num_heads=4, num_layers=2,
                 vocab_size=96, proj_bias=True, max_seq_len=32),
    "qwen_bias": dict(hidden_size=64, num_heads=4, num_layers=2,
                      vocab_size=96, qkv_bias=True),
    "falcon": dict(arch="gpt2", hidden_size=64, num_heads=4, num_layers=2,
                   vocab_size=96, parallel_block=True,
                   parallel_shared_norm=True, use_rope=True,
                   learned_pos=False),
    "looped": dict(hidden_size=64, num_heads=4, num_layers=2, vocab_size=96,
                   num_passes=2, sandwich_norm=True, exit_loss_beta=0.1),
    "moe": dict(hidden_size=64, num_heads=4, num_layers=2, vocab_size=96,
                num_experts=4, top_k=2),
    "share": dict(hidden_size=64, num_heads=4, num_layers=4, vocab_size=96,
                  sliding_window=8, attn_pattern=("window", "full"),
                  num_experts=8, top_k=2, moe_dispatch="grouped",
                  moe_intermediate_size=48, moe_experts_held=4),
    "ssm": dict(hidden_size=64, num_heads=4, num_layers=4, vocab_size=96,
                attn_pattern=("ssm", "ssm", "full", "ssm"), use_rope=False,
                ssm_heads=8, ssm_head_dim=16, ssm_state=16, ssm_groups=2,
                ssm_chunk=8),
}


@pytest.mark.parametrize("family", sorted(FAMILIES) + ["mla"])
def test_num_params_estimate_is_the_leaf_count_of_init(family):
    cfg = (model_for(hf_config(L=4)).cfg if family == "mla"
           else TransformerConfig(**FAMILIES[family]))
    shapes = jax.eval_shape(TransformerLM(cfg).init, jax.random.key(0))
    assert cfg.num_params_estimate() == sum(
        math.prod(x.shape) for x in jax.tree_util.tree_leaves(shapes))


# ---- the engine: the bias rule, weight decay, the checkpoint ---------------

def _engine(model):
    import deepspeed_tpu as ds
    from deepspeed_tpu.parallel import build_mesh

    engine, *_ = ds.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 1e-2, "weight_decay": 0.5}},
        "zero_optimization": {"stage": 0}, "steps_per_print": 10 ** 9},
        mesh=build_mesh(devices=jax.devices()[:1]))
    return engine


def _bias(engine):
    return np.array(engine.params["layers"]["mlp_moe"]["router_bias"])


def test_two_fused_steps_move_the_bias_by_the_rule_and_nothing_else(tmp_path):
    from deepspeed_tpu.observability import steplog

    hf = hf_config()
    engine = _engine(model_for(hf))
    bias = _bias(engine)
    router = np.array(engine.params["layers"]["mlp_moe"]["router"])
    for step in range(2):
        rows = np.random.default_rng(step).integers(
            0, 128, (2, 24)).astype(np.int32)
        want = ref.batch_loss(hf, getter(engine.params, hf), rows, ALPHA)
        loss = float(engine.fused_train_step({"input_ids": rows}))
        np.testing.assert_allclose(loss, want["loss"], atol=2e-5)
        part = steplog.get_steplog().parts(last=1)[-1]
        np.testing.assert_array_equal(part["router_counts"],
                                      want["router_counts"])
        # the reference's rule on the reference's counts; a decay of 0.5 at
        # a rate of 1e-2 would have taken 0.5 % off every bias
        bias = np.asarray(ref.bias_after(bias, want["router_counts"], GAMMA))
        np.testing.assert_allclose(_bias(engine), bias, atol=1e-7)
    assert np.abs(_bias(engine)).max() > 0.05
    # the optimizer moved its own leaves
    assert np.abs(np.array(engine.params["layers"]["mlp_moe"]["router"])
                  - router).max() > 1e-3
    row = [p for p in steplog.programs()
           if p.name.startswith("ds_train_step")][-1]
    assert row.attn_widths == (24, 16) and row.moe_scoring == "sigmoid"
    assert row.layer_pattern == ("mla:dense", "mla:moe")
    assert row.experts_held == (4, 8, 16)
    # saved and restored with the rest
    engine.save_checkpoint(str(tmp_path))
    other = _engine(model_for(hf))
    assert np.abs(_bias(other) - bias).max() > 1e-3
    other.load_checkpoint(str(tmp_path))
    np.testing.assert_array_equal(_bias(other), _bias(engine))


def test_step_paths_that_do_not_carry_the_rule_refuse_the_model():
    engine = _engine(model_for(hf_config()))
    batch = {"input_ids": ROWS}
    engine.forward(batch)
    engine.backward()
    with pytest.raises(NotImplementedError, match="rule"):
        engine.step()
    for attr in ("_offload", "_onebit", "_zpp"):
        setattr(engine, attr, object())
        with pytest.raises(NotImplementedError, match="rule"):
            engine.fused_train_step(batch)
        setattr(engine, attr, None)


def test_every_other_path_refuses_the_model():
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.runtime.pipe import PipelineModule

    for model, refused in (
            (model_for(hf_config()), dict(match="latent attention")),
            (TransformerLM(TransformerConfig(
                hidden_size=64, num_heads=4, num_layers=3, num_experts=4,
                moe_dispatch="grouped", first_k_dense=1)),
             dict(match="FFN kinds by layer"))):
        with pytest.raises(NotImplementedError, **refused):
            InferenceEngine(model)
        with pytest.raises(NotImplementedError, **refused):
            InferenceEngineV2(model, max_sequences=2, max_seq_len=32,
                              block_size=8)
        with pytest.raises(NotImplementedError, **refused):
            model.init_kv_cache(1)
        with pytest.raises(NotImplementedError, **refused):
            model.init_paged_kv_cache(4, 8)
        with pytest.raises(NotImplementedError, **refused):
            PipelineModule(model, num_stages=3)
        with pytest.raises(NotImplementedError, **refused):
            model.set_random_ltd(8)
        with pytest.raises(NotImplementedError, **refused):
            model.set_pld_depth(2)
        params = jax.eval_shape(model.init, jax.random.key(0))
        with pytest.raises(NotImplementedError, **refused):
            jax.eval_shape(lambda p: model.hidden_states(
                p, ROWS, pld_theta=jnp.float32(0.5)), params)
        with pytest.raises(NotImplementedError, **refused):
            jax.eval_shape(lambda p: model.forward_prefill(
                p, ROWS, jnp.asarray([24, 24])), params)
    for bad in (dict(loss_tiling=4), dict(attention_impl="fpdt"),
                dict(num_passes=2), dict(sliding_window=8),
                dict(rope_scaling={"rope_type": "linear", "factor": 2.0})):
        with pytest.raises(NotImplementedError, match="latent attention"):
            model_for(hf_config(), **bad)
    with pytest.raises(NotImplementedError, match="q_lora_rank"):
        model_for(hf_config(), q_lora_rank=16)
    with pytest.raises(ValueError, match="grouped"):
        TransformerConfig(hidden_size=64, num_heads=4, num_experts=4,
                          moe_scoring="sigmoid")
    with pytest.raises(ValueError, match="first_k_dense"):
        TransformerConfig(hidden_size=64, num_heads=4, num_layers=2,
                          num_experts=4, moe_dispatch="grouped",
                          first_k_dense=2)


def test_the_published_config_maps_onto_the_model(tmp_path):
    from deepspeed_tpu.models.hf import config_from_hf, load_hf_checkpoint

    with open(CELL_CONFIG) as f:
        hf = json.load(f)
    published = {**hf, "num_hidden_layers": 48, "n_routed_experts": 128,
                 "vocab_size": 128256}
    cfg = config_from_hf(published, moe_bias_rate=GAMMA)
    assert (cfg.num_layers, cfg.num_experts, cfg.top_k) == (48, 128, 6)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.rope_interleave) == (512, 128, 64, 128, True)
    assert (cfg.first_k_dense, cfg.moe_scoring, cfg.moe_routed_scale,
            cfg.moe_shared_experts) == (1, "sigmoid", 2.448, 2)
    assert cfg.layer_kinds == ("mla:dense",) + ("mla:moe",) * 47
    assert cfg.rope_dim == 64 and not cfg.tie_embeddings
    assert TransformerLM(cfg)._layer_plan() == [
        (0, 1, ("mla:dense",)), (1, 48, ("mla:moe",))]
    # the whole model's count: 48 mixers, one dense FFN, 47 routed layers
    # of 128 experts and two shared, embedding and head
    routed = 128 * 3 * 2048 * 768 + 3 * 2048 * 1536 + 2048 * 128 + 128
    assert cfg.num_params_estimate() == (
        48 * (26_345_984 + 2 * 2048) + 3 * 2048 * 6144 + 47 * routed
        + 2 * 128256 * 2048 + 2048)
    for bad, match in ((dict(q_lora_rank=1536), "q_lora_rank"),
                       (dict(n_group=8), "n_group"),
                       (dict(rope_scaling={"type": "yarn", "factor": 40}),
                        "rope_scaling")):
        with pytest.raises(ValueError, match=match):
            config_from_hf({**published, **bad})
    with open(tmp_path / "config.json", "w") as f:
        json.dump(published, f)
    with pytest.raises(NotImplementedError, match="deepseek_v3"):
        load_hf_checkpoint(str(tmp_path))


# ---- the cell's check sees each fault --------------------------------------

def _attention_scaled_by_nope_width(q, k, v, orig=ref.attention):
    return orig(q * math.sqrt(q.shape[-1] / 16.0), k, v)       # 1/sqrt(dn)


def _rope_by_halves(x, positions, theta):
    dr = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :dr // 2], x[..., dr // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _mla_variant(norm=True, shared_rope=True):
    def mla(h, w, cfg, positions):
        H, r, dn, dr, dv = 4, 32, 16, 8, 16
        T, theta = h.shape[0], float(cfg["rope_theta"])
        q = (h @ w["wq"]).reshape(T, H, dn + dr)
        ckv = h @ w["wkv_a"]
        c = ref.rms_norm(ckv[:, :r], w["kv_norm"], 1e-6) if norm \
            else ckv[:, :r]
        kv = (c @ w["wkv_b"]).reshape(T, H, dn + dv)
        k_rope = jnp.broadcast_to(
            ref.rope_pairs(ckv[:, None, r:], positions, theta), (T, H, dr))
        if not shared_rope:     # only the first head gets the rope key
            k_rope = k_rope * (jnp.arange(H) == 0)[None, :, None]
        q = jnp.concatenate([q[..., :dn], ref.rope_pairs(
            q[..., dn:], positions, theta)], axis=-1)
        k = jnp.concatenate([kv[..., :dn], k_rope], axis=-1)
        return ref.attention(q, k, kv[..., dn:]).reshape(T, H * dv) @ w["wo"]
    return mla


def _route_variant(softmax=False, bias_in_choice=True, bias_in_weights=False):
    def route(x, router, bias, k, scale):
        s = jax.nn.softmax(x @ router, axis=-1) if softmax \
            else jax.nn.sigmoid(x @ router)
        _, top_e = jax.lax.top_k(s + (bias if bias_in_choice else 0.0), k)
        top_s = jnp.take_along_axis(s + (bias if bias_in_weights else 0.0),
                                    top_e, axis=-1)
        return s, top_e, scale * top_s / jnp.sum(top_s, -1, keepdims=True)
    return route


def _fp8(params):
    return jax.tree_util.tree_map(
        lambda w: w.astype(jnp.float8_e4m3fn).astype(jnp.float32), params)


FAULTS = {
    "a softmax scale of 1/sqrt(128)":
        dict(attention=_attention_scaled_by_nope_width),
    "rope by halves on unpermuted weights": dict(rope_pairs=_rope_by_halves),
    "the rope key not shared by the heads":
        dict(mla=_mla_variant(shared_rope=False)),
    "the latent's norm left out": dict(mla=_mla_variant(norm=False)),
    "softmax scoring": dict(route=_route_variant(softmax=True)),
    "the bias left out of the choice":
        dict(route=_route_variant(bias_in_choice=False)),
    "the bias inside the weights":
        dict(route=_route_variant(bias_in_weights=True)),
    "the routed scale 2.448 left out":
        dict(hf={"routed_scaling_factor": 1.0}),
    "the shared experts left out": dict(hf={"n_shared_experts": 0}),
    "the dense layer routed":
        dict(hf={"first_k_dense_replace": 0}, dense_as_routed=True),
    "fp8-rounded weights": dict(weights=_fp8),
}


@pytest.fixture(scope="module")
def cell_check(run_memo):
    """The cell's own tolerances, and the reference at a small size (hidden
    256, a dense and three routed layers, 64-token rows) on bf16-rounded
    weights."""
    with open(CELL_CONFIG) as f:
        check = json.load(f)["check"]
    hf = hf_config(L=4, D=256, V=512, held=16, first=0, routed=16)
    params = init(model_for(hf), seed=5, router_gain=2.0)
    # queries and keys that prefer some positions, so that a rope or a score
    # fault moves what is attended to
    mla = params["layers"]["mla"]
    mla["wq"], mla["wkv_a"] = mla["wq"] * 3.0, mla["wkv_a"] * 3.0
    bias = params["layers"]["mlp_moe"]["router_bias"]
    params = jax.tree_util.tree_map(
        lambda w: w.astype(jnp.bfloat16).astype(jnp.float32), params)
    params["layers"]["mlp_moe"]["router_bias"] = bias      # kept in float32
    rows = np.random.default_rng(7).integers(0, 512, (2, 64)).astype(np.int32)
    return check, hf, params, rows, run_memo(
        "kanana_cell_check", lambda: ref.batch_loss(
            hf, getter(params, hf), rows, ALPHA))


def _failed(check, got, want, bias):
    """The compared quantities that lie outside the cell's tolerance (the
    benchmark runner's rules: ``runners/train_hybrid.py:compare`` and
    ``runners/train_mla_moe.py:compare_biases``)."""
    out = []
    for name in check["compared"]:
        g = np.asarray(got[name], np.float64)
        w = np.asarray(want[name], np.float64)
        if g.shape != w.shape:
            out.append(name)
        elif f"{name}_rel_tol" in check:
            if not np.max(np.abs(g - w) / np.abs(w)) \
                    <= check[f"{name}_rel_tol"]:
                out.append(name)
        elif not np.max(np.abs(g - w)) <= check[f"{name}_abs_tol"]:
            out.append(name)
    counts = np.asarray(want["router_counts"], np.float64)
    far = np.abs(counts - counts.mean(-1, keepdims=True)) \
        > check["expert_pairs_abs_tol"]
    if got["router_counts"].shape != counts.shape or np.max(np.where(
            far, np.abs(ref.bias_after(bias, got["router_counts"], GAMMA)
                        - ref.bias_after(bias, counts, GAMMA)), 0.0)) \
            > check["bias_abs_tol"]:
        out.append("router_bias")
    return out


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_cells_check_sees_the_fault(cell_check, monkeypatch, fault):
    check, hf, params, rows, want = cell_check
    how = FAULTS[fault]
    for name in ("attention", "rope_pairs", "mla", "route"):
        if name in how:
            monkeypatch.setattr(ref, name, how[name])
    bad_hf = {**hf, **how.get("hf", {})}
    bad = how.get("weights", lambda p: p)(params)
    got = ref.batch_loss(bad_hf, getter(
        bad, hf, dense_as_routed=how.get("dense_as_routed", False)), rows,
        ALPHA)
    bias = params["layers"]["mlp_moe"]["router_bias"]
    assert _failed(check, got, want, bias), fault
    assert not _failed(check, want, want, bias)


def test_a_model_without_the_layers_loads_none_of_their_modules():
    """``import deepspeed_tpu`` and building, sharding and running a model
    with plain attention load ``models/mla.py`` no more than they load
    ``models/mamba.py`` (``setup_s`` of the cells that are there)."""
    code = (
        "import sys, jax, jax.numpy as jnp\n"
        "import deepspeed_tpu\n"
        "from deepspeed_tpu.models import TransformerConfig, TransformerLM\n"
        "m = TransformerLM(TransformerConfig(hidden_size=64, num_heads=4,"
        " num_layers=2, vocab_size=64, num_experts=4, moe_dispatch='grouped'))\n"
        "p = m.init(jax.random.key(0)); m.param_specs()\n"
        "m.cfg.num_params_estimate(); m.step_program_facts()\n"
        "jax.jit(jax.grad(m.loss_fn))(p, {'input_ids': jnp.zeros((1, 8), "
        "'int32')})\n"
        "print([k for k in sys.modules if k.endswith('.mla') or 'mamba' in k])")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, text=True,
                         capture_output=True,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"
