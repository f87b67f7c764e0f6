"""Gated delta-rule layers and full-attention layers in turn, a block with its
norm after each branch, q/k norms and a held share of the heads
(Olmo-Hybrid): the chunked rule against the recurrence over positions, the
program against the plain reference (``olmo_hybrid_reference.py``, a copy of
``benchmarks/reference_olmo_hybrid.py``) on seeded random weights, the shares
of the heads against the uncut layer, what the step leaves in the record, the
paths that refuse the model, the published config's mapping, and the faults
the benchmark cell's check has to see."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_olmo_hybrid as ref
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.models.hf import config_from_hf
from deepspeed_tpu.ops import delta_rule
from deepspeed_tpu.ops.delta_rule import (chunked_delta_rule,
                                          unit_lower_inverse)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL_CONFIG = os.path.join(ROOT, "benchmarks", "configs",
                           "olmo_hybrid_7b_train_d4h15v8.json")
PERIOD = ["linear_attention"] * 3 + ["full_attention"]


@pytest.fixture(autouse=True)
def chunks_of_eight(monkeypatch):
    """The rule's chunk is a constant, 64 positions, read when a program is
    traced; the rows here are 24 to 64 positions long, so the tests trace
    with 8 (and say so where they take another)."""
    monkeypatch.setattr(delta_rule, "CHUNK", 8)


PAIR = ["linear_attention", "full_attention"]


def hf_config(L=4, D=64, V=96, heads=4, d=16, types=None, **over):
    return {"model_type": "olmo_hybrid", "vocab_size": V, "hidden_size": D,
            "intermediate_size": 2 * D, "num_hidden_layers": L,
            "num_attention_heads": heads, "num_key_value_heads": heads,
            "head_dim": d, "hidden_act": "silu",
            "max_position_embeddings": 64, "attention_bias": False,
            "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
            "layer_types": types or PERIOD * (L // 4),
            "linear_num_key_heads": heads, "linear_num_value_heads": heads,
            "linear_key_head_dim": d // 2, "linear_value_head_dim": d,
            "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
            "rope_parameters": {"rope_theta": None}, **over}


def model_for(hf, **over):
    kw = dict(dtype="float32", attention_impl="xla",
              head_dim_override=hf["head_dim"])
    kw.update(over)
    return TransformerLM(config_from_hf(hf, **kw))


def getter(params, hf):
    layers = params["layers"]
    kinds = hf["layer_types"][:hf["num_hidden_layers"]]

    def get(name, layer=None):
        if name == "embed":
            return params["embed"]["tokens"]
        if name == "lm_head":
            return params["lm_head"]
        if name == "final_norm":
            return params["final_norm"]["scale"]
        if name in ("ln1_post", "ln2_post"):
            return layers[name]["scale"][layer]
        if name in ("w_gate", "w_up", "w_down"):
            return layers["mlp"][name][layer]
        group = "delta" if kinds[layer] == "linear_attention" else "attn"
        return layers[group][name][kinds[:layer].count(kinds[layer])]

    return get


def init(model, seed=0):
    """Seeded random weights; the leaves the initialiser sets to a constant
    (the norms' scales) drawn too, so that leaving one out shows."""
    params = jax.jit(model.init)(jax.random.key(seed))     # one program, not an op at a time
    keys = iter(jax.random.split(jax.random.key(seed + 100), 8))
    layers = params["layers"]
    for group, name in (("delta", "o_norm"), ("attn", "q_norm"),
                        ("attn", "k_norm"), ("ln1_post", "scale"),
                        ("ln2_post", "scale")):
        s = layers[group][name]
        layers[group][name] = 1.0 + 0.2 * jax.random.normal(next(keys),
                                                            s.shape)
    return params


ROWS = np.random.default_rng(0).integers(0, 96, (2, 24)).astype(np.int32)


# ---- the rule -------------------------------------------------------------

def _rule_inputs(T=40, H=3, dk=8, dv=16, B=2, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa
    return (ref.l2_norm(f(B, T, H, dk)), ref.l2_norm(f(B, T, H, dk)),
            f(B, T, H, dv),
            -jax.nn.softplus(f(B, T, H)) * jnp.exp(f(H)),
            2.0 * jax.nn.sigmoid(f(B, T, H)))


def _recurrence(q, k, v, g, beta):
    return jnp.stack([ref.recurrence(q[i], k[i], v[i], g[i], beta[i])
                      for i in range(q.shape[0])])


@pytest.fixture(scope="module")
def rule():
    args = _rule_inputs()
    with jax.default_matmul_precision("highest"):
        return args, jax.jit(_recurrence)(*args)


@pytest.mark.parametrize("chunk", [8, 16, 40, 64, 7, 50])
def test_the_chunked_rule_is_the_recurrence(rule, chunk, monkeypatch):
    """Whole chunks, one chunk, a T that is padded (40 in chunks of 16, of
    64, of 7), a chunk whose halves are odd (50: rows in turn all through)."""
    (q, k, v, g, beta), want = rule
    monkeypatch.setattr(delta_rule, "CHUNK", chunk)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: chunked_delta_rule(*a))(
            q / np.sqrt(8.0), k, v, g, beta)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_the_rules_gradients_are_the_recurrences(monkeypatch):
    args = _rule_inputs(T=40, B=1, seed=1)
    monkeypatch.setattr(delta_rule, "CHUNK", 16)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(chunked_delta_rule(
            a[0] / np.sqrt(8.0), *a[1:]))), argnums=range(5)))(*args)
        want = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(_recurrence(*a))),
                                argnums=range(5)))(*args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()))


def test_strong_steps_on_one_key_keep_the_inverse_exact():
    """``beta <k_i, k_j>`` = 2 everywhere below the diagonal: the series
    ``sum (-A)^n`` would add terms of 2^n C(63, n); substitution gives the
    inverse, whose entries are +-2."""
    n = 64
    a = jnp.tril(jnp.full((n, n), 2.0), -1)
    t = unit_lower_inverse(a[None])[0]
    np.testing.assert_allclose(t @ (jnp.eye(n) + a), jnp.eye(n), atol=1e-5)
    assert float(jnp.abs(t).max()) == 2.0


def test_bf16_inputs_keep_the_decays_in_float32(monkeypatch):
    q, k, v, g, beta = _rule_inputs(T=64)
    monkeypatch.setattr(delta_rule, "CHUNK", 16)
    rule = jax.jit(lambda *a: chunked_delta_rule(*a))
    lo = rule(*(a.astype(jnp.bfloat16) for a in (q, k, v)), g, beta)
    assert lo.dtype == jnp.bfloat16
    hi = rule(q, k, v, g, beta)
    np.testing.assert_allclose(lo.astype(jnp.float32), hi, atol=0.06)


# ---- the model against the reference --------------------------------------

@pytest.fixture(scope="module")
def small(run_memo):
    hf = hf_config()
    model = model_for(hf)
    params = init(model)
    return hf, model, params, run_memo(
        "olmo_hybrid_small", lambda: ref.batch_loss(hf, getter(params, hf), ROWS))


def test_loss_and_mixer_outputs_match_the_reference(small, monkeypatch):
    """24 positions in chunks of 8 and (a second program) of 16: a T that is
    no multiple of the chunk."""
    hf, model, params, want = small
    for m, chunk in ((model, 8), (model_for(hf), 16)):
        monkeypatch.setattr(delta_rule, "CHUNK", chunk)
        loss, parts = jax.jit(m.loss_and_parts)(params, {"input_ids": ROWS})
        np.testing.assert_allclose(loss, want["loss"], atol=2e-5)
        np.testing.assert_allclose(parts["mix_out_ms"], want["mix_out_ms"],
                                   rtol=5e-5)


def test_gradients_match_the_reference():
    """(A delta layer and a full one: every leaf's gradient at half the
    program.)"""
    hf = hf_config(L=2, types=PAIR)
    model = model_for(hf)
    params = init(model, seed=1)
    got = jax.jit(jax.grad(model.loss_fn))(params, {"input_ids": ROWS})
    get, got_of = getter(params, hf), getter(got, hf)
    weights = {(n, None): get(n) for n in ("embed", "lm_head", "final_norm")}
    for i, kind in enumerate(hf["layer_types"]):
        weights.update({(n, i): get(n, i) for n in ref.TENSORS[kind]})
    _, want = ref.loss_and_grads(hf, weights, ROWS)
    for (name, layer), g in want.items():
        scale = float(np.abs(g).max())
        np.testing.assert_allclose(
            got_of(name, layer), g, atol=5e-5 * max(scale, 1.0) + 1e-7,
            err_msg=f"{name} of layer {layer}")


def test_the_references_gradient_a_layer_at_a_time_is_the_whole_graphs(
        monkeypatch):
    """What the benchmark's runner compares the step's gradient with: a
    layer's ``jax.vjp`` at a time, the recurrence walked in runs of 64 under
    ``jax.checkpoint``, against ``jax.grad`` of the whole with the
    recurrence walked whole; given a sink, the same gradients handed over."""
    hf = hf_config(L=2, types=PAIR)
    params = init(model_for(hf), seed=2)
    get = getter(params, hf)
    weights = {(n, None): get(n) for n in ("embed", "lm_head", "final_norm")}
    for i, kind in enumerate(hf["layer_types"]):
        weights.update({(n, i): get(n, i) for n in ref.TENSORS[kind]})
    rows = np.random.default_rng(3).integers(0, 96, (2, 64)).astype(np.int32)
    out, got = ref.batch_loss_and_grads(hf, get, rows)
    sunk = {}
    out2, none = ref.batch_loss_and_grads(
        hf, get, rows, lambda n, i, g: sunk.__setitem__((n, i), g))
    assert none == {} and sorted(sunk) == sorted(got)
    monkeypatch.setattr(ref, "_STATE_BLOCK", 7)     # 64 = 9 x 7 + 1: whole
    loss, want = ref.loss_and_grads(hf, weights, rows)
    both = ref.batch_loss(hf, get, rows)
    np.testing.assert_allclose(out["loss"], loss, rtol=1e-6)
    np.testing.assert_allclose(out["mix_out_ms"], both["mix_out_ms"],
                               rtol=1e-6)
    for key, g in want.items():
        scale = float(np.abs(g).max())
        for mine in (got, sunk):
            np.testing.assert_allclose(mine[key], g, atol=1e-5 * scale,
                                       err_msg=str(key))


def test_the_references_adamw_step_is_optaxs():
    import optax

    w = jax.random.normal(jax.random.key(0), (5, 7))
    g = 1e-4 * jax.random.normal(jax.random.key(1), (5, 7)).at[0, 0].set(0.0)
    for decay in (0.0, 0.1):
        tx = optax.adamw(1e-3, weight_decay=decay)
        want, _ = tx.update(g, tx.init(w), w)
        np.testing.assert_allclose(
            ref.adamw_first_step(g, w, lr=1e-3, weight_decay=decay), want,
            rtol=1e-5, atol=1e-12)


def test_the_parameters_are_the_counts_estimate(small):
    _, model, params, _ = small
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == model.cfg.num_params_estimate()
    assert sorted(params["layers"]) == ["attn", "delta", "ln1_post",
                                        "ln2_post", "mlp"]
    specs = model.param_specs()
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda x: 0, params)) == \
        jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda x: 0, specs, is_leaf=lambda s: not isinstance(s, dict)))


def test_a_post_norm_block_of_plain_attention_layers():
    """The arm is every mixer kind's: a dense model with rope, a norm after
    each branch and none before, through the one-kind layer loop."""
    model = TransformerLM(TransformerConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        dtype="float32", attention_impl="xla", norm_placement="post"))
    params = model.init(jax.random.key(0))
    assert sorted(params["layers"]) == ["attn", "ln1_post", "ln2_post", "mlp"]
    pre = TransformerLM(TransformerConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        dtype="float32", attention_impl="xla"))
    x = jax.random.normal(jax.random.key(1), (1, 8, 32))
    w = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    from deepspeed_tpu.models import transformer as tf

    y, _ = tf.transformer_block(x, w, model.cfg, model._freqs,
                                tf.xla_attention)
    attn = tf.attention_block(x, w["attn"], pre.cfg, pre._freqs,
                              tf.xla_attention)
    h = x + tf._norm(attn, w["ln1_post"], "rmsnorm", 1e-5)
    want = h + tf._norm(tf.mlp_block(h, w["mlp"], pre.cfg), w["ln2_post"],
                        "rmsnorm", 1e-5)
    np.testing.assert_allclose(y, want, atol=1e-6)
    assert np.isfinite(float(jax.jit(model.loss_fn)(
        params, {"input_ids": ROWS[:, :8] % 64})))


# ---- the share of the heads -----------------------------------------------

def _halves(w, width, names_cols, names_rows):
    """The two halves of an uncut mixer's leaves: the first and the second
    half of the columns of ``names_cols`` and of the rows of ``names_rows``
    (each ``width`` a head)."""
    out = []
    for lo in (0, 1):
        part = dict(w)
        for n in names_cols:
            half = w[n].shape[-1] // 2
            part[n] = w[n][..., lo * half:(lo + 1) * half]
        for n in names_rows:
            half = w[n].shape[0] // 2
            part[n] = w[n][lo * half:(lo + 1) * half]
        out.append(part)
    return out


def test_the_two_halves_of_the_heads_add_up_to_the_uncut_layer():
    """A model that holds heads [0, 2) and one that holds heads [2, 4) of 4,
    given the halves of an uncut model's leaves: a delta layer's partial
    outputs add up to the uncut reference's exactly (heads are independent,
    the output norm is per head); the full layer's to the uncut reference's
    with its q/k norm taken over each half's columns, the one place where
    the share changes arithmetic."""
    from deepspeed_tpu.models import transformer as tf
    from deepspeed_tpu.models.gated_delta import delta_block

    hf = hf_config()
    whole = model_for(hf)
    params = init(whole, seed=3)
    u = jax.random.normal(jax.random.key(9), (24, 64))
    get = getter(params, hf)
    with jax.default_matmul_precision("highest"):
        wd = {n: get(n, 0) for n in ref.TENSORS["linear_attention"]}
        wa = {n: get(n, 3) for n in ref.TENSORS["full_attention"]}
        want_delta = ref.delta_layer(u, wd, hf)
        want_attn = ref.attention_layer(u, wa, hf, norm_shares=2)
        uncut_attn = ref.attention_layer(u, wa, hf)
        got_delta = got_attn = 0.0
        cols = ("wq", "wk", "wv", "wz", "wb", "wa", "conv_q", "conv_k",
                "conv_v", "A_log", "dt_bias")
        cfg = model_for(hf, heads_held=2).cfg
        for d_half, a_half in zip(
                _halves(wd, 0, cols, ("wo",)),
                _halves(wa, 0, ("wq", "wk", "wv", "q_norm", "k_norm"),
                        ("wo",))):
            assert cfg.heads_here == 2 and cfg.kv_heads_here == 2
            got_delta += delta_block(u[None], d_half, cfg)[0]
            got_attn += tf.attention_block(u[None], a_half, cfg, None,
                                           tf.xla_attention)[0]
    np.testing.assert_allclose(got_delta, want_delta, atol=2e-6)
    np.testing.assert_allclose(got_attn, want_attn, atol=2e-6)
    # and the whole-width norm is another number: nothing stands in for it
    assert float(jnp.abs(uncut_attn - want_attn).max()) > 1e-3


# ---- the engine -----------------------------------------------------------

def _engine(model, stage=0, rows=2, **axes):
    """An engine on one device, or on the mesh ``axes`` names (``rows`` the
    global batch)."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.parallel import build_mesh

    mesh = build_mesh(axis_sizes=axes) if axes \
        else build_mesh(devices=jax.devices()[:1])
    return ds.initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": rows // axes.get("fsdp", 1),
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "steps_per_print": 10 ** 9,
                "zero_optimization": {"stage": stage,
                                      "param_persistence_threshold": 0}},
        mesh=mesh)[0]


def test_the_step_record_and_the_step_programs_row():
    """Through ``initialize`` -> ``fused_train_step`` like any model: the
    record carries the mixer outputs, the row the kinds, the share of the
    heads, the chunk, the chunks a step goes through and the lowerings."""
    from deepspeed_tpu.observability import steplog

    model = TransformerLM(config_from_hf(
        hf_config(L=2, types=PAIR), dtype="float32", attention_impl="xla",
        head_dim_override=16, heads_held=2))
    engine = _engine(model)
    params = jax.device_get(engine.params)
    # the leaves are built for the heads held, and the reference given the
    # same share agrees
    assert params["layers"]["attn"]["wq"].shape == (1, 64, 32)
    assert params["layers"]["attn"]["q_norm"].shape == (1, 32)
    assert params["layers"]["delta"]["wv"].shape == (1, 64, 32)
    assert params["layers"]["delta"]["wo"].shape == (1, 32, 64)
    half = hf_config(L=2, types=PAIR, heads=2, d=16,
                     linear_key_head_dim=8, linear_value_head_dim=16)
    ref_want = ref.batch_loss(half, getter(params, half), ROWS)
    want, want_parts = jax.jit(model.loss_and_parts)(params,
                                                     {"input_ids": ROWS})
    np.testing.assert_allclose(want, ref_want["loss"], atol=2e-5)
    np.testing.assert_allclose(want_parts["mix_out_ms"],
                               ref_want["mix_out_ms"], rtol=5e-5)
    loss = float(engine.fused_train_step({"input_ids": ROWS}))
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    parts = steplog.get_steplog().parts(last=1)[-1]
    np.testing.assert_allclose(parts["mix_out_ms"], want_parts["mix_out_ms"],
                               rtol=1e-4)
    row = [p for p in steplog.programs()
           if p.name.startswith("ds_train_step")][-1]
    assert row.layer_pattern == ("delta", "full")
    assert row.heads_held == (2, 4)
    assert row.delta_chunk == 8
    assert row.delta_chunks_per_step == 1 * 2 * 3
    assert row.delta_scan_lowerings == {"xla": 1}
    assert row.conv_lowerings == {"xla": 3}        # q's, k's and v's
    assert row.ssm_chunk is None and row.ssm_scan_lowerings is None
    # A_log and dt_bias moved in float32, by the optimizer
    after = jax.device_get(engine.params)["layers"]["delta"]
    assert after["A_log"].dtype == np.float32
    assert np.any(after["dt_bias"] != params["layers"]["delta"]["dt_bias"])


_SMALL_CELL = {}


def _small_cell():
    """What the benchmark's cell is in small: bf16, keys of 32 and values of
    64 in chunks of 64, the cell's ``dots_saveable``, one delta layer and one
    full one (a second and a third delta layer run what the first runs), rows
    of 80 positions: two chunks, the second padded, and whole sublane tiles
    for the convolution's kernels. Returns (a fresh engine's builder, the
    rows, the loss of the step as the pickers choose on a CPU, and what that
    program's row counted), the last two computed once for the cases that
    compare with them."""
    hf = hf_config(L=2, types=PAIR, D=128, heads=2, d=64,
                   max_position_embeddings=128)
    rows = np.random.default_rng(2).integers(0, 96, (2, 80)).astype(np.int32)

    def build():
        return _engine(model_for(hf, remat_policy="dots_saveable",
                                 dtype="bfloat16", max_seq_len=128))

    if not _SMALL_CELL:
        from deepspeed_tpu.observability import steplog

        _SMALL_CELL["loss"] = float(build().fused_train_step(
            {"input_ids": rows}))
        _SMALL_CELL["counted"] = dict(steplog.programs()[-1].counted)
    return build, rows, _SMALL_CELL["loss"], _SMALL_CELL["counted"]


def test_the_cell_shaped_step_program_takes_the_rule_kernels(monkeypatch):
    """The small cell (:func:`_small_cell`): every rule of the program is the
    Pallas kernels (interpreted here: the CPU stands in for the chip), the
    backward too, and the step gives the einsum form's loss; without the
    handle the picker's answer stands, the einsum form. A kernel that
    mishandled the padded second chunk or the carried state would move the
    loss."""
    import functools

    from deepspeed_tpu.models import gated_delta
    from deepspeed_tpu.observability import steplog

    monkeypatch.setattr(delta_rule, "CHUNK", 64)
    build, rows, plain, counted = _small_cell()
    assert counted["delta_scan"] == {"xla": 1}
    monkeypatch.setattr(gated_delta, "chunked_delta_rule", functools.partial(
        gated_delta.chunked_delta_rule, interpret=True))
    loss = float(build().fused_train_step({"input_ids": rows}))
    prog = steplog.programs()[-1]
    # the rule and its backward
    assert prog.delta_scan_lowerings == {"pallas": 2}
    assert prog.delta_chunks_per_step == 1 * 2 * 2    # 80 tokens: 2 chunks
    np.testing.assert_allclose(loss, plain, atol=2e-3)


def test_the_cell_shaped_step_program_takes_the_conv_kernels(monkeypatch):
    """The same small cell with the convolutions as their Pallas kernels
    (interpreted here): q's and k's leave in float32, v's in bf16, every one
    of the program and its backward is the kernels', and the step gives the
    ``jax.numpy`` form's loss."""
    import functools

    from deepspeed_tpu.models import gated_delta
    from deepspeed_tpu.observability import steplog

    monkeypatch.setattr(delta_rule, "CHUNK", 64)
    build, rows, plain, counted = _small_cell()
    assert counted["conv"] == {"xla": 3}
    monkeypatch.setattr(gated_delta, "causal_conv_silu", functools.partial(
        gated_delta.causal_conv_silu, interpret=True))
    loss = float(build().fused_train_step({"input_ids": rows}))
    # the delta layer's three convolutions and the backward of each
    assert steplog.programs()[-1].conv_lowerings == {"pallas": 6}
    np.testing.assert_allclose(loss, plain, atol=2e-3)


@pytest.mark.parametrize("stage", [3])
def test_zero_stages_shard_the_new_leaves_and_give_the_same_loss(stage):
    model = model_for(hf_config(L=2, types=PAIR))
    rows = np.random.default_rng(4).integers(0, 96, (8, 24)).astype(np.int32)
    eng = _engine(model, stage=stage, rows=8, fsdp=8)
    want = float(jax.jit(model.loss_fn)(jax.device_get(eng.params),
                                        {"input_ids": rows}))
    got = float(eng.fused_train_step({"input_ids": rows}))
    assert got == pytest.approx(want, abs=2e-5)
    for leaf in ("wq", "wz", "wo", "conv_v"):
        spec = eng.param_sharding["layers"]["delta"][leaf].spec
        assert ("fsdp" in jax.tree_util.tree_leaves(tuple(spec))) \
            == (stage == 3), leaf


# ---- what cannot run ------------------------------------------------------

def test_every_other_path_refuses_the_model():
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.runtime.pipe import PipelineModule

    model = model_for(hf_config())
    refused = dict(match="gated delta-rule layers")
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
        PipelineModule(model, num_stages=2)
    with pytest.raises(NotImplementedError, **refused):
        model.set_random_ltd(8)
    with pytest.raises(NotImplementedError, **refused):
        model.set_pld_depth(2)
    params = jax.eval_shape(model.init, jax.random.key(0))
    with pytest.raises(NotImplementedError, **refused):
        jax.eval_shape(lambda p: model.forward_prefill(
            p, ROWS, jnp.asarray([24, 24])), params)
    with pytest.raises(NotImplementedError, match="a tp axis"):
        _engine(model, tp=2)


@pytest.mark.parametrize("bad,says", [
    # (beside routed experts a delta layer runs since PR 66, under the
    # grouped dispatch; the capacity form stays refused)
    (dict(num_experts=4), "runs the grouped dispatch"),
    (dict(num_passes=2), "a looped stack"),
    (dict(sandwich_norm=True, norm_placement="pre"), "a looped stack"),
    (dict(parallel_block=True, norm_placement="pre"), "parallel_block"),
    (dict(loss_tiling=4), "the tiled loss"),
    (dict(attention_impl="fpdt"), "fpdt"),
])
def test_a_delta_layer_refuses_what_cannot_run_beside_it(bad, says):
    with pytest.raises(NotImplementedError, match=says):
        model_for(hf_config(), **bad)


def test_the_new_fields_refuse_what_they_cannot_mean():
    dense = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4)
    for bad, says in (
            (dict(norm_placement="both"), "'pre' or 'post'"),
            (dict(norm_placement="post", sandwich_norm=True), "one norm"),
            (dict(qk_norm="rows"), "None, 'width'"),
            (dict(heads_held=5), "not among"),
            (dict(heads_held=1, num_kv_heads=2), "cut a group"),
            (dict(delta_heads=0, attn_pattern=("delta", "full")),
             "delta_heads"),
            (dict(delta_heads=2, heads_held=3,
                  attn_pattern=("delta", "full")), "delta_heads=2")):
        with pytest.raises(ValueError, match=says):
            TransformerConfig(**{**dense, **bad})
    for bad, says in ((dict(qk_norm="width", kv_lora_rank=16), "qk_norm"),
                      (dict(heads_held=2, qkv_bias=True), "heads_held")):
        with pytest.raises(NotImplementedError, match=says):
            TransformerConfig(**{**dense, **bad})
    # and alone, on a dense model, the serving paths refuse them
    for field in (dict(norm_placement="post"), dict(qk_norm="width"),
                  dict(heads_held=2)):
        model = TransformerLM(TransformerConfig(**dense, **field))
        with pytest.raises(NotImplementedError, match="pre-norm blocks"):
            model.init_kv_cache(1)


# ---- the published config -------------------------------------------------

def _published():
    """The published keys, from the benchmark's configuration file: its own
    values where it cut none, the published ones from ``reduced``."""
    with open(CELL_CONFIG) as f:
        cell = json.load(f)
    hf = {k: v for k, v in cell.items()
          if k not in ("reduced", "assumed", "deployment", "check", "modules",
                       "model", "source", "head_dim")}
    for key, cut in cell["reduced"].items():
        if key in hf:
            hf[key] = cut["published"]
    return hf, cell


def test_the_published_config_maps_onto_the_model():
    hf, cell = _published()
    assert (hf["num_hidden_layers"], hf["num_attention_heads"],
            hf["vocab_size"], hf["linear_num_value_heads"]) == (
                32, 30, 100352, 30)
    cfg = config_from_hf(hf)
    assert cfg.attn_pattern == ("delta", "delta", "delta", "full")
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_layers,
            cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (
                3840, 11008, 32, 30, 30, 128)
    assert (cfg.delta_heads, cfg.delta_key_dim, cfg.delta_value_dim,
            cfg.delta_conv, cfg.delta_neg_eigval) == (30, 96, 192, 4, True)
    assert delta_rule.CHUNK == 8      # (the fixture's; the module's is 64)
    assert (cfg.norm_placement, cfg.qk_norm, cfg.use_rope,
            cfg.tie_embeddings, cfg.norm_eps, cfg.max_seq_len) == (
                "post", "width", False, False, 1e-6, 65536)
    assert cfg.layer_kinds.count("delta") == 24
    # a whole layer of each kind, and the whole model (about 7B)
    one = lambda **kw: config_from_hf(  # noqa: E731
        {**hf, "num_hidden_layers": 4}, **kw).num_params_estimate()
    period = one() - 2 * 100352 * 3840 - 3840
    delta = 3840 * (2 * 2880 + 2 * 5760 + 60) + 4 * 11520 + 252 \
        + 5760 * 3840 + 3 * 3840 * 11008 + 2 * 3840
    full = 4 * 3840 * 3840 + 2 * 3840 + 3 * 3840 * 11008 + 2 * 3840
    assert (delta, full) == (215_570_172, 185_809_920)
    assert period == 3 * delta + full
    # the cell's cut: 15 of 30 heads, an eighth of the vocabulary
    held = config_from_hf({**hf, "num_hidden_layers": 4,
                           "vocab_size": cell["vocab_size"]},
                          heads_held=cell["num_attention_heads"])
    assert held.num_params_estimate() == 766_241_946
    with pytest.raises(ValueError, match="unsupported model_type"):
        config_from_hf({**hf, "model_type": "olmo_hybrid_2"})
    with pytest.raises(ValueError, match="a key head a value head"):
        config_from_hf({**hf, "linear_num_key_heads": 15})
    from deepspeed_tpu.models.hf import load_hf_checkpoint

    with pytest.raises(NotImplementedError, match="olmo_hybrid"):
        load_hf_checkpoint(_config_dir(hf))


def _config_dir(hf):
    import tempfile

    d = tempfile.mkdtemp()
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump(hf, f)
    return d


# ---- what the benchmark cell's check has to see ---------------------------

def _fp8(params):
    return jax.tree_util.tree_map(
        lambda w: w.astype(jnp.float8_e4m3fn).astype(jnp.float32), params)


def _zeroed(group, name):
    def weights(params):
        layers = dict(params["layers"])
        layers[group] = {**layers[group],
                         name: jnp.zeros_like(layers[group][name])}
        return {**params, "layers": layers}
    return weights


def _recurrence_with(decay="first", alpha=True):
    """``ref.recurrence`` with the decay left out, or applied after the delta
    correction and not before."""
    def recurrence(q, k, v, g, beta):
        H, dk = q.shape[1], q.shape[2]

        def step(S, xs):
            q_t, k_t, v_t, g_t, b_t = xs
            a = jnp.exp(g_t)[:, None, None] if alpha else 1.0
            if decay == "first":
                S = a * S
            wrote = b_t[:, None] * (v_t - jnp.einsum("hde,hd->he", S, k_t))
            S = (S if decay == "first" else a * S) \
                + k_t[:, :, None] * wrote[:, None, :]
            return S, jnp.einsum("hde,hd->he", S, q_t) / np.sqrt(dk)

        _, o = jax.lax.scan(step, jnp.zeros((H, dk, v.shape[2])),
                            (q, k, v, g, beta))
        return o
    return recurrence


def _delta_with(two=True, l2=True, scale=True, swap=False, silu=True,
                gate_first=False, softplus=jax.nn.softplus):
    """``ref.delta_layer`` with one thing wrong."""
    def delta_layer(u, w, cfg):
        H = int(cfg["linear_num_value_heads"])
        dk = int(cfg["linear_key_head_dim"])
        dv, T = int(cfg["linear_value_head_dim"]), u.shape[0]
        act = jax.nn.silu if silu else (lambda t: t)
        q, k, v = (act(ref.conv(u @ w[p], w[c])) for p, c in
                   (("wq", "conv_q"), ("wk", "conv_k"), ("wv", "conv_v")))
        if swap:
            q, k = k, q
        beta = (2.0 if two else 1.0) * jax.nn.sigmoid(u @ w["wb"])
        g = -jnp.exp(w["A_log"]) * softplus(u @ w["wa"] + w["dt_bias"])
        unit = ref.l2_norm if l2 else (lambda t: t)
        o = ref.recurrence(unit(q.reshape(T, H, dk)),
                           unit(k.reshape(T, H, dk)), v.reshape(T, H, dv),
                           g, beta) * (1.0 if scale else np.sqrt(dk))
        z = jax.nn.silu(u @ w["wz"]).reshape(T, H, dv)
        eps = float(cfg["rms_norm_eps"])
        y = ref.rms_norm(o * z, w["o_norm"], eps) if gate_first \
            else ref.rms_norm(o, w["o_norm"], eps) * z
        return y.reshape(T, H * dv) @ w["wo"]
    return delta_layer


def _state_dropped_between_chunks(chunk):
    whole = ref.recurrence

    def recurrence(q, k, v, g, beta):
        return jnp.concatenate([
            whole(*(a[lo:lo + chunk] for a in (q, k, v, g, beta)))
            for lo in range(0, q.shape[0], chunk)])
    return recurrence


def _window_shifted_by_one(x, w, conv=ref.conv):
    return conv(jnp.pad(x, ((1, 0), (0, 0)))[:-1], w)


def _pre_norm_block(x, w, cfg, kind):
    eps = float(cfg["rms_norm_eps"])
    mix = (ref.delta_layer if kind == "linear_attention"
           else ref.attention_layer)(ref.rms_norm(x, w["ln1_post"], eps), w,
                                     cfg)
    h = x + mix
    n = ref.rms_norm(h, w["ln2_post"], eps)
    return h + (jax.nn.silu(n @ w["w_gate"]) * (n @ w["w_up"])) \
        @ w["w_down"], jnp.mean(mix * mix)


def _attention_layer_with(rope=False, qk_norm=True):
    def attention_layer(u, w, cfg, norm_shares=1):
        H, d = int(cfg["num_attention_heads"]), int(cfg["head_dim"])
        T, eps = u.shape[0], float(cfg["rms_norm_eps"])
        q, k = u @ w["wq"], u @ w["wk"]
        if qk_norm:
            q = ref.rms_norm(q, w["q_norm"], eps)
            k = ref.rms_norm(k, w["k_norm"], eps)
        q, k = q.reshape(T, H, d), k.reshape(T, H, d)
        if rope:
            ang = jnp.arange(T, dtype=jnp.float32)[:, None] / (
                10000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))[None]
            cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]

            def turn(x):
                x1, x2 = x[..., :d // 2], x[..., d // 2:]
                return jnp.concatenate([x1 * cos - x2 * sin,
                                        x2 * cos + x1 * sin], -1)
            q, k = turn(q), turn(k)
        o = ref.attention(q, k, (u @ w["wv"]).reshape(T, H, d))
        return o.reshape(T, H * d) @ w["wo"]
    return attention_layer


FAULTS = {
    "beta without the 2": dict(delta_layer=_delta_with(two=False)),
    "alpha left out": dict(recurrence=_recurrence_with(alpha=False)),
    "the decay applied after the delta correction":
        dict(recurrence=_recurrence_with(decay="after")),
    "the L2 norms left out": dict(delta_layer=_delta_with(l2=False)),
    "1/sqrt(d_k) left out": dict(delta_layer=_delta_with(scale=False)),
    "q and k exchanged": dict(delta_layer=_delta_with(swap=True)),
    "the convolution's window shifted by one":
        dict(conv=_window_shifted_by_one),
    "the convolution's silu left out": dict(delta_layer=_delta_with(
        silu=False)),
    "the gate before the norm": dict(delta_layer=_delta_with(
        gate_first=True)),
    "the decay without softplus": dict(delta_layer=_delta_with(
        softplus=lambda t: t)),
    "the decay without dt_bias": dict(weights=_zeroed("delta", "dt_bias")),
    "the state not carried across a chunk boundary":
        dict(recurrence=_state_dropped_between_chunks(8)),
    "pre-norm in place of post-norm": dict(block=_pre_norm_block),
    "the q/k norm left out": dict(attention_layer=_attention_layer_with(
        qk_norm=False)),
    "a rope applied": dict(attention_layer=_attention_layer_with(rope=True)),
    "fp8-rounded weights": dict(weights=_fp8),
}


@pytest.fixture(scope="module")
def cell_check(run_memo):
    """The cell's own tolerances, and the reference at a small size (hidden
    256, one period, 64-token rows) on bf16-rounded weights."""
    with open(CELL_CONFIG) as f:
        check = json.load(f)["check"]
    hf = hf_config(D=256, V=512, heads=4, d=32)
    params = init(model_for(hf), seed=5)
    # scales that tell a norm's input from its output: the q/k norms'
    # projections are drawn at unit width, so a norm left out would not show
    attn = params["layers"]["attn"]
    attn["wq"], attn["wk"] = attn["wq"] * 3.0, attn["wk"] * 3.0
    params = jax.tree_util.tree_map(
        lambda w: w.astype(jnp.bfloat16).astype(jnp.float32), params)
    rows = np.random.default_rng(7).integers(0, 512, (2, 64)).astype(np.int32)
    return check, hf, params, rows, run_memo(
        "olmo_hybrid_cell_check", lambda: ref.batch_loss(
            hf, getter(params, hf), rows))


def _failed(check, got, want):
    """What the benchmark runner's own rule makes of the forward's part of
    the cell's check (at this size the loss's range is not the cell's)."""
    from benchmarks.runners.train_hybrid import compare

    return compare({k: np.asarray(v) for k, v in got.items()}, want,
                   {**check, "compared": ["loss", "mix_out_ms"],
                    "first_loss_range": [0.0, np.inf]})[0]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_cells_check_sees_the_fault(cell_check, monkeypatch, fault):
    check, hf, params, rows, want = cell_check
    how = FAULTS[fault]
    for name in ("delta_layer", "attention_layer", "recurrence", "conv",
                 "block"):
        if name in how:
            monkeypatch.setattr(ref, name, how[name])
    got = ref.batch_loss(hf, getter(how.get("weights", lambda p: p)(params),
                                    hf), rows)
    assert _failed(check, got, want), fault
    assert not _failed(check, want, want)


def test_the_program_passes_the_cells_check_at_the_small_size(cell_check,
                                                              monkeypatch):
    check, hf, params, rows, want = cell_check
    monkeypatch.setattr(delta_rule, "CHUNK", 16)
    model = model_for(hf)
    loss, parts = jax.jit(model.loss_and_parts)(params, {"input_ids": rows})
    assert not _failed(check, {"loss": loss, **parts}, want)
