"""Qwen3-Next on the training path: gated delta-rule layers whose key heads
serve several value heads beside a gated full-attention layer, zero-centred
norms, softmax-routed experts beside a gated shared one. The program through
``initialize`` / ``fused_train_step`` against the plain reference
(``benchmarks/reference_qwen3_next.py``) on seeded random weights at a small
size (hidden 64, 2 key heads serving 4 value heads, 16 experts at 3 a token,
one period); the rule's kernels interpreted at 128 / 128 with shared key
heads; the gate a channel; the shares of the experts against the uncut
layer; the published tensors' mapping and back; the refusals by name."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import modelcfg_qwen3_next as mc
from benchmarks import reference_qwen3_next as ref
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.models import hf as hf_map
from deepspeed_tpu.models.hf import config_from_hf
from deepspeed_tpu.ops import delta_rule

ALPHA = 0.01


@pytest.fixture(autouse=True)
def chunks_of_eight(monkeypatch):
    """The rule's chunk is a constant, 64 positions, read when a program is
    traced; the rows here are 24 positions long, so the tests trace with 8
    (the kernels' tests say 64 themselves)."""
    monkeypatch.setattr(delta_rule, "CHUNK", 8)


def file_cfg(**over):
    """A configuration file's keys (``benchmarks/configs/qwen3_next_*``) at
    the small size."""
    cfg = {"model_type": "qwen3_next", "vocab_size": 96, "hidden_size": 64,
           "num_hidden_layers": 4, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16,
           "intermediate_size": 128, "max_position_embeddings": 64,
           "rope_theta": 1e7, "partial_rotary_factor": 0.25,
           "rms_norm_eps": 1e-6, "full_attention_interval": 4,
           "linear_num_key_heads": 2, "linear_num_value_heads": 4,
           "linear_key_head_dim": 16, "linear_value_head_dim": 16,
           "linear_conv_kernel_dim": 4, "num_experts": 16,
           "router_width": 16, "num_experts_per_tok": 3,
           "moe_intermediate_size": 32,
           "shared_expert_intermediate_size": 32, "norm_topk_prob": True,
           "decoder_sparse_step": 1, "mlp_only_layers": [],
           "tie_word_embeddings": False, "hidden_act": "silu",
           "rope_scaling": None, "use_sliding_window": False,
           "deployment": {"balance_coef": ALPHA, "local_pairs_factor": 0.0,
                          "remat_policy": "none", "embed_init_std": 1.0}}
    cfg.update(over)
    return cfg


def model_for(cfg, **over):
    kw = dict(dtype="float32", attention_impl="xla")
    kw.update(over)
    return TransformerLM(mc.transformer_config(
        cfg, max_seq_len=64, param_dtype="float32", **kw))


def init(model, seed=0):
    """Seeded random weights; the leaves the initialiser sets to a constant
    (the norms' scales: 0 where zero-centred, 1 for the delta layer's) drawn
    too, so that leaving one out, or taking ``w`` for ``1 + w``, shows."""
    params = jax.jit(model.init)(jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 100), 8))
    layers = params["layers"]
    for group, name in (("ln1", "scale"), ("ln2", "scale"),
                        ("attn", "q_norm"), ("attn", "k_norm")):
        s = layers[group][name]
        layers[group][name] = 0.2 * jax.random.normal(next(keys), s.shape)
    s = layers["delta"]["o_norm"]
    layers["delta"]["o_norm"] = 1.0 + 0.2 * jax.random.normal(next(keys),
                                                              s.shape)
    params["final_norm"]["scale"] = 0.2 * jax.random.normal(
        next(keys), params["final_norm"]["scale"].shape)
    return params


def all_weights(params, cfg):
    get = mc.weights_getter(params, cfg)
    weights = {(n, None): get(n) for n in ("embed", "lm_head", "final_norm")}
    for i, kind in enumerate(ref.kinds(cfg)):
        weights.update({(n, i): get(n, i) for n in ref.TENSORS[kind]})
    return weights


ROWS = np.random.default_rng(0).integers(0, 96, (2, 24)).astype(np.int32)
#: 8 of the 16 experts from the fifth on: a share whose first is not 0
SHARE = dict(num_experts=8, first_expert=4)


@pytest.fixture(scope="module")
def small(run_memo):
    cfg = file_cfg(**SHARE)
    model = model_for(cfg)
    params = init(model)
    return cfg, model, params, run_memo(
        "qwen3_next_small",
        lambda: ref.batch_loss(cfg, mc.weights_getter(params, cfg), ROWS))


# ---- the program against the reference -------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_loss_and_parts_match_the_reference(small, dtype):
    cfg, model, params, want = small
    if dtype == "bfloat16":
        # the reference on the weights the bf16 program reads
        model = model_for(cfg, dtype=dtype)
        want = ref.batch_loss(cfg, mc.weights_getter(
            params, cfg,
            lambda w: w.astype(jnp.bfloat16).astype(jnp.float32),
            lambda w: w), ROWS)
    loss, parts = jax.jit(model.loss_and_parts)(params, {"input_ids": ROWS})
    f32 = dtype == "float32"
    np.testing.assert_allclose(loss, want["loss"], atol=2e-5 if f32 else 0.05)
    np.testing.assert_allclose(parts["lb_loss"], want["lb_loss"],
                               atol=1e-5 if f32 else 0.3)
    np.testing.assert_allclose(parts["mix_out_ms"], want["mix_out_ms"],
                               rtol=5e-5 if f32 else 0.05)
    if f32:
        np.testing.assert_array_equal(parts["expert_pairs"],
                                      want["expert_pairs"])
    assert not np.asarray(parts["pairs_dropped"]).any()


def test_every_leafs_gradient_matches_the_reference(small):
    cfg, model, params, _ = small
    got = jax.jit(jax.grad(model.loss_fn))(params, {"input_ids": ROWS})
    got_of = mc.weights_getter(got, cfg)
    _, want = ref.loss_and_grads(cfg, all_weights(params, cfg), ROWS)
    seen = 0
    for (name, layer), g in want.items():
        scale = float(np.abs(g).max())
        np.testing.assert_allclose(
            got_of(name, layer), g, atol=5e-5 * max(scale, 1.0) + 1e-7,
            err_msg=f"{name} of layer {layer}")
        seen += np.size(g)
    assert seen == sum(np.size(x) for x in jax.tree_util.tree_leaves(params))
    assert seen == model.cfg.num_params_estimate()


def test_the_references_gradient_a_layer_at_a_time_is_the_whole_graphs():
    """What the benchmark's runner compares the step's gradient with: a
    layer's ``jax.vjp`` at a time (the balance term's cotangent handed to
    each layer) against ``jax.grad`` of the whole."""
    cfg = file_cfg(num_hidden_layers=4, **SHARE)
    params = init(model_for(cfg), seed=2)
    get = mc.weights_getter(params, cfg)
    out, got = ref.batch_loss_and_grads(cfg, get, ROWS)
    loss, want = ref.loss_and_grads(cfg, all_weights(params, cfg), ROWS)
    np.testing.assert_allclose(out["loss"], loss, rtol=1e-6)
    assert sorted(got) == sorted(want)
    for key, g in want.items():
        np.testing.assert_allclose(got[key], g,
                                   atol=1e-5 * float(np.abs(g).max()) + 1e-9,
                                   err_msg=str(key))


def _engine(model, **optimizer):
    import deepspeed_tpu as ds
    from deepspeed_tpu.parallel import build_mesh

    engine, *_ = ds.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 1e-2, "weight_decay": 0.5,
                                 **optimizer}},
        "zero_optimization": {"stage": 0}, "steps_per_print": 10 ** 9},
        mesh=build_mesh(devices=jax.devices()[:1]))
    return engine


def test_the_fused_step_is_the_references_adamw_step_and_the_rows_facts():
    """Through ``initialize`` -> ``fused_train_step`` like any model, with
    weight decay on and the norms' scales away from 0, so that ``1 + w``
    and ``w`` differ in what the decay pulls on: the loss, the record's
    parts, every leaf after the step against the reference's AdamW on the
    reference's gradient; and what the step program's row says."""
    from deepspeed_tpu.observability import steplog

    cfg = file_cfg(**SHARE)
    model = model_for(cfg)
    engine = _engine(model)
    engine.params = jax.device_put(
        init(model, seed=3), jax.tree_util.tree_map(
            lambda x: x.sharding, engine.params))
    before = jax.device_get(engine.params)
    want, grads = ref.batch_loss_and_grads(
        cfg, mc.weights_getter(before, cfg), ROWS)
    loss = float(engine.fused_train_step({"input_ids": ROWS}))
    np.testing.assert_allclose(loss, want["loss"], atol=2e-5)
    part = steplog.get_steplog().parts(last=1)[-1]
    np.testing.assert_array_equal(part["expert_pairs"], want["expert_pairs"])
    np.testing.assert_allclose(part["mix_out_ms"], want["mix_out_ms"],
                               rtol=1e-4)
    np.testing.assert_allclose(part["lb_loss"], want["lb_loss"], rtol=1e-5)
    was, now = (mc.weights_getter(p, cfg)
                for p in (before, jax.device_get(engine.params)))
    for (name, layer), g in grads.items():
        w = np.asarray(was(name, layer))
        step = np.asarray(ref.adamw_first_step(g, w, lr=1e-2,
                                               weight_decay=0.5))
        # (AdamW's first step is each element's sign: a gradient within
        # float32 noise of 0 may take either, so the worst few are let off)
        off = np.abs(np.asarray(now(name, layer)) - w - step)
        assert np.mean(off > 2e-4) < 0.01, f"{name} of layer {layer}"
    # the decay pulled a zero-centred scale towards 0, that is the factor
    # towards 1, and not the factor towards 0
    s0, s1 = was("ln1", 0), now("ln1", 0)
    pulled = -1e-2 * 0.5 * np.asarray(s0)
    assert np.abs(np.asarray(s1) - np.asarray(s0) - pulled).max() < 1.1e-2
    row = [p for p in steplog.programs()
           if p.name.startswith("ds_train_step")][-1]
    assert row.layer_pattern == ("delta", "delta", "delta", "full")
    assert row.delta_heads == (2, 4) and row.delta_chunk == 8
    assert row.delta_chunks_per_step == 3 * 2 * 3
    assert row.delta_rule_lowering == {0: "xla", 1: "xla", 2: "xla"}
    assert row.delta_scan_lowerings == {"xla": 3}
    # off the chip the einsum form repeats q and k to the value heads: the
    # counter shows it (twice the rows of a read once a key head)
    assert row.delta_qk_rows == {"xla": 3 * 2 * 2 * 24 * 4}
    assert row.experts_held == (4, 8, 16)
    assert row.attn_heads_per_step == 4


# ---- the rule's kernels with shared key heads ------------------------------

def _rule_inputs(T, Hk, H, dk=128, dv=128, B=1, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa
    return (f(B, T, Hk, dk), f(B, T, Hk, dk), f(B, T, H, dv),
            -jax.nn.softplus(f(B, T, H)) * 0.5, jax.nn.sigmoid(f(B, T, H)))


def _rule_grads(fn, args):
    w = jnp.asarray(np.random.default_rng(5).standard_normal(
        args[2].shape), jnp.float32)

    def loss(*a):
        o = fn(*a)
        return jnp.sum(o.astype(jnp.float32) * w), o

    (_, o), g = jax.jit(jax.value_and_grad(
        loss, argnums=range(5), has_aux=True))(*args)
    return o, g


@pytest.mark.parametrize("T, Hk, H", [(192, 1, 2), (520, 2, 4)])
def test_the_kernels_share_a_key_heads_q_and_k(monkeypatch, T, Hk, H):
    """The kernels interpreted at 128 / 128 (a block is a head's own
    columns), value heads 2 j and 2 j + 1 reading key head j's q and k as
    the convolutions left them, against the einsum form on q and k normed
    and repeated: o and the five cotangents, dq and dk summed over the
    pair. Three chunks, and nine padded to two grid steps."""
    monkeypatch.setattr(delta_rule, "CHUNK", 64)
    args = _rule_inputs(T, Hk, H)
    unit = (1.0 / np.sqrt(128), 1e-6)

    def oracle(q, k, v, g, beta):
        q = delta_rule.unit_heads(q, unit[0], unit[1], jnp.float32)
        k = delta_rule.unit_heads(k, 1.0, unit[1], jnp.float32)
        q, k = (jnp.repeat(a, H // Hk, axis=2) for a in (q, k))
        return delta_rule.rule_einsum(q, k, v, g, beta)

    with jax.default_matmul_precision("highest"):
        o_k, g_k = _rule_grads(functools.partial(
            delta_rule.chunked_delta_rule, unit=unit, interpret=True), args)
        o_e, g_e = _rule_grads(oracle, args)
    np.testing.assert_allclose(o_k, o_e, atol=1e-5 * float(
        jnp.abs(o_e).max()))
    for name, k, e in zip("q k v g beta".split(), g_k, g_e):
        assert k.shape == e.shape and k.dtype == e.dtype, name
        np.testing.assert_allclose(k, e, atol=2e-5 * float(jnp.abs(e).max()),
                                   err_msg=name)


def test_the_picker_takes_shared_key_heads_at_whole_lane_tiles_only(
        monkeypatch):
    monkeypatch.setattr(delta_rule, "CHUNK", 64)
    bf16 = jnp.bfloat16
    assert delta_rule.rule_lowering(16384, 32, 128, 128, bf16, tpu=True,
                                    key_heads=16) == ("pallas", "")
    assert delta_rule.rule_lowering(4096, 15, 96, 192, bf16,
                                    tpu=True)[0] == "pallas"
    took, why = delta_rule.rule_lowering(4096, 8, 64, 128, bf16, tpu=True,
                                         key_heads=4)
    assert took == "xla" and "4 key heads for 8 value heads" in why
    took, why = delta_rule.rule_lowering(4096, 8, 48, 96, bf16, tpu=True)
    assert took == "xla" and "128 / 128" in why
    with pytest.raises(ValueError, match="divisor of the value heads"):
        delta_rule.chunked_delta_rule(*_rule_inputs(64, 3, 4))


# ---- the gate a channel -----------------------------------------------------

def test_the_gate_a_channel_is_the_ungated_head_times_its_sigmoid():
    """A full layer with ``attn_channel_gate`` against the same layer
    without it: ``wq``'s columns are a head's query then its gate, and with
    an identity ``wo`` the gated block's output is the ungated one's times
    ``sigmoid(u W_gate)`` a channel."""
    from deepspeed_tpu.models.transformer import (attention_block,
                                                  rope_frequencies,
                                                  xla_attention)

    base = dict(vocab_size=32, hidden_size=64, num_layers=1, num_heads=4,
                num_kv_heads=2, head_dim_override=16, dtype="float32",
                qk_norm="head", rope_pct=0.25, max_seq_len=32)
    gated = TransformerConfig(**base, attn_channel_gate=True)
    plain = TransformerConfig(**base)
    k = jax.random.split(jax.random.key(0), 6)
    w = {"wq": jax.random.normal(k[0], (64, 4 * 32)) / 8,
         "wk": jax.random.normal(k[1], (64, 32)) / 8,
         "wv": jax.random.normal(k[2], (64, 32)) / 8,
         "wo": jnp.eye(64), "q_norm": jnp.ones(16), "k_norm": jnp.ones(16)}
    x = jax.random.normal(k[3], (2, 24, 64))
    cols = w["wq"].reshape(64, 4, 32)
    freqs = rope_frequencies(gated.rope_dim, 32, 10000.0)
    got = attention_block(x, w, gated, freqs, xla_attention)
    ungated = attention_block(
        x, {**w, "wq": cols[:, :, :16].reshape(64, 64)}, plain, freqs,
        xla_attention)
    gate = jax.nn.sigmoid(x @ cols[:, :, 16:].reshape(64, 64))
    np.testing.assert_allclose(got, ungated * gate, atol=1e-5)
    assert float(jnp.abs(got - ungated).max()) > 0.05


# ---- the shares of the experts ----------------------------------------------

def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The four shares of four experts, the shared expert and its gate
    counted once, are the uncut reference's FFN: each share is the program's
    layer on its held experts (the router, its top 3 and their weights the
    whole model's) and carries the whole gated shared expert."""
    from deepspeed_tpu.moe.sharded_moe import grouped_moe_mlp_block

    whole = file_cfg()
    model = model_for(whole)
    params = init(model, seed=4)
    w = jax.tree_util.tree_map(lambda a: a[1], params["layers"]["mlp"])
    x = jax.random.normal(jax.random.key(9), (2, 24, 64))
    get = mc.weights_getter(params, whole)
    names = ("router", "w_gate", "w_up", "w_down", "s_gate", "s_up",
             "s_down", "s_sg")
    with jax.default_matmul_precision("highest"):
        want, *_ = ref.ffn(x.reshape(48, 64), {n: get(n, 1) for n in names},
                           whole)
        shared = ((jax.nn.silu(x @ w["shared"]["w_gate"])
                   * (x @ w["shared"]["w_up"])) @ w["shared"]["w_down"]
                  * jax.nn.sigmoid(x @ w["shared"]["w_sg"]))
        total = 0.0
        for share in range(4):
            held = dataclasses.replace(model.cfg, moe_experts_held=4,
                                       moe_first_expert=4 * share)
            cut = {**w, **{n: w[n][4 * share:4 * share + 4]
                           for n in ("w_gate", "w_up", "w_down")}}
            out, aux = grouped_moe_mlp_block(x, cut, held)
            total = total + out
            assert aux["expert_pairs"].shape == (4,)
        np.testing.assert_allclose(
            (total - 3 * shared).reshape(48, 64), want, atol=2e-5)
    assert float(jnp.abs(shared).max()) > 0.05


# ---- the published config and tensors ---------------------------------------

PUBLISHED = {
    "model_type": "qwen3_next", "decoder_sparse_step": 1,
    "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "moe_intermediate_size": 512,
    "norm_topk_prob": True, "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def test_the_published_config_maps_onto_the_model():
    cfg = config_from_hf(PUBLISHED)
    assert cfg.attn_pattern == ("delta", "delta", "delta", "full")
    assert cfg.layer_kinds.count("full") == 12
    assert (cfg.delta_key_heads, cfg.delta_heads) == (16, 32)
    assert (cfg.delta_key_dim, cfg.delta_value_dim, cfg.delta_conv) == (
        128, 128, 4)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (16, 2, 256)
    assert cfg.rope_dim == 64 and cfg.rope_theta == 1e7
    assert cfg.qk_norm == "head" and cfg.norm_zero_centred
    assert cfg.attn_channel_gate and not cfg.mla_head_gate
    assert (cfg.num_experts, cfg.top_k, cfg.moe_intermediate_size) == (
        512, 10, 512)
    assert cfg.moe_shared_experts == 1 and cfg.moe_shared_gate
    assert cfg.moe_aux_loss_coef == 0.001 and not cfg.tie_embeddings
    assert cfg.num_params_estimate() == 79_674_391_296
    cut = dataclasses.replace(cfg, num_layers=4, vocab_size=18992,
                              moe_experts_held=32)
    assert cut.num_params_estimate() == 625_667_136
    # the interval spelled out as layer_types is the same model
    types = ["linear_attention"] * 3 + ["full_attention"]
    assert config_from_hf({**PUBLISHED, "layer_types": types * 12}) == cfg
    for bad in ({"mlp_only_layers": [0]}, {"attention_bias": True},
                {"shared_expert_intermediate_size": 768},
                {"norm_topk_prob": False}):
        with pytest.raises(ValueError, match="qwen3_next"):
            config_from_hf({**PUBLISHED, **bad})


def _numbered(tree):
    """``tree`` with every element of every leaf a number of its own."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out, at = [], 0
    for leaf in leaves:
        out.append(np.arange(at, at + leaf.size, dtype=np.float64).reshape(
            leaf.shape))
        at += leaf.size
    return jax.tree_util.tree_unflatten(treedef, out)


def test_the_published_tensors_map_to_the_leaves_and_back():
    """A state dict laid out as ``modeling_qwen3_next.py`` lays it out
    (interleaved ``in_proj_qkvz`` / ``in_proj_ba`` a key head, the joined
    ``conv1d``, ``q_proj`` with its gates), every element a number of its
    own so that a swapped pair shows: read the way the modelling code reads
    it, it gives the program's leaves; and the round trip is exact."""
    cfg = file_cfg()
    tc = mc.transformer_config(cfg, max_seq_len=64, param_dtype="float32",
                               dtype="float32")
    params = _numbered(jax.eval_shape(TransformerLM(tc).init,
                                      jax.random.key(0)))
    sd = hf_map.qwen3_next_state_dict(params, tc)
    D, Hk, Hv, dk, dv, r = 64, 2, 4, 16, 16, 2
    # layer 1, a delta layer, as Qwen3NextGatedDeltaNet reads it:
    # fix_query_key_value_ordering views in_proj_qkvz's output a key head
    la, w = "model.layers.1.linear_attn.", params["layers"]["delta"]
    qkvz = sd[la + "in_proj_qkvz.weight"]
    assert qkvz.shape == (2 * Hk * dk + 2 * Hv * dv, D)
    per = qkvz.reshape(Hk, 2 * dk + 2 * r * dv, D)
    q, k, v, z = np.split(per, [dk, 2 * dk, 2 * dk + r * dv], axis=1)
    for name, part in (("wq", q), ("wk", k), ("wv", v), ("wz", z)):
        np.testing.assert_array_equal(part.reshape(-1, D).T, w[name][1])
    ba = sd[la + "in_proj_ba.weight"].reshape(Hk, 2 * r, D)
    np.testing.assert_array_equal(ba[:, :r].reshape(-1, D).T, w["wb"][1])
    np.testing.assert_array_equal(ba[:, r:].reshape(-1, D).T, w["wa"][1])
    conv = sd[la + "conv1d.weight"]
    assert conv.shape == (2 * Hk * dk + Hv * dv, 1, 4)
    np.testing.assert_array_equal(conv[:Hk * dk, 0].T, w["conv_q"][1])
    np.testing.assert_array_equal(conv[2 * Hk * dk:, 0].T, w["conv_v"][1])
    # layer 3, the full layer: q_proj's rows a head's query then its gate
    qp = sd["model.layers.3.self_attn.q_proj.weight"]
    assert qp.shape == (4 * 2 * 16, D)
    np.testing.assert_array_equal(qp.T, params["layers"]["attn"]["wq"][0])
    assert sd["model.layers.2.mlp.shared_expert_gate.weight"].shape == (1, D)
    assert sd["model.layers.0.mlp.experts.15.down_proj.weight"].shape == (
        D, 32)
    # and back, the multi-token module's tensors named and not read
    sd["mtp.layers.0.input_layernorm.weight"] = np.zeros(D)
    back, head = hf_map._build_qwen3_next(dict(sd), tc, "qwen3_next")
    back["lm_head"] = sd[head].T
    assert jax.tree_util.tree_structure(back) \
        == jax.tree_util.tree_structure(params)
    jax.tree_util.tree_map(np.testing.assert_array_equal, back, params)
    # a share of the experts is cut out of the same tensors
    held = dataclasses.replace(tc, moe_experts_held=4, moe_first_expert=8)
    cut, _ = hf_map._build_qwen3_next(dict(sd), held, "qwen3_next")
    np.testing.assert_array_equal(cut["layers"]["mlp"]["w_up"],
                                  params["layers"]["mlp"]["w_up"][:, 8:12])


# ---- what refuses the model ---------------------------------------------------

BASE = dict(vocab_size=96, hidden_size=64, num_layers=4, num_heads=4,
            num_kv_heads=2, head_dim_override=16, max_seq_len=64,
            dtype="float32")


@pytest.mark.parametrize("what, kw, error", [
    ("delta_key_heads=3 does not divide",
     dict(attn_pattern=("delta", "full"), delta_heads=4, delta_key_heads=3),
     ValueError),
    ("cut a group of a delta layer's value heads",
     dict(attn_pattern=("delta", "full"), delta_heads=4, delta_key_heads=1,
          heads_held=2), ValueError),
    ("runs the grouped dispatch",
     dict(attn_pattern=("delta", "full"), delta_heads=4, num_experts=4),
     NotImplementedError),
    ("one gate or the other",
     dict(attn_channel_gate=True, mla_head_gate=True), ValueError),
    ("attn_channel_gate with qk_norm='width'",
     dict(attn_channel_gate=True, qk_norm="width"), NotImplementedError),
    ("a gate on 'window' / 'full' layers' heads",
     dict(attn_channel_gate=True, num_passes=2), NotImplementedError),
    ("not norm='layernorm'",
     dict(norm_zero_centred=True, norm="layernorm"), ValueError),
    ("zero-centred norms", dict(norm_zero_centred=True, num_passes=2),
     NotImplementedError),
    ("zero-centred norms",
     dict(norm_zero_centred=True, norm_placement="post"),
     NotImplementedError),
    ("zero-centred norms",
     dict(norm_zero_centred=True, diffusion_block=4, mask_token_id=95),
     NotImplementedError),
    ("moe_shared_gate gates the shared experts",
     dict(moe_shared_gate=True), ValueError),
])
def test_what_the_model_does_not_run_refuses_at_config_time(what, kw, error):
    with pytest.raises(error, match=what):
        TransformerConfig(**{**BASE, **kw})


def test_serving_the_pipeline_and_the_other_step_paths_refuse_by_name():
    import deepspeed_tpu as ds
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.parallel import build_mesh
    from deepspeed_tpu.runtime.pipe import PipelineModule

    model = model_for(file_cfg(**SHARE))
    refused = dict(match="zero-centred norms")
    for call in (lambda: InferenceEngine(model),
                 lambda: InferenceEngineV2(model, max_sequences=2,
                                           max_seq_len=32, block_size=8),
                 lambda: model.init_kv_cache(1),
                 lambda: model.init_paged_kv_cache(4, 8),
                 lambda: PipelineModule(model, num_stages=2),
                 lambda: model.set_random_ltd(8),
                 lambda: model.set_pld_depth(2)):
        with pytest.raises(NotImplementedError, **refused):
            call()
    # without the norms and the shared gate the delta layers refuse
    plain = TransformerLM(dataclasses.replace(
        model.cfg, norm_zero_centred=False, moe_shared_gate=False,
        attn_channel_gate=False))
    with pytest.raises(NotImplementedError, match="a gate on its attention"):
        TransformerLM(dataclasses.replace(
            plain.cfg, attn_channel_gate=True)).init_kv_cache(1)
    with pytest.raises(NotImplementedError,
                       match="gated delta-rule layers"):
        plain.init_kv_cache(1)
    with pytest.raises(NotImplementedError, match="a tp axis"):
        model.check_topology({"tp": 2})
    for what, extra in (
            ("zero_optimization", {"zero_optimization": {
                "stage": 0, "offload_optimizer": {"device": "cpu"}}}),
            ("onebit", {"optimizer": {"type": "onebitadam",
                                      "params": {"lr": 1e-3,
                                                 "freeze_step": 2}}})):
        config = {"train_micro_batch_size_per_gpu": 2,
                  "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                  "steps_per_print": 10 ** 9,
                  "zero_optimization": {"stage": 0}, **extra}
        eng, *_ = ds.initialize(model=model, config=config,
                                mesh=build_mesh(devices=jax.devices()[:1]))
        with pytest.raises(NotImplementedError,
                           match="delta-rule layers beside routed experts"):
            eng.fused_train_step({"input_ids": ROWS})
