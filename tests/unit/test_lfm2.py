"""A gated short-convolution mixer beside GQA layers whose q and k are normed
per head before a rope, a leading dense FFN and a held share of
sigmoid-routed experts under a pattern of mixer kinds (LFM2-MoE): the program
against the plain reference (``benchmarks/reference_lfm2.py``: the tests
import it from there, a reference is held once) on seeded random weights,
forward, gradients, AdamW's first step and the bias rule; the eight shares
adding up to the whole layer; the plan of the layer loop; what refuses the
model; the published config's mapping; and the faults the benchmark cell's
check has to see."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import modelcfg_lfm2 as modelcfg
from benchmarks import opcount_lfm2 as opcount
from benchmarks import reference_lfm2 as ref
from benchmarks.runners.train_hybrid import compare
from benchmarks.runners.train_mla_moe import compare_biases
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.models import transformer as tf

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL_CONFIG = os.path.join(ROOT, "benchmarks", "configs",
                           "lfm2_24b_train_d5e8v8.json")
ALPHA, GAMMA = 1e-2, 1e-3
#: the published list: two conv layers, then (full, conv, conv, conv) nine
#: times, then full, conv
LAYER_TYPES = ["conv", "conv"] + ["full_attention", "conv", "conv",
                                  "conv"] * 9 + ["full_attention", "conv"]
KINDS = ("conv:dense", "full:moe", "conv:moe", "conv:moe", "conv:moe")


def hf_config(**over):
    """A small file of the cell's keys: hidden 64, 4 query heads of 16 on 2
    key-value heads, published layers 1-5 (one dense), 4 of 16 experts held
    from the 4th on, 4 a token."""
    hf = {"model_type": "lfm2_moe", "hidden_size": 64,
          "num_hidden_layers": 5, "first_layer": 1,
          "layer_types": list(LAYER_TYPES), "num_dense_layers": 1,
          "vocab_size": 256, "num_attention_heads": 4,
          "num_key_value_heads": 2, "intermediate_size": 96,
          "moe_intermediate_size": 48, "norm_eps": 1e-5, "conv_L_cache": 3,
          "conv_bias": False, "num_experts": 4, "router_width": 16,
          "first_expert": 4, "num_experts_per_tok": 4,
          "routed_scaling_factor": 1.0, "norm_topk_prob": True,
          "use_expert_bias": True, "tie_word_embeddings": True,
          "rope_parameters": {"rope_theta": 1000000.0,
                              "rope_type": "default"},
          "deployment": {"local_pairs_factor": 4.0, "bias_update_rate": GAMMA,
                         "bias_init": 0.1, "balance_coef": ALPHA,
                         "remat_policy": "none", "embed_init_std": 0.02}}
    hf.update(over)
    return hf


def model_for(hf, dtype="float32", **over):
    return TransformerLM(modelcfg.transformer_config(
        hf, max_seq_len=64, param_dtype="float32", dtype=dtype,
        attention_impl="xla", **over))


def init(model, seed=0, router_gain=4.0, qk_gain=1.0):
    """Seeded weights: a router that prefers some experts, so that the top k
    is no toss-up; q/k norm scales that differ by channel, so that a norm on
    the wrong side of the rope shows (at a scale of ones it commutes with
    the rotation)."""
    params = jax.jit(model.init)(jax.random.key(seed))     # one program, not an op at a time
    moe, attn = params["layers"]["mlp_moe"], params["layers"]["attn"]
    moe["router"] = moe["router"] * router_gain
    for i, n in enumerate(("q_norm", "k_norm")):
        attn[n] = jax.random.uniform(jax.random.key(100 + i),
                                     attn[n].shape, jnp.float32, 0.5, 1.5)
    attn["wq"], attn["wk"] = attn["wq"] * qk_gain, attn["wk"] * qk_gain
    return params


ROWS = np.random.default_rng(0).integers(0, 256, (2, 24)).astype(np.int32)


@pytest.fixture(scope="module")
def small(run_memo):
    hf = hf_config()
    model = model_for(hf)
    params = init(model)
    want, grads = run_memo("lfm2_small", lambda: ref.batch_loss_and_grads(
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
    # float32: the same function, up to the order of sums and the 1e-6 the
    # reference's router adds to the chosen scores' sum
    ("float32", 5e-5, 1e-4, 0, 2e-4),
    # bf16 activations on the same bf16-rounded weights: a rounding of every
    # branch's output, a few near-tied choices flipped
    ("bfloat16", 0.15, 0.05, 3, 0.6)])
def test_loss_mixer_outputs_counts_and_every_gradient_match_the_reference(
        small, dtype, loss_tol, ms_tol, pairs_tol, grad_tol):
    hf, model, params, want, grads = small
    if dtype != "float32":
        model, params = model_for(hf, dtype), _bf16(params)
        want, grads = ref.batch_loss_and_grads(
            hf, modelcfg.weights_getter(params, hf), list(ROWS), ALPHA)
    (loss, parts), got = jax.jit(jax.value_and_grad(
        model.loss_and_parts, has_aux=True))(params, {"input_ids": ROWS})
    assert parts["mix_out_ms"].shape == (5,)
    assert parts["router_counts"].shape == (4, 16)
    assert abs(float(loss) - float(want["loss"])) <= loss_tol
    assert abs(float(parts["lb_loss"]) - float(want["lb_loss"])) \
        <= max(loss_tol, 0.02 * float(want["lb_loss"]) * (dtype != "float32"))
    np.testing.assert_allclose(parts["mix_out_ms"], want["mix_out_ms"],
                               rtol=ms_tol)
    assert np.abs(np.asarray(parts["expert_pairs"])
                  - np.asarray(want["expert_pairs"])).max() <= pairs_tol
    assert int(np.sum(parts["pairs_dropped"])) == 0
    get = modelcfg.weights_getter(got, hf)
    # embed (the tied head's with it), the final norm; two norms a layer;
    # four conv mixers, one attention mixer with its two norm scales, the
    # dense FFN, four routed FFNs without their biases
    assert len(grads) == 2 + 2 * 5 + 3 * 4 + 6 + 3 + 4 * 4
    for (name, layer), g in grads.items():
        mine, g = np.asarray(get(name, layer)), np.asarray(g)
        assert np.linalg.norm(mine - g) <= grad_tol * np.linalg.norm(g), \
            (name, layer)
    # the selection bias picks and gets no gradient
    assert not np.any(np.asarray(got["layers"]["mlp_moe"]["router_bias"]))


def test_the_layer_at_a_time_gradient_is_the_whole_models(small):
    hf, _, params, want, grads = small
    get = modelcfg.weights_getter(params, hf)
    names = [("embed", None), ("final_norm", None)] + [
        (n, i) for i, k in enumerate(ref.kinds(hf)) for n in ref.tensors(k)]
    loss, whole = ref.loss_and_grads(
        hf, {k: jnp.asarray(get(*k), jnp.float32) for k in names},
        list(ROWS), ALPHA)
    assert ref.kinds(hf) == KINDS
    assert float(loss) == pytest.approx(float(want["loss"]), rel=1e-6)
    for key, g in grads.items():
        np.testing.assert_allclose(
            g, whole[key], rtol=2e-4,
            atol=2e-6 * float(jnp.abs(whole[key]).max()))


def test_the_mixers_alone_are_the_references():
    """The conv block and the attention block with its per-head norm, each
    on its own against the reference's function of the same tensors."""
    from deepspeed_tpu.models import short_conv

    hf = hf_config()
    model = model_for(hf)
    params = init(model, seed=2, qk_gain=3.0)
    u = jax.random.normal(jax.random.key(9), (24, 64), jnp.float32)
    w = jax.tree_util.tree_map(lambda a: a[1], params["layers"]["conv"])
    np.testing.assert_allclose(
        short_conv.conv_block(u[None], w, model.cfg)[0],
        ref.short_conv(u, w, hf), rtol=2e-5, atol=2e-6)
    # the sum of three shifted arrays, by hand at one position
    v = jax.random.normal(jax.random.key(10), (6, 4), jnp.float32)
    taps = jnp.asarray([[1.0] * 4, [10.0] * 4, [100.0] * 4])
    np.testing.assert_allclose(ref.conv(v, taps)[2],
                               v[0] + 10 * v[1] + 100 * v[2], rtol=1e-6)
    np.testing.assert_allclose(ref.conv(v, taps)[0], 100 * v[0], rtol=1e-6)
    w = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["attn"])
    ck, freqs = model._kinds["full:moe"]
    assert w["q_norm"].shape == w["k_norm"].shape == (16,)
    np.testing.assert_allclose(
        tf.attention_block(u[None], w, ck, freqs, tf.xla_attention)[0],
        ref.attention_layer(u, w, hf), rtol=2e-4, atol=2e-5)


# ---- the engine's step: AdamW's first step and the bias rule ---------------

def _engine(hf, dtype="float32", lr=1e-3):
    import deepspeed_tpu as ds
    from deepspeed_tpu.parallel import build_mesh

    config = {"train_micro_batch_size_per_gpu": 2, "seed": 3,
              "optimizer": {"type": "adamw", "params": {"lr": lr}},
              "zero_optimization": {"stage": 0},
              "steps_per_print": 10 ** 9}
    if dtype != "float32":
        config["bf16"] = {"enabled": True}
    engine, *_ = ds.initialize(model=model_for(hf, dtype), config=config,
                               mesh=build_mesh(devices=jax.devices()[:1]))
    return engine


def test_the_first_fused_step_is_the_references_adamw_and_bias_rule():
    """Through ``deepspeed_tpu.initialize`` -> ``fused_train_step`` in
    float32: every parameter's change is AdamW's first step on the
    reference's gradient (its sign, where the gradient is clear of zero), and
    the selection biases are the rule's on the reference's counts."""
    from deepspeed_tpu.observability import steplog

    hf, lr = hf_config(), 1e-3
    engine = _engine(hf, lr=lr)
    before = jax.device_get(engine.params)
    want, grads = ref.batch_loss_and_grads(
        hf, modelcfg.weights_getter(before, hf), list(ROWS), ALPHA)
    loss = float(engine.fused_train_step({"input_ids": ROWS}))
    assert loss == pytest.approx(float(want["loss"]), abs=5e-5)
    parts = steplog.get_steplog().parts(last=1)[-1]
    np.testing.assert_array_equal(parts["router_counts"],
                                  want["router_counts"])
    np.testing.assert_allclose(
        np.asarray(modelcfg.biases(engine.params)),
        ref.bias_after(modelcfg.biases(before), want["router_counts"], GAMMA),
        atol=1e-7)
    t0 = modelcfg.weights_getter(before, hf)
    t1 = modelcfg.weights_getter(jax.device_get(engine.params), hf)
    num = den = 0.0
    for (name, layer), g in grads.items():
        d_ref = np.asarray(ref.adamw_first_step(g, t0(name, layer), lr=lr))
        d = np.asarray(t1(name, layer)) - np.asarray(t0(name, layer))
        # an element whose gradient is within float32's noise of zero may
        # take the other sign, and one within a few eps of it a shorter step
        clear = np.abs(np.asarray(g)) > 1e-4 * np.abs(np.asarray(g)).max()
        np.testing.assert_allclose(d[clear], d_ref[clear], rtol=1e-2,
                                   atol=1e-2 * lr, err_msg=f"{name}.{layer}")
        num += float(np.sum((d - d_ref) ** 2))
        den += float(np.sum(d_ref ** 2))
    assert np.sqrt(num / den) < 0.02        # a state left as it was reads 1


def test_two_bf16_fused_steps_carry_the_records_and_move_the_bias_by_rule():
    from deepspeed_tpu.observability import steplog

    engine = _engine(hf_config(), "bfloat16", lr=1e-4)
    for _ in range(2):
        before = np.array(modelcfg.biases(engine.params))
        engine.fused_train_step({"input_ids": ROWS})
        parts = steplog.get_steplog().parts(last=1)[-1]
        counts = np.asarray(parts["router_counts"], np.float64)
        np.testing.assert_allclose(
            np.array(modelcfg.biases(engine.params)),
            before + GAMMA * np.sign(counts.mean(-1, keepdims=True) - counts),
            atol=1e-7)
        assert parts["mix_out_ms"].shape == (5,)
        assert counts.shape == (4, 16) and counts.sum() == 4 * 48 * 4
        assert int(np.sum(parts["pairs_dropped"])) == 0
    row = [p for p in steplog.programs()
           if p.name.startswith("ds_train_step")][-1]
    assert row.layer_applications == 5
    assert row.layer_pattern == ("conv:dense", "full:moe", "conv:moe")
    assert row.experts_held == (4, 4, 16) and row.moe_scoring == "sigmoid"
    # four conv layers in three block bodies, a forward each (the jax.numpy
    # form's backward is autodiff's)
    assert row.conv_lowerings == {"xla": 2}


# ---- the shares add up ----------------------------------------------------

def test_the_eight_expert_shares_of_a_routed_layer_are_the_uncut_layer():
    """Eight models that each hold 8 of the 64 experts (what the cell's
    configuration holds): their partial results add up to the reference's
    uncut layer (there is no shared expert to count once)."""
    from deepspeed_tpu.moe.sharded_moe import grouped_moe_mlp_block

    hf = hf_config(num_experts=64, router_width=64, first_expert=0)
    whole = model_for(hf)
    w = jax.tree_util.tree_map(lambda a: a[0], whole.init(
        jax.random.key(5))["layers"]["mlp_moe"])
    w["router"] = w["router"] * 4.0
    u = jax.random.normal(jax.random.key(6), (2, 12, 64), jnp.float32)
    rw = {"router": w["router"], "router_bias": w["router_bias"],
          "w1": w["w_gate"], "w3": w["w_up"], "w2": w["w_down"]}
    want = jnp.stack([ref.experts(row, rw, hf, held=range(64))[0]
                      for row in u])
    total = 0.0
    for s in range(8):
        cut = dataclasses.replace(whole.cfg, moe_experts_held=8,
                                  moe_first_expert=8 * s,
                                  moe_ep_capacity_factor=8.0)
        ws = {**w, **{n: w[n][8 * s:8 * s + 8]
                      for n in ("w_gate", "w_up", "w_down")}}
        out, aux = grouped_moe_mlp_block(u, ws, cut)
        assert int(aux["pairs_dropped"]) == 0
        # and the reference's share is the program's
        mine = jnp.stack([ref.experts(
            row, {**rw, **{a: ws[b] for a, b in (
                ("w1", "w_gate"), ("w3", "w_up"), ("w2", "w_down"))}}, hf,
            held=range(8 * s, 8 * s + 8))[0] for row in u])
        np.testing.assert_allclose(out, mine, rtol=2e-4, atol=2e-5)
        total = total + out
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


# ---- the layer loop -------------------------------------------------------

def test_a_pattern_with_ffn_kinds_runs_as_three_runs_over_four_stacks(small):
    """``first_k_dense`` with ``attn_pattern``: a dense conv layer, a routed
    attention layer, three routed conv layers are three runs of one kind;
    each kind's stack has a row a layer of that kind, the norms a row a
    layer; unrolled, the same numbers."""
    hf, model, params, want, _ = small
    assert model.cfg.layer_kinds == KINDS
    assert model._layer_plan() == [(0, 1, ("conv:dense",)),
                                   (1, 2, ("full:moe",)),
                                   (2, 5, ("conv:moe",))]
    layers = params["layers"]
    assert sorted(layers) == ["attn", "conv", "ln1", "ln2", "mlp_dense",
                              "mlp_moe"]
    assert layers["conv"]["in_proj"].shape == (4, 64, 192)
    assert layers["conv"]["conv_w"].shape == (4, 3, 64)
    assert layers["attn"]["wq"].shape == (1, 64, 64)
    assert layers["mlp_dense"]["w_up"].shape == (1, 64, 96)
    assert layers["mlp_moe"]["w_up"].shape == (4, 4, 64, 48)
    assert layers["mlp_moe"]["router"].shape == (4, 64, 16)
    assert layers["ln1"]["scale"].shape == (5, 64)
    batch = {"input_ids": ROWS}
    loss, parts = jax.jit(model.loss_and_parts)(params, batch)
    unrolled = model_for(hf, scan_layers=False, remat_policy="full")
    loss2, parts2 = jax.jit(unrolled.loss_and_parts)(params, batch)
    assert float(loss2) == pytest.approx(float(loss), rel=1e-6)
    np.testing.assert_allclose(parts2["mix_out_ms"], parts["mix_out_ms"],
                               rtol=1e-5)
    np.testing.assert_array_equal(parts2["router_counts"],
                                  parts["router_counts"])


def test_num_params_estimate_and_param_specs_follow_init(small):
    hf, model, params, _, _ = small
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert model.cfg.num_params_estimate() == n == opcount.total_params(hf)
    specs = model.param_specs()
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda x: 0, params)) \
        == jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda x: 0, specs, is_leaf=lambda x: not isinstance(x, dict)))
    facts = model.step_program_facts((2, 24))
    assert facts["layer_applications"] == 5
    assert facts["layer_pattern"] == ("conv:dense", "full:moe", "conv:moe")
    assert facts["experts_held"] == (4, 4, 16)
    assert facts["moe_scoring"] == "sigmoid"


def test_the_cells_parameter_count_is_the_programs():
    """The cell's configuration at its published widths, as shapes: the
    program's leaves are the 469,285,248 the file states."""
    with open(CELL_CONFIG) as f:
        cfg = json.load(f)
    model = TransformerLM(modelcfg.transformer_config(
        cfg, max_seq_len=8192, param_dtype="float32"))
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == model.cfg.num_params_estimate() == 469_285_248
    assert n == cfg["deployment"]["parameters"] == opcount.total_params(cfg)
    assert model.cfg.layer_kinds == KINDS and model.cfg.tie_embeddings
    assert shapes["layers"]["conv"]["in_proj"].shape == (4, 2048, 6144)
    assert shapes["layers"]["conv"]["conv_w"].shape == (4, 3, 2048)
    assert shapes["layers"]["attn"]["wq"].shape == (1, 2048, 2048)
    assert shapes["layers"]["attn"]["wk"].shape == (1, 2048, 512)
    assert shapes["layers"]["attn"]["q_norm"].shape == (1, 64)
    assert shapes["layers"]["mlp_dense"]["w_up"].shape == (1, 2048, 11776)
    assert shapes["layers"]["mlp_moe"]["w_up"].shape == (4, 8, 2048, 1536)
    assert shapes["layers"]["mlp_moe"]["router"].shape == (4, 2048, 64)
    assert "lm_head" not in shapes


def test_a_model_without_the_kind_does_not_load_the_mixer():
    code = ("import sys, jax\n"
            "import deepspeed_tpu\n"
            "from deepspeed_tpu.models import TransformerConfig, "
            "TransformerLM\n"
            "m = TransformerLM(TransformerConfig(vocab_size=64, "
            "hidden_size=32, num_layers=2, num_heads=2, qk_norm='head'))\n"
            "m.init(jax.random.key(0))\n"
            "assert 'deepspeed_tpu.models.short_conv' not in sys.modules\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]


# ---- what refuses ---------------------------------------------------------

BASE = dict(vocab_size=64, hidden_size=32, num_layers=4, num_heads=2,
            attn_pattern=("conv", "conv", "full", "conv"), qk_norm="head")


@pytest.mark.parametrize("what, kw, error", [
    ("looped stack", dict(num_passes=2), NotImplementedError),
    ("looped stack", dict(sandwich_norm=True), NotImplementedError),
    ("parallel_block", dict(parallel_block=True), NotImplementedError),
    ("tiled loss", dict(loss_tiling=2), NotImplementedError),
    ("fpdt", dict(attention_impl="fpdt"), NotImplementedError),
    ("heads_held", dict(heads_held=1), NotImplementedError),
    ("conv_taps", dict(conv_taps=0), ValueError),
    ("one_branch", dict(one_branch=True,
                        attn_pattern=("conv", "dense", "full", "dense")),
     NotImplementedError),
    ("'head'", dict(qk_norm="heads"), ValueError),
    ("'conv'", dict(attn_pattern=("conv", "convolution")), ValueError),
])
def test_what_a_conv_model_does_not_run_refuses_at_config_time(what, kw,
                                                               error):
    with pytest.raises(error, match=what):
        TransformerConfig(**{**BASE, **kw})


def test_serving_the_pipeline_and_a_tp_axis_refuse_by_name():
    model = TransformerLM(TransformerConfig(**BASE))
    for call in (lambda: model.init_kv_cache(1),
                 lambda: model.init_paged_kv_cache(4),
                 lambda: model.set_random_ltd(4),
                 lambda: model.set_pld_depth(2)):
        with pytest.raises(NotImplementedError, match="short-convolution"):
            call()
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.runtime.pipe import PipelineModule

    with pytest.raises(NotImplementedError, match="short-convolution"):
        InferenceEngineV2(model, max_sequences=2, max_seq_len=32,
                          block_size=16)
    with pytest.raises(NotImplementedError, match="short-convolution"):
        PipelineModule(model, num_stages=2)
    with pytest.raises(NotImplementedError, match="tp axis"):
        model.check_topology({"tp": 2})
    model.check_topology({"tp": 1, "fsdp": 4})
    # the per-head norm alone keeps serving out too (only the train step's
    # block applies it)
    plain = TransformerLM(TransformerConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        qk_norm="head"))
    with pytest.raises(NotImplementedError, match="qk_norm"):
        plain.init_kv_cache(1)


# ---- the published config -------------------------------------------------

def test_the_published_config_maps_onto_the_model():
    """``config_from_hf`` on the catalog's keys: 30 conv and 10 attention
    layers, two dense FFNs, 64 sigmoid-routed experts at 4 a token, the
    per-head norm, a tied head; 23,843,661,440 parameters by the program's
    count and the benchmark's alike. A convolution bias and a scaled rope
    are refused by name."""
    from deepspeed_tpu.models.hf import config_from_hf

    with open(CELL_CONFIG) as f:
        cell = json.load(f)
    hf = {k: v for k, v in opcount.published(cell).items() if k not in (
        "reduced", "assumed", "modules", "deployment", "check",
        "first_expert", "tie_word_embeddings")}
    cfg = config_from_hf(hf, max_seq_len=128)
    kinds = cfg.layer_kinds
    assert len(kinds) == 40 and kinds[:3] == ("conv:dense", "conv:dense",
                                              "full:moe")
    assert sum(k.startswith("conv") for k in kinds) == 30
    assert sum(k.endswith(":dense") for k in kinds) == 2
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.qk_norm,
            cfg.rope_theta, cfg.conv_taps) == (32, 8, 64, "head", 1e6, 3)
    assert (cfg.num_experts, cfg.top_k, cfg.moe_intermediate_size,
            cfg.intermediate_size, cfg.moe_scoring, cfg.moe_routed_scale,
            cfg.moe_shared_experts, cfg.first_k_dense, cfg.tie_embeddings) \
        == (64, 4, 1536, 11776, "sigmoid", 1.0, 0, 2, True)
    assert cfg.num_params_estimate() == opcount.whole_model_params(cell) \
        == 23_843_661_440
    assert 2.3e9 < opcount.active_params(cell) < 2.35e9
    # a pattern with FFN kinds is runs, not a period body: at 40 layers the
    # two dense conv layers, then ten attention layers and ten runs of conv
    # layers in turn
    assert len(TransformerLM(cfg)._layer_plan()) == 21
    with pytest.raises(ValueError, match="conv_bias"):
        config_from_hf({**hf, "conv_bias": True})
    with pytest.raises(ValueError, match="rope"):
        config_from_hf({**hf, "rope_parameters": {
            "rope_theta": 1e6, "rope_type": "yarn", "factor": 4.0}})
    with pytest.raises(ValueError, match="layer_types"):
        config_from_hf({**hf, "layer_types": ["conv", "mamba"] * 20})


# ---- the faults the cell's check has to see -------------------------------

_CONV, _RMS = ref.conv, ref.rms_norm


def _short_conv_variant(gate_c=True, gate_b_after=False):
    def short_conv(u, w, cfg):
        B, C, z = jnp.split(u @ w["in_proj"], 3, axis=-1)
        c = B * _CONV(z, w["conv_w"]) if gate_b_after \
            else _CONV(B * z, w["conv_w"])
        return ((C * c) if gate_c else c) @ w["out_proj"]
    return short_conv


def _attention_variant(norm="head", theta=None, norm_after_rope=False):
    def attention_layer(u, w, cfg):
        H, K = int(cfg["num_attention_heads"]), \
            int(cfg["num_key_value_heads"])
        d, T, eps = ref.head_dim(cfg), u.shape[0], float(cfg["norm_eps"])
        th = theta or float(cfg["rope_parameters"]["rope_theta"])

        def normed(x, scale, heads):
            if norm is None:
                return x
            if norm == "width":     # one norm over every head's channels
                return _RMS(x.reshape(T, heads * d), jnp.tile(scale, heads),
                            eps).reshape(T, heads, d)
            return _RMS(x, scale, eps)

        q, k = (u @ w["wq"]).reshape(T, H, d), (u @ w["wk"]).reshape(T, K, d)
        if norm_after_rope:
            q = normed(ref.rope(q, th), w["q_norm"], H)
            k = normed(ref.rope(k, th), w["k_norm"], K)
        else:
            q = ref.rope(normed(q, w["q_norm"], H), th)
            k = ref.rope(normed(k, w["k_norm"], K), th)
        o = ref.attention(q, k, (u @ w["wv"]).reshape(T, K, d))
        return o.reshape(T, H * d) @ w["wo"]
    return attention_layer


def _route_variant(bias_in_weights=False, normalised=True):
    def route(x, router, bias, k, scale):
        s = jax.nn.sigmoid(x @ router)
        _, top_e = jax.lax.top_k(s + bias, k)
        top_s = jnp.take_along_axis(s + (bias if bias_in_weights else 0.0),
                                    top_e, axis=-1)
        if normalised:
            top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + ref.SUM_EPS)
        return s, top_e, scale * top_s
    return route


def _fp8(params):
    return jax.tree_util.tree_map(
        lambda w: w.astype(jnp.float8_e4m3fn).astype(jnp.float32), params)


FAULTS = {
    "a silu after the convolution":
        dict(conv=lambda v, w: jax.nn.silu(_CONV(v, w))),
    "the taps reversed": dict(conv=lambda v, w: _CONV(v, w[::-1])),
    # (a fourth tap three positions back, with the first tap's weights)
    "four taps":
        dict(conv=lambda v, w: _CONV(v, jnp.concatenate([w[:1], w]))),
    "gate C left out": dict(short_conv=_short_conv_variant(gate_c=False)),
    "gate B applied after the convolution":
        dict(short_conv=_short_conv_variant(gate_b_after=True)),
    "the q/k norm over the width and not by head":
        dict(attention_layer=_attention_variant(norm="width")),
    "the norm after the rope":
        dict(attention_layer=_attention_variant(norm_after_rope=True)),
    "no norm": dict(attention_layer=_attention_variant(norm=None)),
    "theta 1e4": dict(attention_layer=_attention_variant(theta=1e4)),
    "the bias inside the weights":
        dict(route=_route_variant(bias_in_weights=True)),
    "weights not normalised": dict(route=_route_variant(normalised=False)),
    "the dense layer routed":
        dict(hf={"num_dense_layers": 0}, dense_as_routed=True),
    # (a head of its own, drawn as the program draws one: unit logits where
    # the tied table's are 0.02 sqrt(D) wide; the first loss leaves the range)
    "an untied head": dict(untied=True),
    "fp8-rounded weights": dict(weights=_fp8),
}


def _getter(params, hf, dense_as_routed=False, head=None):
    """``modelcfg.weights_getter``; with ``dense_as_routed`` the first
    layer's FFN reads the first routed layer's tensors; ``head`` is what an
    untied ``lm_head`` reads."""
    get = modelcfg.weights_getter(params, hf)

    def faulty(name, layer=None):
        if name == "lm_head":
            return head
        if dense_as_routed and layer == 0 and name in ref.FFN["moe"]:
            return get(name, 1)
        return get(name, layer)
    return faulty


@pytest.fixture(scope="module")
def cell_check(run_memo):
    """The cell's own tolerances on the forward's parts, and the reference
    at a small size (hidden 256, the five layers, 64-token rows) on
    bf16-rounded weights."""
    with open(CELL_CONFIG) as f:
        check = json.load(f)["check"]
    check["compared"] = [n for n in check["compared"]
                         if n not in ("grad_err", "param_change_err")]
    hf = hf_config(hidden_size=256, vocab_size=512, num_attention_heads=8,
                   num_key_value_heads=2, intermediate_size=384,
                   moe_intermediate_size=96, num_experts=16, first_expert=0,
                   router_width=16)
    # queries and keys that prefer some positions, so that a norm or a rope
    # fault moves what is attended to
    params = _bf16(init(model_for(hf), seed=5, router_gain=2.0, qk_gain=3.0))
    rows = list(np.random.default_rng(7).integers(0, 512, (2, 64))
                .astype(np.int32))
    return check, hf, params, rows, run_memo(
        "lfm2_cell_check", lambda: ref.batch_loss(
            hf, modelcfg.weights_getter(params, hf), rows, ALPHA))


def _judged(check, got, want, bias):
    """The runner's own rules (``runners/train_hybrid.py:compare``,
    ``runners/train_mla_moe.py:compare_biases``) on a stand-in's parts."""
    got = {k: np.asarray(v) for k, v in got.items()}
    problems, _ = compare(got, want, {**check, "first_loss_range": [0, 999]})
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
    if how.get("untied"):
        # the loss is not among the cell's compared parts (its ``tol_why``):
        # the limit that sees this fault is ``first_loss_range``, which lies
        # 0.065 either side of what the cell reads (9.42 = ln V + 0.41); at
        # this size too the untied model's first loss is further than that
        # from the tied one's
        untied = model_for({**hf, "tie_word_embeddings": False})
        head = untied.init(jax.random.key(5))["lm_head"]
        got = ref.batch_loss({**hf, "tie_word_embeddings": False},
                             _getter(params, hf, head=head), rows, ALPHA)
        lo, hi = check["first_loss_range"]
        assert "loss" not in check["compared"]
        assert abs(float(got["loss"]) - float(want["loss"])) > (hi - lo) / 2
        assert hi - lo < 0.2 and lo < 9.011 + 0.41 < hi < 9.011 + 0.5
        return
    for name in ("conv", "short_conv", "attention_layer", "route"):
        if name in how:
            monkeypatch.setattr(ref, name, how[name])
    bad = how.get("weights", lambda p: p)(params)
    got = ref.batch_loss(
        {**hf, **how.get("hf", {})},
        _getter(bad, hf, how.get("dense_as_routed", False)), rows, ALPHA)
    bias = np.asarray(params["layers"]["mlp_moe"]["router_bias"])
    assert _judged(check, got, want, bias), fault
    monkeypatch.undo()
    assert not _judged(check, want, want, bias)


def test_the_program_passes_the_cells_forward_limits_at_the_small_size(
        cell_check):
    """What the faults are measured against: the program itself, bf16, on
    the same weights and rows, inside limits on the forward's parts that the
    faults' readings lie well outside (at 128 tokens a flipped pair is a
    larger share than at 16,384, so these are the small size's limits, not
    the cell's)."""
    check, hf, params, rows, want = cell_check
    loss, parts = jax.jit(model_for(hf, "bfloat16").loss_and_parts)(
        params, {"input_ids": np.stack(rows)})
    got = {**{k: np.asarray(v) for k, v in parts.items()},
           "loss": np.asarray(loss)}
    loose = {**check, "expert_pairs_abs_tol": 3, "mix_out_ms_rel_tol": 0.05,
             "lb_loss_abs_tol": 0.05, "first_loss_range": [0, 999]}
    assert not compare(got, want, loose)[0]
