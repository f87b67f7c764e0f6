"""State-space layers and attention layers in turn (Granite-4.0-H): the
chunked scan against the recurrence over positions, the program against the
plain reference (``granite_reference.py``, a copy of
``benchmarks/reference_granite4h.py``) on seeded random weights, the stacks of
parameters by layer kind and the layer plan, what the step leaves in the
record, the paths that refuse the model, the published config's mapping, and
the faults the benchmark cell's check has to see."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_granite4h as ref
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.models import transformer as tf
from deepspeed_tpu.ops.causal_conv import causal_conv
from deepspeed_tpu.ops.ssd_scan import ssd_scan

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL_CONFIG = os.path.join(ROOT, "benchmarks", "configs",
                           "granite4_h_micro_train_d10v8.json")
KINDS = {"mamba": "ssm", "attention": "full"}
PERIOD = ["mamba", "mamba", "attention", "mamba"]


def hf_config(L=4, D=64, V=96, types=None, **over):
    return {"hidden_size": D, "num_attention_heads": 4,
            "num_key_value_heads": 2, "num_hidden_layers": L,
            "layer_types": (types or PERIOD * (L // 4)),
            "shared_intermediate_size": 2 * D,
            "mamba_n_heads": 8, "mamba_d_head": D // 4, "mamba_d_state": 16,
            "mamba_n_groups": 2, "mamba_d_conv": 4, "mamba_chunk_size": 8,
            "rms_norm_eps": 1e-5, "attention_multiplier": 1 / 64,
            "embedding_multiplier": 12.0, "residual_multiplier": 0.22,
            "logits_scaling": 8.0, "position_embedding_type": "nope",
            "tie_word_embeddings": True, "vocab_size": V, **over}


def model_for(hf, **over):
    L = hf["num_hidden_layers"]
    kw = dict(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        num_layers=L, num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        intermediate_size=hf["shared_intermediate_size"], max_seq_len=64,
        tie_embeddings=True, norm_eps=hf["rms_norm_eps"], dtype="float32",
        use_rope=False, attention_impl="xla",
        attn_pattern=tuple(KINDS[k] for k in hf["layer_types"][:L]),
        ssm_heads=hf["mamba_n_heads"], ssm_head_dim=hf["mamba_d_head"],
        ssm_state=hf["mamba_d_state"], ssm_groups=hf["mamba_n_groups"],
        ssm_conv=hf["mamba_d_conv"], ssm_chunk=hf["mamba_chunk_size"],
        attention_multiplier=hf["attention_multiplier"],
        embedding_multiplier=hf["embedding_multiplier"],
        residual_multiplier=hf["residual_multiplier"],
        logits_scaling=hf["logits_scaling"])
    kw.update(over)
    return TransformerLM(TransformerConfig(**kw))


def getter(params, hf):
    layers = params["layers"]
    kinds = hf["layer_types"][:hf["num_hidden_layers"]]

    def get(name, layer=None):
        if name == "embed":
            return params["embed"]["tokens"]
        if name == "final_norm":
            return params["final_norm"]["scale"]
        if name in ("ln1", "ln2"):
            return layers[name]["scale"][layer]
        if name in ("w_gate", "w_up", "w_down"):
            return layers["mlp"][name][layer]
        group, kind = (("attn", "attention") if name in ("wq", "wk", "wv",
                                                         "wo")
                       else ("ssm", "mamba"))
        return layers[group][name][kinds[:layer].count(kind)]

    return get


def init(model, seed=0):
    """Seeded random weights; the leaves the initialiser sets to a constant
    (D, the norms' scales) drawn too, so that leaving one out shows."""
    params = jax.jit(model.init)(jax.random.key(seed))     # one program, not an op at a time
    k = jax.random.split(jax.random.key(seed + 100), 4)
    ssm = params["layers"]["ssm"]
    ssm["D"] = 1.0 + 0.5 * jax.random.normal(k[0], ssm["D"].shape)
    ssm["norm"] = 1.0 + 0.2 * jax.random.normal(k[1], ssm["norm"].shape)
    for n, key in (("ln1", k[2]), ("ln2", k[3])):
        s = params["layers"][n]["scale"]
        params["layers"][n]["scale"] = 1.0 + 0.2 * jax.random.normal(
            key, s.shape)
    return params


ROWS = np.random.default_rng(0).integers(0, 96, (2, 24)).astype(np.int32)


# ---- the scan and the convolution -----------------------------------------

def _scan_inputs(T=40, H=4, P=8, G=2, N=16, B=2, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa
    return (f(B, T, H, P), jax.nn.softplus(f(B, T, H)),
            -jnp.asarray(rng.uniform(1, 16, (H,)), jnp.float32),
            f(B, T, G, N), f(B, T, G, N), f(H))


def _recurrence(x, dt, A, B, C, D):
    return jnp.stack([ref.recurrence(x[i], dt[i], A, B[i], C[i], D)
                      for i in range(x.shape[0])])


@pytest.fixture(scope="module")
def scan_recurrence():
    """The inputs, and the recurrence's output and gradients of every input
    position by position: the same for every chunk length, so computed once
    (as one program each, not op by op)."""
    args = _scan_inputs()
    with jax.default_matmul_precision("highest"):
        want = jax.jit(_recurrence)(*args)
        g_want = jax.jit(jax.grad(
            lambda *a: jnp.sum(jnp.sin(_recurrence(*a))),
            argnums=range(6)))(*args)
    return args, want, g_want


@pytest.mark.parametrize("chunk", [8, 16, 40, 64, 7])
def test_the_chunked_scan_is_the_recurrence(chunk, scan_recurrence):
    """Chunks that divide T (8), that do not (16, 7: the tail is padded), one
    chunk (40) and one chunk longer than T (64): forward and the gradient of
    every input."""
    args, want, g_want = scan_recurrence
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: ssd_scan(*a, chunk))(*args)
        np.testing.assert_allclose(got, want, atol=2e-4)
        g_got = jax.jit(jax.grad(
            lambda *a: jnp.sum(jnp.sin(ssd_scan(*a, chunk))),
            argnums=range(6)))(*args)
    for name, a, b in zip("x dt A B C D".split(), g_got, g_want):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(a, b, atol=1e-4 * float(jnp.abs(b).max()),
                                   err_msg=name)


def test_two_chunk_sizes_give_the_same_numbers():
    args = _scan_inputs(T=48, seed=3)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(ssd_scan(*args, 8), ssd_scan(*args, 24),
                                   atol=2e-4)


def test_bf16_inputs_keep_the_decays_in_float32():
    x, dt, A, B, C, D = _scan_inputs(T=32)
    y = ssd_scan(x.astype(jnp.bfloat16), dt, A, B.astype(jnp.bfloat16),
                 C.astype(jnp.bfloat16), D, 8)
    assert y.dtype == jnp.bfloat16
    want = _recurrence(x, dt, A, B, C, D)
    assert float(jnp.abs(y.astype(jnp.float32) - want).max()) \
        < 0.05 * float(jnp.abs(want).max())


def test_the_convolution_is_the_direct_sum():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 19, 12)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((4, 12)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((12,)), jnp.float32)
    got = causal_conv(x, w, b)
    for i in range(2):
        np.testing.assert_allclose(got[i], ref.conv(x[i], w, b), atol=1e-5)
    # written out for one position: the last tap meets the position itself
    np.testing.assert_allclose(
        got[0, 5], b + sum(w[k] * x[0, 5 - 3 + k] for k in range(4)),
        atol=1e-5)
    np.testing.assert_allclose(got[0, 0], b + w[3] * x[0, 0], atol=1e-5)


# ---- the model against the reference --------------------------------------

@pytest.fixture(scope="module")
def small(run_memo):
    hf = hf_config()
    model = model_for(hf)
    params = init(model)
    return hf, model, params, run_memo("granite_small", lambda: ref.batch_loss(
        hf, getter(params, hf), ROWS))


def test_loss_and_mixer_outputs_match_the_reference(small):
    hf, model, params, want = small
    loss, parts = jax.jit(model.loss_and_parts)(params, {"input_ids": ROWS})
    np.testing.assert_allclose(loss, want["loss"], atol=2e-5)
    np.testing.assert_allclose(parts["mix_out_ms"], want["mix_out_ms"],
                               rtol=2e-5)


def test_gradients_match_the_reference(small):
    hf, model, params, _ = small
    got = jax.jit(jax.grad(model.loss_fn))(params, {"input_ids": ROWS})
    get, got_of = getter(params, hf), getter(got, hf)
    weights = {(n, None): get(n) for n in ("embed", "final_norm")}
    for i, kind in enumerate(hf["layer_types"]):
        weights.update({(n, i): get(n, i) for n in ref.TENSORS[kind]})
    _, want = ref.loss_and_grads(hf, weights, ROWS)
    for (name, layer), g in want.items():
        scale = float(np.abs(g).max())
        np.testing.assert_allclose(
            got_of(name, layer), g, atol=3e-5 * max(scale, 1.0) + 1e-7,
            err_msg=f"{name} of layer {layer}")


@pytest.mark.parametrize("scan", [True, False])
def test_runs_of_one_kind_and_a_period_body_give_the_same_numbers(
        monkeypatch, scan):
    """A period of ten is cut into runs of one kind, each reading its own
    kind's stack; run as one period body of ten blocks it gives the same."""
    types = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    hf = hf_config(L=10, types=types)
    model = model_for(hf, scan_layers=scan)
    assert model._layer_plan() == [(0, 5, ("ssm",)), (5, 6, ("full",)),
                                   (6, 10, ("ssm",))]
    params = init(model, seed=2)
    # (each a program of its own, traced under the plan of its moment)
    cut = jax.jit(lambda p: model.logits(p, ROWS))(params)
    monkeypatch.setattr(tf, "_MAX_PERIOD", 10)
    assert len(model._layer_plan()) == 1
    np.testing.assert_allclose(
        jax.jit(lambda p: model.logits(p, ROWS))(params), cut, atol=2e-5)
    want = ref.batch_loss(hf, getter(params, hf), ROWS)
    np.testing.assert_allclose(
        jax.jit(lambda p: model.loss_fn(p, {"input_ids": ROWS}))(params),
        want["loss"], atol=2e-5)


# ---- stacks by kind -------------------------------------------------------

def test_a_40_layer_model_holds_a_stack_for_each_kind(monkeypatch):
    types = (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4
    hf = hf_config(L=40, types=types)
    model = model_for(hf)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    rows = {g: {a.shape[0] for a in jax.tree_util.tree_leaves(t)}
            for g, t in shapes["layers"].items()}
    assert rows == {"attn": {4}, "ssm": {36}, "mlp": {40}, "ln1": {40},
                    "ln2": {40}}
    assert shapes["layers"]["ssm"]["in_proj"].shape == (
        36, 64, 2 * 128 + 2 * 2 * 16 + 8)
    assert set(shapes["layers"]["attn"]) == {"wq", "wk", "wv", "wo"}
    specs = model.param_specs()
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda a: 0, shapes)) \
        == jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda s: 0, specs, is_leaf=lambda s: isinstance(
                s, jax.sharding.PartitionSpec)))
    # nine runs of one kind: no more block bodies traced than the plan says
    calls = []
    block = tf.transformer_block

    def counting(x, w, cfg, *a, **kw):
        calls.append(kw.get("kind"))
        assert ("ssm" in w) == (kw["kind"] == "ssm") \
            and ("attn" in w) == (kw["kind"] == "full")
        return block(x, w, cfg, *a, **kw)

    monkeypatch.setattr(tf, "transformer_block", counting)
    plan = model._layer_plan()
    assert len(plan) == 9
    jax.make_jaxpr(model.loss_fn)(shapes, {"input_ids": ROWS})
    assert calls == [p[2][0] for p in plan]


OLDER = {
    "mistral": dict(num_layers=3, num_kv_heads=2, sliding_window=16,
                    tie_embeddings=False),
    "ouro": dict(num_layers=3, num_passes=4, sandwich_norm=True,
                 exit_loss_beta=0.1, tie_embeddings=False),
    "mellum": dict(num_layers=4, num_kv_heads=2, sliding_window=8,
                   attn_pattern=("window", "window", "window", "full"),
                   num_experts=8, top_k=2, moe_dispatch="grouped",
                   moe_intermediate_size=32, moe_experts_held=4,
                   tie_embeddings=False),
}


@pytest.mark.parametrize("family", sorted(OLDER))
def test_the_older_models_parameter_trees_keep_names_and_shapes(family):
    """Every leaf under ``layers`` is ``[L, ...]`` for all layers alike, under
    the names checkpoints of these models were written with."""
    kw = OLDER[family]
    cfg = TransformerConfig(vocab_size=96, hidden_size=64, num_heads=4,
                            intermediate_size=128, max_seq_len=32, **kw)
    shapes = jax.eval_shape(TransformerLM(cfg).init, jax.random.key(0))
    L, D, K = cfg.num_layers, 64, cfg.num_kv_heads * 16
    want = {"ln1": {"scale": (L, D)}, "ln2": {"scale": (L, D)},
            "attn": {"wq": (L, D, D), "wk": (L, D, K), "wv": (L, D, K),
                     "wo": (L, D, D)}}
    if family == "mellum":
        want["mlp"] = {"w_gate": (L, 4, D, 32), "w_up": (L, 4, D, 32),
                       "w_down": (L, 4, 32, D), "router": (L, D, 8)}
    else:
        want["mlp"] = {"w_gate": (L, D, 128), "w_up": (L, D, 128),
                       "w_down": (L, 128, D)}
    if family == "ouro":
        want["ln1_post"] = want["ln2_post"] = {"scale": (L, D)}
    assert jax.tree_util.tree_map(lambda a: a.shape, shapes["layers"]) == want
    top = {"embed", "layers", "final_norm", "lm_head"}
    assert set(shapes) == top | ({"exit_gate"} if family == "ouro" else set())


# ---- the step record, the step-program row, ZeRO --------------------------

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


def test_the_step_record_carries_the_mixer_outputs():
    from deepspeed_tpu.observability import steplog

    hf = hf_config()
    eng = _engine(model_for(hf, remat_policy="full"))
    want = ref.batch_loss(hf, getter(eng.params, hf), ROWS)
    loss = float(eng.fused_train_step({"input_ids": ROWS}))
    row = steplog.get_steplog().parts(last=1)[-1]
    assert row["loss"] == loss
    np.testing.assert_allclose(loss, want["loss"], atol=2e-5)
    np.testing.assert_allclose(row["mix_out_ms"], want["mix_out_ms"],
                               rtol=2e-5)
    prog = steplog.programs()[-1]
    assert prog.layer_pattern == ("ssm", "ssm", "full", "ssm")
    assert prog.ssm_chunk == 8
    assert prog.ssm_chunks_per_step == 3 * 2 * 3     # layers x rows x 24 / 8
    # float32 heads of 16 on a CPU: every scan the trace holds is the einsum
    # form (its backward is autodiff's and is not counted)
    assert prog.ssm_scan_lowerings == {"xla": 3}
    assert prog.conv_lowerings == {"xla": 3}
    assert prog.layer_applications == 4
    dense = TransformerLM(TransformerConfig(hidden_size=64, num_heads=4))
    assert "ssm_chunk" not in dense.step_program_facts()
    assert "ssm_chunks_per_step" not in dense.step_program_facts((2, 24))


_SMALL_CELL = {}


def _small_cell():
    """What the benchmark's cell is in small: bf16, heads of 64, a state of
    128, chunks of 128, recomputation, one state-space layer and one
    attention layer (a second and a third state-space layer run what the
    first runs), rows of 144 positions: two chunks, the second padded, and
    whole sublane tiles for the convolution's kernels. Returns (a fresh
    engine's builder, the rows, the loss of the step as the pickers choose
    on a CPU, and what that program's row counted), the last two computed
    once for the cases that compare with them."""
    hf = hf_config(L=2, types=["mamba", "attention"], D=128, mamba_n_heads=4,
                   mamba_d_head=64, mamba_d_state=128, mamba_n_groups=2,
                   mamba_chunk_size=128)
    rows = np.random.default_rng(2).integers(0, 96, (2, 144)).astype(np.int32)

    def build():
        return _engine(model_for(hf, remat_policy="full", dtype="bfloat16",
                                 max_seq_len=256))

    if not _SMALL_CELL:
        from deepspeed_tpu.observability import steplog

        _SMALL_CELL["loss"] = float(build().fused_train_step(
            {"input_ids": rows}))
        _SMALL_CELL["counted"] = dict(steplog.programs()[-1].counted)
    return build, rows, _SMALL_CELL["loss"], _SMALL_CELL["counted"]


def test_the_cell_shaped_step_program_takes_the_scan_kernels(monkeypatch):
    """The small cell (:func:`_small_cell`): every scan of the program is the
    Pallas kernels (interpreted here: the CPU stands in for the chip), the
    backward too, and the step gives the einsum form's loss. A kernel that
    mishandled the padded second chunk or the state carried into it would
    move the loss."""
    import functools

    from deepspeed_tpu.models import mamba
    from deepspeed_tpu.observability import steplog

    build, rows, plain, counted = _small_cell()
    assert counted["ssm_scan"] == {"xla": 1}
    monkeypatch.setattr(mamba, "ssd_scan", functools.partial(
        mamba.ssd_scan, interpret=True))
    loss = float(build().fused_train_step({"input_ids": rows}))
    prog = steplog.programs()[-1]
    # the scan and its backward
    assert prog.ssm_scan_lowerings == {"pallas": 2}
    assert prog.ssm_chunks_per_step == 1 * 2 * 2     # 144 tokens: two chunks
    np.testing.assert_allclose(loss, plain, atol=2e-3)


def test_the_cell_shaped_step_program_takes_the_conv_kernels(monkeypatch):
    """The same small cell with the convolution as its Pallas kernels
    (interpreted here): every convolution of the program and its backward is
    the kernels', and the step gives the ``jax.numpy`` form's loss."""
    import functools

    from deepspeed_tpu.models import mamba
    from deepspeed_tpu.observability import steplog

    build, rows, plain, counted = _small_cell()
    assert counted["conv"] == {"xla": 1}
    monkeypatch.setattr(mamba, "causal_conv_silu", functools.partial(
        mamba.causal_conv_silu, interpret=True))
    loss = float(build().fused_train_step({"input_ids": rows}))
    # the convolution and its backward
    assert steplog.programs()[-1].conv_lowerings == {"pallas": 2}
    np.testing.assert_allclose(loss, plain, atol=2e-3)


_ONE_DEVICE_LOSS = []


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_zero_stages_shard_the_new_leaves_and_give_the_same_loss(stage):
    hf = hf_config(D=64)
    model = model_for(hf)
    rows = np.random.default_rng(4).integers(0, 96, (8, 24)).astype(np.int32)
    if not _ONE_DEVICE_LOSS:    # the same whatever the stage
        _ONE_DEVICE_LOSS.append(float(_engine(model, rows=8).fused_train_step(
            {"input_ids": rows})))
    want, = _ONE_DEVICE_LOSS
    eng = _engine(model, stage=stage, rows=8, fsdp=8)
    got = float(eng.fused_train_step({"input_ids": rows}))
    assert got == pytest.approx(want, abs=2e-5)
    for leaf in ("in_proj", "out_proj", "conv_w", "norm"):
        spec = eng.param_sharding["layers"]["ssm"][leaf].spec
        assert ("fsdp" in jax.tree_util.tree_leaves(tuple(spec))) \
            == (stage == 3), leaf


# ---- the paths that refuse ------------------------------------------------

def test_every_other_path_refuses_a_state_space_layer():
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.runtime.pipe import PipelineModule

    model = model_for(hf_config())
    refused = dict(match="state-space layers")
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
        jax.eval_shape(lambda p: model.hidden_states(
            p, ROWS, pld_theta=jnp.float32(0.5)), params)
    with pytest.raises(NotImplementedError, **refused):
        jax.eval_shape(lambda p: model.forward_prefill(
            p, ROWS, jnp.asarray([24, 24])), params)
    # (routed experts beside state-space layers run since PR 46:
    # tests/unit/test_nemotron_h.py)
    for bad in (dict(loss_tiling=4), dict(attention_impl="fpdt"),
                dict(num_passes=2), dict(parallel_block=True)):
        with pytest.raises(NotImplementedError, match="state-space layers"):
            model_for(hf_config(), **bad)
    with pytest.raises(ValueError, match="ssm_heads"):
        model_for(hf_config(), ssm_heads=0)


def test_the_multipliers_alone_are_refused_where_nothing_applies_them():
    model = TransformerLM(TransformerConfig(
        hidden_size=64, num_heads=4, residual_multiplier=0.22))
    with pytest.raises(NotImplementedError, match="residual_multiplier"):
        model.init_kv_cache(1)


# ---- the published config -------------------------------------------------

def _published():
    with open(CELL_CONFIG) as f:
        cell = json.load(f)
    pub = {k: v for k, v in cell.items()
           if k not in ("reduced", "assumed", "modules", "deployment",
                        "check", "model", "source")}
    pub.update(num_hidden_layers=40, vocab_size=100352)
    return pub


def test_the_published_config_maps_onto_the_model():
    from deepspeed_tpu.models.hf import config_from_hf, load_hf_checkpoint

    pub = _published()
    cfg = config_from_hf(pub)
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.num_layers, cfg.vocab_size, cfg.intermediate_size) \
        == (2048, 32, 8, 64, 40, 100352, 8192)
    assert cfg.attn_pattern == ("ssm",) * 5 + ("full",) + ("ssm",) * 4
    assert cfg.layer_kinds.count("full") == 4 and cfg.has_ssm
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_conv, cfg.ssm_chunk) == (64, 64, 128, 1, 4, 256)
    assert (cfg.attention_multiplier, cfg.embedding_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) \
        == (0.015625, 12.0, 0.22, 8.0)
    assert not cfg.use_rope and not cfg.learned_pos and cfg.tie_embeddings
    assert cfg.norm_eps == 1e-5
    # 3.19B: the name's "3B"
    assert cfg.num_params_estimate() == pytest.approx(3.19e9, rel=5e-3)
    with pytest.raises(ValueError, match="routed experts"):
        config_from_hf({**pub, "num_local_experts": 8})

    import tempfile
    with tempfile.TemporaryDirectory() as d:
        with open(os.path.join(d, "config.json"), "w") as f:
            json.dump(pub, f)
        with pytest.raises(NotImplementedError, match="tensor names"):
            load_hf_checkpoint(d)


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


def _mamba_with(sign=-1.0, softplus=jax.nn.softplus, gate_first=True,
                swap=False):
    """``ref.mamba`` with one thing wrong."""
    def mamba(u, w, cfg):
        H, P = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
        G, N = int(cfg["mamba_n_groups"]), int(cfg["mamba_d_state"])
        inner, T = H * P, u.shape[0]
        z, xbc, dt = jnp.split(u @ w["in_proj"],
                               [inner, 2 * inner + 2 * G * N], axis=-1)
        xbc = jax.nn.silu(ref.conv(xbc, w["conv_w"], w["conv_b"]))
        x, B, C = jnp.split(xbc, [inner, inner + G * N], axis=-1)
        if swap:
            B, C = C, B
        y = ref.recurrence(
            x.reshape(T, H, P), softplus(dt + w["dt_bias"]),
            sign * jnp.exp(w["A_log"]), B.reshape(T, G, N),
            C.reshape(T, G, N), w["D"]).reshape(T, inner)
        eps = float(cfg["rms_norm_eps"])
        y = ref.rms_norm(y * jax.nn.silu(z), w["norm"], eps) if gate_first \
            else ref.rms_norm(y, w["norm"], eps) * jax.nn.silu(z)
        return y @ w["out_proj"]
    return mamba


def _state_dropped_between_chunks(chunk):
    whole = ref.recurrence

    def recurrence(x, dt, A, B, C, D):
        return jnp.concatenate([
            whole(x[lo:lo + chunk], dt[lo:lo + chunk], A, B[lo:lo + chunk],
                  C[lo:lo + chunk], D) for lo in range(0, x.shape[0], chunk)])
    return recurrence


def _window_shifted_by_one(x, w, b, conv=ref.conv):
    return conv(jnp.pad(x, ((1, 0), (0, 0)))[:-1], w, b)


def _attention_with_rope(u, w, cfg):
    H, K = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d, T = int(cfg["hidden_size"]) // H, u.shape[0]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] \
        / (10000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]

    def rope(x):
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    o = ref.attention(rope((u @ w["wq"]).reshape(T, H, d)),
                      rope((u @ w["wk"]).reshape(T, K, d)),
                      (u @ w["wv"]).reshape(T, K, d),
                      float(cfg["attention_multiplier"]))
    return o.reshape(T, H * d) @ w["wo"]


FAULTS = {
    "A without its sign": dict(mamba=_mamba_with(sign=1.0)),
    "dt without softplus": dict(mamba=_mamba_with(softplus=lambda t: t)),
    "dt without dt_bias": dict(weights=_zeroed("ssm", "dt_bias")),
    "D x left out": dict(weights=_zeroed("ssm", "D")),
    "the convolution's bias left out": dict(weights=_zeroed("ssm", "conv_b")),
    "the convolution's window shifted by one":
        dict(conv=_window_shifted_by_one),
    "the gate applied after the norm":
        dict(mamba=_mamba_with(gate_first=False)),
    "B and C exchanged": dict(mamba=_mamba_with(swap=True)),
    "the state not carried across chunks":
        dict(recurrence=_state_dropped_between_chunks(8)),
    "a residual multiplier of 1": dict(hf={"residual_multiplier": 1.0}),
    "the softmax scale 1/8": dict(hf={"attention_multiplier": 0.125}),
    "rope applied": dict(attention_layer=_attention_with_rope),
    "logits_scaling left out": dict(hf={"logits_scaling": 1.0}),
    "embedding_multiplier left out": dict(hf={"embedding_multiplier": 1.0}),
    "fp8-rounded weights": dict(weights=_fp8),
}


@pytest.fixture(scope="module")
def cell_check(run_memo):
    """The cell's own tolerances, and the reference at a small size (hidden
    256, the period m m a m, 64-token rows, chunks of 8) on bf16-rounded
    weights."""
    with open(CELL_CONFIG) as f:
        check = json.load(f)["check"]
    hf = hf_config(D=256, V=512)
    params = init(model_for(hf), seed=5)
    # queries and keys that prefer some positions: at a scale of 1/64 the
    # scores of freshly drawn projections are all but flat, and where nothing
    # is attended to, a rope moves nothing
    attn = params["layers"]["attn"]
    attn["wq"], attn["wk"] = attn["wq"] * 8.0, attn["wk"] * 8.0
    params = jax.tree_util.tree_map(
        lambda w: w.astype(jnp.bfloat16).astype(jnp.float32), params)
    rows = np.random.default_rng(7).integers(0, 512, (2, 64)).astype(np.int32)
    return check, hf, params, rows, run_memo(
        "granite_cell_check", lambda: ref.batch_loss(
            hf, getter(params, hf), rows))


def _failed(check, got, want):
    """The compared quantities that lie outside the cell's tolerance (the
    benchmark runner's rule: ``runners/train_hybrid.py:compare``)."""
    out = []
    for name in check["compared"]:
        g = np.asarray(got[name], np.float64)
        w = np.asarray(want[name], np.float64)
        if f"{name}_rel_tol" in check:
            ok = np.max(np.abs(g - w) / np.abs(w)) <= check[f"{name}_rel_tol"]
        else:
            ok = np.max(np.abs(g - w)) <= check[f"{name}_abs_tol"]
        if not ok:
            out.append(name)
    return out


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_cells_check_sees_the_fault(cell_check, monkeypatch, fault):
    check, hf, params, rows, want = cell_check
    how = FAULTS[fault]
    for name in ("mamba", "conv", "recurrence", "attention_layer"):
        if name in how:
            monkeypatch.setattr(ref, name, how[name])
    bad_hf = {**hf, **how.get("hf", {})}
    got = ref.batch_loss(bad_hf, getter(how.get("weights", lambda p: p)(
        params), bad_hf), rows)
    assert _failed(check, got, want), fault
    assert not _failed(check, want, want)


def test_a_model_without_the_layer_loads_none_of_its_modules():
    """``import deepspeed_tpu`` and building, sharding and running a model
    whose layers are all attention load neither ``ops/ssd_scan.py`` nor
    ``models/mamba.py``, neither ``ops/delta_rule.py`` nor
    ``models/gated_delta.py`` (``setup_s`` of the cells that are there)."""
    import subprocess

    code = (
        "import sys, jax, jax.numpy as jnp\n"
        "import deepspeed_tpu\n"
        "from deepspeed_tpu.models import TransformerConfig, TransformerLM\n"
        "m = TransformerLM(TransformerConfig(hidden_size=64, num_heads=4,"
        " num_layers=2, vocab_size=64))\n"
        "p = m.init(jax.random.key(0)); m.param_specs()\n"
        "m.cfg.num_params_estimate(); m.step_program_facts()\n"
        "jax.jit(jax.grad(m.loss_fn))(p, {'input_ids': jnp.zeros((1, 8), "
        "'int32')})\n"
        "print([k for k in sys.modules if 'ssd_scan' in k or 'mamba' in k"
        " or 'delta' in k])")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, text=True,
                         capture_output=True,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"
