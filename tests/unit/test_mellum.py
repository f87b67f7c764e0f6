"""Window and full attention layers in turn with a rope of their own each,
and an expert layer that holds a share (Mellum2): the program against the
plain reference (``mellum_reference.py``, a copy of
``benchmarks/reference_mellum2.py``) on seeded random weights, the shares
adding up to the whole layer, the layer plan, what the step leaves in the
record, and the faults the benchmark cell's check has to see."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_mellum2 as ref
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.models import transformer as tf

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ROPE = {"full_attention": {"rope_type": "yarn", "rope_theta": 10000.0,
                           "factor": 4, "beta_fast": 4, "beta_slow": 1,
                           "original_max_position_embeddings": 16,
                           "attention_factor": 1.2},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000.0}}
COEF = 0.001


def hf_config(L=8, D=64, held=4, first=2, routed=8, k=3, F=48, V=96,
              window=8, **over):
    types = (["sliding_attention"] * 3 + ["full_attention"]) * (L // 4)
    return {"hidden_size": D, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 32, "num_hidden_layers": L,
            "layer_types": types, "sliding_window": window,
            "rope_parameters": ROPE, "num_experts": held,
            "router_width": routed, "first_expert": first,
            "num_experts_per_tok": k, "moe_intermediate_size": F,
            "rms_norm_eps": 1e-6, "vocab_size": V, **over}


def model_for(hf, **over):
    held, routed = hf["num_experts"], hf["router_width"]
    kw = dict(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        num_layers=hf["num_hidden_layers"], num_heads=4, num_kv_heads=2,
        head_dim_override=32, intermediate_size=128, max_seq_len=64,
        tie_embeddings=False, norm_eps=1e-6, dtype="float32",
        sliding_window=hf["sliding_window"],
        attn_pattern=("window", "window", "window", "full"),
        rope_by_kind={"full": ROPE["full_attention"],
                      "window": ROPE["sliding_attention"]},
        num_experts=routed, top_k=hf["num_experts_per_tok"],
        moe_dispatch="grouped", moe_aux_loss_coef=COEF,
        moe_intermediate_size=hf["moe_intermediate_size"],
        moe_experts_held=None if held == routed else held,
        moe_first_expert=hf["first_expert"], attention_impl="xla")
    kw.update(over)
    return TransformerLM(TransformerConfig(**kw))


def getter(params):
    layers = params["layers"]

    def get(name, layer=None):
        if name == "embed":
            return params["embed"]["tokens"]
        if name == "final_norm":
            return params["final_norm"]["scale"]
        if name == "head":
            return params["lm_head"]
        if name.startswith("ln"):
            return layers[name]["scale"][layer]
        group = "attn" if name in ("wq", "wk", "wv", "wo") else "mlp"
        return layers[group][name][layer]

    return get


def init(model, seed=0, router_gain=4.0):
    params = jax.jit(model.init)(jax.random.key(seed))     # one program, not an op at a time
    # a router that prefers some experts, so that the top k is not a toss-up
    params["layers"]["mlp"]["router"] = \
        params["layers"]["mlp"]["router"] * router_gain
    return params


ROWS = np.random.default_rng(0).integers(0, 96, (2, 24)).astype(np.int32)


@pytest.fixture(scope="module")
def small(run_memo):
    hf = hf_config()
    model = model_for(hf)
    params = init(model)
    want = run_memo("mellum_small", lambda: ref.batch_loss(
        hf, getter(params), ROWS, COEF))
    return hf, model, params, want


def test_loss_load_balance_term_and_counts_match_the_reference(small):
    hf, model, params, want = small
    loss, parts = jax.jit(model.loss_and_parts)(params, {"input_ids": ROWS})
    np.testing.assert_allclose(loss, want["loss"], atol=2e-5)
    np.testing.assert_allclose(parts["lb_loss"], want["lb_loss"], rtol=1e-5)
    np.testing.assert_array_equal(parts["expert_pairs"],
                                  want["expert_pairs"])
    np.testing.assert_array_equal(parts["pairs_here"],
                                  np.asarray(want["expert_pairs"]).sum(-1))
    assert not np.asarray(parts["pairs_dropped"]).any()
    pairs = np.asarray(want["expert_pairs"], np.float64)
    np.testing.assert_allclose(parts["load_max_over_mean"],
                               pairs.max(-1) / pairs.mean(-1), rtol=1e-5)


def test_gradients_match_the_reference(small):
    hf, model, params, _ = small
    got = jax.jit(jax.grad(model.loss_fn))(params, {"input_ids": ROWS})
    get, got_of = getter(params), getter(got)
    weights = {(n, None): get(n) for n in ("embed", "final_norm", "head")}
    for i in range(hf["num_hidden_layers"]):
        weights.update({(n, i): get(n, i) for n in ref.LAYER_TENSORS})
    _, want = ref.loss_and_grads(hf, weights, ROWS, COEF)
    for (name, layer), g in want.items():
        scale = float(np.abs(g).max())
        np.testing.assert_allclose(
            got_of(name, layer), g, atol=2e-5 * max(scale, 1.0) + 1e-7,
            err_msg=f"{name} of layer {layer}")


def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """model-configs section 4's test: the partial sums that the four
    shares give, added, are what the uncut reference gives for the layer."""
    from deepspeed_tpu.moe.sharded_moe import grouped_moe_mlp_block

    rng = np.random.default_rng(1)
    D, F, E, k, T = 64, 48, 8, 3, 40
    x = jnp.asarray(rng.standard_normal((1, T, D)), jnp.float32)
    w = {"router": jnp.asarray(rng.standard_normal((D, E)) * 0.5, jnp.float32),
         "w_gate": jnp.asarray(rng.standard_normal((E, D, F)) / 8, jnp.float32),
         "w_up": jnp.asarray(rng.standard_normal((E, D, F)) / 8, jnp.float32),
         "w_down": jnp.asarray(rng.standard_normal((E, F, D)) / 7, jnp.float32)}
    whole, *_ = ref.experts(x[0], w, {"num_experts": E,
                                      "num_experts_per_tok": k})
    total, pairs = 0.0, []
    for first in range(0, E, 2):
        cfg = TransformerConfig(num_experts=E, top_k=k, moe_dispatch="grouped",
                                moe_experts_held=2, moe_first_expert=first)
        share = {n: (v if n == "router" else v[first:first + 2])
                 for n, v in w.items()}
        out, aux = grouped_moe_mlp_block(x, share, cfg)
        want, *_ = ref.experts(x[0], share, {
            "num_experts": 2, "first_expert": first, "router_width": E,
            "num_experts_per_tok": k})
        np.testing.assert_allclose(out[0], want, atol=1e-5)
        total = total + out[0]
        pairs.append(int(aux["expert_pairs"].sum()))
    np.testing.assert_allclose(total, whole, atol=2e-5)
    assert sum(pairs) == T * k           # every pair computed exactly once


@pytest.mark.parametrize("factor, dropped", [(0.0, False), (4.0, False),
                                             (0.5, True)])
def test_a_pair_that_does_not_fit_the_buffer_is_counted(small, factor,
                                                        dropped):
    hf, _, params, want = small
    model = model_for(hf, moe_ep_capacity_factor=factor)
    loss, parts = jax.jit(model.loss_and_parts)(params, {"input_ids": ROWS})
    n = int(np.asarray(parts["pairs_dropped"]).sum())
    assert (n > 0) == dropped
    np.testing.assert_array_equal(
        np.asarray(parts["pairs_here"]) + np.asarray(parts["pairs_dropped"]),
        np.asarray(parts["expert_pairs"]).sum(-1))
    # the benchmark cell is not correct unless the counter reads 0; and a
    # dropped pair changes what the layers compute
    assert (abs(float(loss) - float(want["loss"])) > 1e-4) == dropped


# ---- the layer plan -------------------------------------------------------

def _plan(**kw):
    return TransformerLM(TransformerConfig(
        hidden_size=64, num_heads=4, max_seq_len=32, **kw))._layer_plan()


def test_the_layer_plan():
    assert _plan(num_layers=5) == [(0, 5, ("full",))]
    assert _plan(num_layers=5, sliding_window=8) == [(0, 5, ("window",))]
    period = ("window", "window", "window", "full")
    assert _plan(num_layers=28, sliding_window=8, attn_pattern=period) \
        == [(0, 28, period)]
    # HF qwen2's leading run of full layers: two scans of one kind each
    qwen = ("full",) * 3 + ("window",) * 9
    assert _plan(num_layers=12, sliding_window=8, attn_pattern=qwen) \
        == [(0, 3, ("full",)), (3, 12, ("window",))]
    # a short one is a period of the whole stack
    assert _plan(num_layers=3, sliding_window=8,
                 attn_pattern=("full", "window", "window")) \
        == [(0, 3, ("full", "window", "window"))]
    with pytest.raises(ValueError, match="attn_pattern"):
        _plan(num_layers=6, sliding_window=8, attn_pattern=("full",) * 4)
    with pytest.raises(ValueError, match="sliding_window"):
        _plan(num_layers=4, attn_pattern=("window", "full"))


def test_a_28_layer_patterned_model_traces_four_block_bodies(monkeypatch):
    calls = []
    block = tf.transformer_block

    def counting(x, w, cfg, *a, **kw):
        calls.append((cfg.sliding_window, kw.get("kind")))
        return block(x, w, cfg, *a, **kw)

    monkeypatch.setattr(tf, "transformer_block", counting)
    model = model_for(hf_config(L=28))
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    jax.make_jaxpr(model.loss_fn)(shapes, {"input_ids": ROWS})
    assert calls == [(8, "window")] * 3 + [(None, "full")]


@pytest.mark.parametrize("scan", [True, False])
def test_a_leading_run_of_full_layers_is_the_patterns_special_case(
        monkeypatch, scan):
    """What ``window_start_layer`` did (full layers first, then windowed
    ones) written as a pattern: cut into two scans of one kind (a list longer
    than a period body may be) it gives the numbers of one period of twelve
    blocks, and of the layers applied one by one."""
    kinds = ("full",) * 3 + ("window",) * 9
    cfg = TransformerConfig(
        vocab_size=96, hidden_size=64, num_layers=12, num_heads=4,
        max_seq_len=32, dtype="float32", sliding_window=6,
        attn_pattern=kinds, attention_impl="xla", scan_layers=scan)
    model = TransformerLM(cfg)
    assert len(model._layer_plan()) == 2
    params = model.init(jax.random.key(3))
    ids = ROWS[:, :20]
    cut = model.logits(params, ids)
    monkeypatch.setattr(tf, "_MAX_PERIOD", 12)
    assert len(model._layer_plan()) == 1
    np.testing.assert_allclose(model.logits(params, ids), cut, atol=2e-5)
    # one by one: each layer under its kind's config
    x = params["embed"]["tokens"][ids]
    for i, kind in enumerate(kinds):
        ck, freqs = model._kinds[kind]
        assert ck.sliding_window == (6 if kind == "window" else None)
        x, _ = tf.transformer_block(
            x, jax.tree_util.tree_map(lambda a: a[i], params["layers"]), ck,
            freqs, tf.xla_attention)
    x = tf._norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    np.testing.assert_allclose(x @ params["embed"]["tokens"].T, cut,
                               atol=2e-5)


def test_yarn_frequencies_and_factor_are_the_references():
    for d, rp in [(32, ROPE["full_attention"]),
                  (128, {"rope_type": "yarn", "rope_theta": 500000,
                         "factor": 16, "beta_fast": 32, "beta_slow": 1,
                         "original_max_position_embeddings": 8192,
                         "attention_factor": 1.2772588722239782}),
                  (128, {"rope_type": "yarn", "rope_theta": 500000,
                         "factor": 16,
                         "original_max_position_embeddings": 8192})]:
        inv, scale = ref.rope_inverse_frequencies(d, rp)
        scaling = {k: v for k, v in rp.items() if k != "rope_theta"}
        table = tf.rope_frequencies(d, 9, rp["rope_theta"], scaling)
        np.testing.assert_allclose(table, np.outer(np.arange(9), inv),
                                   rtol=1e-6)
        assert tf.rope_attention_factor(scaling) == pytest.approx(scale)
    assert tf.rope_attention_factor({"rope_type": "llama3"}) == 1.0
    # the published factor is 0.1 ln(16) + 1
    assert ref.rope_inverse_frequencies(128, {
        "rope_type": "yarn", "rope_theta": 5e5, "factor": 16,
        "original_max_position_embeddings": 8192})[1] \
        == pytest.approx(1.2772588722239782)


# ---- the step record and the step-program row -----------------------------

def test_the_step_record_carries_the_routers_counts():
    import deepspeed_tpu as ds
    from deepspeed_tpu.observability import steplog
    from deepspeed_tpu.parallel import build_mesh

    hf = hf_config(L=4)
    model = model_for(hf, remat_policy="full")
    eng, *_ = ds.initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "steps_per_print": 10 ** 9, "zero_optimization": {"stage": 0}},
        mesh=build_mesh(devices=jax.devices()[:1]))
    want = ref.batch_loss(hf, getter(eng.params), ROWS, COEF)
    loss = float(eng.fused_train_step({"input_ids": ROWS}))
    row = steplog.get_steplog().parts(last=1)[-1]
    assert row["loss"] == loss
    np.testing.assert_allclose(loss, want["loss"], atol=2e-5)
    np.testing.assert_allclose(row["lb_loss"], want["lb_loss"], rtol=1e-5)
    np.testing.assert_array_equal(row["expert_pairs"], want["expert_pairs"])
    assert row["pairs_dropped"].tolist() == [0] * 4
    assert row["pairs_here"].shape == row["load_max_over_mean"].shape == (4,)
    prog = steplog.programs()[-1]
    assert prog.layer_pattern == ("window", "window", "window", "full")
    assert prog.moe_kernel_resolved == "ragged"
    # float32 at a width of 64 on a CPU: every product is lax.ragged_dot
    # (three a layer; its transposes are autodiff's and are not counted)
    assert prog.moe_grouped_lowerings == {"xla": 12}
    # and every dispatch and combine, with its backward, is jnp.take
    assert prog.moe_dispatch_lowerings == {"xla": 16}
    # and a router's 2 of 8 off a TPU is lax.top_k (ops/topk_select.py)
    assert prog.moe_topk_lowerings == {"xla": 4}
    assert prog.experts_held == (2, 4, 8)
    assert prog.layer_applications == 4
    dense = TransformerLM(TransformerConfig(hidden_size=64, num_heads=4))
    assert dense.step_program_facts() == {"layer_applications": 4,
                                          "layer_pattern": ("full",)}


def test_the_cell_shaped_step_program_takes_the_kernels(monkeypatch):
    """What the benchmark's cell is in small: bf16, whole-lane widths, a tile
    of rows for each held expert, no recomputation. ``ragged`` still names
    the algebra, and every grouped product of the program, nine a layer, is
    the Pallas kernel (interpreted here: the CPU stands in for the chip), as
    is every move into and out of the buffer of pairs, four a layer."""
    import functools

    import deepspeed_tpu as ds
    from deepspeed_tpu.moe import sharded_moe as sm
    from deepspeed_tpu.observability import steplog
    from deepspeed_tpu.parallel import build_mesh

    monkeypatch.setattr(sm, "grouped_moe_mlp_block", functools.partial(
        sm.grouped_moe_mlp_block, interpret=True))
    hf = hf_config(L=4, D=128, F=128)
    model = model_for(hf, dtype="bfloat16", max_seq_len=128)
    eng, *_ = ds.initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "steps_per_print": 10 ** 9, "zero_optimization": {"stage": 0}},
        mesh=build_mesh(devices=jax.devices()[:1]))
    rows = np.random.default_rng(1).integers(0, 96, (2, 128)).astype(np.int32)
    assert np.isfinite(float(eng.fused_train_step({"input_ids": rows})))
    prog = steplog.programs()[-1]
    assert prog.moe_kernel_resolved == "ragged"
    assert prog.moe_grouped_lowerings == {"pallas": 36}
    # dispatch, combine and the backward of each, a layer, are row kernels
    assert prog.moe_dispatch_lowerings == {"pallas": 16}
    row = steplog.get_steplog().parts(last=1)[-1]
    assert row["pairs_dropped"].tolist() == [0] * 4
    assert row["expert_pairs"].sum() == row["pairs_here"].sum() > 0


# ---- the published config -------------------------------------------------

def test_the_published_config_maps_onto_the_model():
    from deepspeed_tpu.models.hf import config_from_hf, load_hf_checkpoint

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "mellum2_12b_train_d4e16.json")) as f:
        cell = json.load(f)
    pub = {k: v for k, v in cell.items()
           if k not in ("reduced", "assumed", "modules", "deployment",
                        "check", "router_width", "first_expert")}
    pub.update(num_hidden_layers=28, num_experts=64, vocab_size=98304)
    cfg = config_from_hf(pub)
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.num_layers, cfg.vocab_size) == (2304, 32, 4, 128, 28, 98304)
    assert cfg.attn_pattern == ("window", "window", "window", "full")
    assert cfg.sliding_window == 1024 and not cfg.tie_embeddings
    assert (cfg.num_experts, cfg.top_k, cfg.moe_intermediate_size,
            cfg.moe_dispatch, cfg.moe_experts_held) \
        == (64, 8, 896, "grouped", None)
    full, window = cfg.kind_cfg("full"), cfg.kind_cfg("window")
    assert window.rope_scaling is None and window.rope_theta == 5e5
    assert full.rope_scaling["rope_type"] == "yarn" \
        and full.sliding_window is None and full.rope_theta == 5e5
    assert tf.rope_attention_factor(full.rope_scaling) \
        == 1.2772588722239782
    assert cfg.norm_eps == 1e-6

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


def _renormalised_over_the_held_picks(x, router, k):
    p, top_e, top_w = ref_route(x, router, k)
    here = (top_e >= 2) & (top_e < 6)
    w = jnp.where(here, top_w, 0.0)
    return p, top_e, w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)


def _top_k_over_the_held_only(x, router, k):
    p = jax.nn.softmax(x @ router, axis=-1)
    top_p, top_e = jax.lax.top_k(p[:, 2:6], k)
    return p, top_e + 2, top_p / top_p.sum(-1, keepdims=True)


ref_route = ref.route
FAULTS = {
    "weights renormalised over the held picks only":
        dict(route=_renormalised_over_the_held_picks),
    "top k taken over the held experts only":
        dict(route=_top_k_over_the_held_only),
    "the window on the full layer":
        dict(hf={"layer_types": ["sliding_attention"] * 4,
                 "rope_parameters": {**ROPE, "sliding_attention":
                                     ROPE["full_attention"]}},
             kinds=["sliding_attention"] * 3 + ["full_attention"]),
    "no window on a sliding layer": dict(hf={"sliding_window": None}),
    "plain rope on the full layer":
        dict(hf={"rope_parameters": {**ROPE, "full_attention":
                                     ROPE["sliding_attention"]}}),
    "the attention factor left out":
        dict(hf={"rope_parameters": {**ROPE, "full_attention": {
            **ROPE["full_attention"], "attention_factor": 1.0}}}),
    "fp8-rounded weights": dict(weights=_fp8),
}


@pytest.fixture(scope="module")
def cell_check(run_memo):
    """The cell's own tolerances, and the reference at a small size (hidden
    256, one period, 64-token rows) on bf16-rounded weights."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "mellum2_12b_train_d4e16.json")) as f:
        check = json.load(f)["check"]
    hf = hf_config(L=4, D=256, held=4, first=2, routed=16, k=4, F=96, V=512)
    hf["num_attention_heads"], hf["num_key_value_heads"] = 8, 2
    model = TransformerLM(TransformerConfig(
        vocab_size=512, hidden_size=256, num_layers=4, num_heads=8,
        num_kv_heads=2, head_dim_override=32, tie_embeddings=False,
        sliding_window=8, attn_pattern=("window",) * 3 + ("full",),
        num_experts=16, top_k=4, moe_dispatch="grouped",
        moe_intermediate_size=96, moe_experts_held=4, moe_first_expert=2))
    params = jax.tree_util.tree_map(
        lambda w: w.astype(jnp.bfloat16).astype(jnp.float32),
        init(model, seed=5, router_gain=1.0))
    rows = np.random.default_rng(7).integers(0, 512, (2, 64)).astype(np.int32)
    return check, hf, params, rows, run_memo(
        "mellum_cell_check", lambda: ref.batch_loss(
            hf, getter(params), rows, COEF))


def _failed(check, got, want):
    """The compared quantities that lie outside the cell's tolerance."""
    return [name for name in check["compared"]
            if not np.max(np.abs(np.asarray(got[name], np.float64)
                                 - np.asarray(want[name], np.float64)))
            <= check[f"{name}_abs_tol"]]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_cells_check_sees_the_fault(cell_check, monkeypatch, fault):
    check, hf, params, rows, want = cell_check
    how = FAULTS[fault]
    if "route" in how:
        monkeypatch.setattr(ref, "route", how["route"])
    bad_hf = {**hf, **how.get("hf", {})}
    if "kinds" in how:      # the layers keep their ropes, all get the window
        bad_hf["layer_types"] = how["kinds"]
        bad_hf["rope_parameters"] = ROPE
        monkeypatch.setattr(ref, "block", _window_everywhere(ref.block))
    got = ref.batch_loss(bad_hf, getter(how.get("weights", lambda p: p)(
        params)), rows, COEF)
    assert _failed(check, got, want), fault
    assert not _failed(check, want, want)


def _window_everywhere(block):
    def faulty(x, w, cfg, kind, positions):
        if kind == "full_attention":
            # the full layer's own (yarn) rope, under the sliding layers' mask
            cfg = {**cfg, "rope_parameters": {
                **cfg["rope_parameters"],
                "sliding_attention": cfg["rope_parameters"]["full_attention"]}}
            kind = "sliding_attention"
        return block(x, w, cfg, kind, positions)
    return faulty
