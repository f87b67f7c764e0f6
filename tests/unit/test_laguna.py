"""Window and full attention layers that differ in their query heads (two
stacks of leaves, two group sizes over the same key-value heads), each kind
under a rope of its own width and scaling, every head under a sigmoid gate,
over a dense first layer and sigmoid-routed experts beside a shared one
(Laguna-S-2.1): the program against the plain reference
(``benchmarks/reference_laguna.py``) on seeded random weights, the engine's
fused step on the mesh, the shares adding up to the whole layer, what the
published file maps onto and what refuses it, that a model with one head
count is the program it was, and the faults the benchmark cell's check has
to see."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import modelcfg_laguna as mc
from benchmarks import reference_laguna as ref
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.models import transformer as tf

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL_CONFIG = os.path.join(ROOT, "benchmarks", "configs",
                           "laguna_s21_train_d5h24e8v8.json")
ALPHA, GAMMA = 1e-4, 1e-3
FULL, SLIDE = "full_attention", "sliding_attention"
YARN = {"rope_theta": 500000, "rope_type": "yarn", "factor": 8,
        "original_max_position_embeddings": 16, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5}
PLAIN = {"rope_type": "default", "rope_theta": 10000,
         "partial_rotary_factor": 1}


def hf_config(D=64, d=16, heads=(4, 6), K=2, share=2, held=8, first=4,
              routed=16, k=3, V=128, window=8, **over):
    """The cell's structure at a small size: a full dense layer, then
    sliding x 3 and full; ``heads`` (full, sliding) query heads held over
    ``K`` key-value heads held (groups of 2 and of 3), ``share`` times as
    many published."""
    types = [FULL, SLIDE, SLIDE, SLIDE, FULL]
    return {"model_type": "laguna", "hidden_size": D, "head_dim": d,
            "num_hidden_layers": 5, "layer_types": types,
            "num_attention_heads": heads[0], "num_key_value_heads": K,
            "num_attention_heads_per_layer": [
                heads[t == SLIDE] for t in types],
            "heads": heads[0] * share, "kv_heads": K * share,
            "gating": "per-head", "sliding_window": window,
            "rope_parameters": {FULL: dict(YARN), SLIDE: dict(PLAIN)},
            "rms_norm_eps": 1e-6, "mlp_only_layers": [0],
            "intermediate_size": 96, "moe_intermediate_size": 32,
            "shared_expert_intermediate_size": 32, "num_experts": held,
            "router_width": routed, "first_expert": first,
            "num_experts_per_tok": k, "norm_topk_prob": True,
            "moe_routed_scaling_factor": 2.5, "vocab_size": V,
            "tie_word_embeddings": False,
            "deployment": {"local_pairs_factor": 0.0,
                           "bias_update_rate": GAMMA, "bias_init": 0.1,
                           "balance_coef": ALPHA, "remat_policy": "none",
                           "embed_init_std": 1.0}, **over}


def model_for(hf, **over):
    kw = dict(max_seq_len=64, param_dtype="float32", dtype="float32",
              attention_impl="xla")
    kw.update(over)
    return TransformerLM(mc.transformer_config(hf, **kw))


def init(model, seed=0, router_gain=4.0, qk_gain=1.0):
    params = jax.jit(model.init)(jax.random.key(seed))     # one program, not an op at a time
    # a router that prefers some experts, so that the top k is not a toss-up
    moe = params["layers"]["mlp_moe"]
    moe["router"] = moe["router"] * router_gain
    for grp in ("attn_window", "attn_full"):
        # queries and keys that prefer some positions, so that a rope, a
        # window or a group fault moves what is attended to
        g = params["layers"][grp]
        g["wq"], g["wk"] = g["wq"] * qk_gain, g["wk"] * qk_gain
    return params


ROWS = np.random.default_rng(0).integers(0, 128, (2, 24)).astype(np.int32)


@pytest.fixture(scope="module")
def small(run_memo):
    hf = hf_config()
    model = model_for(hf)
    params = init(model, qk_gain=2.0)
    return hf, model, params, run_memo("laguna_small", lambda: ref.batch_loss(
        hf, mc.weights_getter(params, hf), ROWS, ALPHA))


def test_the_kinds_keep_stacks_of_their_own_heads(small):
    hf, model, params, _ = small
    cfg, layers = model.cfg, params["layers"]
    assert cfg.layer_kinds == ("full:dense", "window:moe", "window:moe",
                               "window:moe", "full:moe")
    assert model._layer_plan() == [(0, 1, ("full:dense",)),
                                   (1, 4, ("window:moe",)),
                                   (4, 5, ("full:moe",))]
    assert "attn" not in layers
    shapes = {g: {n: a.shape for n, a in layers[g].items()}
              for g in ("attn_full", "attn_window")}
    assert shapes == {
        "attn_full": {"wq": (2, 64, 64), "wk": (2, 64, 32),
                      "wv": (2, 64, 32), "wg": (2, 64, 4),
                      "wo": (2, 64, 64)},
        "attn_window": {"wq": (3, 64, 96), "wk": (3, 64, 32),
                        "wv": (3, 64, 32), "wg": (3, 64, 6),
                        "wo": (3, 96, 64)}}
    kinds = {k.partition(":")[0]: c for k, (c, _) in model._kinds.items()}
    assert (kinds["full"].num_heads, kinds["full"].heads_here,
            kinds["full"].kv_heads_here, kinds["full"].rope_dim,
            kinds["full"].sliding_window) == (8, 4, 2, 8, None)
    assert (kinds["window"].num_heads, kinds["window"].heads_here,
            kinds["window"].kv_heads_here, kinds["window"].rope_dim,
            kinds["window"].sliding_window) == (12, 6, 2, 16, 8)
    assert kinds["full"].rope_scaling["rope_type"] == "yarn"
    assert "partial_rotary_factor" not in kinds["full"].rope_scaling
    assert kinds["window"].rope_scaling is None
    assert cfg.num_params_estimate() == sum(
        a.size for a in jax.tree_util.tree_leaves(params))
    assert set(model.param_specs()["layers"]) == set(layers)
    assert model.step_program_facts((2, 24)) == {
        "layer_applications": 5,
        "layer_pattern": ("full:dense", "window:moe", "full:moe"),
        "heads_held": {"full": (4, 8), "window": (6, 12)},
        "attn_heads_per_step": 2 * 4 + 3 * 6, "moe_scoring": "sigmoid",
        "experts_held": (4, 8, 16), "moe_kernel_resolved": "ragged"}


def test_loss_balance_term_mixer_outputs_and_counts_match_the_reference(
        small):
    hf, model, params, want = small
    loss, parts = jax.jit(model.loss_and_parts)(params, {"input_ids": ROWS})
    np.testing.assert_allclose(loss, want["loss"], atol=2e-5)
    np.testing.assert_allclose(parts["lb_loss"], want["lb_loss"], rtol=1e-5)
    np.testing.assert_allclose(parts["mix_out_ms"], want["mix_out_ms"],
                               rtol=1e-4)
    assert parts["mix_out_ms"].shape == (5,)
    np.testing.assert_array_equal(parts["router_counts"],
                                  want["router_counts"])
    np.testing.assert_array_equal(parts["expert_pairs"],
                                  want["expert_pairs"])
    assert parts["expert_pairs"].shape == (4, 8)      # the routed layers'
    assert not np.asarray(parts["pairs_dropped"]).any()


def _all_weights(hf, get):
    weights = {(n, None): get(n) for n in ("embed", "final_norm", "head")}
    for i in range(hf["num_hidden_layers"]):
        names = ref.ATTN_TENSORS + (ref.DENSE_TENSORS if ref.is_dense(hf, i)
                                    else ref.ROUTED_TENSORS)
        weights.update({(n, i): get(n, i) for n in names})
    return weights


def test_gradients_of_every_leaf_match_the_reference(small):
    hf, model, params, _ = small
    got = jax.jit(jax.grad(model.loss_fn))(params, {"input_ids": ROWS})
    got_of = mc.weights_getter(got, hf)
    _, want = ref.loss_and_grads(
        hf, _all_weights(hf, mc.weights_getter(params, hf)), ROWS, ALPHA)
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


# ---- the engine: the fused step, the bias rule, the row's facts ------------

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


def test_fused_steps_on_the_mesh_match_the_reference_and_move_the_bias():
    from deepspeed_tpu.observability import steplog

    hf = hf_config()
    engine = _engine(model_for(hf))
    bias = np.array(mc.biases(engine.params))
    wg = np.array(engine.params["layers"]["attn_window"]["wg"])
    for step in range(2):
        rows = np.random.default_rng(step).integers(
            0, 128, (2, 24)).astype(np.int32)
        want = ref.batch_loss(hf, mc.weights_getter(engine.params, hf), rows,
                              ALPHA)
        loss = float(engine.fused_train_step({"input_ids": rows}))
        np.testing.assert_allclose(loss, want["loss"], atol=2e-5)
        part = steplog.get_steplog().parts(last=1)[-1]
        np.testing.assert_array_equal(part["router_counts"],
                                      want["router_counts"])
        np.testing.assert_allclose(part["mix_out_ms"], want["mix_out_ms"],
                                   rtol=1e-4)
        bias = np.asarray(ref.bias_after(bias, want["router_counts"], GAMMA))
        np.testing.assert_allclose(mc.biases(engine.params), bias, atol=1e-7)
    # the optimizer moved the gates of either kind
    assert np.abs(np.array(engine.params["layers"]["attn_window"]["wg"])
                  - wg).max() > 1e-3
    row = [p for p in steplog.programs()
           if p.name.startswith("ds_train_step")][-1]
    assert row.heads_held == {"full": (4, 8), "window": (6, 12)}
    assert row.attn_heads_per_step == 26 and row.layer_applications == 5
    assert row.layer_pattern == ("full:dense", "window:moe", "full:moe")
    # a bf16 model's carried copy holds both kinds' stacks, gates and all
    bf16 = model_for(hf, dtype="bfloat16")
    work = jax.eval_shape(bf16.working_copy,
                          jax.eval_shape(bf16.init, jax.random.key(0)))
    assert set(work["layers"]["attn_window"]) == {"wq", "wk", "wv", "wg",
                                                  "wo"}
    assert work["layers"]["attn_full"]["wg"].dtype == jnp.bfloat16
    assert "router_bias" not in work["layers"]["mlp_moe"]


# ---- the shares add up -----------------------------------------------------

def test_both_head_shares_and_every_expert_share_are_the_uncut_layer():
    """model-configs section 4's test: the partial sums that the two shares
    of a layer's heads give, added, are the uncut reference's mixer, for a
    layer of either kind; and the shares of the experts, with the shared
    expert counted once, its FFN."""
    from deepspeed_tpu.moe.sharded_moe import grouped_moe_mlp_block

    whole = hf_config(heads=(8, 12), K=4, share=1, held=16, first=0)
    half = model_for(hf_config())
    rng = np.random.default_rng(1)
    f32 = lambda a: jnp.asarray(a, jnp.float32)      # noqa: E731
    T, D, d = 24, 64, 16
    u = f32(rng.standard_normal((1, T, D)))
    for layer, kind in ((1, "window:moe"), (4, "full:moe")):
        H, K = whole["num_attention_heads_per_layer"][layer], 4
        w = {"wq": f32(rng.standard_normal((D, H * d)) / 4),
             "wk": f32(rng.standard_normal((D, K * d)) / 4),
             "wv": f32(rng.standard_normal((D, K * d)) / 8),
             "wg": f32(rng.standard_normal((D, H)) / 4),
             "wo": f32(rng.standard_normal((H * d, D)) / 8)}
        with jax.default_matmul_precision("highest"):
            want = ref.mixer(u[0], w, whole, layer, jnp.arange(T))
        ck, freqs = half._kinds[kind]
        assert (ck.heads_here, ck.kv_heads_here) == (H // 2, K // 2)
        total = jnp.zeros((T, D), jnp.float32)
        for share in range(2):
            hs = slice(share * H // 2 * d, (share + 1) * H // 2 * d)
            ks = slice(share * K // 2 * d, (share + 1) * K // 2 * d)
            ws = {"wq": w["wq"][:, hs], "wk": w["wk"][:, ks],
                  "wv": w["wv"][:, ks], "wo": w["wo"][hs],
                  "wg": w["wg"][:, share * H // 2:(share + 1) * H // 2]}
            total = total + tf.attention_block(u, ws, ck, freqs,
                                               tf.xla_attention)[0]
        np.testing.assert_allclose(total, want, atol=2e-5)
    E, F, k = 16, 32, 3
    w = {"router": f32(rng.standard_normal((D, E)) * 0.5),
         "router_bias": f32(rng.uniform(-0.1, 0.1, (E,))),
         "w_gate": f32(rng.standard_normal((E, D, F)) / 8),
         "w_up": f32(rng.standard_normal((E, D, F)) / 8),
         "w_down": f32(rng.standard_normal((E, F, D)) / 6)}
    shared = {"w_gate": f32(rng.standard_normal((D, F)) / 8),
              "w_up": f32(rng.standard_normal((D, F)) / 8),
              "w_down": f32(rng.standard_normal((F, D)) / 8)}
    want, counts, _ = ref.experts(u[0], {
        **w, "shared_gate": shared["w_gate"], "shared_up": shared["w_up"],
        "shared_down": shared["w_down"]}, whole)
    total, pairs = jnp.zeros((T, D), jnp.float32), []
    for share in range(4):
        lo = 4 * share
        cfg = TransformerConfig(
            hidden_size=D, num_heads=4, num_experts=E, top_k=k,
            moe_dispatch="grouped", moe_intermediate_size=F,
            moe_experts_held=4, moe_first_expert=lo, moe_scoring="sigmoid",
            moe_routed_scale=2.5, dtype="float32")
        ws = {**w, **{n: w[n][lo:lo + 4]
                      for n in ("w_gate", "w_up", "w_down")}}
        if share == 2:      # whole on every chip: counted once
            ws["shared"] = shared
        out, aux = grouped_moe_mlp_block(u, ws, cfg)
        total = total + out[0]
        pairs.append(aux["expert_pairs"])
    np.testing.assert_allclose(total, want, atol=2e-5)
    np.testing.assert_array_equal(jnp.concatenate(pairs), counts)


# ---- the published file, and what refuses ----------------------------------

def _published():
    with open(CELL_CONFIG) as f:
        cell = json.load(f)
    return {**mc.published_heads(cell), "num_hidden_layers": 48,
            "num_experts": 256, "vocab_size": 100352}


def test_the_published_config_maps_onto_the_model(tmp_path):
    from deepspeed_tpu.models.hf import config_from_hf, load_hf_checkpoint

    published = _published()
    cfg = config_from_hf(published, moe_bias_rate=GAMMA)
    assert (cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.heads_by_kind) == (48, 48, 8, 128, {"window": 72})
    assert cfg.attn_pattern == ("full", "window", "window", "window")
    assert cfg.sliding_window == 512 and cfg.mla_head_gate
    assert (cfg.first_k_dense, cfg.num_experts, cfg.top_k,
            cfg.moe_intermediate_size, cfg.moe_shared_experts,
            cfg.moe_scoring, cfg.moe_routed_scale) == (
                1, 256, 10, 1024, 1, "sigmoid", 2.5)
    assert cfg.layer_kinds[:5] == ("full:dense", "window:moe", "window:moe",
                                   "window:moe", "full:moe")
    full, window = cfg.kind_cfg("full"), cfg.kind_cfg("window")
    assert (full.heads_here, full.kv_heads_here, full.rope_dim,
            full.rope_theta) == (48, 8, 64, 500000.0)
    assert tf.rope_attention_factor(full.rope_scaling) == pytest.approx(
        1.4852030263919618)
    assert (window.heads_here, window.kv_heads_here, window.rope_dim,
            window.rope_theta, window.rope_scaling) == (
                72, 8, 128, 10000.0, None)
    # the whole model's count: 12 full and 36 window mixers with their gates,
    # one dense FFN, 47 routed layers of 256 experts and a shared one
    D = 3072
    full_attn = 2 * D * 48 * 128 + 2 * D * 8 * 128 + D * 48
    window_attn = 2 * D * 72 * 128 + 2 * D * 8 * 128 + D * 72
    routed = 257 * 3 * D * 1024 + D * 256 + 256
    assert cfg.num_params_estimate() == (
        12 * full_attn + 36 * window_attn + 48 * 2 * D + 3 * D * 12288
        + 47 * routed + 2 * 100352 * D + D)
    # the cell's share of it through the benchmark's mapping
    with open(CELL_CONFIG) as f:
        cell = json.load(f)
    held = mc.transformer_config(cell, max_seq_len=8192,
                                 param_dtype="float32")
    assert (held.heads_held, held.num_heads, held.heads_by_kind,
            held.moe_experts_held) == (24, 48, {"window": 72}, 8)
    assert held.num_params_estimate() == cell["deployment"]["parameters"]
    for bad, match in (
            (dict(gating="per-channel"), "gating"),
            (dict(gating_types=["per_head", "none"] * 24), "gating"),
            (dict(num_attention_heads_per_layer=[48, 72, 64, 72] * 12),
             "num_attention_heads_per_layer"),
            (dict(moe_router_logit_softcapping=30.0), "softcapping"),
            (dict(moe_apply_router_weight_on_input=True),
             "moe_apply_router_weight_on_input"),
            (dict(shared_expert_intermediate_size=1536),
             "shared_expert_intermediate_size"),
            (dict(mlp_only_layers=[0, 2]), "mlp_only_layers"),
            (dict(norm_topk_prob=False), "norm_topk_prob")):
        with pytest.raises(ValueError, match=match):
            config_from_hf({**published, **bad})
    with open(tmp_path / "config.json", "w") as f:
        json.dump(published, f)
    with pytest.raises(NotImplementedError, match="laguna"):
        load_hf_checkpoint(str(tmp_path))


def test_every_other_path_refuses_the_model_by_name():
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.runtime.pipe import PipelineModule

    refused = dict(match="heads_by_kind")
    # (every head held: heads_held alone would refuse the cell's share)
    model = model_for(hf_config(heads=(8, 12), K=4, share=1))
    for call in (lambda: InferenceEngine(model),
                 lambda: InferenceEngineV2(model, max_sequences=2,
                                           max_seq_len=32, block_size=8),
                 lambda: model.init_kv_cache(1),
                 lambda: model.init_paged_kv_cache(4, 8),
                 lambda: PipelineModule(model, num_stages=5),
                 lambda: model.set_random_ltd(8),
                 lambda: model.set_pld_depth(2),
                 lambda: model.check_topology({"tp": 2})):
        with pytest.raises(NotImplementedError, **refused):
            call()
    params = jax.eval_shape(model.init, jax.random.key(0))
    for program in (
            lambda p: model.forward_prefill(p, ROWS, jnp.asarray([24, 24])),
            lambda p: model.hidden_states(p, ROWS,
                                          pld_theta=jnp.float32(0.5)),
            lambda p: model._serve_layers(p, ROWS, None, None, None)):
        with pytest.raises(NotImplementedError, **refused):
            jax.eval_shape(program, params)
    with pytest.raises(NotImplementedError, match="mla_head_gate"):
        tf._decode_block(jnp.zeros((2, 1, 64)), {}, model.cfg, None, None,
                         None)
    for bad in (dict(loss_tiling=4), dict(attention_impl="fpdt"),
                dict(attention_impl="ring"), dict(num_passes=2),
                dict(parallel_block=True), dict(qkv_bias=True)):
        with pytest.raises(NotImplementedError, **refused):
            model_for(hf_config(heads=(8, 12), K=4, share=1), **bad)
    # one count for both kinds is no split, and a gate alone refuses too
    same = TransformerConfig(
        hidden_size=64, num_heads=4, num_layers=4, head_dim_override=16,
        sliding_window=8, attn_pattern=("window", "full"),
        heads_by_kind={"window": 4, "full": 4})
    assert same.heads_by_kind is None
    gated = TransformerLM(TransformerConfig(
        hidden_size=64, num_heads=4, num_layers=2, mla_head_gate=True))
    assert jax.eval_shape(gated.init, jax.random.key(0))[
        "layers"]["attn"]["wg"].shape == (2, 64, 4)
    with pytest.raises(NotImplementedError, match="mla_head_gate"):
        gated.init_kv_cache(1)
    for bad, match in ((dict(heads_by_kind={"window": 5}), "heads_by_kind"),
                       (dict(heads_by_kind={"mla": 8}), "heads_by_kind"),
                       (dict(heads_by_kind={"window": 6}, heads_held=3,
                             num_kv_heads=2), "heads_held")):
        with pytest.raises(ValueError, match=match):
            TransformerConfig(**{**dict(
                hidden_size=64, num_heads=4, num_kv_heads=2, num_layers=4,
                head_dim_override=16, sliding_window=8,
                attn_pattern=("window", "full")), **bad})


# ---- a model with one head count is the program it was ---------------------

def test_a_model_with_one_head_count_lowers_to_the_step_it_did():
    """A Mellum2-like model (window and full layers at one head count, a
    rope a kind, a held share of softmax-routed experts): its leaves' paths
    and shapes and the text its loss and gradient lower to, against the
    hashes read on the parent commit (4fba420) with this same function."""
    cfg = TransformerConfig(
        vocab_size=128, hidden_size=64, num_layers=4, num_heads=4,
        num_kv_heads=2, head_dim_override=16, intermediate_size=96,
        max_seq_len=32, tie_embeddings=False, norm_eps=1e-6,
        sliding_window=8, attn_pattern=("window", "window", "window", "full"),
        rope_by_kind={
            "full": {"rope_theta": 500000.0, "rope_type": "yarn",
                     "factor": 8.0, "original_max_position_embeddings": 16},
            "window": {"rope_theta": 10000.0, "rope_type": "default"}},
        num_experts=8, top_k=3, moe_dispatch="grouped",
        moe_intermediate_size=48, moe_experts_held=4, moe_first_expert=2,
        attention_impl="xla")
    model = TransformerLM(cfg)
    assert cfg.heads_by_kind is None and not cfg.reports_mixer_outputs
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    paths = [(jax.tree_util.keystr(p), a.shape, str(a.dtype))
             for p, a in jax.tree_util.tree_leaves_with_path(shapes)]
    assert "attn" in shapes["layers"] and not tf._split(shapes["layers"])
    text = jax.jit(jax.value_and_grad(model.loss_and_parts, has_aux=True)
                   ).lower(shapes, {"input_ids": jax.ShapeDtypeStruct(
                       (2, 16), jnp.int32)}).as_text()
    sha = lambda s: hashlib.sha256(s.encode()).hexdigest()   # noqa: E731
    assert sha(repr(paths)) == ("f1db6e43f681af72702ca75ddefafeaca0d5de9bbee"
                                "3b91d7988bdd09eb49b76")
    assert sha(text) == ("25d4cc6f06a987ad6cef7f6c2f044b92b75aaa2f32e2ee3f3"
                         "3bb31512fa270e2")


# ---- the cell's check sees each fault --------------------------------------

def _scaled_whole_head(x, positions, rp, orig=ref.rope):
    """yarn's attention factor on the passed-through channels too."""
    rotary = int(x.shape[-1] * float(rp.get("partial_rotary_factor", 1.0)))
    out = orig(x, positions, rp)
    scale = ref.inv_frequencies(rotary, rp)[1]
    return jnp.concatenate([out[..., :rotary], out[..., rotary:] * scale],
                           axis=-1)


def _gate_a_channel(u, w: dict, cfg, layer, positions, orig=ref.mixer):
    """One gate value a channel: channel c of head h under the gate of head
    (h + c) % H."""
    H = int(cfg["num_attention_heads_per_layer"][layer])
    d = int(cfg["head_dim"])
    seen = {}

    def head_gate(u, wg):
        seen["g"] = jax.nn.sigmoid(u @ wg)
        return jnp.ones_like(seen["g"])

    real, ref.head_gate = ref.head_gate, head_gate
    try:
        inner = {**w, "wo": jnp.eye(H * d, dtype=w["wo"].dtype)}
        a = orig(u, inner, cfg, layer, positions).reshape(-1, H, d)
    finally:
        ref.head_gate = real
    heads = (jnp.arange(H)[:, None] + jnp.arange(d)[None, :]) % H
    return (a * seen["g"][:, heads]).reshape(-1, H * d) @ w["wo"]


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


def _ropes(full=None, slide=None):
    return {"rope_parameters": {FULL: {**YARN, **(full or {})},
                                SLIDE: slide or dict(PLAIN)}}


FAULTS = {
    "every layer at one head count":
        dict(hf={"num_attention_heads_per_layer": [4] * 5}, heads=4),
    "a group of 2 where 3 belongs":
        dict(kv_head_of=lambda h, H, K: min(h // 2, K - 1)),
    "the full layers' rope on a window layer":
        dict(hf=_ropes(slide=dict(YARN))),
    "the window layers' rope on a full layer":
        dict(hf={"rope_parameters": {FULL: dict(PLAIN),
                                     SLIDE: dict(PLAIN)}}),
    "every channel turned on a full layer":
        dict(hf=_ropes(full={"partial_rotary_factor": 1.0})),
    "yarn's attention factor left out":
        dict(hf=_ropes(full={"attention_factor": 1.0})),
    "yarn's attention factor on the passed-through half":
        dict(rope=_scaled_whole_head),
    "a window twice as long": dict(hf={"sliding_window": 16}),
    "the gate left out":
        dict(head_gate=lambda u, wg: jnp.ones((u.shape[0], wg.shape[1]))),
    "the gate one a channel": dict(mixer=_gate_a_channel),
    "softmax scoring": dict(route=_route_variant(softmax=True)),
    "the bias left out of the choice":
        dict(route=_route_variant(bias_in_choice=False)),
    "the bias inside the weights":
        dict(route=_route_variant(bias_in_weights=True)),
    "the routed scale 2.5 left out":
        dict(hf={"moe_routed_scaling_factor": 1.0}),
    "the shared expert left out":
        dict(hf={"shared_expert_intermediate_size": 0}),
    "the dense layer routed":
        dict(hf={"mlp_only_layers": []}, dense_as_routed=True),
    "fp8-rounded weights": dict(weights=_fp8),
}
CHECK_ROWS = np.random.default_rng(7).integers(0, 512, (1, 64)).astype(
    np.int32)


def _getter(params, hf, heads=None, dense_as_routed=False):
    """``mc.weights_getter`` with a fault's reading of the tree: every
    layer's first ``heads`` heads, or layer 0's FFN read from the first
    routed layer's row."""
    get = mc.weights_getter(params, hf)
    d = hf["head_dim"]

    def faulty(name, layer=None):
        if dense_as_routed and layer == 0 and name in ref.ROUTED_TENSORS:
            return get(name, 1)
        t = get(name, layer)
        if heads and name in ("wq", "wg"):
            return t[:, :heads * (d if name == "wq" else 1)]
        return t[:heads * d] if heads and name == "wo" else t

    return faulty


@pytest.fixture(scope="module")
def cell_check(run_memo):
    """The cell's own tolerances, and the reference at a small size (hidden
    256, the cell's five layers, a 64-token row) on bf16-rounded weights."""
    with open(CELL_CONFIG) as f:
        check = json.load(f)["check"]
    hf = hf_config(D=256, V=512, held=16, first=0, routed=16)
    params = init(model_for(hf), seed=5, router_gain=2.0, qk_gain=3.0)
    bias = params["layers"]["mlp_moe"]["router_bias"]
    params = jax.tree_util.tree_map(
        lambda w: w.astype(jnp.bfloat16).astype(jnp.float32), params)
    params["layers"]["mlp_moe"]["router_bias"] = bias      # kept in float32
    return check, hf, params, run_memo(
        "laguna_cell_check", lambda: ref.batch_loss(
            hf, mc.weights_getter(params, hf), CHECK_ROWS, ALPHA))


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
    check, hf, params, want = cell_check
    how = FAULTS[fault]
    for name in ("kv_head_of", "rope", "head_gate", "mixer", "route"):
        if name in how:
            monkeypatch.setattr(ref, name, how[name])
    bad_hf = {**hf, **how.get("hf", {})}
    bad = how.get("weights", lambda p: p)(params)
    got = ref.batch_loss(bad_hf, _getter(
        bad, hf, how.get("heads"), how.get("dense_as_routed", False)),
        CHECK_ROWS, ALPHA)
    bias = params["layers"]["mlp_moe"]["router_bias"]
    assert _failed(check, got, want, bias), fault
    assert not _failed(check, want, want, bias)
