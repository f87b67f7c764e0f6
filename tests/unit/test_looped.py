"""The looped decoder (``num_passes`` passes over shared weights, sandwich
norms, the exit gate and the expected-exit loss) against its plain reference
(``looped_reference.py``, a copy of ``benchmarks/reference_ouro.py``), on the
CPU in float32 at hidden 64, 3 layers, 4 passes, vocabulary 256, 32 tokens.

Tolerances. Program and reference do the same float32 arithmetic in another
order (stacked weights and a scan against a Python loop, ``log_sigmoid`` sums
against products of sigmoids), so they agree to summation order: a relative
1e-5 of each array's largest magnitude is about 100 float32 ulps, measured
differences are under 3e-6 of it. Gradients pass through 12 blocks and four
heads and are held to 1e-4 of each leaf's largest magnitude (measured: under
2e-5)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_ouro as ref

import deepspeed_tpu as ds
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.models.transformer import lm_loss
from deepspeed_tpu.observability import steplog
from deepspeed_tpu.parallel import build_mesh

BETA, R, L, T, V = 0.1, 4, 3, 32, 256
REF_CFG = {"hidden_size": 64, "intermediate_size": 128,
           "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
           "vocab_size": V, "num_hidden_layers": L, "rms_norm_eps": 1e-6,
           "rope_theta": 1e6, "total_ut_steps": R}
PLAIN = dict(vocab_size=V, hidden_size=64, num_layers=L, num_heads=4,
             intermediate_size=128, max_seq_len=T, tie_embeddings=False,
             rope_theta=1e6, norm_eps=1e-6, dtype="float32",
             attention_impl="xla")
LOOPED = dict(PLAIN, num_passes=R, sandwich_norm=True, exit_loss_beta=BETA)
VALUE_TOL, GRAD_TOL = 1e-5, 1e-4


def _getter(params):
    """The program's tree under the reference's names (shared weights: the
    pass that asks is ignored)."""
    lay = params["layers"]

    def get(name, layer=None, step=None):
        if name in ("embed", "final_norm", "head", "gate_w", "gate_b"):
            return {"embed": params["embed"]["tokens"],
                    "final_norm": params["final_norm"]["scale"],
                    "head": params["lm_head"],
                    "gate_w": params["exit_gate"]["w"],
                    "gate_b": params["exit_gate"]["b"]}[name]
        if name.startswith("ln"):
            return lay[name]["scale"][layer]
        return lay["attn" if name in ("wq", "wk", "wv", "wo") else "mlp"][
            name][layer]

    return get


@pytest.fixture(scope="module")
def case():
    """Model, weights with every norm scale and the gate moved off their
    initial 1 and 0 (so that each matters), a batch of one row."""
    model = TransformerLM(TransformerConfig(**LOOPED))
    key = jax.random.key(2)
    leaves, tree = jax.tree_util.tree_flatten(model.init(jax.random.key(1)))
    params = jax.tree_util.tree_unflatten(tree, [
        x + 0.1 * jax.random.normal(jax.random.fold_in(key, i), x.shape)
        for i, x in enumerate(leaves)])
    toks = np.random.default_rng(0).integers(0, V, T).astype(np.int32)
    return model, params, toks


def _close(got, want, tol):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= tol * max(np.max(np.abs(want)), 1e-6)


def test_per_pass_hidden_states_and_logits_match_the_reference(case):
    model, params, toks = case
    with jax.default_matmul_precision("highest"):
        hs, _ = model._hidden_passes(params, jnp.asarray(toks)[None])
        logits = [model._project(params, h)[0] for h in hs]
    want = ref.hidden_passes(REF_CFG, _getter(params), toks)
    assert len(hs) == R
    for h, z, w in zip(hs, logits, want):
        _close(h[0], w, VALUE_TOL)
        _close(z, ref.pass_logits(_getter(params), w), VALUE_TOL)
    # the passes differ: the loop is not one pass repeated
    assert float(jnp.max(jnp.abs(hs[0] - hs[-1]))) > 0.1
    # and the model's own logits are the last pass's
    with jax.default_matmul_precision("highest"):
        _close(model.logits(params, jnp.asarray(toks)[None])[0], logits[-1],
               VALUE_TOL)


def test_loss_and_its_parts_match_the_reference(case):
    model, params, toks = case
    with jax.default_matmul_precision("highest"):
        loss, parts = model.loss_and_parts(
            params, {"input_ids": jnp.asarray(toks)[None]})
    want = ref.expected_exit_loss(REF_CFG, _getter(params), toks, BETA)
    _close(loss, want["loss"], VALUE_TOL)
    assert sorted(parts) == ["exit_entropy", "exit_prob", "pass_loss"]
    for name in parts:
        _close(parts[name], want[name], VALUE_TOL)
    assert parts["pass_loss"].shape == parts["exit_prob"].shape == (R,)
    assert abs(float(parts["exit_prob"].sum()) - 1.0) < 1e-5
    assert float(model.loss_fn(params, {"input_ids": jnp.asarray(toks)[None]}
                               )) == pytest.approx(float(loss), rel=1e-6)


def _reference_grads(params, toks, untied):
    """Gradients of the reference's loss by ``jax.grad``, keyed (name, layer)
    or, with ``untied``, (name, layer, pass): R copies of the stack (and of
    the final norm), one for each pass."""
    get0 = _getter(params)
    per_pass = ref.LAYER_TENSORS + ("final_norm",)
    w = {}
    for name in per_pass:
        for i in ([None] if name == "final_norm" else range(L)):
            for t in (range(R) if untied else [None]):
                w[name, i, t] = jnp.asarray(get0(name, i))
    for name in ("embed", "head", "gate_w", "gate_b"):
        w[name, None, None] = jnp.asarray(get0(name))

    def getter(w):
        return lambda name, layer=None, step=None: w[
            name, layer, step if untied and name in per_pass else None]

    return ref.loss_and_grads(REF_CFG, w, toks, BETA, getter=getter)[1]


@pytest.fixture(scope="module")
def program_grads(case):
    model, params, toks = case
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.grad(model.loss_fn))(
            params, {"input_ids": jnp.asarray(toks)[None]})


def test_every_gradient_leaf_matches_the_reference(case, program_grads):
    _, params, toks = case
    want = _reference_grads(params, toks, untied=False)
    get = _getter(program_grads)
    assert len(want) == L * len(ref.LAYER_TENSORS) + 5
    for (name, layer, _), g in want.items():
        assert float(jnp.max(jnp.abs(g))) > 0, name     # every leaf is used
        _close(get(name, layer), g, GRAD_TOL)


def test_a_shared_weights_gradient_is_the_sum_over_untied_passes(
        case, program_grads):
    """One backward over the looped program sums each weight's gradient over
    its R uses: against a reference that holds R untied copies of the stack."""
    _, params, toks = case
    untied = _reference_grads(params, toks, untied=True)
    get = _getter(program_grads)
    for name in ref.LAYER_TENSORS + ("final_norm",):
        for i in ([None] if name == "final_norm" else range(L)):
            per_pass = [untied[name, i, t] for t in range(R)]
            _close(get(name, i), sum(per_pass), GRAD_TOL)
            # each pass contributes: no term of the sum is zero
            assert all(float(jnp.max(jnp.abs(g))) > 0 for g in per_pass)


@pytest.mark.parametrize("policy", ["full", "dots_saveable"])
def test_recomputation_on_and_off_give_the_same_loss_and_gradients(
        case, program_grads, policy):
    model, params, toks = case
    remat = TransformerLM(dataclasses.replace(model.cfg, remat_policy=policy))
    batch = {"input_ids": jnp.asarray(toks)[None]}
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(remat.loss_fn)(params, batch)
        base = model.loss_fn(params, batch)
    _close(loss, base, 1e-6)
    for g, w in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(program_grads)):
        _close(g, w, 1e-5)


def test_one_pass_without_norms_or_gate_is_todays_model():
    """The new switches at their defaults: the parameter tree, the program
    and the loss are the plain model's."""
    model = TransformerLM(TransformerConfig(**PLAIN))
    assert not model.cfg.looped
    params = model.init(jax.random.key(0))
    paths = sorted("/".join(str(getattr(k, "key", k)) for k in path)
                   for path, _ in jax.tree_util.tree_leaves_with_path(params))
    assert paths == [
        "embed/tokens", "final_norm/scale", "layers/attn/wk",
        "layers/attn/wo", "layers/attn/wq", "layers/attn/wv",
        "layers/ln1/scale", "layers/ln2/scale", "layers/mlp/w_down",
        "layers/mlp/w_gate", "layers/mlp/w_up", "lm_head"]
    batch = {"input_ids": jnp.asarray(
        np.random.default_rng(1).integers(0, V, (2, T)), jnp.int32)}
    loss, parts = model.loss_and_parts(params, batch)
    assert parts == {}
    want = lm_loss(model.cfg, model.logits(params, batch["input_ids"]), batch)
    assert float(loss) == float(want)
    # the looped model's tree is that tree plus four leaves, the shared
    # weights drawn from the same keys
    looped = TransformerLM(TransformerConfig(**LOOPED)).init(jax.random.key(0))
    extra = {"exit_gate": looped.pop("exit_gate")}
    for name in ("ln1_post", "ln2_post"):
        extra[name] = looped["layers"].pop(name)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool(jnp.array_equal(a, b)), looped, params))
    assert extra["exit_gate"]["w"].shape == (64,)
    assert extra["ln1_post"]["scale"].shape == (L, 64)


def test_parameter_count_and_operations_count_the_looped_model():
    from deepspeed_tpu.models.spec import model_flops_per_token

    cfg, plain = TransformerConfig(**LOOPED), TransformerConfig(**PLAIN)
    made = sum(int(x.size) for x in jax.tree_util.tree_leaves(jax.eval_shape(
        TransformerLM(cfg).init, jax.random.key(0))))
    # the estimate is the leaf count of init (since PR 39: it counted each
    # RMSNorm scale of ln1 and ln2 twice); what the looped model adds is two
    # scales a layer, the gate and its bias
    assert cfg.num_params_estimate() == made
    assert cfg.num_params_estimate() - plain.num_params_estimate() \
        == 2 * L * 64 + 64 + 1
    # every weight but the embedding table works once a pass
    once = V * 64
    n = cfg.num_params_estimate()
    assert model_flops_per_token(cfg) == pytest.approx(
        6.0 * (once + R * (n - once)) + R * 6.0 * L * T * 64)
    assert model_flops_per_token(plain) == pytest.approx(
        6.0 * plain.num_params_estimate() + 6.0 * L * T * 64)


# ---- through the engine ----------------------------------------------------

@pytest.fixture(scope="module")
def trained():
    """Four fused steps on one repeated batch: losses, the step record's
    loss parts, the step-program table."""
    n_before = len(steplog.programs())
    parts_before = steplog.get_steplog().n_parts
    eng, *_ = ds.initialize(
        model=TransformerLM(TransformerConfig(**dict(
            LOOPED, remat_policy="full"))),
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "adamw", "params": {"lr": 3e-3}},
                "steps_per_print": 2, "zero_optimization": {"stage": 0},
                "observability": {"enabled": True}},
        mesh=build_mesh(devices=jax.devices()[:1]))
    batch = {"input_ids": np.random.default_rng(5).integers(
        0, V, (2, T)).astype(np.int32)}
    losses = [eng.fused_train_step(batch) for _ in range(4)]
    rows = steplog.get_steplog().parts(
        last=steplog.get_steplog().n_parts - parts_before)
    return eng, [float(x) for x in losses], rows, \
        steplog.programs()[n_before:]


def test_fused_step_leaves_the_loss_parts_in_the_step_record(trained):
    eng, losses, rows, _ = trained
    assert [r["step"] for r in rows] == [0, 1, 2, 3]
    for loss, row in zip(losses, rows):
        assert row["loss"] == loss
        assert row["pass_loss"].shape == row["exit_prob"].shape == (R,)
        assert abs(float(row["exit_prob"].sum()) - 1.0) < 1e-5
        # the loss is its parts put together again (to the covariance of
        # p and CE over tokens, which the means leave out: small, not zero)
        rebuilt = float((row["exit_prob"] * row["pass_loss"]).sum()
                        - BETA * row["exit_entropy"])
        assert abs(rebuilt - loss) < 0.5
    # they are the step program's own outputs, still device values
    assert isinstance(eng._last_loss_parts["pass_loss"], jax.Array)


def test_the_second_step_builds_no_program(trained):
    _, _, _, programs = trained
    assert [p.name for p in programs] == ["ds_train_step"]
    assert programs[0].layer_applications == R * L


def test_the_loss_falls_on_a_repeated_batch(trained):
    _, losses, _, _ = trained
    assert losses[0] > losses[1] > losses[2] > losses[3]
    assert losses[3] < losses[0] - 0.3


def test_the_loss_parts_reach_the_metrics_registry(trained):
    from deepspeed_tpu.observability import get_registry

    text = get_registry().render_prometheus()
    for name in ("train_pass_loss", "train_exit_prob"):
        for i in range(R):
            assert f'{name}{{pass="{i}"}}' in text


def test_the_parts_ring_keeps_the_newest_rows():
    log = steplog.StepLog()
    for i in range(steplog.PARTS_KEPT + 3):
        log.loss_parts(i, np.float32(i), {"pass_loss": np.full(2, i)})
    rows = log.parts()
    assert len(rows) == steplog.PARTS_KEPT
    assert rows[0]["step"] == 3 and rows[-1]["step"] == steplog.PARTS_KEPT + 2
    assert rows[-1]["pass_loss"].tolist() == [rows[-1]["step"]] * 2
    assert [r["step"] for r in log.parts(last=2)] == [rows[-2]["step"],
                                                      rows[-1]["step"]]


# ---- what cannot loop says so ----------------------------------------------

def _looped_model():
    return TransformerLM(TransformerConfig(**dict(LOOPED, max_seq_len=64)))


def _serving_call(name):
    """The cache constructors refuse before they read an argument; the four
    forwards refuse in the layer loop they share, once they have read their
    arguments' shapes (``params`` is the loop's to read: None)."""
    def call():
        ids = jnp.zeros((2, 2), jnp.int32)
        kv = {"k": jnp.zeros((L, 2, 2, 2, 2)), "pos": ids[0]}
        kv["v"] = kv["k"]
        args = {"init_kv_cache": (2,), "init_paged_kv_cache": (2,),
                "forward_with_cache": (None, ids, kv),
                "forward_with_packed_cache": (None, ids[0], kv, ids, ids[0],
                                              ids[0], ids[0] > 0, ids[0]),
                "forward_prefill": (None, ids, ids[0]),
                "forward_decode_tail": (None, ids[0], kv, kv, 0, ids, ids[0],
                                        ids[0])}[name]
        getattr(_looped_model(), name)(*args)
    return call


def _engine_v2():
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    InferenceEngineV2(_looped_model(), max_sequences=2, max_seq_len=64,
                      block_size=16)


def _engine_v1():
    from deepspeed_tpu.inference.engine import InferenceEngine
    InferenceEngine(_looped_model())


def _pipeline():
    from deepspeed_tpu.runtime.pipe import PipelineModule
    PipelineModule(TransformerLM(TransformerConfig(**dict(
        LOOPED, num_layers=4))), num_stages=2)


def _tiled_loss():
    m = TransformerLM(TransformerConfig(**dict(LOOPED, loss_tiling=4)))
    m.loss_fn(m.init(jax.random.key(0)),
              {"input_ids": jnp.zeros((1, T), jnp.int32)})


def _tiled_loss_no_gate():
    m = TransformerLM(TransformerConfig(**dict(PLAIN, num_passes=2,
                                               loss_tiling=4)))
    m.loss_fn(m.init(jax.random.key(0)),
              {"input_ids": jnp.zeros((1, T), jnp.int32)})


def _pld_theta():
    m = _looped_model()
    m.loss_fn(m.init(jax.random.key(0)),
              {"input_ids": jnp.zeros((1, T), jnp.int32),
               "pld_theta": jnp.ones((1,), jnp.float32)})


def _cost_model():
    from deepspeed_tpu.parallel.cost_model import ModelProfile
    ModelProfile.from_transformer_config(TransformerConfig(**LOOPED))


REFUSALS = {
    **{name: _serving_call(name) for name in (
        "init_kv_cache", "init_paged_kv_cache", "forward_with_cache",
        "forward_with_packed_cache", "forward_prefill",
        "forward_decode_tail")},
    "InferenceEngineV2": _engine_v2, "InferenceEngine": _engine_v1,
    "PipelineModule": _pipeline, "tiled_loss": _tiled_loss,
    "tiled_loss_no_gate": _tiled_loss_no_gate,
    "random_ltd": lambda: _looped_model().set_random_ltd(16),
    "pld_depth": lambda: _looped_model().set_pld_depth(2),
    "pld_theta": _pld_theta, "cost_model": _cost_model,
}


@pytest.mark.parametrize("path", sorted(REFUSALS))
def test_a_path_that_cannot_loop_raises_and_names_the_mechanism(path):
    with pytest.raises(NotImplementedError, match=r"looped.*num_passes=\d"):
        REFUSALS[path]()


def test_the_configuration_refuses_what_it_cannot_mean():
    with pytest.raises(ValueError, match="num_passes"):
        TransformerConfig(**dict(PLAIN, exit_loss_beta=0.1))
    with pytest.raises(ValueError, match="parallel_block"):
        TransformerConfig(**dict(PLAIN, sandwich_norm=True,
                                 parallel_block=True))
    with pytest.raises(ValueError, match="num_passes"):
        TransformerConfig(**dict(PLAIN, num_passes=0))


# ---- checkpoint names ------------------------------------------------------

def test_the_checkpoint_names_map_both_ways(tmp_path):
    """A tree written out under the published tensor names (the llama
    family's, plus ``hf.OURO_TENSORS`` for what the looped model adds) reads
    back as the same tree, and ``config.json`` as the same configuration."""
    import json

    torch = pytest.importorskip("torch")
    from safetensors.torch import save_file

    from deepspeed_tpu.models import hf

    cfg = TransformerConfig(**dict(LOOPED, dtype="float32"))
    params = jax.tree_util.tree_map(
        np.asarray, TransformerLM(cfg).init(jax.random.key(3)))
    params["exit_gate"]["b"] = np.float32(0.25)
    lay = params["layers"]
    llama = {"self_attn.q_proj": lay["attn"]["wq"],
             "self_attn.k_proj": lay["attn"]["wk"],
             "self_attn.v_proj": lay["attn"]["wv"],
             "self_attn.o_proj": lay["attn"]["wo"],
             "mlp.gate_proj": lay["mlp"]["w_gate"],
             "mlp.up_proj": lay["mlp"]["w_up"],
             "mlp.down_proj": lay["mlp"]["w_down"]}
    sd = {"model.embed_tokens.weight": params["embed"]["tokens"],
          "model.norm.weight": params["final_norm"]["scale"],
          "lm_head.weight": params["lm_head"].T}
    for i in range(L):
        for name, w in llama.items():
            sd[f"model.layers.{i}.{name}.weight"] = w[i].T
        sd[f"model.layers.{i}.input_layernorm.weight"] = lay["ln1"]["scale"][i]
        sd[f"model.layers.{i}.post_attention_layernorm.weight"] = \
            lay["ln2"]["scale"][i]
    for name, path in hf.OURO_TENSORS.items():       # tree -> names
        leaf = params
        for k in path:
            leaf = leaf[k]
        if "{}" in name:
            for i in range(L):
                sd[name.format(i)] = leaf[i]
        else:                                        # a Linear(D, 1)
            sd[name] = np.reshape(leaf, (1, -1) if leaf.ndim else (1,))
    save_file({k: torch.tensor(np.ascontiguousarray(v))
               for k, v in sd.items()}, str(tmp_path / "model.safetensors"))
    (tmp_path / "config.json").write_text(json.dumps({
        "model_type": "ouro", "vocab_size": V, "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": L,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
        "max_position_embeddings": T, "rms_norm_eps": 1e-6,
        "rope_theta": 1e6, "tie_word_embeddings": False,
        "total_ut_steps": R, "sliding_window": None,
        "use_sliding_window": False}))
    model, got = hf.load_hf_checkpoint(str(tmp_path))      # names -> tree
    assert (model.cfg.num_passes, model.cfg.sandwich_norm,
            model.cfg.exit_loss_beta) == (R, True, 0.1)
    assert jax.tree_util.tree_structure(got) \
        == jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), b)
    # a tensor the table does not know is refused, not dropped
    sd["model.layers.0.extra_norm.weight"] = lay["ln1"]["scale"][0]
    save_file({k: torch.tensor(np.ascontiguousarray(v))
               for k, v in sd.items()}, str(tmp_path / "model.safetensors"))
    with pytest.raises(ValueError, match="unmapped"):
        hf.load_hf_checkpoint(str(tmp_path))
