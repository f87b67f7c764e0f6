"""Engine tests — the TPU analog of ``tests/unit/v1/zero/test_zero.py``: tiny models
trained a few steps on a virtual 8-device mesh, asserting convergence and
cross-stage equivalence instead of hook/partition internals."""

import os

import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import TransformerLM, get_preset


def make_config(stage=0, mesh=None, **over):
    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": stage},
        "mesh": mesh or {},
        "steps_per_print": 100,
    }
    cfg.update(over)
    return cfg


def data_iter(batch, seq=32, seed=0):
    """A fixed batch, repeated — convergence tests overfit it deterministically."""
    rng = np.random.default_rng(seed)
    fixed = {"input_ids": rng.integers(0, 256, (batch, seq))}
    while True:
        yield fixed


def train_steps(engine, steps, ga=1, seed=0):
    it = data_iter(engine.train_micro_batch_size_per_gpu()
                   * engine.topology.dp_world_size, seed=seed)
    losses = []
    for _ in range(steps):
        for _ in range(ga):
            loss = engine.forward(next(it))
            engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    return losses


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_zero_stages_converge(stage, eight_devices):
    model = TransformerLM(get_preset("tiny"))
    mesh = {"fsdp": 8} if stage else {"dp": 8}
    eng, *_ = ds.initialize(model=model, config=make_config(stage, mesh))
    losses = train_steps(eng, 5)
    assert losses[-1] < losses[0]
    assert eng.global_steps == 5


def test_stage3_param_sharding(eight_devices):
    model = TransformerLM(get_preset("tiny"))
    eng, *_ = ds.initialize(model=model, config=make_config(
        3, {"fsdp": 8}, zero_optimization={"stage": 3, "param_persistence_threshold": 0}))
    # large params must actually be sharded over fsdp
    wq = eng.params["layers"]["attn"]["wq"]
    assert "fsdp" in str(eng.param_spec_tree["layers"]["attn"]["wq"])
    shard_shape = wq.sharding.shard_shape(wq.shape)
    assert np.prod(shard_shape) < np.prod(wq.shape)


def test_grad_accumulation_boundary(eight_devices):
    model = TransformerLM(get_preset("tiny"))
    eng, *_ = ds.initialize(model=model, config=make_config(
        1, {"fsdp": 8}, gradient_accumulation_steps=2))
    it = data_iter(2 * 8)
    loss = eng.forward(next(it))
    eng.backward(loss)
    assert not eng.is_gradient_accumulation_boundary()
    eng.step()  # no-op before boundary
    assert eng.global_steps == 0
    loss = eng.forward(next(it))
    eng.backward(loss)
    assert eng.is_gradient_accumulation_boundary()
    eng.step()
    assert eng.global_steps == 1


def test_stage_equivalence(eight_devices):
    """ZeRO stages are layout choices — the math must be identical."""
    ref_losses = None
    for stage in (0, 2, 3):
        model = TransformerLM(get_preset("tiny"))
        mesh = {"fsdp": 8} if stage else {"dp": 8}
        eng, *_ = ds.initialize(model=model, config=make_config(stage, mesh))
        losses = train_steps(eng, 3, seed=7)
        if ref_losses is None:
            ref_losses = losses
        else:
            np.testing.assert_allclose(losses, ref_losses, rtol=2e-3)


def test_fp16_loss_scaler_state(eight_devices):
    model = TransformerLM(get_preset("tiny"))
    eng, *_ = ds.initialize(model=model, config=make_config(
        0, {"dp": 8}, fp16={"enabled": True, "initial_scale_power": 8},
        bf16={"enabled": False}))
    losses = train_steps(eng, 2)
    assert float(eng.scaler_state["scale"]) >= 1.0
    assert all(np.isfinite(losses))


def test_tp_matches_dp(eight_devices):
    """Tensor-parallel must compute the same loss as pure DP."""
    model = TransformerLM(get_preset("tiny"))
    eng_dp, *_ = ds.initialize(model=model, config=make_config(0, {"dp": 8}))
    l_dp = train_steps(eng_dp, 2, seed=3)
    model2 = TransformerLM(get_preset("tiny"))
    eng_tp, *_ = ds.initialize(model=model2, config=make_config(
        0, {"dp": 2, "tp": 4}, train_micro_batch_size_per_gpu=8))
    l_tp = train_steps(eng_tp, 2, seed=3)
    np.testing.assert_allclose(l_dp, l_tp, rtol=2e-3)


def test_checkpoint_roundtrip(tmp_path, eight_devices):
    model = TransformerLM(get_preset("tiny"))
    eng, *_ = ds.initialize(model=model, config=make_config(2, {"fsdp": 8}))
    train_steps(eng, 2)
    eng.save_checkpoint(str(tmp_path), client_state={"note": "hi"})
    step_before = eng.global_steps
    p_before = np.asarray(eng.params["final_norm"]["scale"])

    model2 = TransformerLM(get_preset("tiny"))
    eng2, *_ = ds.initialize(model=model2, config=make_config(2, {"fsdp": 8}))
    path, client = eng2.load_checkpoint(str(tmp_path))
    assert path is not None
    assert client["note"] == "hi"
    assert eng2.global_steps == step_before
    np.testing.assert_allclose(np.asarray(eng2.params["final_norm"]["scale"]),
                               p_before, rtol=1e-6)


def test_checkpoint_reshard(tmp_path, eight_devices):
    """Universal-checkpoint behavior: save at stage 3 / fsdp=8, load at stage 0 / dp=8."""
    model = TransformerLM(get_preset("tiny"))
    eng, *_ = ds.initialize(model=model, config=make_config(3, {"fsdp": 8}))
    train_steps(eng, 1)
    eng.save_checkpoint(str(tmp_path))

    model2 = TransformerLM(get_preset("tiny"))
    eng2, *_ = ds.initialize(model=model2, config=make_config(0, {"dp": 8}))
    eng2.load_checkpoint(str(tmp_path))
    l2 = train_steps(eng2, 1, seed=9)
    assert np.isfinite(l2[0])


def test_fused_matches_imperative_fp16(eight_devices):
    """fused_train_step must carry the fp16 loss-scaler semantics of the
    forward/backward/step path (reference weak spot: the fused path silently
    dropping DynamicLossScaler)."""
    # scale 2^126 (still finite in fp32): loss*scale overflows to inf, so step 1
    # must be SKIPPED and the scale halved — on both paths identically
    cfg = make_config(0, {"dp": 8}, fp16={"enabled": True, "initial_scale_power": 126})
    m1 = TransformerLM(get_preset("tiny"))
    e1, *_ = ds.initialize(model=m1, config=cfg)
    m2 = TransformerLM(get_preset("tiny"))
    e2, *_ = ds.initialize(model=m2, config=cfg)
    it = data_iter(16)
    batch = next(it)
    l_imp = None
    for _ in range(3):
        loss = e1.forward(batch)
        e1.backward(loss)
        e1.step()
        l_imp = float(loss)
    for _ in range(3):
        l_fused = float(e2.fused_train_step(batch))
    assert e1.skipped_steps >= 1, "overflow case never triggered"
    assert e1.skipped_steps == e2.skipped_steps
    assert e1.global_steps == e2.global_steps
    assert float(e1.scaler_state["scale"]) == float(e2.scaler_state["scale"])
    assert float(e1.scaler_state["scale"]) < 2.0 ** 126  # halved after overflow
    np.testing.assert_allclose(l_imp, l_fused, rtol=2e-2)


def test_fused_step_with_offload(tmp_path, eight_devices):
    """fused_train_step must work with the host-offload optimizer."""
    cfg = make_config(
        2, {"dp": 8},
        zero_optimization={"stage": 2,
                           "offload_optimizer": {"device": "cpu"}})
    model = TransformerLM(get_preset("tiny"))
    eng, *_ = ds.initialize(model=model, config=cfg)
    it = data_iter(16)
    losses = [float(eng.fused_train_step(next(it))) for _ in range(4)]
    assert losses[-1] < losses[0]
    assert eng.global_steps == 4


# ---- the weights' working copy (the plain fused step carries it) -----------

_EXPERTS_RULE = dict(
    num_layers=3, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, rope_interleave=True, first_k_dense=1, num_experts=8,
    top_k=2, moe_dispatch="grouped", moe_intermediate_size=32,
    moe_experts_held=4, moe_scoring="sigmoid", moe_routed_scale=2.448,
    moe_shared_experts=2, moe_bias_rate=1e-3, moe_bias_init=0.1,
    tie_embeddings=False, remat_policy="full")
#: case -> (model overrides, engine config overrides, the master leaves the
#: copy must leave out)
COPY_CASES = {
    "dense": (dict(tie_embeddings=False), {}, [("final_norm", "scale")]),
    # gathered from and projected with: two cotangents summed in float32
    "tied_table": ({}, {}, [("embed", "tokens")]),
    # the stack read by every pass through one cast; the head projected with
    # once a pass, its cotangents summed in float32
    "looped": (dict(num_passes=3, sandwich_norm=True, exit_loss_beta=0.1,
                    tie_embeddings=False), {},
               [("lm_head",), ("exit_gate", "w")]),
    # the selection bias is moved by the model's rule and read as float32
    "experts_rule_leaf": (_EXPERTS_RULE, {},
                          [("layers", "mlp_moe", "router_bias")]),
    "ga2": (dict(tie_embeddings=False), dict(gradient_accumulation_steps=2),
            []),
    # 2^17 overflows float16's range until the scaler has halved it twice
    "fp16_skipped_step": (dict(tie_embeddings=False, dtype="float16"),
                          dict(fp16={"enabled": True,
                                     "initial_scale_power": 17}), []),
    "fp32_compute": (dict(dtype="float32"), {}, None),
}


def _one_chip_engine(model_over, cfg_over, copy=True, seed=11):
    import jax

    from deepspeed_tpu.parallel import build_mesh

    model = TransformerLM(get_preset("tiny", **model_over))
    if not copy:
        # the step body traced with an empty copy, the form fp32 compute
        # takes anyway: every cast stays in the step, as before the copy
        model.working_copy = lambda params: {}
    eng, *_ = ds.initialize(
        model=model, config=make_config(0, seed=seed, **cfg_over),
        mesh=build_mesh(devices=jax.devices()[:1]))
    return eng


def _same_bits(a, b):
    import jax

    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("case", sorted(COPY_CASES))
def test_the_carried_copy_changes_no_bit_of_the_training_state(case):
    """N fused steps with AdamW writing the working copy beside the masters
    leave master weights, ``m``, ``v`` and losses bit-equal to the same steps
    with every cast in the step; the copy holds what the model says, in the
    compute dtype, and after each step is the cast of the masters beside
    it; no master, moment or gradient changes dtype."""
    if COPY_CASES[case][0].get("dtype") == "float16":
        # float16 arithmetic on the CPU is LLVM's to legalise, and without
        # its optimiser (tests/conftest.py builds the tests' programs at
        # -O0) two programs that are one function round differently: this
        # case compares them in a process that compiles as XLA comes
        import subprocess
        import sys

        from tests.conftest import LLVM_O0

        out = subprocess.run(
            [sys.executable, "-c", "from tests.unit.test_engine import "
             f"_carried_copy; _carried_copy({case!r})"],
            cwd=os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))),
            env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS":
                 os.environ["XLA_FLAGS"].replace(LLVM_O0, "")},
            capture_output=True, text=True, timeout=900)
        assert out.returncode == 0, out.stderr[-3000:]
    else:
        _carried_copy(case)


def _carried_copy(case):
    import jax
    import jax.numpy as jnp

    model_over, cfg_over, left_out = COPY_CASES[case]
    eng = _one_chip_engine(model_over, cfg_over)
    ref = _one_chip_engine(model_over, cfg_over, copy=False)
    assert ref._work == {} and ref._work_bytes == 0
    dt = jnp.dtype(eng.module.cfg.dtype)
    if left_out is None:                    # fp32 compute: no copy
        assert eng._work == {} and eng._work_bytes == 0
    else:
        copy = jax.tree_util.tree_leaves(eng._work)
        assert copy and all(x.dtype == dt for x in copy)
        assert eng._work_bytes == sum(x.nbytes for x in copy)
        assert "final_norm" not in eng._work
        for path in left_out:
            node = eng._work
            for name in path[:-1]:
                node = node.get(name, {})
            assert path[-1] not in node, path
    rows = 2 * int(eng.config.gradient_accumulation_steps)
    rng = np.random.default_rng(5)
    steps = 4
    for _ in range(steps):
        batch = {"input_ids": rng.integers(0, 256, (rows, 32), dtype=np.int32)}
        loss, ref_loss = eng.fused_train_step(batch), ref.fused_train_step(batch)
        assert float(loss) == float(ref_loss)
        _same_bits(eng._work, eng.module.working_copy(eng.params))
    _same_bits((eng.params, eng.opt_state, eng.scaler_state),
               (ref.params, ref.opt_state, ref.scaler_state))
    assert all(x.dtype == jnp.float32 for x in jax.tree_util.tree_leaves(
        (eng.params, eng.opt_state[0].mu, eng.opt_state[0].nu)))
    assert eng.skipped_steps == ref.skipped_steps
    if case == "fp16_skipped_step":
        assert 1 <= eng.skipped_steps < steps   # skipped, then stepped


def test_whoever_else_writes_the_masters_drops_the_copy(tmp_path):
    """A checkpoint holds no copy and a load drops the engine's; so do a
    direct write of ``engine.params``, the imperative ``step()`` and a leaf
    written in place. The next fused step makes the copy again from the
    masters it finds: its loss is that of an engine that casts in the
    step."""
    import jax

    from deepspeed_tpu.utils.tensor_fragment import safe_set_full_fp32_param

    over = dict(tie_embeddings=False)
    eng = _one_chip_engine(over, {})
    ref = _one_chip_engine(over, {}, copy=False)
    rng = np.random.default_rng(6)

    def batch():
        return {"input_ids": rng.integers(0, 256, (2, 32), dtype=np.int32)}

    def both_step():
        b = batch()
        loss, ref_loss = eng.fused_train_step(b), ref.fused_train_step(b)
        assert float(loss) == float(ref_loss)
        assert eng._work is not None and ref._work == {}

    both_step()
    eng.save_checkpoint(str(tmp_path))
    assert not any("work" in name for _, _, files in os.walk(str(tmp_path))
                   for name in files)
    both_step()                 # past the checkpoint, then back to it
    for e in (eng, ref):
        e.load_checkpoint(str(tmp_path))
    assert eng._work is None
    both_step()
    # a direct write (a compression pass): the stale copy would give the
    # loss of the weights before it
    for e in (eng, ref):
        e.params = jax.tree_util.tree_map(lambda x: x * 0.5, e.params)
    assert eng._work is None
    both_step()
    b = batch()
    for e in (eng, ref):        # the imperative path
        e.backward(e.forward(b))
        e.step()
    assert eng._work is None
    both_step()
    for e in (eng, ref):        # one leaf, in place
        safe_set_full_fp32_param(
            e, "lm_head", np.zeros(e.params["lm_head"].shape, np.float32))
    assert eng._work is None
    both_step()
    _same_bits((eng.params, eng.opt_state), (ref.params, ref.opt_state))
