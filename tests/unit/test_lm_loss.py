"""``lm_loss`` against the plain autodiff formula it replaced, and the shape of
what it keeps: the logits as they arrive and ``logz``, never an f32 copy of
the logits and never an array cut to ``T - 1`` rows (each was a pass of its
own over ``[T, V]`` on the chip: PERF.md, PR 27)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.models import TransformerConfig, TransformerLM, get_preset
from deepspeed_tpu.models.transformer import lm_loss

B, T, V = 2, 7, 96


def plain_lm_loss(cfg, logits, batch):
    """The formula ``lm_loss`` had before it got its own derivative rule:
    slice the logits for the shift, cast them to f32, let autodiff decide."""
    ids = batch["input_ids"]
    if "labels" in batch:
        labels, lmask = batch["labels"], (batch["labels"] >= 0)
        labels = jnp.maximum(labels, 0)
        lg = logits
    else:
        labels, lg = ids[:, 1:], logits[:, :-1]
        lmask = (batch["attention_mask"][:, 1:].astype(bool)
                 if "attention_mask" in batch else jnp.ones_like(labels, bool))
    lg = lg.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if cfg.z_loss > 0.0:
        nll = nll + cfg.z_loss * jnp.square(logz)
    denom = jnp.maximum(lmask.sum(), 1)
    return jnp.where(lmask, nll, 0.0).sum() / denom


def _batch(kind, rng):
    ids = rng.integers(0, V, (B, T)).astype(np.int32)
    if kind == "next_token":
        return {"input_ids": ids}
    if kind == "attention_mask":
        mask = (rng.random((B, T)) > 0.3).astype(np.int32)
        mask[0, -1] = 0
        return {"input_ids": ids, "attention_mask": mask}
    labels = rng.integers(0, V, (B, T)).astype(np.int32)
    labels[rng.random((B, T)) < 0.3] = -100
    labels[1, 0] = -1           # any negative label is padding
    return {"input_ids": ids, "labels": labels}


def _logits(rng, dtype):
    return jnp.asarray(rng.normal(size=(B, T, V)) * 3.0, dtype)


def _assert_same(cfg, logits, batch, loss, grad):
    want, gwant = jax.value_and_grad(
        lambda lg: plain_lm_loss(cfg, lg, batch))(logits)
    assert loss.dtype == jnp.float32 and grad.dtype == logits.dtype
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
    g, gw = (np.asarray(x, np.float32) for x in (grad, gwant))
    if logits.dtype == jnp.bfloat16:
        # one ulp of a bf16 value x is at most |x| * 2**-7
        assert (np.abs(g - gw) <= np.abs(gw) * 2.0 ** -7).all()
    else:
        np.testing.assert_allclose(g, gw, rtol=2e-5, atol=1e-8)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("z_loss", [0.0, 1e-4])
@pytest.mark.parametrize("kind", ["next_token", "labels", "attention_mask"])
def test_value_and_gradient_match_the_plain_formula(kind, z_loss, dtype):
    rng = np.random.default_rng(len(kind) + (dtype == "float32"))
    cfg = TransformerConfig(vocab_size=V, z_loss=z_loss)
    batch, logits = _batch(kind, rng), _logits(rng, jnp.dtype(dtype))
    loss, grad = jax.jit(jax.value_and_grad(
        lambda lg: lm_loss(cfg, lg, batch)))(logits)
    _assert_same(cfg, logits, batch, loss, grad)
    masked = (np.asarray(batch["labels"]) < 0 if kind == "labels" else
              np.arange(T)[None, :] == T - 1)
    assert not np.asarray(grad, np.float32)[
        np.broadcast_to(masked, (B, T))].any()


def test_every_token_masked_gives_zero_loss_and_gradient():
    cfg = TransformerConfig(vocab_size=V)
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, V, (B, T)),
             "labels": np.full((B, T), -100)}
    loss, grad = jax.value_and_grad(lambda lg: lm_loss(cfg, lg, batch))(
        _logits(rng, jnp.bfloat16))
    assert float(loss) == 0.0 and not np.asarray(grad, np.float32).any()


@pytest.mark.parametrize("z_loss", [0.0, 1e-4])
def test_vocabulary_sharded_over_tp(eight_devices, z_loss):
    """GSPMD partitions the rule's reductions and its iota: logits sharded
    ``P(dp, None, tp)`` as ``_project`` constrains them, gradient too."""
    mesh = Mesh(np.asarray(eight_devices).reshape(2, 4), ("dp", "tp"))
    spec = NamedSharding(mesh, P("dp", None, "tp"))
    cfg = TransformerConfig(vocab_size=V, z_loss=z_loss)
    rng = np.random.default_rng(3)
    batch = _batch("attention_mask", rng)
    logits = _logits(rng, jnp.bfloat16)
    loss, grad = jax.jit(
        jax.value_and_grad(lambda lg: lm_loss(cfg, lg, batch)),
        in_shardings=spec)(jax.device_put(logits, spec))
    assert grad.sharding.is_equivalent_to(spec, grad.ndim)
    _assert_same(cfg, logits, batch, loss, grad)


def test_forward_over_reverse_goes_through_the_rule():
    """``runtime/eigenvalue.py`` takes ``jvp(grad(loss))``: the rule's forward
    and backward are plain ``jnp``, so that is the plain formula's HVP."""
    cfg = TransformerConfig(vocab_size=V, z_loss=1e-4)
    rng = np.random.default_rng(5)
    batch, logits = _batch("labels", rng), _logits(rng, jnp.float32)
    v = _logits(rng, jnp.float32)

    def hvp(fn):
        return jax.jvp(jax.grad(lambda lg: fn(cfg, lg, batch)),
                       (logits,), (v,))[1]

    np.testing.assert_allclose(np.asarray(hvp(lm_loss)),
                               np.asarray(hvp(plain_lm_loss)),
                               rtol=1e-4, atol=1e-8)


# ---- what is kept between forward and backward, and in which shape ---------

ST, SV = 24, 320    # T - 1 = 23 and V are no other size of the tiny model


def _tiny(**overrides):
    model = TransformerLM(get_preset("tiny", dtype="bfloat16", vocab_size=SV,
                                     **overrides))
    params = model.init(jax.random.key(0))
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, SV, (B, ST)).astype(np.int32)}
    return model, params, batch


@pytest.mark.parametrize("overrides", [{}, {"z_loss": 1e-4},
                                       {"num_experts": 4, "top_k": 2}],
                         ids=["dense", "z_loss", "moe"])
def test_no_f32_copy_of_the_logits_is_kept_for_the_backward(overrides):
    model, params, batch = _tiny(**overrides)
    kept = [aval for aval, _ in saved_residuals(model.loss_fn, params, batch)]
    # the logits as they arrived, which the head's backward needs anyway
    assert [str(a.dtype) for a in kept
            if a.shape == (B, ST, SV)] == ["bfloat16"]
    assert not [a for a in kept if ST - 1 in a.shape]


@pytest.mark.parametrize("with_mask", [False, True], ids=["ids", "mask"])
def test_no_float_array_has_t_minus_one_rows(with_mask):
    """Shifting the labels and not the logits: no float array of the lowered
    gradient is cut to T - 1 (only the [B, T - 1] integer labels are), so no
    pad back to T appears in the backward."""
    model, params, batch = _tiny()
    if with_mask:
        batch["attention_mask"] = np.ones((B, ST), np.int32)
    text = jax.jit(jax.grad(model.loss_fn)).lower(params, batch).as_text()
    shapes = set(re.findall(r"tensor<((?:\d+x)+)(f32|bf16|f16)>", text))
    assert (f"{B}x{ST}x{SV}x", "bf16") in shapes
    assert not [s for s in shapes if str(ST - 1) in s[0].split("x")]
    # and the f32 working copy lives only inside fused passes: the one f32
    # [B, T, V] the text may hold is never an operand of the head's matmuls
    for line in text.splitlines():
        if "dot_general" in line:
            assert f"{B}x{ST}x{SV}xf32" not in line
