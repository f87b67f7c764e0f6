"""The gated delta rule's Pallas lowering (``ops/delta_rule.py``: ``rule_fwd``,
``rule_bwd`` behind a ``custom_vjp``), interpreted on the CPU, against its
einsum lowering and against the recurrence over positions; the picker's
answers; the counter a step program's row reads."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_olmo_hybrid as ref
from deepspeed_tpu.ops import delta_rule as dr, lowerings

NAMES = "q k v g beta".split()
kernels = functools.partial(dr.chunked_delta_rule, interpret=True)


def _inputs(T, H, dk, dv, B=1, seed=0, dtype=jnp.float32, decay=0.5):
    """Unit keys, ``q`` scaled as the layer scales it, steps in (0, 2);
    ``decay``: the mean of ``-g`` a position."""
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa
    return ((ref.l2_norm(f(B, T, H, dk)) / np.sqrt(dk)).astype(dtype),
            ref.l2_norm(f(B, T, H, dk)).astype(dtype),
            f(B, T, H, dv).astype(dtype),
            -jax.nn.softplus(f(B, T, H)) * decay,
            2.0 * jax.nn.sigmoid(f(B, T, H)))


def _recurrence(q, k, v, g, beta):
    """The reference scales q itself: hand it q as it was before."""
    f = [a.astype(jnp.float32) for a in (q, k, v, g, beta)]
    q = f[0] * np.sqrt(q.shape[-1])
    return jnp.stack([ref.recurrence(q[i], f[1][i], f[2][i], f[3][i], f[4][i])
                      for i in range(q.shape[0])])


def _grads(fn, args):
    """``o`` and the five cotangents under a fixed random cotangent of o:
    one program, ``fn`` traced once (its forward is the forward the
    cotangents went through)."""
    w = jnp.asarray(np.random.default_rng(5).standard_normal(
        jax.eval_shape(fn, *args).shape), jnp.float32)

    def loss(*a):
        o = fn(*a)
        return jnp.sum(o.astype(jnp.float32) * w), o

    (_, o), g = jax.jit(jax.value_and_grad(
        loss, argnums=range(5), has_aux=True))(*args)
    return o, g


# (T, H, dk, dv): the cell's 15 heads of 96 / 192 over two and over three
# chunks (the carried state and its cotangent); four heads over a T that is
# padded to nine chunks, which are two grid steps of eight; one padded chunk
SHAPES = {
    "cell-heads-two-chunks": (128, 15, 96, 192),
    "cell-heads-three-chunks": (192, 15, 96, 192),
    "four-heads-T520-padded": (520, 4, 32, 64),
    "one-padded-chunk": (40, 4, 64, 128),
    "nine-chunks-padded-to-two-steps": (576, 2, 32, 64),
}


@pytest.mark.parametrize("shape", sorted(
    set(SHAPES) - {"nine-chunks-padded-to-two-steps"}))
def test_float32_kernels_are_the_einsum_form_and_the_recurrence(shape):
    args = _inputs(*SHAPES[shape])
    with jax.default_matmul_precision("highest"):
        o_k, g_k = _grads(kernels, args)
        o_e, g_e = _grads(dr.rule_einsum, args)
        o_r, g_r = _grads(_recurrence, args)
    top = float(jnp.abs(o_r).max())
    np.testing.assert_allclose(o_k, o_e, atol=1e-5 * top)
    np.testing.assert_allclose(o_k, o_r, atol=1e-5 * top)
    for name, k, e, r in zip(NAMES, g_k, g_e, g_r):
        assert k.shape == r.shape and k.dtype == r.dtype, name
        top = float(jnp.abs(r).max())
        np.testing.assert_allclose(k, e, atol=2e-5 * top, err_msg=name)
        np.testing.assert_allclose(k, r, atol=2e-5 * top, err_msg=name)


@pytest.mark.parametrize("shape", ["cell-heads-two-chunks",
                                   "nine-chunks-padded-to-two-steps"])
def test_bf16_kernels_round_where_the_einsum_form_rounds(shape):
    """bf16 operands, float32 sums, decays, inverse and state: the kernels'
    forward is the einsum form's to a rounding of the result, and each
    cotangent is as near the float32 recurrence as the einsum form's is."""
    args = _inputs(*SHAPES[shape], dtype=jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        o_k, g_k = _grads(kernels, args)
        o_e, g_e = _grads(dr.rule_einsum, args)
        o_r, g_r = _grads(_recurrence, args)
    assert o_k.dtype == jnp.bfloat16
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    top = float(jnp.abs(o_r).max())
    assert np.abs(f32(o_k) - f32(o_e)).max() <= top / 128
    assert np.abs(f32(o_k) - f32(o_r)).max() <= top / 64
    for name, k, e, r in zip(NAMES, g_k, g_e, g_r):
        assert k.dtype == e.dtype and k.shape == e.shape, name
        assert np.isfinite(f32(k)).all(), name
        off_k = np.abs(f32(k) - f32(r)).max()
        off_e = np.abs(f32(e) - f32(r)).max()
        assert off_k <= max(3 * off_e, float(jnp.abs(r).max()) / 64), \
            (name, off_k, off_e)


def test_strong_steps_on_one_key_keep_the_inverse_exact():
    """Every position writes the same key with ``beta`` = 2 and no decay:
    ``A`` is 2 everywhere below the diagonal, where the series ``sum (-A)^n``
    would add terms of 2^n C(63, n); substitution gives the inverse, whose
    entries are +-2, and the kernels' output is the recurrence's (each
    position overwrites what the last one wrote: ``o_t = <q, k> v_t`` with
    alternating remainders), as are the cotangents."""
    T, H, dk, dv = 128, 2, 32, 64
    q, k, v, g, beta = _inputs(T, H, dk, dv)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    args = (q, k, v, 0.0 * g, 2.0 + 0.0 * beta)
    with jax.default_matmul_precision("highest"):
        o_k, g_k = _grads(kernels, args)
        o_r, g_r = _grads(_recurrence, args)
    # (128 overwrites in float32: the einsum form is 4e-5 of the largest
    # output from the recurrence here, the kernels 2e-5)
    np.testing.assert_allclose(o_k, o_r, atol=5e-5 * float(
        jnp.abs(o_r).max()))
    for name, a, b in zip(NAMES, g_k, g_r):
        np.testing.assert_allclose(a, b, atol=1e-4 * float(jnp.abs(b).max()),
                                   err_msg=name)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decays_that_a_factored_form_would_overflow(dtype):
    """exp(-3.3) a position on average: the running sum passes -200 inside
    one chunk, where exp(-G_j) overflows float32 (largest exponent 88). The
    mask sits in the exponent, so the result and every cotangent stay finite
    and are the recurrence's."""
    args = _inputs(128, 2, 32, 64, dtype=dtype, decay=4.0)
    assert float(jnp.cumsum(args[3][0, :64], axis=0).min()) < -150
    with jax.default_matmul_precision("highest"):
        o_k, g_k = _grads(kernels, args)
        o_r, g_r = _grads(_recurrence, args)
    tol = 1e-5 if dtype == jnp.float32 else 1 / 64
    np.testing.assert_allclose(np.asarray(o_k, np.float32), o_r,
                               atol=tol * float(jnp.abs(o_r).max()))
    for name, a, b in zip(NAMES, g_k, g_r):
        a = np.asarray(a, np.float32)
        assert np.isfinite(a).all(), name
        np.testing.assert_allclose(
            a, b, atol=max(tol, 1e-4) * float(jnp.abs(b).max()),
            err_msg=name)


def test_every_row_of_a_batch_starts_from_a_zero_state():
    """Two sequences over two grid steps each: the second sequence's result
    is what it is alone (the carried state is zeroed at a row's first step,
    and so is its cotangent)."""
    args = _inputs(576, 2, 32, 64, B=2)
    alone = tuple(a[1:] for a in args)
    with jax.default_matmul_precision("highest"):
        o_2, g_2 = _grads(kernels, args)
        w = jnp.asarray(np.random.default_rng(5).standard_normal(o_2.shape),
                        jnp.float32)[1:]

        def loss(*a):
            o = kernels(*a)
            return jnp.sum(o * w), o

        (_, o_1), g_1 = jax.jit(jax.value_and_grad(
            loss, argnums=range(5), has_aux=True))(*alone)
    np.testing.assert_allclose(o_2[1:], o_1, atol=1e-6)
    for name, a, b in zip(NAMES, g_2, g_1):
        np.testing.assert_allclose(a[1:], b, atol=1e-5 * float(
            jnp.abs(b).max()), err_msg=name)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_kernels_put_the_norms_on_q_and_k_themselves(dtype):
    """``unit``: q and k as the convolutions leave them (float32, any
    length), v in the compute dtype. The kernels scale each head's row in
    VMEM and hand back the cotangents of the rows as they arrived; the
    einsum form takes :func:`unit_heads` first, as the layer did."""
    T, H, dk, dv = 140, 3, 32, 64
    q, k, v, g, beta = _inputs(T, H, dk, dv, B=2)
    scale = jnp.asarray(np.random.default_rng(7).uniform(
        0.2, 3.0, (2, T, H, 1)), jnp.float32)
    args = (q * scale * 5.0, k * scale, v.astype(dtype), g, beta)
    unit = (1.0 / np.sqrt(dk), 1e-6)
    with jax.default_matmul_precision("highest"):
        o_k, g_k = _grads(functools.partial(kernels, unit=unit), args)
        o_e, g_e = _grads(functools.partial(dr.chunked_delta_rule, unit=unit),
                          args)
        o_r, g_r = _grads(lambda q, k, *a: _recurrence(
            dr.unit_heads(q, unit[0], unit[1], jnp.float32),
            dr.unit_heads(k, 1.0, unit[1], jnp.float32), *a), args)
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    tol = 1e-5 if dtype == jnp.float32 else 1 / 64
    top = float(jnp.abs(o_r).max())
    assert np.abs(f32(o_k) - f32(o_r)).max() <= tol * top
    for name, a, e, r in zip(NAMES, g_k, g_e, g_r):
        assert a.shape == e.shape and a.dtype == e.dtype, name
        off_k, off_e = (np.abs(f32(x) - f32(r)).max() for x in (a, e))
        assert off_k <= max(3 * off_e, 2e-5 * float(jnp.abs(r).max())), \
            (name, off_k, off_e)


# ---- the picker -------------------------------------------------------------

CELL = dict(T=4096, H=15, dk=96, dv=192, dtype=jnp.bfloat16)
PICKS = {
    "the-olmo-hybrid-cell": ({}, "pallas", ""),
    "all-thirty-heads": (dict(H=30), "pallas", ""),
    "a-T-that-is-padded": (dict(T=1000), "pallas", ""),
    "keys-of-64-values-of-128": (dict(dk=64, dv=128), "pallas", ""),
    "keys-of-32-values-of-64": (dict(dk=32, dv=64), "pallas", ""),
    "float32": (dict(dtype=jnp.float32), "xla", "float32"),
    "float16": (dict(dtype=jnp.float16), "xla", "float16"),
    "keys-of-128": (dict(dk=128, dv=128), "pallas", ""),
    "keys-of-48": (dict(dk=48, dv=96), "xla", "keys of 48"),
    "shared-key-heads-off-whole-tiles": (dict(H=30, key_heads=15), "xla",
                                         "15 key heads for 30 value heads"),
    "values-of-96": (dict(dv=96), "xla", "values of 96"),
}


@pytest.mark.parametrize("case", sorted(PICKS))
def test_the_picker_answers_by_widths_and_dtype(case):
    over, want, why = PICKS[case]
    took, said = dr.rule_lowering(**{**CELL, **over}, tpu=True)
    assert took == want
    assert (why in said) if why else said == ""


def test_the_picker_gives_the_einsum_form_off_the_chip():
    assert dr.rule_lowering(**CELL)[0] == "xla"            # this is a CPU
    assert dr.rule_lowering(**CELL, tpu=False) == ("xla", "not a TPU backend")


def test_the_picker_gives_the_einsum_form_at_another_chunk(monkeypatch):
    monkeypatch.setattr(dr, "CHUNK", 16)
    took, why = dr.rule_lowering(**CELL, tpu=True)
    assert took == "xla" and "chunks of 16" in why


def test_the_test_handle_refuses_shapes_the_kernels_do_not_take():
    args = _inputs(64, 2, 8, 16)
    with pytest.raises(ValueError, match="keys of 8 and values of 16"):
        dr.chunked_delta_rule(*args, interpret=True)


def test_rules_are_counted_by_lowering_when_traced():
    args = _inputs(64, 2, 32, 64)

    def took(fn):
        before = lowerings.snapshot()
        jax.make_jaxpr(fn)(*args)
        return lowerings.since(before)["delta_scan"]

    assert took(dr.chunked_delta_rule) == {"xla": 1}
    assert took(kernels) == {"pallas": 1}
    # a rule and the kernels' own backward; the einsum form's is autodiff's
    assert took(jax.grad(lambda *a: kernels(*a).sum())) \
        == {"pallas": 2}
    assert took(jax.grad(lambda *a: dr.chunked_delta_rule(*a).sum())) \
        == {"xla": 1}
