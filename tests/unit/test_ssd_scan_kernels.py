"""The state-space scan's Pallas lowering (``ops/ssd_scan.py``: ``ssd_fwd``,
``ssd_bwd`` behind a ``custom_vjp``), interpreted on the CPU, against its
einsum lowering and against the recurrence over positions; the picker's
answers; the counter a step program's row reads."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_granite4h as ref
from deepspeed_tpu.ops import lowerings, ssd_scan as ss

NAMES = "x dt A B C D".split()


def _inputs(T, H, P, G, N, B=1, seed=0, dtype=jnp.float32, strong=False):
    """``strong``: every head decays by exp(-1.6) a position, exp(-205) over
    128 of them: a decay factored as exp(cum_i) exp(-cum_j) would overflow
    float32 (largest exponent 88) inside one tile."""
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa
    dt = jax.nn.softplus(f(B, T, H))
    A = -jnp.asarray(rng.uniform(1, 16, (H,)), jnp.float32)
    if strong:
        dt, A = 0.1 + 0.0 * dt, -16.0 + 0.0 * A
    return (f(B, T, H, P).astype(dtype), dt, A, f(B, T, G, N).astype(dtype),
            f(B, T, G, N).astype(dtype), f(H))


def _recurrence(x, dt, A, B, C, D):
    f = [a.astype(jnp.float32) for a in (x, dt, A, B, C, D)]
    return jnp.stack([ref.recurrence(f[0][i], f[1][i], f[2], f[3][i], f[4][i],
                                     f[5]) for i in range(x.shape[0])])


def _grads(fn, args):
    """``y`` and the six cotangents under a fixed random cotangent of y: one
    program, ``fn`` traced once (its forward is the forward the cotangents
    went through)."""
    w = jnp.asarray(np.random.default_rng(5).standard_normal(
        jax.eval_shape(fn, *args).shape), jnp.float32)

    def loss(*a):
        y = fn(*a)
        return jnp.sum(y.astype(jnp.float32) * w), y

    (_, y), g = jax.jit(jax.value_and_grad(
        loss, argnums=range(6), has_aux=True))(*args)
    return y, g


# (T, H, P, G, N, chunk): more than one chunk each, so that the carried state
# and its cotangent are exercised; a T that is no multiple of the chunk; one
# and two groups; a chunk of one tile and of two by two; heads that share a
# lane block (64) and heads that fill one (128)
SHAPES = {
    "three-chunks-padded": (300, 4, 64, 1, 128, 128),
    "two-groups-padded": (300, 4, 64, 2, 128, 128),
    "tiles-2x2": (512, 2, 64, 1, 128, 256),
    "two-groups-tiles-2x2-padded": (300, 4, 64, 2, 128, 256),
    "heads-of-128": (256, 2, 128, 2, 128, 128),
}


@pytest.mark.parametrize("shape", sorted(set(SHAPES) - {"two-groups-padded"}))
def test_float32_kernels_are_the_einsum_form_and_the_recurrence(shape):
    T, H, P, G, N, Q = SHAPES[shape]
    args = _inputs(T, H, P, G, N)
    with jax.default_matmul_precision("highest"):
        y_k, g_k = _grads(lambda *a: ss.ssd_scan(*a, Q, interpret=True), args)
        y_e, g_e = _grads(lambda *a: ss.scan_einsum(*a, Q), args)
        y_r, g_r = _grads(_recurrence, args)
    # the running sums reach hundreds here and float32 keeps seven digits
    # of them: two orders of adding them up differ by 1e-5 of a decay
    top = float(jnp.abs(y_r).max())
    np.testing.assert_allclose(y_k, y_e, atol=5e-5 * top)
    np.testing.assert_allclose(y_k, y_r, atol=5e-5 * top)
    for name, k, e, r in zip(NAMES, g_k, g_e, g_r):
        assert k.shape == r.shape and k.dtype == r.dtype, name
        top = float(jnp.abs(r).max())
        # dt's cotangent holds A x (the running sums' cotangents from k on)
        # and A's sums dt_k x that over every position: differences of large
        # terms, which the three forms add up in three orders (the einsum
        # form is as far from the recurrence as the kernels are)
        tol = {"A": 1e-2, "dt": 5e-4}.get(name, 1e-4)
        np.testing.assert_allclose(k, e, atol=tol * top, err_msg=name)
        np.testing.assert_allclose(k, r, atol=tol * top, err_msg=name)


@pytest.mark.parametrize("shape", ["two-groups-padded",
                                   "two-groups-tiles-2x2-padded"])
def test_bf16_kernels_round_where_the_einsum_form_rounds(shape):
    """bf16 operands, float32 sums, decays and state: the kernels' forward
    is the einsum form's to a rounding of the result, and each cotangent is
    as near the float32 recurrence as the einsum form's is."""
    T, H, P, G, N, Q = SHAPES[shape]
    args = _inputs(T, H, P, G, N, dtype=jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        y_k, g_k = _grads(lambda *a: ss.ssd_scan(*a, Q, interpret=True), args)
        y_e, g_e = _grads(lambda *a: ss.scan_einsum(*a, Q), args)
        y_r, g_r = _grads(_recurrence, args)
    assert y_k.dtype == jnp.bfloat16
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    top = float(jnp.abs(y_r).max())
    assert np.abs(f32(y_k) - f32(y_e)).max() <= top / 128
    assert np.abs(f32(y_k) - f32(y_r)).max() <= top / 64
    for name, k, e, r in zip(NAMES, g_k, g_e, g_r):
        assert k.dtype == e.dtype and k.shape == e.shape, name
        assert np.isfinite(f32(k)).all(), name
        off_k = np.abs(f32(k) - f32(r)).max()
        off_e = np.abs(f32(e) - f32(r)).max()
        assert off_k <= max(3 * off_e, float(jnp.abs(r).max()) / 64), \
            (name, off_k, off_e)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decays_that_a_factored_form_would_overflow(dtype):
    """exp(-1.6) a position: the running sum passes -200 inside one tile and
    -400 over a chunk of 256. The mask sits in the exponent, so the result
    and every cotangent stay finite and are the recurrence's."""
    T, H, P, G, N, Q = 512, 2, 64, 1, 128, 256
    args = _inputs(T, H, P, G, N, dtype=dtype, strong=True)
    with jax.default_matmul_precision("highest"):
        y_k, g_k = _grads(lambda *a: ss.ssd_scan(*a, Q, interpret=True), args)
        y_r, g_r = _grads(_recurrence, args)
    tol = 1e-5 if dtype == jnp.float32 else 1 / 64
    np.testing.assert_allclose(np.asarray(y_k, np.float32), y_r,
                               atol=tol * float(jnp.abs(y_r).max()))
    for name, k, r in zip(NAMES, g_k, g_r):
        k = np.asarray(k, np.float32)
        assert np.isfinite(k).all(), name
        np.testing.assert_allclose(
            k, r, atol=max(tol, 1e-4) * float(jnp.abs(r).max()),
            err_msg=name)


def test_a_short_sequence_is_one_padded_chunk():
    args = _inputs(40, 2, 64, 1, 128)
    with jax.default_matmul_precision("highest"):
        got = ss.ssd_scan(*args, 128, interpret=True)
        want = _recurrence(*args)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=2e-5 * float(
        jnp.abs(want).max()))


# ---- the picker -------------------------------------------------------------

CELL = dict(Q=256, H=64, P=64, G=1, N=128, dtype=jnp.bfloat16)
PICKS = {
    "the-granite-cell": ({}, "pallas", ""),
    "two-groups": (dict(G=2), "pallas", ""),
    "heads-of-128": (dict(P=128, H=32), "pallas", ""),
    "one-tile-chunks": (dict(Q=128), "pallas", ""),
    "float32": (dict(dtype=jnp.float32), "xla", "float32"),
    "a-chunk-of-64": (dict(Q=64), "xla", "chunk of 64"),
    "a-chunk-of-192": (dict(Q=192), "xla", "chunk of 192"),
    "heads-of-32": (dict(P=32), "xla", "heads of 32"),
    "a-state-of-16": (dict(N=16), "xla", "state of 16"),
    "heads-that-leave-a-group-over": (dict(H=64, G=3), "xla", "3 groups"),
    "one-head-of-64-a-group": (dict(H=4, G=4), "xla", "lane blocks"),
}


@pytest.mark.parametrize("case", sorted(PICKS))
def test_the_picker_answers_by_shape_and_dtype(case):
    over, want, why = PICKS[case]
    took, said = ss.scan_lowering(**{**CELL, **over}, tpu=True)
    assert took == want
    assert (why in said) if why else said == ""


def test_the_picker_gives_the_einsum_form_off_the_chip():
    assert ss.scan_lowering(**CELL)[0] == "xla"           # this is a CPU
    assert ss.scan_lowering(**CELL, tpu=False) == ("xla", "not a TPU backend")


def test_the_test_handle_refuses_shapes_the_kernels_do_not_take():
    args = _inputs(128, 2, 16, 1, 16)
    with pytest.raises(ValueError, match="heads of 16"):
        ss.ssd_scan(*args, 128, interpret=True)


def test_scans_are_counted_by_lowering_when_traced():
    args = _inputs(128, 2, 64, 1, 128)

    def took(fn):
        before = lowerings.snapshot()
        jax.make_jaxpr(fn)(*args)
        return lowerings.since(before)["ssm_scan"]

    assert took(lambda *a: ss.ssd_scan(*a, 128)) == {"xla": 1}
    kernels = functools.partial(ss.ssd_scan, chunk=128, interpret=True)
    assert took(kernels) == {"pallas": 1}
    # a scan and the kernels' own backward; the einsum form's is autodiff's
    assert took(jax.grad(lambda *a: kernels(*a).sum())) \
        == {"pallas": 2}
    assert took(jax.grad(lambda *a: ss.ssd_scan(*a, 128).sum())) \
        == {"xla": 1}
