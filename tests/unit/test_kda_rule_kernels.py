"""The Pallas lowering of the delta rule with a decay a key channel
(``ops/kda_rule.py``: ``kda_fwd``, ``kda_bwd`` behind a ``custom_vjp``),
interpreted on the CPU, against its einsum lowering and against the
recurrence over positions; the picker's answers; the counter a step
program's row reads."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_ling3 as ref
from deepspeed_tpu.ops import delta_rule as dr, kda_rule as kr, lowerings

NAMES = "q k v g beta".split()
kernels = functools.partial(kr.chunked_kda_rule, interpret=True)
recurrence = jax.vmap(ref.recurrence)
H, DK, DV = 2, 128, 128


def _inputs(T, B=1, seed=0, dtype=jnp.float32, lower=-5.0, H=H):
    """Unit keys, ``q`` scaled as the layer scales it, steps in (0, 2), ``g``
    over its whole range (``lower`` to 0: channels that forget inside a
    block of 16 rows beside channels that carry the chunk's state)."""
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa
    return ((ref.l2_norm(f(B, T, H, DK)) / np.sqrt(DK)).astype(dtype),
            ref.l2_norm(f(B, T, H, DK)).astype(dtype),
            f(B, T, H, DV).astype(dtype),
            lower * jnp.asarray(rng.uniform(size=(B, T, H, DK)),
                                jnp.float32) ** 3,
            2.0 * jax.nn.sigmoid(f(B, T, H)))


def _float32(fn):
    return lambda *a: fn(*(x.astype(jnp.float32) for x in a))


def _grads(fn, args, flip=False):
    """``o`` and the five cotangents under a fixed random cotangent of o
    (``flip``: the batch's rows, and the cotangent's, in reverse order): one
    program, ``fn`` traced once."""
    w = jnp.asarray(np.random.default_rng(5).standard_normal(
        jax.eval_shape(fn, *args).shape), jnp.float32)
    if flip:
        w, args = w[::-1], tuple(a[::-1] for a in args)

    def loss(w, *a):
        o = fn(*a)
        return jnp.sum(o.astype(jnp.float32) * w), o

    (_, o), g = jax.jit(jax.value_and_grad(
        loss, argnums=range(1, 6), has_aux=True))(w, *args)
    return o, g


def _f32(a):
    return np.asarray(a, np.float32)


# T: two chunks (one pair); three chunks, padded to two pairs (the carried
# state and its cotangent); 600 positions, padded to two grid steps of 512;
# one padded chunk
LENGTHS = {"two-chunks": 128, "three-chunks": 192,
           "T600-padded-to-two-steps": 600, "one-padded-chunk": 40}


@pytest.mark.parametrize("length", sorted(
    set(LENGTHS) - {"T600-padded-to-two-steps"}))
def test_float32_kernels_are_the_einsum_form_and_the_recurrence(length):
    args = _inputs(LENGTHS[length])
    with jax.default_matmul_precision("highest"):
        o_k, g_k = _grads(kernels, args)
        o_e, g_e = _grads(kr.kda_einsum, args)
        o_r, g_r = _grads(recurrence, args)
    top = float(jnp.abs(o_r).max())
    np.testing.assert_allclose(o_k, o_e, atol=1e-5 * top)
    np.testing.assert_allclose(o_k, o_r, atol=1e-5 * top)
    for name, k, e, r in zip(NAMES, g_k, g_e, g_r):
        assert k.shape == r.shape and k.dtype == r.dtype, name
        top = float(jnp.abs(r).max())
        np.testing.assert_allclose(k, e, atol=2e-5 * top, err_msg=name)
        np.testing.assert_allclose(k, r, atol=2e-5 * top, err_msg=name)


@pytest.mark.parametrize("length", ["two-chunks",
                                    "T600-padded-to-two-steps"])
def test_bf16_kernels_round_where_the_einsum_form_rounds(length):
    """bf16 operands, float32 sums, decays, inverse and state: the kernels'
    forward is the einsum form's to a rounding of the result, and each
    cotangent (``dg`` a key channel among them) is as near the float32
    recurrence as the einsum form's is."""
    args = _inputs(LENGTHS[length], dtype=jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        o_k, g_k = _grads(kernels, args)
        o_e, g_e = _grads(kr.kda_einsum, args)
        o_r, g_r = _grads(_float32(recurrence), args)
    assert o_k.dtype == jnp.bfloat16
    top = float(jnp.abs(o_r).max())
    assert np.abs(_f32(o_k) - _f32(o_e)).max() <= top / 128
    assert np.abs(_f32(o_k) - _f32(o_r)).max() <= top / 64
    for name, k, e, r in zip(NAMES, g_k, g_e, g_r):
        assert k.dtype == e.dtype and k.shape == e.shape, name
        assert np.isfinite(_f32(k)).all(), name
        off_k = np.abs(_f32(k) - _f32(r)).max()
        off_e = np.abs(_f32(e) - _f32(r)).max()
        assert off_k <= max(3 * off_e, float(jnp.abs(r).max()) / 64), \
            (name, off_k, off_e)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_gate_at_its_bound_overflows_nothing(dtype):
    """``g`` = -5 at every position and channel of the second chunk: inside
    a block of 16 rows the columns' factor reaches ``exp(75)``, which float32
    and bf16 hold, and the columns after a block are never built. The result
    and every cotangent stay finite and are the recurrence's."""
    q, k, v, g, beta = _inputs(192, dtype=dtype)
    g = g.at[:, 64:128].set(-5.0)
    args = (q, k, v, g, beta)
    with jax.default_matmul_precision("highest"):
        o_k, g_k = _grads(kernels, args)
        o_r, g_r = _grads(_float32(recurrence), args)
    tol = 1e-5 if dtype == jnp.float32 else 1 / 64
    np.testing.assert_allclose(_f32(o_k), o_r,
                               atol=tol * float(jnp.abs(o_r).max()))
    for name, a, b in zip(NAMES, g_k, g_r):
        assert np.isfinite(_f32(a)).all(), name
        np.testing.assert_allclose(
            _f32(a), b, atol=max(tol, 2e-5) * float(jnp.abs(b).max()),
            err_msg=name)


def test_without_a_decay_the_kernels_are_the_recurrence():
    q, k, v, g, beta = _inputs(128)
    args = (q, k, v, 0.0 * g, beta)
    with jax.default_matmul_precision("highest"):
        o_k, g_k = _grads(kernels, args)
        o_r, g_r = _grads(recurrence, args)
    np.testing.assert_allclose(o_k, o_r, atol=1e-5 * float(
        jnp.abs(o_r).max()))
    for name, a, b in zip(NAMES, g_k, g_r):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()),
                                   err_msg=name)


def test_with_one_decay_a_head_the_kernels_are_the_scalar_rule():
    """A decay constant over a head's channels is ``ops/delta_rule.py``'s
    rule, forward and backward (``dg`` summed over the channels)."""
    q, k, v, g, beta = _inputs(150, lower=-0.5)
    g = g[..., 0]

    def per_channel(q, k, v, g, beta):
        return kernels(q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta)

    with jax.default_matmul_precision("highest"):
        o_k, g_k = _grads(per_channel, (q, k, v, g, beta))
        o_d, g_d = _grads(dr.chunked_delta_rule, (q, k, v, g, beta))
    np.testing.assert_allclose(o_k, o_d, atol=1e-5 * float(
        jnp.abs(o_d).max()))
    for name, a, b in zip(NAMES, g_k, g_d):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()),
                                   err_msg=name)


def test_every_row_of_a_batch_starts_from_a_zero_state():
    """Two sequences (of one head) over two grid steps each: the second
    sequence's result is what it is as the first (the carried state is
    zeroed at a row's first step, and so is its cotangent)."""
    args = _inputs(576, B=2, H=1)
    with jax.default_matmul_precision("highest"):
        o_2, g_2 = _grads(kernels, args)
        o_1, g_1 = _grads(kernels, args, flip=True)
    np.testing.assert_allclose(o_2[1], o_1[0], atol=1e-6)
    for name, a, b in zip(NAMES, g_2, g_1):
        np.testing.assert_allclose(a[1], b[0], atol=1e-5 * float(
            jnp.abs(b).max()), err_msg=name)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_kernels_put_the_norms_on_q_and_k_themselves(dtype):
    """``unit``: q and k as the convolutions leave them (float32, any
    length), v in the compute dtype. The kernels scale each head's row in
    VMEM and hand back the cotangents of the rows as they arrived; the
    einsum form takes :func:`unit_heads` first, as the layer did."""
    T = 140
    q, k, v, g, beta = _inputs(T)
    scale = jnp.asarray(np.random.default_rng(7).uniform(
        0.2, 3.0, (1, T, H, 1)), jnp.float32)
    args = (q * scale * 5.0, k * scale, v.astype(dtype), g, beta)
    unit = (1.0 / np.sqrt(DK), 1e-6)
    with jax.default_matmul_precision("highest"):
        o_k, g_k = _grads(functools.partial(kernels, unit=unit), args)
        o_e, g_e = _grads(functools.partial(kr.chunked_kda_rule, unit=unit),
                          args)
        o_r, g_r = _grads(lambda q, k, v, *a: recurrence(
            dr.unit_heads(q, unit[0], unit[1], jnp.float32),
            dr.unit_heads(k, 1.0, unit[1], jnp.float32),
            v.astype(jnp.float32), *a), args)
    tol = 1e-5 if dtype == jnp.float32 else 1 / 64
    top = float(jnp.abs(o_r).max())
    assert np.abs(_f32(o_k) - _f32(o_r)).max() <= tol * top
    for name, a, e, r in zip(NAMES, g_k, g_e, g_r):
        assert a.shape == e.shape and a.dtype == e.dtype, name
        off_k, off_e = (np.abs(_f32(x) - _f32(r)).max() for x in (a, e))
        assert off_k <= max(3 * off_e, 2e-5 * float(jnp.abs(r).max())), \
            (name, off_k, off_e)


# ---- the picker -------------------------------------------------------------

CELL = dict(T=8192, H=16, dk=128, dv=128, dtype=jnp.bfloat16)
PICKS = {
    "the-ling-cell": ({}, "pallas", ""),
    "all-thirty-two-heads": (dict(H=32), "pallas", ""),
    "a-T-that-is-padded": (dict(T=1000), "pallas", ""),
    "float32": (dict(dtype=jnp.float32), "xla", "float32"),
    "float16": (dict(dtype=jnp.float16), "xla", "float16"),
    "keys-of-96": (dict(dk=96, dv=192), "xla", "keys of 96"),
    "values-of-64": (dict(dv=64), "xla", "values of 64"),
}


@pytest.mark.parametrize("case", sorted(PICKS))
def test_the_picker_answers_by_widths_and_dtype(case):
    over, want, why = PICKS[case]
    took, said = kr.kda_lowering(**{**CELL, **over}, tpu=True)
    assert took == want
    assert (why in said) if why else said == ""


def test_the_picker_gives_the_einsum_form_off_the_chip():
    assert kr.kda_lowering(**CELL)[0] == "xla"             # this is a CPU
    assert kr.kda_lowering(**CELL, tpu=False) == ("xla", "not a TPU backend")


def test_the_picker_gives_the_einsum_form_at_another_chunk(monkeypatch):
    monkeypatch.setattr(kr, "CHUNK", 16)
    took, why = kr.kda_lowering(**CELL, tpu=True)
    assert took == "xla" and "chunks of 16" in why


def test_the_test_handle_refuses_shapes_the_kernels_do_not_take():
    rng = np.random.default_rng(0)
    q, v = (jnp.asarray(rng.standard_normal((1, 64, 2, d)), jnp.float32)
            for d in (16, 24))
    with pytest.raises(ValueError, match="keys of 16 and values of 24"):
        kr.chunked_kda_rule(q, q, v, -q * q, v[..., 0], interpret=True)


def test_rules_are_counted_by_lowering_when_traced():
    args = _inputs(64)

    def took(fn):
        before = lowerings.snapshot()
        jax.make_jaxpr(fn)(*args)
        return lowerings.since(before)["kda_scan"]

    assert took(kr.chunked_kda_rule) == {"xla": 1}
    assert took(kernels) == {"pallas": 1}
    # a rule and the kernels' own backward; the einsum form's is autodiff's
    assert took(jax.grad(lambda *a: kernels(*a).sum())) \
        == {"pallas": 2}
    assert took(jax.grad(lambda *a: kr.chunked_kda_rule(*a).sum())) \
        == {"xla": 1}


# ---- the kernels' own layout -------------------------------------------------

@pytest.mark.parametrize("lowering", ["einsum-form", "kernels"])
def test_the_lanes_entry_is_the_rule_with_the_heads_side_by_side(lowering):
    """``kda_rule_lanes`` takes q, k, v and ``g`` as ``[B, T, H d]`` and
    hands o out, and takes its cotangent, the same way: the four-dimensional
    entry is a reshape of it, to the bit, in both lowerings, a ``T`` that is
    padded to whole steps and the norms of q and k among them; and its
    kernels' program holds no array with an axis of heads but ``beta``."""
    interpret = {"einsum-form": None, "kernels": True}[lowering]
    unit = (DK ** -0.5, 1e-6)
    q, k, v, g, beta = _inputs(192)
    flat = tuple(a.reshape(a.shape[:2] + (-1,)) for a in (q, k, v, g))
    o_4, g_4 = _grads(functools.partial(
        kr.chunked_kda_rule, unit=unit, interpret=interpret),
        (q, k, v, g, beta))
    o_3, g_3 = _grads(functools.partial(
        kr.kda_rule_lanes, unit=unit, interpret=interpret), flat + (beta,))
    assert o_3.shape == (1, 192, H * DV)
    np.testing.assert_array_equal(o_3.reshape(o_4.shape), o_4)
    for name, a, b in zip(NAMES, g_3, g_4):
        assert a.shape == b.reshape(b.shape[:2] + (-1,)).shape, name
        np.testing.assert_array_equal(np.asarray(a).reshape(b.shape), b,
                                      err_msg=name)
    if interpret:
        jaxpr = str(jax.make_jaxpr(jax.grad(lambda *a: kr.kda_rule_lanes(
            *a, unit=unit, interpret=True).sum(), argnums=range(5)))(
                *flat, beta))
        assert f"192,{H},{DK}]" not in jaxpr and f"256,{H},{DK}]" not in jaxpr
