"""A mixer's output norm and head gate as one op on rows whose heads lie side
by side (``ops/head_norm_gate.py``: ``gate_fwd``, ``gate_bwd`` behind a
``custom_vjp``), the kernels interpreted on the CPU against the ``jax.numpy``
lines they replace on the chip; the picker's answers; the counter a step
program's row reads; the scope the backward's kernel is read under."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import head_norm_gate as hg, lowerings

BF, F32 = jnp.bfloat16, jnp.float32
# the Ling cell's heads: 16 of 128 values, one lane tile each
H, DV, EPS = 16, 128, 1e-6
NAMES = ("o", "z", "scale")
# (T, rows of a grid step's tile): one tile; four (``dscale`` summed over
# the grid); two
TILES = {"one-tile": (32, None), "four-tiles": (64, 16),
         "two-tiles": (64, 32)}

kernels = functools.partial(hg.head_norm_gate, interpret=True)


numpy_lines = hg.head_norm_gate_xla


def _inputs(T, dtype, B=2, H=H, dv=DV, seed=0):
    """Rows of heads at scales from 1/30 to 30 (``o`` is a sum over a
    state: its size is the data's), gates both sides of 0, a scale round
    1."""
    rng = np.random.default_rng(seed)
    size = np.exp(rng.uniform(-3.4, 3.4, (B, T, H, 1)))
    o = (rng.standard_normal((B, T, H, dv)) * size).reshape(B, T, H * dv)
    return (jnp.asarray(o, dtype),
            jnp.asarray(2.0 * rng.standard_normal((B, T, H)), dtype),
            jnp.asarray(1.0 + 0.2 * rng.standard_normal(dv), F32))


def _tiles_of(monkeypatch, tiles):
    T, rows = TILES[tiles]
    if rows is not None:
        monkeypatch.setattr(hg, "_TILE_ROWS", (rows,))
    return T


def _grads(fn, args, eps=EPS):
    """``y`` and the cotangents of o, z and scale under a fixed random
    cotangent of y."""
    ct = jnp.asarray(np.random.default_rng(5).standard_normal(args[0].shape),
                     F32)
    y, vjp = jax.vjp(lambda *a: fn(*a, eps), *args)
    return y, vjp(ct.astype(y.dtype))


def _f32(a):
    return np.asarray(a, np.float32)


@pytest.mark.parametrize("tiles", sorted(TILES))
def test_float32_kernels_are_the_jax_numpy_lines(monkeypatch, tiles):
    args = _inputs(_tiles_of(monkeypatch, tiles), F32)
    y_k, g_k = _grads(kernels, args)
    y_n, g_n = _grads(numpy_lines, args)
    assert y_k.dtype == y_n.dtype and y_k.shape == y_n.shape
    np.testing.assert_allclose(y_k, y_n, atol=2e-6, rtol=2e-6)
    for name, k, n in zip(NAMES, g_k, g_n):
        assert k.shape == n.shape and k.dtype == n.dtype, name
        # a head's 128 lanes, and dscale's rows, add up in another order
        np.testing.assert_allclose(k, n, rtol=1e-5, err_msg=name,
                                   atol=1e-5 * float(jnp.abs(n).max()))


@pytest.mark.parametrize("tiles", sorted(TILES))
def test_bf16_kernels_round_where_the_jax_numpy_lines_round(monkeypatch,
                                                            tiles):
    """bf16 rows and gates, float32 statistics and sigmoid, one rounding on
    the way out: ``y`` and ``do`` are the lines' to a unit of their last
    place, and rarely (the kernels' sigmoid is a tanh, a head's sum another
    order); ``dz`` is a float32 sum rounded to bf16, ``dscale`` stays
    float32."""
    args = _inputs(_tiles_of(monkeypatch, tiles), BF)
    y_k, g_k = _grads(kernels, args)
    y_n, g_n = _grads(numpy_lines, args)
    assert y_k.dtype == y_n.dtype == BF
    np.testing.assert_allclose(_f32(y_k), _f32(y_n), rtol=2.0 ** -7,
                               atol=1e-6)
    assert np.mean(_f32(y_k) != _f32(y_n)) < 1e-3
    for name, k, n in zip(NAMES, g_k, g_n):
        assert k.shape == n.shape and k.dtype == n.dtype, name
        np.testing.assert_allclose(
            _f32(k), _f32(n), rtol=2.0 ** -7, err_msg=name,
            atol=2.0 ** -8 * float(jnp.abs(_f32(n)).max()))
    assert [g.dtype for g in g_k] == [BF, BF, F32]


@pytest.mark.parametrize("dtype", [F32, BF], ids=["float32", "bf16"])
def test_a_head_of_zeros_is_held_by_eps(dtype):
    """A head whose values are all zero has no norm to divide by: ``eps``
    alone stands under the root. Its result is zero, its ``do`` is ``dy
    scale s / sqrt(eps)`` and finite, and the heads beside it are what they
    are without it; rows of 1e-3 read another result under an ``eps`` of
    their own size."""
    o, z, scale = _inputs(32, dtype)
    o = o.at[:, :, 3 * DV:4 * DV].set(0.0)
    y_k, g_k = _grads(kernels, (o, z, scale))
    y_n, g_n = _grads(numpy_lines, (o, z, scale))
    head = slice(3 * DV, 4 * DV)
    assert not np.any(_f32(y_k)[..., head])
    assert np.all(np.isfinite(_f32(g_k[0])))
    top = float(np.abs(_f32(g_n[0])[..., head]).max())
    assert top > 100.0                          # dy s / sqrt(1e-6)
    tol = 2e-6 if dtype == F32 else 2.0 ** -7
    np.testing.assert_allclose(_f32(g_k[0]), _f32(g_n[0]), rtol=tol,
                               atol=tol * top)
    np.testing.assert_allclose(_f32(g_k[1]), _f32(g_n[1]), rtol=tol,
                               atol=tol * float(np.abs(_f32(g_n[1])).max()))
    small = jnp.asarray(1e-3 * np.random.default_rng(2).standard_normal(
        o.shape), dtype)
    a = _f32(kernels(small, z, scale, 1e-6))
    b = _f32(kernels(small, z, scale, 1e-2))
    assert np.abs(a).max() > 5 * np.abs(b).max()
    np.testing.assert_allclose(b, _f32(numpy_lines(small, z, scale, 1e-2)),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("shape", ["two-lane-tiles-a-head", "three-heads"])
def test_other_widths(shape):
    """Heads of 256 values (two lane tiles a head) and a count of heads that
    fills no sublane tile."""
    heads, dv = {"two-lane-tiles-a-head": (2, 256), "three-heads": (3, 128)}[
        shape]
    args = _inputs(16, F32, B=1, H=heads, dv=dv)
    y_k, g_k = _grads(kernels, args)
    y_n, g_n = _grads(numpy_lines, args)
    np.testing.assert_allclose(y_k, y_n, atol=2e-6, rtol=2e-6)
    for name, k, n in zip(NAMES, g_k, g_n):
        np.testing.assert_allclose(k, n, rtol=1e-5, err_msg=name,
                                   atol=1e-5 * float(jnp.abs(n).max()))


def test_rows_of_a_batch_are_on_their_own():
    """Two rows a batch: each one's ``y``, ``do`` and ``dz`` are what it
    gets alone, to the bit, and ``dscale`` is the two rows' sum."""
    args = _inputs(32, F32)
    ct = jnp.asarray(np.random.default_rng(7).standard_normal(args[0].shape),
                     F32)

    def run(o, z, ct):
        y, vjp = jax.vjp(lambda o, z, s: kernels(o, z, s, EPS), o, z,
                         args[2])
        return (y,) + vjp(ct)

    both = run(args[0], args[1], ct)
    alone = [run(args[0][i:i + 1], args[1][i:i + 1], ct[i:i + 1])
             for i in range(2)]
    for i in range(2):
        for j in range(3):
            np.testing.assert_array_equal(both[j][i], alone[i][j][0])
    np.testing.assert_allclose(both[3], alone[0][3] + alone[1][3],
                               rtol=1e-5, atol=1e-5)


# ---- the picker -------------------------------------------------------------

CELL = dict(T=8192, H=16, dv=128, dtype=BF)
PICKS = {
    "the-ling-cell": ({}, "pallas", ""),
    "two-lane-tiles-a-head": (dict(dv=256), "pallas", ""),
    "a-short-row": (dict(T=16), "pallas", ""),
    "float32": (dict(dtype=F32), "xla", "float32 rows"),
    "olmo-hybrids-heads": (dict(dv=192), "xla", "heads of 192"),
    "a-ragged-T": (dict(T=8190), "xla", "T of 8190"),
    "one-head": (dict(H=1), "pallas", ""),
    "rows-too-wide-for-a-tile": (dict(dv=1 << 16), "xla", "do not fit"),
}


@pytest.mark.parametrize("case", sorted(PICKS))
def test_the_picker_answers_by_shape_and_dtype(case):
    over, want, why = PICKS[case]
    took, said = hg.gate_lowering(**{**CELL, **over}, tpu=True)
    assert took == want
    assert (why in said) if why else said == ""


def test_the_picker_gives_the_jax_numpy_lines_off_the_chip():
    assert hg.gate_lowering(**CELL)[0] == "xla"           # this is a CPU
    assert hg.gate_lowering(**CELL, tpu=False) == ("xla",
                                                   "not a TPU backend")


def test_the_test_handle_refuses_shapes_the_kernels_do_not_take():
    o, z, scale = _inputs(24, F32)
    with pytest.raises(ValueError, match="T of 24"):
        kernels(o, z, scale, EPS)
    o, z, scale = _inputs(16, F32, H=4, dv=192)
    with pytest.raises(ValueError, match="heads of 192"):
        kernels(o, z, scale, EPS)


def test_a_tile_is_what_fits_the_kernels_vmem():
    """The backward's three wide blocks, pipelined twice each: the cell's
    tile is 256 rows of 2,048 bf16 values (6 MiB), a divisor of ``T``."""
    assert hg._tile_rows(8192, 2048, 2) == 256
    assert 6 * 256 * 2048 * 2 <= hg._TILE_BYTES
    assert hg._tile_rows(8192 + 16, 2048, 2) == 16
    assert hg._tile_rows(8192, 256, 2) == 256             # the most measured


# ---- what a step program's row reads ------------------------------------------

def test_gates_are_counted_by_lowering_when_traced():
    args = _inputs(16, F32, B=1)

    def took(fn):
        before = lowerings.snapshot()
        jax.make_jaxpr(fn)(*args)
        return lowerings.since(before)["kda_gate"]

    assert took(lambda *a: hg.head_norm_gate(*a, EPS)) == {"xla": 1}
    assert took(lambda *a: kernels(*a, EPS)) == {"pallas": 1}
    # a norm and gate and the kernels' own backward; the jax.numpy lines'
    # is autodiff's
    assert took(jax.grad(lambda *a: kernels(*a, EPS).sum())) \
        == {"pallas": 2}
    assert took(jax.grad(lambda *a: hg.head_norm_gate(*a, EPS).sum())) \
        == {"xla": 1}


def test_the_backwards_kernel_lies_under_the_callers_scope():
    """The ``custom_vjp``'s backward carries the name stack the call was
    traced under, once: the benchmark's scope reader finds the backward's
    kernel where it finds the forward's."""
    args = _inputs(16, F32, B=1)

    def loss(*a):
        with jax.named_scope("attn"), jax.named_scope("kda_gate"):
            return kernels(*a, EPS).sum()

    text = jax.jit(jax.grad(loss)).lower(*args).as_text(debug_info=True)
    assert "transpose(jvp(attn))/kda_gate/jit(gate_bwd)" in text
    assert "kda_gate/kda_gate" not in text
