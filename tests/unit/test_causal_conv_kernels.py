"""The mixers' causal convolution with its silu as one op
(``ops/causal_conv.py``: ``conv_fwd``, ``conv_bwd`` behind a ``custom_vjp``),
the kernels interpreted on the CPU against the ``jax.numpy`` form they
replace on the chip; the picker's answers; the counter a step program's row
reads."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import causal_conv as cc, lowerings

BF, F32 = jnp.bfloat16, jnp.float32
# (channels, bias, the result's dtype in a bf16 program, where the result
# is split): the Granite cell's call (xBC: 4096 inner channels + 2 x 128 of B
# and C, which leave as three arrays) and the Olmo-Hybrid cell's three (q and
# k at 15 heads of 96, float32 for the rule's norms; v at 15 heads of 192 in
# the block's dtype), whose rows end inside a lane tile
CALLS = {
    "granite-xbc": (4352, True, BF, (4096, 4224)),
    "olmo-q-and-k": (1440, False, F32, ()),
    "olmo-v": (2880, False, BF, ()),
}
T = 64
# one tile of all 64 positions, or four of 16: the halo before a tile and
# the backward's after it, both ways
TILES = {"one-tile": None, "four-tiles": 16}

kernels = functools.partial(cc.causal_conv_silu, interpret=True)


def numpy_form(x, w, b=None, out_dtype=None, splits=()):
    """What the models ran before the op: the whole result, never split."""
    del splits
    return jax.nn.silu(cc.causal_conv(x, w, b)).astype(out_dtype or x.dtype)


def _whole(y):
    """The op's result with its parts side by side again."""
    return jnp.concatenate(y, axis=-1) if isinstance(y, list) else y


def _inputs(C, bias, dtype, B=2, K=4, T=T, seed=0):
    """Rows, taps drawn as the models draw them (uniform +-1/sqrt(K))."""
    rng = np.random.default_rng(seed)
    bound = K ** -0.5
    x = jnp.asarray(rng.standard_normal((B, T, C)), dtype)
    w = jnp.asarray(rng.uniform(-bound, bound, (K, C)), dtype)
    b = jnp.asarray(rng.uniform(-bound, bound, (C,)), dtype) if bias else None
    return x, w, b


def _tiles_of(monkeypatch, rows):
    """Tiles of ``rows`` positions in both kernels."""
    if rows is not None:
        monkeypatch.setattr(cc, "_TILE_ROWS", (rows,))


def _grads(fn, args, out_dtype, splits=()):
    """``y`` and the cotangents of x, w (and b) under a fixed random
    cotangent of y (of its parts, where it comes in parts)."""
    x, w, b = args
    y = fn(x, w, b, out_dtype, splits)
    assert isinstance(y, list) == (bool(splits) and fn is not numpy_form)
    ct = jnp.asarray(np.random.default_rng(5).standard_normal(x.shape), F32)
    n = 2 if b is None else 3
    g = jax.grad(lambda *a: jnp.sum(_whole(
        fn(*a, *(None,) * (3 - n), out_dtype, splits)).astype(F32) * ct),
        argnums=tuple(range(n)))(*args[:n])
    return _whole(y), g


def _f32(a):
    return np.asarray(a, np.float32)


@pytest.mark.parametrize("tiles", sorted(TILES))
@pytest.mark.parametrize("call", sorted(CALLS))
def test_float32_kernels_are_the_jax_numpy_form(monkeypatch, call, tiles):
    C, bias, _, splits = CALLS[call]
    _tiles_of(monkeypatch, TILES[tiles])
    args = _inputs(C, bias, F32)
    y_k, g_k = _grads(kernels, args, F32, splits)
    y_n, g_n = _grads(numpy_form, args, F32)
    assert y_k.dtype == y_n.dtype and y_k.shape == y_n.shape
    np.testing.assert_allclose(y_k, y_n, atol=2e-6, rtol=2e-6)
    for name, k, n in zip("xwb", g_k, g_n):
        assert k.shape == n.shape and k.dtype == n.dtype, name
        # dw and db add 128 positions up in another order
        np.testing.assert_allclose(k, n, rtol=2e-6, err_msg=name,
                                   atol=2e-6 * float(jnp.abs(n).max()))


@pytest.mark.parametrize("tiles", sorted(TILES))
@pytest.mark.parametrize("call", sorted(CALLS))
def test_bf16_kernels_round_where_the_jax_numpy_form_rounds(monkeypatch,
                                                            call, tiles):
    """bf16 rows and taps, float32 arithmetic, one rounding on the way out:
    the result and ``dx`` are the ``jax.numpy`` form's to one rounding of
    theirs (XLA on a CPU contracts a multiply-add where the interpreter does
    not, and the kernels' sigmoid is a tanh), ``dw`` and ``db`` to a
    rounding of a float32 sum to bf16."""
    C, bias, out, splits = CALLS[call]
    _tiles_of(monkeypatch, TILES[tiles])
    args = _inputs(C, bias, BF)
    y_k, g_k = _grads(kernels, args, out, splits)
    y_n, g_n = _grads(numpy_form, args, out)
    assert y_k.dtype == y_n.dtype == out
    # a float32 silu 1e-7 apart rounds the other way in a few elements of
    # a hundred thousand: one unit of the result's last place, and rarely
    ulp = 2.0 ** -7 if out == BF else 2.0 ** -22
    np.testing.assert_allclose(_f32(y_k), _f32(y_n), rtol=ulp, atol=1e-6)
    if out == BF:
        assert np.mean(_f32(y_k) != _f32(y_n)) < 1e-4
    for name, k, n in zip("xwb", g_k, g_n):
        assert k.shape == n.shape and k.dtype == n.dtype == BF, name
        np.testing.assert_allclose(
            _f32(k), _f32(n), rtol=2.0 ** -8, err_msg=name,
            atol=2.0 ** -8 * float(jnp.abs(_f32(n)).max()))


@pytest.mark.parametrize("tiles", sorted(TILES))
def test_nothing_crosses_a_rows_start(monkeypatch, tiles):
    """Two rows a batch are two sequences: each one's result and ``dx`` are
    what it gets alone, to the bit (zeros before its first position, nothing
    after its last), and ``dw``, ``db`` are the two rows' sums."""
    C = 256
    _tiles_of(monkeypatch, TILES[tiles])
    x, w, b = _inputs(C, True, F32)
    ct = jnp.asarray(np.random.default_rng(7).standard_normal(x.shape), F32)

    def run(x, ct):
        y, vjp = jax.vjp(lambda x, w, b: kernels(x, w, b), x, w, b)
        return (y,) + vjp(ct)

    both = run(x, ct)
    alone = [run(x[i:i + 1], ct[i:i + 1]) for i in range(2)]
    for i in range(2):
        np.testing.assert_array_equal(both[0][i], alone[i][0][0])
        np.testing.assert_array_equal(both[1][i], alone[i][1][0])
    for j in (2, 3):
        np.testing.assert_allclose(both[j], alone[0][j] + alone[1][j],
                                   rtol=1e-5, atol=1e-5)
    # the second row's first position sees the bias and its own tap alone
    first = b + w[3] * x[1, 0]
    np.testing.assert_allclose(both[0][1, 0], first * jax.nn.sigmoid(first),
                               atol=1e-6)


@pytest.mark.parametrize("tiles", sorted(TILES))
@pytest.mark.parametrize("bias", [False, True], ids=["no-bias", "bias"])
@pytest.mark.parametrize("K", [3, 4])
@pytest.mark.parametrize("dtype", [F32, BF], ids=["float32", "bf16"])
def test_without_an_activation_the_kernels_are_the_convolution(
        monkeypatch, dtype, K, bias, tiles):
    """``activation=None`` (a short-convolution mixer's call: 3 taps, no
    bias, 2048 channels): the interpreted kernels against the ``"xla"`` form
    of the same op, forward and both gradients (``g = dy``: the backward
    rebuilds nothing). In float32 to the order of a sum; in bf16 to one
    rounding of the result, ``dx`` and the float32 sums of ``dw``, ``db``."""
    C = 2048 if not bias and K == 3 else 384
    _tiles_of(monkeypatch, TILES[tiles])
    args = _inputs(C, bias, dtype, K=K)
    op = functools.partial(cc.causal_conv_act, activation=None)

    def plain(x, w, b=None, out_dtype=None, splits=()):
        return cc.causal_conv(x, w, b).astype(out_dtype or x.dtype)

    snap = lowerings.snapshot()
    assert op(*args).dtype == dtype                      # the "xla" form
    assert lowerings.since(snap)["conv"] == {"xla": 1}
    np.testing.assert_array_equal(_f32(op(*args)), _f32(plain(*args)))
    y_k, g_k = _grads(functools.partial(op, interpret=True), args, dtype)
    y_n, g_n = _grads(plain, args, dtype)
    assert y_k.dtype == y_n.dtype == dtype
    tol = 2.0 ** -8 if dtype == BF else 2e-6
    np.testing.assert_allclose(_f32(y_k), _f32(y_n), rtol=tol, atol=1e-6)
    for name, k, n in zip("xwb", g_k, g_n):
        assert k.shape == n.shape and k.dtype == n.dtype == dtype, name
        np.testing.assert_allclose(
            _f32(k), _f32(n), rtol=tol, err_msg=name,
            atol=tol * float(jnp.abs(_f32(n)).max()))
    # and it is not the silu form
    assert not np.allclose(_f32(y_k), _f32(numpy_form(*args, dtype)),
                           atol=1e-2)


def test_an_unknown_activation_is_refused():
    with pytest.raises(ValueError, match="'silu' or None"):
        cc.causal_conv_act(*_inputs(128, False, F32, B=1, T=16),
                           activation="relu")


@pytest.mark.parametrize("K", [1, 2, 8])
def test_other_tap_counts(monkeypatch, K):
    """One tap (no halo at all), two, and eight: the whole of the eight rows
    the kernels keep before a tile, and seven after it in the backward."""
    C = 200
    _tiles_of(monkeypatch, 16)
    args = _inputs(C, True, F32, K=K)
    y_k, g_k = _grads(kernels, args, F32)
    y_n, g_n = _grads(numpy_form, args, F32)
    np.testing.assert_allclose(y_k, y_n, atol=2e-6, rtol=2e-6)
    for name, k, n in zip("xwb", g_k, g_n):
        np.testing.assert_allclose(k, n, rtol=2e-6, err_msg=name,
                                   atol=2e-6 * float(jnp.abs(n).max()))


def test_the_result_takes_x_dtype_unless_told():
    x, w, b = _inputs(128, True, BF, B=1, T=16)
    assert kernels(x, w, b).dtype == BF
    assert kernels(x, w, b, F32).dtype == F32
    assert cc.causal_conv_silu(x, w, b).dtype == BF       # the jax.numpy form
    assert cc.causal_conv_silu(x, w, b, F32).dtype == F32


def test_both_lowerings_give_the_parts_asked_for():
    x, w, b = _inputs(640, True, F32, B=1, T=16)
    want = jnp.split(numpy_form(x, w, b), (384, 512), axis=-1)
    for got in (kernels(x, w, b, splits=(384, 512)),
                cc.causal_conv_silu(x, w, b, splits=(384, 512))):
        assert [p.shape[-1] for p in got] == [384, 128, 128]
        for g, n in zip(got, want):
            np.testing.assert_allclose(g, n, atol=2e-6, rtol=2e-6)


# ---- the picker -------------------------------------------------------------

CELL = dict(T=4096, C=4352, K=4, dtype=BF, out_dtype=BF,
            splits=(4096, 4224))
PICKS = {
    "the-granite-cell": ({}, "pallas", ""),
    "one-part": (dict(splits=()), "pallas", ""),
    "parts-inside-a-lane-tile": (dict(splits=(4096, 4160)), "xla",
                                 "parts at [4096, 4160]"),
    "olmo-q-and-k": (dict(C=1440, out_dtype=F32, splits=()), "pallas", ""),
    "olmo-v": (dict(C=2880, splits=()), "pallas", ""),
    "eight-taps": (dict(K=8), "pallas", ""),
    "a-short-row": (dict(T=16), "pallas", ""),
    "float32": (dict(dtype=F32, out_dtype=F32), "xla", "float32 rows"),
    "a-float16-result": (dict(out_dtype=jnp.float16), "xla",
                         "float16 result"),
    "an-odd-T": (dict(T=4090), "xla", "T of 4090"),
    "nine-taps": (dict(K=9), "xla", "9 taps"),
    "rows-too-wide-for-a-tile": (dict(C=1 << 20), "xla", "do not fit"),
}


@pytest.mark.parametrize("case", sorted(PICKS))
def test_the_picker_answers_by_shape_and_dtype(case):
    over, want, why = PICKS[case]
    took, said = cc.conv_lowering(**{**CELL, **over}, tpu=True)
    assert took == want
    assert (why in said) if why else said == ""


def test_the_picker_gives_the_jax_numpy_form_off_the_chip():
    assert cc.conv_lowering(**CELL)[0] == "xla"           # this is a CPU
    assert cc.conv_lowering(**CELL, tpu=False) == ("xla",
                                                   "not a TPU backend")


def test_the_test_handle_refuses_shapes_the_kernels_do_not_take():
    x, w, b = _inputs(128, True, F32, T=24)
    with pytest.raises(ValueError, match="T of 24"):
        kernels(x, w, b)
    x, w, b = _inputs(128, True, F32, K=9)
    with pytest.raises(ValueError, match="9 taps"):
        kernels(x, w, b)


def test_a_tile_is_what_fits_the_kernels_vmem():
    """By the blocks a kernel pipelines and its float32 scratch: the cells'
    backward holds half the forward's rows, and every kernel stays under the
    16 MiB XLA leaves a kernel whose operands it has fused."""
    fwd, bwd = cc._row_bytes(BF, BF, False), cc._row_bytes(BF, BF, True)
    assert (cc._tile_rows(4096, 4352, fwd), cc._tile_rows(4096, 4352, bwd)) \
        == (128, 64)
    assert cc._tile_rows(4096, 1440, cc._row_bytes(BF, F32, True)) == 256
    for C, rb in ((4352, fwd), (4352, bwd), (2880, bwd)):
        assert cc._tile_rows(4096, C, rb) * cc._lanes(C) * rb <= 10 << 20
    assert cc._tile_rows(4090 * 16, 128, bwd) == 32       # a divisor of T


def test_convolutions_are_counted_by_lowering_when_traced():
    args = _inputs(128, True, F32, B=1, T=16)

    def took(fn):
        before = lowerings.snapshot()
        jax.make_jaxpr(fn)(*args)
        return lowerings.since(before)["conv"]

    assert took(cc.causal_conv_silu) == {"xla": 1}
    assert took(kernels) == {"pallas": 1}
    # a convolution and the kernels' own backward; the jax.numpy form's is
    # autodiff's
    assert took(jax.grad(lambda *a: kernels(*a).sum())) \
        == {"pallas": 2}
    assert took(jax.grad(lambda *a: cc.causal_conv_silu(*a).sum())) \
        == {"xla": 1}
