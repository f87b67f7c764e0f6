"""The depthwise causal convolution in front of a state-space or delta mixer
or inside a short-convolution mixer, and the same convolution with its
activation as one differentiable op.

``y_t = act(b + sum_k w[k] x_{t-(K-1)+k})`` over the last ``K`` positions
of each channel, zeros before a sequence's start, ``act`` silu
(:func:`causal_conv_silu`) or nothing (:func:`causal_conv_act` with
``activation=None``); taps, bias and activation in float32, one rounding to
the output's dtype.

One algorithm, two lowerings (:func:`conv_lowering` picks by what the call
can see: backend, dtype, shapes):

* ``"xla"``: ``act(causal_conv(x, w, b)).astype(out_dtype)``:
  ``jnp.pad``, a cast to float32, ``K`` shifted multiply-adds and autodiff's
  backward (the pre-activation rebuilt, a second padded pass for ``dx``, a
  reduction over positions for each tap's weight). What a CPU and a float32
  program run, and the unit tests' yardstick.
* ``"pallas"``: two Mosaic kernels behind a ``jax.custom_vjp``
  (:func:`conv_fwd`, :func:`conv_bwd`, each a ``jax.jit`` of its own) whose
  grid step is a tile of positions over all channels. The tile and the rows
  before it (a second, 16-row block of ``x``; zeros at a sequence's start:
  the batch axis is a grid axis) are widened to float32 once into VMEM
  scratch, where the taps are reads at sublane offsets. The forward reads
  ``x`` and writes ``y``, as one array or as the parts the caller splits it
  into (a state-space mixer's ``x``, ``B`` and ``C``), whose cotangents
  the backward then takes as it gets them. The backward visits a
  sequence's tiles last first: it rebuilds the pre-activation, forms ``g =
  dy silu'`` (without an activation ``g = dy`` and nothing is rebuilt),
  keeps the first rows of ``g`` for the tile before (the
  anti-causal halo of ``dx = sum_k w[k] g_{t+(K-1)-k}``), and sums ``dw``
  and ``db`` in float32 scratch over every tile, written once. Nothing
  ``[T, C]`` in float32 goes to HBM.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.accelerator.real_accelerator import on_tpu as _on_tpu
from deepspeed_tpu.ops import lowerings

F32 = jnp.float32
# rows of float32 in front of a tile in the taps' scratch (one sublane tile:
# the tile itself then starts aligned), so the most taps the kernels take
_HALO = 8
# rows of the block of ``x`` that carries the halo, and what ``T`` comes in:
# bf16's sublane tile
_SUB = 16
# a tile's rows are the most of these whose blocks (each pipelined twice)
# and float32 scratch stay under _TILE_BYTES, which leaves Mosaic's default
# 16 MiB of scoped VMEM room for what it spills. XLA fuses a producer into
# an operand's read (``xBC`` as a slice of ``in_proj``'s product) only for a
# kernel that fits that default, whatever limit the kernel sets for itself:
# the operand then stays in VMEM and the kernel keeps 16 MiB.
_TILE_ROWS = (1024, 512, 256, 128, 64, 32, 16)
_TILE_BYTES = 10 * 1024 * 1024
# the piece of a tile the arithmetic goes over at a time, its chain of
# operations written out before the next piece's: 16 vector registers a
# float32 value. Measured on a v5e at both cells' calls: 64 x 256 runs the
# forward in 0.102 ms at 4352 channels and the backward in 0.220, 128 x 128
# in 0.109 and 0.236, 32 x 512 in 0.112 and 0.246, 256 x 256 in 0.109 and
# 0.219 there but a third slower at 1440 channels
_PIECE_ROWS, _PIECE_LANES = 64, 256

def causal_conv(x: jax.Array, w: jax.Array,
                b: Optional[jax.Array] = None) -> jax.Array:
    """Depthwise causal convolution over the last ``K`` positions: x
    [B, T, C], w [K, C], b [C] or None; ``y_t = b + sum_k w[k]
    x_{t-(K-1)+k}`` with zeros before the sequence's start. ``K`` shifted
    multiply-adds in float32, returned in float32, no activation: the
    ``"xla"`` lowering of :func:`causal_conv_silu` puts ``jax.nn.silu`` and
    the rounding after it."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(F32), ((0, 0), (K - 1, 0), (0, 0)))
    y = None if b is None else b.astype(F32)
    for k in range(K):
        tap = xp[:, k:k + T] * w[k].astype(F32)
        y = tap if y is None else y + tap
    return y


# ---------------------------------------------------------------------------
# which lowering: from the call's own facts
# ---------------------------------------------------------------------------

def _lanes(C: int) -> int:
    return -(-C // 128) * 128


def _row_bytes(dtype, out_dtype, backward: bool) -> int:
    """Bytes a channel and row a kernel holds: its pipelined blocks twice
    each (``x`` and ``y``; in the backward ``x``, ``dx`` and a ``dy`` in the
    result's dtype) and its float32 scratch (the tile; the tile and ``g``)."""
    x, y = jnp.dtype(dtype).itemsize, jnp.dtype(out_dtype).itemsize
    return 2 * (2 * x + y) + 8 if backward else 2 * (x + y) + 4


def _tile_rows(T: int, C: int, row_bytes: int) -> Optional[int]:
    """Rows of a grid step's tile for a kernel that holds ``row_bytes`` a
    channel and row; None where 16 rows of ``C`` channels are over the
    budget already."""
    return next((r for r in _TILE_ROWS if T % r == 0
                 and r * _lanes(C) * row_bytes <= _TILE_BYTES), None)


def _shapes_taken(T: int, C: int, K: int, dtype, out_dtype,
                  splits=()) -> str:
    """Why the kernels do not take these shapes; "" where they do."""
    if any(c % 128 for c in splits):
        return f"parts at {list(splits)}, not whole lane tiles"
    if not 1 <= K <= _HALO:
        return f"{K} taps (the kernels: at most {_HALO})"
    if T % _SUB:
        return f"a T of {T} is not whole sublane tiles of {_SUB}"
    if _tile_rows(T, C, _row_bytes(dtype, out_dtype, True)) is None:
        return f"{_SUB} rows of {C} channels do not fit a tile"
    return ""


def conv_lowering(T: int, C: int, K: int, dtype, out_dtype, splits=(), *,
                  tpu: Optional[bool] = None) -> Tuple[str, str]:
    """``("pallas" | "xla", why)`` for one convolution: the kernels where
    they were measured (a TPU, bf16 ``x``, a bf16 or float32 result, at
    most 8 taps, a ``T`` of whole sublane tiles, parts of whole lane
    tiles), the ``jax.numpy`` form everywhere else."""
    if tpu is None:
        tpu = _on_tpu()
    if not tpu:
        return "xla", "not a TPU backend"
    if jnp.dtype(dtype) != jnp.bfloat16:
        return "xla", f"{jnp.dtype(dtype).name} rows (the kernels: bf16)"
    if jnp.dtype(out_dtype) not in (jnp.bfloat16, jnp.float32):
        return "xla", f"a {jnp.dtype(out_dtype).name} result"
    why = _shapes_taken(T, C, K, dtype, out_dtype, splits)
    return ("xla", why) if why else ("pallas", "")


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _widths(C: int, splits) -> Tuple[int, ...]:
    """The channels of the parts ``splits`` cuts ``C`` into."""
    edges = (0,) + tuple(splits) + (C,)
    return tuple(hi - lo for lo, hi in zip(edges, edges[1:]))


def _pieces(rows: int, widths):
    """A tile as (row offset, rows, lane slice, part, the part's lane slice)
    pieces, none across two of the result's parts, a lane block's rows one
    after the other."""
    out, start = [], 0
    for p, width in enumerate(widths):
        for c in range(0, width, _PIECE_LANES):
            upto = min(c + _PIECE_LANES, width)
            out += [(r, min(_PIECE_ROWS, rows - r),
                     slice(start + c, start + upto), p, slice(c, upto))
                    for r in range(0, rows, _PIECE_ROWS)]
        start += width
    return out


def _stage(x_ref, halo_ref, xf_ref, at_start):
    """The tile and the rows before it, float32, into the taps' scratch."""
    before = halo_ref[...].astype(F32)[_SUB - _HALO:]
    xf_ref[:_HALO, :] = jnp.where(at_start, 0.0, before)
    xf_ref[_HALO:, :] = x_ref[...].astype(F32)


def _taps(xf_ref, r, n, lanes, K):
    """``x`` at the ``K`` taps of the tile's rows ``r .. r + n``."""
    return [xf_ref[_HALO - (K - 1) + k + r:_HALO - (K - 1) + k + r + n, lanes]
            for k in range(K)]


def _pre(taps, w, b):
    """The pre-activation in :func:`causal_conv`'s order: bias, then taps."""
    acc = b
    for k, tap in enumerate(taps):
        term = tap * w[k:k + 1]
        acc = term if acc is None else acc + term
    return acc


def _sigmoid(v):
    """``1 / (1 + exp(-v))`` as one transcendental and no division
    (``jax.nn.sigmoid`` in a kernel is both, a third of the forward kernel's
    time on a v5e); within float32's rounding of 1/2 of it (6e-8)."""
    return 0.5 * jnp.tanh(0.5 * v) + 0.5


def _fwd_kernel(x_ref, halo_ref, w_ref, *rest, K: int, bias: bool, widths,
                act: Optional[str] = "silu"):
    """One tile of positions. ``rest``: the bias (where ``bias``), the
    result's parts, then scratch: the tile in float32 behind its halo
    [_HALO + rows, C]."""
    b_ref = rest[0] if bias else None
    y_refs, xf_ref = rest[bias:-1], rest[-1]
    _stage(x_ref, halo_ref, xf_ref, pl.program_id(1) == 0)
    for r, n, lanes, p, cols in _pieces(x_ref.shape[0], widths):
        pre = _pre(_taps(xf_ref, r, n, lanes, K), w_ref[:, lanes],
                   b_ref[:, lanes] if bias else None)
        y_refs[p][r:r + n, cols] = (pre * _sigmoid(pre) if act else pre
                                    ).astype(y_refs[p].dtype)


def _eight(v):
    """[n, L] -> [8, L]: the sum of ``v``'s groups of eight rows (vector
    adds; the eight are added up once, when the sums are written)."""
    return jnp.sum(v.reshape(v.shape[0] // 8, 8, v.shape[1]), axis=0)


def _bwd_kernel(x_ref, halo_ref, w_ref, *rest, K: int, bias: bool, widths,
                act: Optional[str] = "silu"):
    """One tile of positions, a sequence's tiles last first. ``rest``: the
    bias (where ``bias``), ``dy``'s parts, then ``dx``, ``dw``, ``db``
    (where ``bias``), then scratch, all float32: the tile behind its halo,
    ``g`` with the first rows of the following tile's after it
    [rows + _HALO, C], and the sums for ``dw`` and ``db`` [(K + bias) 8, C]."""
    b_ref = rest[0] if bias else None
    dy_refs = rest[bias:bias + len(widths)]
    dx_ref, dw_ref = rest[bias + len(widths):bias + len(widths) + 2]
    db_ref = rest[-4] if bias else None
    xf_ref, g_ref, acc_ref = rest[-3:]
    rows = x_ref.shape[0]
    bi, t = pl.program_id(0), pl.program_id(1)
    last = pl.num_programs(1) - 1

    @pl.when((bi == 0) & (t == 0))
    def _zero():
        acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

    _stage(x_ref, halo_ref, xf_ref, t == last)
    # what the step before left at the top is the following tile's
    g_ref[rows:, :] = jnp.where(t == 0, 0.0, g_ref[:_HALO, :])
    # a lane block's pieces last first: a piece's dx reads g of the rows
    # after it
    sums = None
    for r, n, lanes, p, cols in reversed(_pieces(rows, widths)):
        taps = _taps(xf_ref, r, n, lanes, K)
        w = w_ref[:, lanes]
        g = dy_refs[p][r:r + n, cols].astype(F32)
        if act:
            pre = _pre(taps, w, b_ref[:, lanes] if bias else None)
            s = _sigmoid(pre)
            g = g * (s * (1.0 + pre * (1.0 - s)))
        g_ref[r:r + n, lanes] = g
        dx = None
        for k in range(K):
            term = g_ref[r + K - 1 - k:r + K - 1 - k + n, lanes] * w[k:k + 1]
            dx = term if dx is None else dx + term
        dx_ref[r:r + n, lanes] = dx.astype(dx_ref.dtype)
        here = [_eight(g * tap) for tap in taps] + [_eight(g)] * bias
        sums = here if sums is None else [a + h for a, h in zip(sums, here)]
        if r == 0:
            for k, v in enumerate(sums):
                acc_ref[8 * k:8 * k + 8, lanes] += v
            sums = None

    @pl.when((bi == pl.num_programs(0) - 1) & (t == last))
    def _write():
        for k in range(K):
            dw_ref[k:k + 1, :] = jnp.sum(acc_ref[8 * k:8 * k + 8, :], axis=0,
                                         keepdims=True)
        if bias:
            db_ref[...] = jnp.sum(acc_ref[8 * K:8 * K + 8, :], axis=0,
                                  keepdims=True)


def _specs(rows, C, K, nt, flip: bool):
    """Block specs over the grid (sequence, tile): a tile of an array a
    given number of channels wide, the 16 rows of ``x`` before a tile (the
    first tile's are masked in the kernel), the taps' weights, a channel's
    row; ``flip`` turns the tile axis around, for the backward."""
    def at(t):
        return nt - 1 - t if flip else t

    def tile(width):
        return pl.BlockSpec((None, rows, width), lambda b, t: (b, at(t), 0))

    halo = pl.BlockSpec(
        (None, _SUB, C),
        lambda b, t: (b, jnp.maximum(at(t) * (rows // _SUB) - 1, 0), 0))
    taps = pl.BlockSpec((K, C), lambda b, t: (0, 0))
    row = pl.BlockSpec((1, C), lambda b, t: (0, 0))
    return tile, halo, taps, row


def _operands(x, w, b):
    ops = [x, x, w.astype(F32)]
    if b is not None:
        ops.append(b.astype(F32).reshape(1, -1))
    return ops


@functools.partial(jax.jit, static_argnames=("out_dtype", "widths",
                                             "interpret", "act"))
def conv_fwd(x, w, b, *, out_dtype: str, widths: Tuple[int, ...],
             interpret: bool = False, act: Optional[str] = "silu"):
    """``act(causal_conv(x, w, b))`` in ``out_dtype`` as its parts
    [B, T, width], side by side the whole [B, T, C]."""
    B, T, C = x.shape
    K = w.shape[0]
    rows = _tile_rows(T, C, _row_bytes(x.dtype, out_dtype, False))
    tile, halo, taps, row = _specs(rows, C, K, T // rows, flip=False)
    bias = b is not None
    return pl.pallas_call(
        functools.partial(_fwd_kernel, K=K, bias=bias, widths=widths,
                          act=act),
        grid=(B, T // rows),
        in_specs=[tile(C), halo, taps] + [row] * bias,
        out_specs=[tile(width) for width in widths],
        out_shape=[jax.ShapeDtypeStruct((B, T, width), jnp.dtype(out_dtype))
                   for width in widths],
        scratch_shapes=[pltpu.VMEM((_HALO + rows, C), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            allow_input_fusion=[True] * (3 + bias)),
        interpret=interpret,
    )(*_operands(x, w, b))


@functools.partial(jax.jit, static_argnames=("interpret", "act"))
def conv_bwd(x, w, b, dy, *, interpret: bool = False,
             act: Optional[str] = "silu"):
    """The cotangents of :func:`conv_fwd`'s ``x``, ``w`` and ``b`` (None
    where there is no bias) from ``dy``, the cotangents of its parts."""
    B, T, C = x.shape
    K = w.shape[0]
    widths = tuple(part.shape[-1] for part in dy)
    rows = _tile_rows(T, C, _row_bytes(x.dtype, dy[0].dtype, True))
    tile, halo, taps, row = _specs(rows, C, K, T // rows, flip=True)
    bias = b is not None
    out = pl.pallas_call(
        functools.partial(_bwd_kernel, K=K, bias=bias, widths=widths,
                          act=act),
        grid=(B, T // rows),
        in_specs=[tile(C), halo, taps] + [row] * bias
        + [tile(width) for width in widths],
        out_specs=[tile(C), taps] + [row] * bias,
        out_shape=[jax.ShapeDtypeStruct((B, T, C), x.dtype),
                   jax.ShapeDtypeStruct((K, C), F32)]
        + [jax.ShapeDtypeStruct((1, C), F32)] * bias,
        scratch_shapes=[pltpu.VMEM((_HALO + rows, C), F32),
                        pltpu.VMEM((rows + _HALO, C), F32),
                        pltpu.VMEM((8 * (K + bias), C), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            allow_input_fusion=[True] * (3 + bias + len(widths))),
        interpret=interpret,
    )(*_operands(x, w, b), *dy)
    return (out[0], out[1].astype(w.dtype),
            out[2].reshape(-1).astype(b.dtype) if bias else None)


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _conv_pallas(x, w, b, out_dtype, widths, interpret, act):
    return tuple(conv_fwd(x, w, b, out_dtype=out_dtype, widths=widths,
                          interpret=interpret, act=act))


def _conv_pallas_fwd(x, w, b, out_dtype, widths, interpret, act):
    return _conv_pallas(x, w, b, out_dtype, widths, interpret, act), (x, w, b)


def _conv_pallas_bwd(out_dtype, widths, interpret, act, res, dy):
    lowerings.count("conv", "pallas")     # the kernels' own backward
    return conv_bwd(*res, tuple(dy), interpret=interpret, act=act)


_conv_pallas.defvjp(_conv_pallas_fwd, _conv_pallas_bwd)


def causal_conv_act(x: jax.Array, w: jax.Array,
                    b: Optional[jax.Array] = None, out_dtype=None,
                    splits: Tuple[int, ...] = (),
                    interpret: Optional[bool] = None, *,
                    activation: Optional[str]):
    """x [B, T, C], w [K, C], b [C] or None -> ``act(causal_conv(x, w,
    b))`` [B, T, C] rounded once, to ``out_dtype`` (``x``'s where None);
    ``activation`` is ``"silu"`` or None (the convolution as it is).
    With ``splits`` (channel indices, as ``jnp.split`` takes them) the
    result comes as its parts between them, a list: the kernels write each
    as an array of its own and take their cotangents the same way, so that
    nothing puts the parts side by side in either direction. ``interpret``
    is the kernels' test handle (None: ask :func:`conv_lowering`; True: the
    kernels, interpreted, in any float dtype, for shapes they take)."""
    if activation not in (None, "silu"):
        raise ValueError(f"activation={activation!r}: 'silu' or None")
    _, T, C = x.shape
    K, splits = w.shape[0], tuple(int(c) for c in splits)
    out_dtype = jnp.dtype(x.dtype if out_dtype is None else out_dtype)
    if interpret is None:
        lowering, _ = conv_lowering(T, C, K, x.dtype, out_dtype, splits)
    else:
        why = _shapes_taken(T, C, K, x.dtype, out_dtype, splits)
        if why:
            raise ValueError(f"the convolution's kernels do not take {why}")
        lowering = "pallas"
    # a convolution by the lowering it took: one for a call, one more for
    # the kernels' backward (the ``jax.numpy`` form's is autodiff's)
    lowerings.count("conv", lowering)
    if lowering == "xla":
        y = causal_conv(x, w, b)
        y = (jax.nn.silu(y) if activation else y).astype(out_dtype)
        return jnp.split(y, splits, axis=-1) if splits else y
    parts = _conv_pallas(x, w, b, out_dtype.name, _widths(C, splits),
                         bool(interpret), activation)
    return list(parts) if splits else parts[0]


#: the silu spelling, what a state-space and a delta mixer call
causal_conv_silu = functools.partial(causal_conv_act, activation="silu")
