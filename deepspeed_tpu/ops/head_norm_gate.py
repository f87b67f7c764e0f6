"""A mixer's output norm and head gate on rows whose heads lie side by side
in lanes, as one differentiable op.

``y[b, t, h] = rmsnorm(o[b, t, h]; scale) sigmoid(z[b, t, h])``: the RMS norm
over a head's ``dv`` values with one ``scale`` [dv] for every head, then the
head's scalar gate (Kimi Delta Attention's output stage, ``models/kda.py``);
the statistics, the scale and the sigmoid in float32, one rounding to ``o``'s
dtype.

Why the layout is the op's business. The rule's kernels (``ops/kda_rule.py``)
write ``o`` as ``[B, T, H dv]``: a row's heads side by side in lanes, a head of
128 values exactly one lane tile. ``[B, T, H, 128]`` is the same numbers but
on a TPU other bytes: its last two axes are what is tiled, so 16 heads become
the sublanes of a tile and the rows an axis outside it, and a program that
norms ``o`` as ``[B, T, H, dv]`` between two neighbours that hold ``[B, T,
H dv]`` pays a copy each way, forward and backward, plus the gate's ``[T, H]
-> [T, H, dv]`` broadcast written out in front of such a copy. A norm over a
head is a reduction over one lane tile of a row: it needs no array tiled over
the heads at all. So ``o``, ``y`` and their cotangents stay ``[B, T, H dv]``
here, and ``z``'s column ``h`` is broadcast over a head's lanes in registers.

One algorithm, two lowerings (:func:`gate_lowering` picks by what the call
can see: backend, dtype, widths):

* ``"xla"``: :func:`head_norm_gate_xla`, the ``jax.numpy`` lines on ``[B, T,
  H, dv]`` in float32 and autodiff's backward. What a CPU and a float32
  program run, what heads that are not whole lane tiles run, and the unit
  tests' oracle.
* ``"pallas"``: two Mosaic kernels behind a ``jax.custom_vjp``
  (:func:`gate_fwd`, :func:`gate_bwd`, each a ``jax.jit`` of its own) whose
  grid step is a tile of rows over all heads. The forward reads ``o`` and
  ``z`` and writes ``y``. The backward keeps no residual beside the op's
  inputs: it reads ``o``, ``z``, ``scale`` and ``dy``, rebuilds the
  statistics, and writes ``do`` in ``o``'s dtype, ``dz`` [B, T, H] (float32:
  a head's lane sum times ``s (1 - s)``) and ``dscale`` summed in float32
  scratch over every tile, written once. Nothing ``[T, H dv]`` in float32
  goes to HBM.

The ops traced are counted by lowering (``ops/lowerings.py``, site
``kda_gate``) for the step-program table: one for a call, one more for the
kernels' own backward.
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
from deepspeed_tpu.ops.causal_conv import _eight, _sigmoid

F32 = jnp.float32
#: a head's width comes in whole lane tiles
_LANES = 128
# a tile's rows are the most of these that divide ``T`` and whose blocks
# (each pipelined twice) stay under _TILE_BYTES, well inside Mosaic's default
# 16 MiB of scoped VMEM. The arithmetic goes over a whole tile a head: on a
# v5e at [1, 8192, 2048] the backward reads 0.148 ms at 256 rows a pass,
# 0.196 at 128, 0.274 at 64 and 0.457 at 32 (the forward 0.085-0.091 at all)
_TILE_ROWS = (256, 128, 64, 32, 16)
_TILE_BYTES = 8 * 1024 * 1024


def head_norm_gate_xla(o: jax.Array, z: jax.Array, scale: jax.Array,
                       eps: float) -> jax.Array:
    """:func:`head_norm_gate` as ``jax.numpy`` lines on ``[B, T, H, dv]``."""
    B, T, H = z.shape
    x = o.reshape(B, T, H, -1).astype(F32)
    n = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    y = n * scale.astype(F32) * jax.nn.sigmoid(z.astype(F32))[..., None]
    return y.astype(o.dtype).reshape(o.shape)


# ---------------------------------------------------------------------------
# which lowering: from the call's own facts
# ---------------------------------------------------------------------------

def _tile_rows(T: int, width: int, itemsize: int) -> Optional[int]:
    """Rows of a grid step's tile: the backward's three blocks ``width``
    wide, pipelined twice each, within the budget."""
    return next((r for r in _TILE_ROWS if T % r == 0
                 and 6 * r * width * itemsize <= _TILE_BYTES), None)


def _shapes_taken(T: int, H: int, dv: int, dtype) -> str:
    """Why the kernels do not take these shapes; "" where they do."""
    if dv % _LANES:
        return f"heads of {dv} values, not whole lane tiles of {_LANES}"
    if T % _TILE_ROWS[-1]:
        return f"a T of {T} is not whole row tiles of {_TILE_ROWS[-1]}"
    if _tile_rows(T, H * dv, jnp.dtype(dtype).itemsize) is None:
        return f"{_TILE_ROWS[-1]} rows of {H * dv} values do not fit a tile"
    return ""


def gate_lowering(T: int, H: int, dv: int, dtype, *,
                  tpu: Optional[bool] = None) -> Tuple[str, str]:
    """``("pallas" | "xla", why)`` for one norm and gate over ``T`` rows of
    ``H`` heads ``dv`` wide: the kernels where they were measured (a TPU,
    bf16 rows, heads of whole lane tiles, a ``T`` of whole row tiles), the
    ``jax.numpy`` lines everywhere else."""
    if tpu is None:
        tpu = _on_tpu()
    if not tpu:
        return "xla", "not a TPU backend"
    if jnp.dtype(dtype) != jnp.bfloat16:
        return "xla", f"{jnp.dtype(dtype).name} rows (the kernels: bf16)"
    why = _shapes_taken(T, H, dv, dtype)
    return ("xla", why) if why else ("pallas", "")


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _lanes_sum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _gates(z_ref):
    """Every head's gate ``sigmoid(z)`` [rows, H] float32, and each lane's
    head."""
    s = _sigmoid(z_ref[...].astype(F32))
    return s, jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)


def _column(a, lane, h):
    """Column ``h`` of ``a`` [rows, H] as [rows, 1]: what a head's lanes are
    multiplied by (broadcast in registers, never written out)."""
    return _lanes_sum(jnp.where(lane == h, a, 0.0))


def _head(ref, h, dv):
    """Head ``h``'s values of a tile, float32."""
    return ref[:, h * dv:(h + 1) * dv].astype(F32)


def _inv_rms(x, eps):
    """Each row's ``rsqrt(mean(x^2) + eps)`` [rows, 1]."""
    return jax.lax.rsqrt(_lanes_sum(x * x) * (1.0 / x.shape[1]) + eps)


def _fwd_kernel(o_ref, z_ref, scale_ref, y_ref, *, dv: int, eps: float):
    """One tile of rows, a head after the other."""
    scale = scale_ref[...]
    s, lane = _gates(z_ref)
    for h in range(z_ref.shape[1]):
        x = _head(o_ref, h, dv)
        y_ref[:, h * dv:(h + 1) * dv] = (
            x * _inv_rms(x, eps) * scale * _column(s, lane, h)
        ).astype(y_ref.dtype)


def _bwd_kernel(o_ref, z_ref, scale_ref, dy_ref, do_ref, dz_ref, ds_ref,
                acc_ref, *, dv: int, eps: float):
    """One tile of rows, a head after the other. With ``n = x inv`` and ``w =
    dy scale``: ``do = inv s (w - n mean(w n))``, ``dz = sum(w n) s (1 -
    s)``, ``dscale = sum over rows and heads of dy n s`` (scratch: its sums,
    eight rows a lane, float32)."""
    first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)
    last = (pl.program_id(0) == pl.num_programs(0) - 1) \
        & (pl.program_id(1) == pl.num_programs(1) - 1)

    @pl.when(first)
    def _zero():
        acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

    scale = scale_ref[...]
    s, lane = _gates(z_ref)
    dz, sums = jnp.zeros(s.shape, F32), 0.0
    for h in range(z_ref.shape[1]):
        x, dy = _head(o_ref, h, dv), _head(dy_ref, h, dv)
        inv, gate = _inv_rms(x, eps), _column(s, lane, h)
        xn, w = x * inv, dy * scale
        wn = _lanes_sum(w * xn)                                 # [rows, 1]
        do_ref[:, h * dv:(h + 1) * dv] = (
            (inv * gate) * (w - xn * (wn * (1.0 / dv)))).astype(do_ref.dtype)
        dz = jnp.where(lane == h, wn, dz)
        sums = sums + _eight(dy * xn * gate)
    dz_ref[...] = dz * s * (1.0 - s)
    acc_ref[...] += sums

    @pl.when(last)
    def _write():
        ds_ref[...] = jnp.sum(acc_ref[...], axis=0, keepdims=True)


def _plan(o, z):
    """A call's grid (sequence, tile of rows), its block specs (a tile of
    the values' arrays, of the gates', the scale's row) and a head's
    width."""
    B, T, W = o.shape
    H = z.shape[-1]
    rows = _tile_rows(T, W, o.dtype.itemsize)
    wide = pl.BlockSpec((None, rows, W), lambda b, t: (b, t, 0))
    gates = pl.BlockSpec((None, rows, H), lambda b, t: (b, t, 0))
    row = pl.BlockSpec((1, W // H), lambda b, t: (0, 0))
    return (B, T // rows), wide, gates, row, W // H


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def gate_fwd(o, z, scale, *, eps: float, interpret: bool = False):
    """``y`` [B, T, H dv] in ``o``'s dtype."""
    grid, wide, gates, row, dv = _plan(o, z)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, dv=dv, eps=eps),
        grid=grid,
        in_specs=[wide, gates, row],
        out_specs=wide,
        out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(o, z, scale.astype(F32).reshape(1, dv))


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def gate_bwd(o, z, scale, dy, *, eps: float, interpret: bool = False):
    """The cotangents of :func:`gate_fwd`'s ``o``, ``z`` and ``scale`` from
    ``dy``."""
    grid, wide, gates, row, dv = _plan(o, z)
    do, dz, ds = pl.pallas_call(
        functools.partial(_bwd_kernel, dv=dv, eps=eps),
        grid=grid,
        in_specs=[wide, gates, row, wide],
        out_specs=[wide, gates, row],
        out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype),
                   jax.ShapeDtypeStruct(z.shape, F32),
                   jax.ShapeDtypeStruct((1, dv), F32)],
        scratch_shapes=[pltpu.VMEM((8, dv), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(o, z, scale.astype(F32).reshape(1, dv), dy)
    return do, dz.astype(z.dtype), ds.reshape(dv).astype(scale.dtype)


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gate_pallas(o, z, scale, eps, interpret):
    return gate_fwd(o, z, scale, eps=eps, interpret=interpret)


def _gate_pallas_fwd(o, z, scale, eps, interpret):
    return _gate_pallas(o, z, scale, eps, interpret), (o, z, scale)


def _gate_pallas_bwd(eps, interpret, res, dy):
    lowerings.count("kda_gate", "pallas")     # the kernels' own backward
    return gate_bwd(*res, dy, eps=eps, interpret=interpret)


_gate_pallas.defvjp(_gate_pallas_fwd, _gate_pallas_bwd)


def head_norm_gate(o: jax.Array, z: jax.Array, scale: jax.Array, eps: float,
                   interpret: Optional[bool] = None) -> jax.Array:
    """o [B, T, H dv] (a row's heads side by side), z [B, T, H] (a head's
    gate before its sigmoid), scale [dv] -> ``rmsnorm_head(o; scale)
    sigmoid(z)`` [B, T, H dv] in ``o``'s dtype. ``interpret`` is the
    kernels' test handle (None: ask :func:`gate_lowering`; True: the
    kernels, interpreted, in any float dtype, for shapes they take)."""
    _, T, H = z.shape
    dv = o.shape[-1] // H
    if interpret is None:
        lowering, _ = gate_lowering(T, H, dv, o.dtype)
    else:
        why = _shapes_taken(T, H, dv, o.dtype)
        if why:
            raise ValueError(f"the gate's kernels do not take {why}")
        lowering = "pallas"
    # a norm and gate by the lowering it took: one for a call, one more for
    # the kernels' backward (the ``jax.numpy`` lines' is autodiff's)
    lowerings.count("kda_gate", lowering)
    if lowering == "xla":
        return head_norm_gate_xla(o, z, scale, eps)
    return _gate_pallas(o, z, scale, float(eps), bool(interpret))
