"""Pallas grouped matmul over rows sorted by group — the experts' products.

Parity target: the reference's ``inference/v2/kernels/cutlass_ops/moe_gemm``
(one GEMM launch over all experts' token groups). ``jax.lax.ragged_dot`` has
that algebra and XLA lowers it on a v5e at about a fifth of what a plain
matmul of the same work reaches (PERF.md section 6, PR 33); here the product
and its two transposes are Mosaic kernels:

* ``gmm(xs [rows, C], w [E, C, O], group_sizes [E]) -> [rows, O]``: row ``r`` of
  group ``e`` times ``w[e]``; with ``transpose_w`` the weight is ``[E, O, C]``
  and is read transposed (the backward's product for the rows);
* ``tgmm(xs [rows, K], dys [rows, N], group_sizes) -> [E, K, N]``: each group's
  ``xs^T @ dys`` (the backward's product for the weights);
* :func:`grouped_matmul` ties them with a ``jax.custom_vjp`` whose residuals are
  the product's own operands, and takes ``lax.ragged_dot`` wherever
  :func:`grouped_lowering` says the kernel is not the measured case. Stacks that
  read the same rows (an FFN's gate and up projection) go in as a tuple: the
  rows' cotangent is then one ``gmm`` over both, summed in f32 in the kernel,
  not two and an add over the whole buffer.

How the kernels follow the groups. The rows come in tiles of ``tm``; a *visit*
is one (row tile, group) pair that shares rows, and the grid's row dimension
runs over visits, from scalar-prefetched tables (:func:`_visits`): time follows
the rows the groups hold, not the rows given. A tile a group boundary crosses is
visited once per group under a row mask. The grid is as long as the worst case
(``rows / tm + E - 1``); the visits past the live ones name the blocks already
resident, fetch nothing and compute nothing. Rows of a visited tile that no
group holds come back as zeros; tiles past the groups are never written (the
caller masks them, as it did ``ragged_dot``'s). bf16 operands, f32 accumulation,
the result in the operands' dtype: what ``ragged_dot`` gives.
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

# A weight tile of [2304, 896] bf16, a row tile and an f32 result, each
# pipelined tile twice, are about 15 MB: over Mosaic's default 16 MiB scope
# with anything else alive. The calls are given this limit (a v5e core has
# 128 MiB), and tiles are chosen so that what a call holds stays under the
# budget below it, the rest being Mosaic's own scratch for the matmul.
_VMEM_LIMIT = 48 * 1024 * 1024
_VMEM_BUDGET = 36 * 1024 * 1024
_ROW_TILES = (512, 256, 128)

# ---------------------------------------------------------------------------
# which lowering, which tiles: from the call's own facts
# ---------------------------------------------------------------------------

def _row_tile(rows: int, groups: int) -> Optional[int]:
    """The largest row tile that divides ``rows`` and still gives a group 16
    tiles of them on average, so that the tiles a group boundary crosses
    (worked once per group) stay a small share: at the Mellum2 cell, 4,096
    buffer rows a group, 256 measured 4 % faster than 512 and 3 % faster than
    128. Fewer rows take the smallest tile; under one of those a group (a
    decode step), None."""
    for tm in _ROW_TILES:
        if rows % tm == 0 and rows >= 16 * tm * groups:
            return tm
    tm = _ROW_TILES[-1]
    return tm if rows % tm == 0 and rows >= tm * groups else None


def _divisors(n: int):
    """Divisors of ``n`` that are whole lanes, largest first."""
    return [d for d in range(n, 0, -128) if n % d == 0]


def _widest_fit(A: int, B: int, held) -> Tuple[int, int]:
    """The first (a, b) over the whole-lane divisors of A, then of B, widest
    first, whose ``held(a, b)`` bytes are within the budget."""
    for a in _divisors(A):
        for b in _divisors(B):
            if held(a, b) <= _VMEM_BUDGET:
                return a, b
    return 128, 128


def _gmm_tiles(tm: int, C: int, O: int, itemsize: int, pairs: int = 1
               ) -> Tuple[int, int]:
    """(tc, to): the contraction whole where the weight tiles fit (no
    accumulator pass), then the widest output tile that fits; ``pairs`` row
    and weight tiles are held at once."""
    def held(tc, to):
        acc = tm * to * 4 * (1 if tc == C else 2)
        return 2 * itemsize * (pairs * (tm * tc + tc * to) + tm * to) + acc

    return _widest_fit(C, O, held)


def _tgmm_tiles(tm: int, K: int, N: int, itemsize: int) -> Tuple[int, int]:
    """(tk, tn) of the ``[K, N]`` result a group accumulates in VMEM."""
    return _widest_fit(K, N, lambda tk, tn: (
        2 * itemsize * (tm * tk + tm * tn + tk * tn) + 2 * tk * tn * 4))


def grouped_lowering(rows: int, C: int, O: int, groups: int, dtype, *,
                     dense: bool = True, tpu: Optional[bool] = None
                     ) -> Tuple[str, str]:
    """``("pallas" | "xla", why)`` for one grouped product ``[rows, C] x
    [groups, C, O]``. One algorithm, two lowerings: the kernels where they
    were measured (a TPU, dense bf16 stacks, whole-lane widths, rows enough
    for a tile a group), ``lax.ragged_dot`` everywhere else, exactly as
    before. ``dense`` is False for the int8 serving leaves (their
    dequantisation folds into ``ragged_dot``'s operand read; a
    ``pallas_call`` would force it through HBM)."""
    if tpu is None:
        tpu = _on_tpu()
    if not tpu:
        return "xla", "not a TPU backend"
    if not dense:
        return "xla", "int8 expert leaves dequantise inside ragged_dot"
    if jnp.dtype(dtype) != jnp.bfloat16:
        return "xla", f"{jnp.dtype(dtype).name} operands (the kernels: bf16)"
    if C % 128 or O % 128:
        return "xla", f"widths {C} x {O} are not whole lanes of 128"
    if _row_tile(rows, groups) is None:
        return "xla", f"{rows} rows are under a tile for each of {groups}"
    return "pallas", ""


# ---------------------------------------------------------------------------
# the visits: (row tile, group) pairs that share rows
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("rows", "tm", "empty"))
def _visits(group_sizes: jax.Array, *, rows: int, tm: int, empty: bool):
    """Scalar-prefetch tables of a call: ``offsets`` [E + 1], and for each of
    the ``rows / tm + E - 1`` grid steps its group and its row tile, and
    ``live`` [1], how many of them are real. A group's visits are the tiles
    its rows touch; with ``empty`` a group without rows gets one (``tgmm``
    owes it a block of zeros). Steps past ``live`` repeat the last live one,
    so their index maps move nothing."""
    E = group_sizes.shape[0]
    steps = rows // tm + E - 1
    # (lax-level ops: the tables are traced with every distinct kernel, and a
    # step program's tracing is set-up time)
    sizes = group_sizes.astype(jnp.int32)
    ends = jax.lax.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1,
                      1 if empty else 0)
    upto = jax.lax.cumsum(tiles)
    live = upto[E - 1:]
    v = jnp.minimum(jax.lax.iota(jnp.int32, steps), jnp.maximum(live - 1, 0))
    # the group of visit v: how many groups' visits end at or before it
    gid = jnp.minimum((v[:, None] >= upto[None, :]).sum(axis=1, dtype=jnp.int32),
                      E - 1)
    before = jnp.take(upto - tiles, gid)
    tid = jnp.minimum(jnp.take(first, gid) + v - before, rows // tm - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, gid, tid, live


def _in_group(offs_ref, g, t, tm):
    """[tm, 1] mask of the tile's rows that group ``g`` holds, and whether it
    holds them all."""
    lo, hi = offs_ref[g], offs_ref[g + 1]
    r = t * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return (r >= lo) & (r < hi), (lo <= t * tm) & (hi >= (t + 1) * tm)


# ---------------------------------------------------------------------------
# gmm: rows x their group's weight
# ---------------------------------------------------------------------------

def _gmm_kernel(offs_ref, gid_ref, tid_ref, live_ref, *refs, tm: int, nc: int,
                pairs: int, transpose_w: bool):
    """``refs``: ``pairs`` row tiles, their ``pairs`` weight tiles, the result
    tile and, where the contraction is tiled, its f32 accumulator."""
    x_refs, w_refs = refs[:pairs], refs[pairs:2 * pairs]
    o_ref, acc = refs[2 * pairs], refs[2 * pairs + 1:]
    v, c = pl.program_id(1), pl.program_id(2)

    @pl.when(v < live_ref[0])
    def _visit():
        g, t = gid_ref[v], tid_ref[v]
        dims = (((1,), (1 if transpose_w else 0,)), ((), ()))
        part = sum(jax.lax.dot_general(x_ref[...], w_ref[0], dims,
                                       preferred_element_type=jnp.float32)
                   for x_ref, w_ref in zip(x_refs, w_refs))

        def store(res):
            # a tile's first visit clears the rows no group holds; a later
            # one (the tile is crossed by a boundary) keeps the earlier
            # groups' rows
            inside, _ = _in_group(offs_ref, g, t, tm)
            fresh = (v == 0) | (tid_ref[jnp.maximum(v - 1, 0)] != t)
            res = res.astype(o_ref.dtype)

            @pl.when(fresh)
            def _clear():
                o_ref[...] = jnp.where(inside, res, 0)

            @pl.when(jnp.logical_not(fresh))
            def _keep():
                o_ref[...] = jnp.where(inside, res, o_ref[...])

        if nc == 1:
            store(part)
        else:
            acc_ref, = acc

            @pl.when(c == 0)
            def _first():
                acc_ref[...] = part

            @pl.when(c > 0)
            def _more():
                acc_ref[...] += part

            @pl.when(c == nc - 1)
            def _last():
                store(acc_ref[...])


# gmm and tgmm are jitted in their own right: a step program calls each with a
# few distinct shapes many times (layers x gate, up, down), and an inner jit is
# traced and lowered to its Mosaic module once per shape, not once per call
@functools.partial(jax.jit, static_argnames=("transpose_w", "interpret", "tm"))
def gmm(xs, w, group_sizes: jax.Array, *, transpose_w: bool = False,
        interpret: bool = False, tm: Optional[int] = None) -> jax.Array:
    """``xs`` [rows, C] sorted by group, ``w`` [E, C, O] (``transpose_w``:
    [E, O, C]) -> [rows, O]. ``xs`` and ``w`` may be equally long tuples of
    such: the products are summed in f32 before the result is rounded (the
    cotangent of rows that several stacks read). Rows of a visited tile
    outside every group are zero; tiles past the groups are not written."""
    xs, w = (xs, w) if isinstance(xs, tuple) else ((xs,), (w,))
    rows, C = xs[0].shape
    E = w[0].shape[0]
    O = w[0].shape[1] if transpose_w else w[0].shape[2]
    tm = tm or _row_tile(rows, E)
    tc, to = _gmm_tiles(tm, C, O, xs[0].dtype.itemsize, len(xs))
    nc = C // tc
    offsets, gid, tid, live = _visits(group_sizes, rows=rows, tm=tm, empty=False)

    def c_of(v, c, live):
        # a dead step repeats the last live step's last block
        return jnp.where(v < live[0], c, nc - 1)

    x_in = pl.BlockSpec(
        (tm, tc), lambda n, v, c, offs, gid, tid, live:
        (tid[v], c_of(v, c, live)))
    if transpose_w:
        w_in = pl.BlockSpec(
            (1, to, tc), lambda n, v, c, offs, gid, tid, live:
            (gid[v], n, c_of(v, c, live)))
    else:
        w_in = pl.BlockSpec(
            (1, tc, to), lambda n, v, c, offs, gid, tid, live:
            (gid[v], c_of(v, c, live), n))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, nc=nc, pairs=len(xs),
                          transpose_w=transpose_w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(O // to, gid.shape[0], nc),
            in_specs=[x_in] * len(xs) + [w_in] * len(xs),
            out_specs=pl.BlockSpec(
                (tm, to), lambda n, v, c, offs, gid, tid, live: (tid[v], n)),
            scratch_shapes=([] if nc == 1
                            else [pltpu.VMEM((tm, to), jnp.float32)])),
        out_shape=jax.ShapeDtypeStruct((rows, O), xs[0].dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(offsets, gid, tid, live, *xs, *w)


# ---------------------------------------------------------------------------
# tgmm: each group's xs^T @ dys
# ---------------------------------------------------------------------------

def _tgmm_kernel(offs_ref, gid_ref, tid_ref, live_ref, x_ref, dy_ref, o_ref,
                 acc_ref, *, tm: int, steps: int):
    v = pl.program_id(2)
    live = live_ref[0]

    @pl.when(v < live)
    def _visit():
        g, t = gid_ref[v], tid_ref[v]
        inside, whole = _in_group(offs_ref, g, t, tm)

        @pl.when((v == 0) | (gid_ref[jnp.maximum(v - 1, 0)] != g))
        def _first():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def add(x, dy):
            acc_ref[...] += jax.lax.dot_general(
                x, dy, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(whole)
        def _all_rows():
            add(x_ref[...], dy_ref[...])

        @pl.when(jnp.logical_not(whole))
        def _some_rows():
            # both operands: a row another group holds, or none, may be
            # anything (0 x NaN is NaN)
            add(jnp.where(inside, x_ref[...], 0),
                jnp.where(inside, dy_ref[...], 0))

        @pl.when((v == live - 1)
                 | (gid_ref[jnp.minimum(v + 1, steps - 1)] != g))
        def _last():
            o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "tm"))
def tgmm(xs: jax.Array, dys: jax.Array, group_sizes: jax.Array, *,
         interpret: bool = False, tm: Optional[int] = None) -> jax.Array:
    """``xs`` [rows, K], ``dys`` [rows, N], sorted by group -> [E, K, N]: each
    group's ``xs^T @ dys`` (zeros for a group without rows), accumulated in
    f32 over the group's row tiles, in the operands' dtype."""
    rows, K = xs.shape
    N = dys.shape[1]
    E = group_sizes.shape[0]
    tm = tm or _row_tile(rows, E)
    tk, tn = _tgmm_tiles(tm, K, N, xs.dtype.itemsize)
    offsets, gid, tid, live = _visits(group_sizes, rows=rows, tm=tm, empty=True)
    steps = gid.shape[0]
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, steps=steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(K // tk, N // tn, steps),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda i, j, v, offs, gid, tid, live:
                             (tid[v], i)),
                pl.BlockSpec((tm, tn), lambda i, j, v, offs, gid, tid, live:
                             (tid[v], j)),
            ],
            out_specs=pl.BlockSpec(
                (1, tk, tn), lambda i, j, v, offs, gid, tid, live:
                (gid[v], i, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((E, K, N), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(offsets, gid, tid, live, xs, dys)


# ---------------------------------------------------------------------------
# the product with its transposes
# ---------------------------------------------------------------------------

def _forward(xs, ws, group_sizes, interpret):
    return tuple(gmm(xs, w, group_sizes, interpret=interpret) for w in ws)


# ``xs`` times each stack of the tuple ``ws`` (the gate's and the up
# projection's read the same rows)
_products = jax.custom_vjp(_forward, nondiff_argnums=(3,))


def _products_fwd(xs, ws, group_sizes, interpret):
    # the residuals autodiff keeps of a ragged_dot: its operands
    return _forward(xs, ws, group_sizes, interpret), (xs, ws, group_sizes)


def _products_bwd(interpret, res, dys):
    xs, ws, group_sizes = res
    lowerings.count("moe_grouped", "pallas", 2 * len(ws))  # the transposes
    # the rows' cotangent over all the stacks in one call, summed in f32
    dxs = gmm(tuple(dys), tuple(ws), group_sizes, transpose_w=True,
              interpret=interpret)
    dws = tuple(tgmm(xs, dy, group_sizes, interpret=interpret) for dy in dys)
    return dxs, dws, None


_products.defvjp(_products_fwd, _products_bwd)


def grouped_matmul(xs: jax.Array, w, group_sizes: jax.Array, *,
                   lowering: str, interpret: bool = False):
    """``lax.ragged_dot(xs, w, group_sizes)`` by ``lowering``, which is what
    :func:`grouped_lowering` said of the call: ``"pallas"`` (the kernels,
    interpreted if ``interpret``: the CPU tests) or ``"xla"``. For a tuple of
    stacks ``w`` of one shape, the tuple of their products with the same
    rows (their backward then forms the rows' cotangent in one kernel
    instead of one each and an add)."""
    ws = w if isinstance(w, tuple) else (w,)
    # a grouped product by the lowering it took: one for a product, two more
    # for its transposes when the kernels' backward is traced
    lowerings.count("moe_grouped", lowering, len(ws))
    if lowering == "xla":
        outs = tuple(jax.lax.ragged_dot(xs, v, group_sizes) for v in ws)
    else:
        outs = _products(xs, ws, group_sizes, interpret)
    return outs if isinstance(w, tuple) else outs[0]
