"""Row kernels for the expert layer's dispatch and combine: each live row is
fetched once, and its weight or its sum is done in the same pass.

The buffer of local pairs (``moe/sharded_moe.py:grouped_moe_mlp_block``) has
``bound`` rows of which ``n_here`` carry a pair, and a token names at most
``k`` rows (``slot``; ``bound`` for a pair without a row). As ``jnp.take``s the
moves fetch every row they are given, 27-44 ns a row on a v5e whatever it
holds (PERF.md section 6, PR 36); here:

* :func:`rows_of_tokens` ``(xp, tok, n_here) -> [bound, D]``: row ``r`` is
  token ``tok[r]``'s; tiles past ``n_here`` fetch nothing and are not written.
  With a weight a row it is the combine's backward (``g[tok[r]] * w[r]`` in
  f32, rounded once), and with ``ys`` beside it, it returns each row's dot
  ``<g[tok[r]], ys[r]>`` in f32 too (the router weights' gradient);
* :func:`sum_of_rows` ``(ysp, slot, line, runs, weights) -> [S, D]``: each
  token's ``sum_j weights[t, j] * ys[slot[t, j]]`` in f32, j = 0..k-1, a
  slot of ``bound`` skipped (adding its zero is exact), rounded once: the
  combine, and without weights the dispatch's backward.

How a row is fetched. The chip's compiler refuses a one-row slice of a tiled
``[n, D]`` array in HBM ("must be aligned to tiling (8)"), in bf16 and in
float32 alike; what it copies alone is an element of the leading dimension of
``[n, 1, w]`` 32-bit words, which XLA lays out row by row (``T(1,128)``). So
the fetched operand is packed first (:func:`pack_rows`, one pass): word
``c`` of a row holds column ``c`` in its low half and column ``c + w`` in its
high half, both 128-lane aligned, and a row is one contiguous copy HBM ->
VMEM, a tile's copies all in flight at once and the next tile's behind them.
In VMEM the rows lie one after another; a strided load (every ``w / 128``-th
128-word line) brings one column chunk of a whole tile into dense registers,
where the halves are shifted apart, weighted and summed in f32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the sums hold two tiles of k rows a token (18.9 MB at k 8, 256 tokens, 1152
# words a row): over Mosaic's default 16 MiB scope (a v5e core has 128 MiB)
_VMEM_LIMIT = 64 * 1024 * 1024
_TILES = (256, 128, 64, 32, 16, 8)
_HIGH = 0xFFFF0000
_BLOCK = 16


def _tile(n: int) -> int:
    """The largest tile that divides ``n`` (``n`` a multiple of 8)."""
    return next(t for t in _TILES if n % t == 0)


def packed_width(D: int) -> int:
    """Words a packed row of ``D`` bf16 columns takes: half of them, in whole
    lanes of 128."""
    return -(-(D // 2) // 128) * 128


def _loop(n: int, body, init, step: int = 8):
    """``fori_loop`` over ``n`` (a multiple of ``step``) with ``step``
    iterations written out a trip (Mosaic unrolls a loop whole or not at
    all)."""
    def trip(q, carry):
        for d in range(step):
            carry = body(q * step + d, carry)
        return carry
    return jax.lax.fori_loop(0, n // step, trip, init)


def _pack_kernel(n_ref, a_ref, o_ref, *, tr: int, D: int, interpret: bool):
    w = o_ref.shape[-1]
    lines = w // 128

    @pl.when(pl.program_id(0) * tr < n_ref[0])
    def _live():
        def chunk(c, both, carry):
            lo = a_ref[:, pl.ds(c * 128, 128)]
            word = pltpu.bitcast(lo.astype(jnp.float32), jnp.uint32) >> 16
            if both:
                hi = a_ref[:, pl.ds(w + c * 128, 128)]
                word = word | (pltpu.bitcast(hi.astype(jnp.float32),
                                             jnp.uint32) & jnp.uint32(_HIGH))
            if not interpret:
                o_ref.reshape(tr * lines, 128)[
                    pl.ds(c, tr, stride=lines), :] = word
            else:       # the interpreter cannot store through a reshaped ref
                o_ref[:, 0, pl.ds(c * 128, 128)] = word
            return carry
        _chunks(w, D, chunk)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pack_rows(a: jax.Array, n: Optional[jax.Array] = None, *,
              interpret: bool = False) -> jax.Array:
    """``a`` [rows, D] bf16 -> [rows, 1, w] uint32, ``w = packed_width(D)``:
    word ``c`` is column ``c`` (low half) and column ``c + w`` (high half;
    zero past the last column), the form the kernels fetch rows from. XLA
    lays such an array out row by row, and writes it from the tiled ``a`` at
    a small share of the memory's rate; this kernel stores each column chunk
    of a tile with a stride of a row. Tiles of rows past ``n`` (all rows
    if None) are neither read nor written."""
    rows, D = a.shape
    w = packed_width(D)
    tr = _tile(rows)
    n = jnp.full((1,), rows, jnp.int32) if n is None \
        else jnp.reshape(n, (1,)).astype(jnp.int32)
    tile = lambda i, n: jnp.minimum(
        i, jnp.maximum((n[0] + tr - 1) // tr - 1, 0))
    return pl.pallas_call(
        functools.partial(_pack_kernel, tr=tr, D=D, interpret=interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows // tr,),
            in_specs=[pl.BlockSpec((tr, D), lambda i, n: (tile(i, n), 0))],
            out_specs=pl.BlockSpec((tr, 1, w),
                                   lambda i, n: (tile(i, n), 0, 0))),
        out_shape=jax.ShapeDtypeStruct((rows, 1, w), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(n, a.astype(jnp.bfloat16))


def _lane(col):
    """A first column, a multiple of 128 whether it is traced or not."""
    return col if isinstance(col, int) else pl.multiple_of(col, 128)


def _halves(u, c, w: int, both: bool):
    """(first column, f32 values) of the 128-column chunks that chunk ``c``
    of packed words ``u`` [T, 128] holds: its low halves, and with ``both``
    its high halves."""
    out = [(_lane(c * 128), pltpu.bitcast(u << 16, jnp.float32))]
    if both:
        out.append((_lane(w + c * 128),
                    pltpu.bitcast(u & jnp.uint32(_HIGH), jnp.float32)))
    return out


def _chunks(w: int, D: int, body, carry=None, unroll: bool = True):
    """``carry = body(c, both, carry)`` over a packed row's chunks of 128
    words: those whose two halves both hold columns, then the last one alone
    where the columns are an odd number of chunks. Written out (``unroll``)
    where a chunk is a few instructions a row block, the rows' kernels: a
    loop there measured 0.17-0.32 ms a call slower at the Mellum2 cell's
    shape. A loop where a chunk is the k terms of a sum: written out, each
    sum kernel took 0.4-0.6 s more of every run's set-up to trace and lower,
    for 0.1 ms a call."""
    full = (D - w) // 128
    if unroll:
        for c in range(full):
            carry = body(c, True, carry)
    else:
        carry = jax.lax.fori_loop(
            0, full, lambda c, carry: body(c, True, carry), carry)
    if full < w // 128:
        carry = body(full, False, carry)
    return carry


def _chunk(buf, b, first, c, rows: int):
    """Chunk ``c`` of ``rows`` consecutive packed rows from row ``first`` of
    half ``b`` of ``buf`` [2, n, 1, w]: [rows, 128] words, by a load with a
    stride of a row over the lines of 128 words."""
    _, n, _, w = buf.shape
    lines = w // 128
    return buf.reshape(2 * n * lines, 128)[
        pl.ds((b * n + first) * lines + c, rows, stride=lines), :]


def _blocks(tile: int, body):
    """``body(first row)`` over the tile in blocks of ``_BLOCK`` rows: what a
    block holds at once (its sums, weights and masks) stays in registers."""
    sb = min(_BLOCK, tile)

    def block(q, carry):
        body(pl.multiple_of(q * sb, sb), sb)
        return carry
    jax.lax.fori_loop(0, tile // sb, block, 0)


# ---------------------------------------------------------------------------
# rows of tokens: out[r] = x[tok[r]] (* w[r]), and <x[tok[r]], ys[r]>
# ---------------------------------------------------------------------------

def _rows_kernel(tok_ref, n_ref, xp_hbm, *refs, tr: int, D: int,
                 weighted: bool, dotted: bool):
    refs = list(refs)
    w_ref = refs.pop(0) if weighted else None
    ys_ref = refs.pop(0) if dotted else None
    o_ref = refs.pop(0)
    dot_ref = refs.pop(0) if dotted else None
    buf, sem = refs
    w = buf.shape[-1]
    i = pl.program_id(0)
    tiles = (n_ref[0] + tr - 1) // tr

    def copies(tile, b, start: bool):
        def body(r, carry):
            src = xp_hbm.at[tok_ref[tile * tr + r] if start else 0]
            cp = pltpu.make_async_copy(src, buf.at[b, r], sem.at[b])
            cp.start() if start else cp.wait()
            return carry
        _loop(tr, body, 0)

    @pl.when((i == 0) & (tiles > 0))
    def _first():
        copies(0, 0, True)

    @pl.when(i + 1 < tiles)
    def _next():
        copies(i + 1, (i + 1) % 2, True)

    @pl.when(i < tiles)
    def _work():
        b = i % 2
        copies(i, b, False)

        def block(r0, sb):
            here = pl.ds(r0, sb)
            wt = None if w_ref is None \
                else jnp.broadcast_to(w_ref[here, :], (sb, 128))

            def chunk(c, both, dot):
                for col, val in _halves(_chunk(buf, b, r0, c, sb), c, w,
                                        both):
                    cols = pl.ds(col, 128)
                    if dotted:
                        dot = dot + val * ys_ref[here, cols].astype(
                            jnp.float32)
                    if wt is not None:
                        val = val * wt
                    o_ref[here, cols] = val.astype(o_ref.dtype)
                return dot
            dot = _chunks(w, D, chunk, jnp.zeros((sb, 128), jnp.float32)
                          if dotted else None)
            if dotted:
                dot_ref[here, :] = dot.sum(axis=1, keepdims=True)
        _blocks(tr, block)


@functools.partial(jax.jit, static_argnames=("D", "dtype", "interpret"))
def rows_of_tokens(xp: jax.Array, tok: jax.Array, n_here: jax.Array, *,
                   D: int, dtype=jnp.bfloat16,
                   weight: Optional[jax.Array] = None,
                   ys: Optional[jax.Array] = None, interpret: bool = False):
    """``xp`` [S, 1, w] packed rows of ``x`` [S, D], ``tok`` [bound] the token
    of each buffer row, ``n_here`` the rows that carry a pair -> [bound, D]
    ``x[tok]`` in ``dtype``. ``weight`` [bound] f32: ``x[tok] * weight`` in
    f32, rounded once. ``ys`` [bound, D]: also [bound] f32, each row's
    ``<x[tok[r]], ys[r]>`` (the row unweighted). Tiles of rows past
    ``n_here`` are not written, in either result."""
    bound = tok.shape[0]
    w = xp.shape[-1]
    tr = _tile(bound)
    last = lambda n: jnp.maximum((n[0] + tr - 1) // tr - 1, 0)
    tile = lambda i, tok, n: (jnp.minimum(i, last(n)), 0)
    operands, in_specs = [xp], [pl.BlockSpec(memory_space=pl.ANY)]
    if weight is not None:
        operands.append(weight.astype(jnp.float32).reshape(bound, 1))
        in_specs.append(pl.BlockSpec((tr, 1), tile))
    if ys is not None:
        operands.append(ys)
        in_specs.append(pl.BlockSpec((tr, D), tile))
    out_shape = [jax.ShapeDtypeStruct((bound, D), dtype)]
    out_specs = [pl.BlockSpec((tr, D), tile)]
    if ys is not None:
        out_shape.append(jax.ShapeDtypeStruct((bound, 1), jnp.float32))
        out_specs.append(pl.BlockSpec((tr, 1), tile))
    outs = pl.pallas_call(
        functools.partial(_rows_kernel, tr=tr, D=D,
                          weighted=weight is not None, dotted=ys is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(bound // tr,),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((2, tr, 1, w), jnp.uint32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(tok.astype(jnp.int32), jnp.reshape(n_here, (1,)).astype(jnp.int32),
      *operands)
    return outs[0] if ys is None else (outs[0], outs[1].reshape(bound))


# ---------------------------------------------------------------------------
# sum of rows: out[t] = sum_j w[t, j] * ys[slot[t, j]]
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("S", "k"))
def token_tile_runs(rows: jax.Array, group_sizes: jax.Array, *, S: int,
                    k: int):
    """What :func:`sum_of_rows` walks instead of every slot. The buffer's
    rows are sorted by group and, within one, by token (the sort is stable),
    so the rows whose token lies in one tile of tokens are one run a group.
    ``rows`` [bound] the pair ``t * k + j`` of each row, ``group_sizes`` [G]
    -> ``line`` [bound]: where a row goes in its tile's half of the kernel's
    buffer, ``j * tile + t % tile``; ``runs`` [G * tiles + 1]: the rows of
    group ``g`` for tile ``i`` are ``runs[g * tiles + i]`` up to ``runs[g *
    tiles + i + 1]``."""
    tt = _tile(S)
    tiles = S // tt
    G = group_sizes.shape[0]
    rows = rows.astype(jnp.int32)
    tok = rows // k
    line = (rows % k) * tt + tok % tt
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    r = jnp.arange(rows.shape[0], dtype=jnp.int32)
    # the rows of each (group, tile), counted as a product of one-hots (exact
    # in f32 under 2**24 rows), the rows along the lanes of both; a row past
    # the groups is in none
    in_group = (r[None, :] < ends[:, None]) \
        & (r[None, :] >= (ends - group_sizes)[:, None])
    in_tile = (tok // tt)[None, :] \
        == jnp.arange(tiles, dtype=jnp.int32)[:, None]
    counts = jnp.einsum("gr,tr->gt", in_group.astype(jnp.bfloat16),
                        in_tile.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    runs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(counts.reshape(-1).astype(jnp.int32))])
    return line, runs


def _sum_kernel(line_ref, runs_ref, ysp_hbm, slots_ref, *refs, tt: int,
                k: int, D: int, bound: int, groups: int, weighted: bool):
    refs = list(refs)
    w_ref = refs.pop(0) if weighted else None
    o_ref, buf, sem, live = refs
    w = buf.shape[-1]
    i = pl.program_id(0)
    tiles = pl.num_programs(0)

    def start(tile, b):
        """One copy for each row of the tile's runs, into the line of its
        pair in half ``b``; how many is kept for the wait."""
        def group(g, n):
            lo, hi = runs_ref[g * tiles + tile], runs_ref[g * tiles + tile + 1]

            def row(r, carry):
                pltpu.make_async_copy(ysp_hbm.at[r], buf.at[b, line_ref[r]],
                                      sem.at[b]).start()
                return carry
            jax.lax.fori_loop(lo, hi, row, 0)
            return n + hi - lo
        live[b] = jax.lax.fori_loop(0, groups, group, jnp.int32(0))

    @pl.when(i == 0)
    def _first():
        start(0, 0)

    @pl.when(i + 1 < tiles)
    def _next():
        start(i + 1, (i + 1) % 2)

    b = i % 2

    def wait(_, carry):
        pltpu.make_async_copy(ysp_hbm.at[0], buf.at[b, 0], sem.at[b]).wait()
        return carry
    jax.lax.fori_loop(0, live[b], wait, 0)

    def block(r0, sb):
        here = pl.ds(r0, sb)
        named = [jnp.broadcast_to(slots_ref[here, j:j + 1] < bound, (sb, 128))
                 for j in range(k)]
        wts = [None if w_ref is None
               else jnp.broadcast_to(w_ref[here, j:j + 1], (sb, 128))
               for j in range(k)]

        def chunk(c, both, carry):
            acc = {}
            for j in range(k):          # in this order: the takes' sum
                u = _chunk(buf, b, j * tt + r0, c, sb)
                for h, (col, val) in enumerate(_halves(u, c, w, both)):
                    # a slot that names no row reads what its line last
                    # held: its zero is selected, never multiplied
                    term = jnp.where(named[j], val, 0.0)
                    if wts[j] is not None:
                        term = term * wts[j]
                    acc[h] = (col, term if j == 0 else acc[h][1] + term)
            for col, val in acc.values():
                o_ref[here, pl.ds(col, 128)] = val.astype(o_ref.dtype)
            return carry
        _chunks(w, D, chunk, unroll=False)
    _blocks(tt, block)


@functools.partial(jax.jit, static_argnames=("D", "dtype", "interpret"))
def sum_of_rows(ysp: jax.Array, slot: jax.Array, line: jax.Array,
                runs: jax.Array, weights: Optional[jax.Array] = None, *,
                D: int, dtype=jnp.bfloat16, interpret: bool = False
                ) -> jax.Array:
    """``ysp`` [bound, 1, w] packed rows of ``ys`` [bound, D], ``slot`` [S, k]
    the row of each of a token's pairs (``bound``: none), ``line`` and
    ``runs`` as :func:`token_tile_runs` gives them, ``weights`` [S, k] f32 or
    None -> [S, D] in ``dtype``: ``sum_j weights[t, j] * ys[slot[t, j]]``,
    f32, j ascending, one rounding."""
    bound, _, w = ysp.shape
    S, k = slot.shape
    tt = _tile(S)
    groups = (runs.shape[0] - 1) // (S // tt)
    operands = [slot.astype(jnp.int32)]
    in_specs = [pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((tt, k), lambda i, l, r: (i, 0))]
    if weights is not None:
        operands.append(weights.astype(jnp.float32))
        in_specs.append(pl.BlockSpec((tt, k), lambda i, l, r: (i, 0)))
    return pl.pallas_call(
        functools.partial(_sum_kernel, tt=tt, k=k, D=D, bound=bound,
                          groups=groups, weighted=weights is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(S // tt,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tt, D), lambda i, l, r: (i, 0)),
            scratch_shapes=[pltpu.VMEM((2, k * tt, 1, w), jnp.uint32),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((2,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((S, D), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(line, runs, ysp, *operands)
