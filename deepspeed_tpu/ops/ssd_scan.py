"""The chunked scan of a Mamba-2 (state-space duality) layer (the causal
depthwise convolution in front of it: :mod:`deepspeed_tpu.ops.causal_conv`).

Per head, over a state ``h`` [P, N] that starts at zero::

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T        y_t = h_t C_t + D x_t

The chunked form computes the same ``y`` from matmuls. Within a chunk of
``Q`` positions ``y = ((C B^T) o L)(dt x)`` with ``L_ij = exp(sum_{j<k<=i}
dt_k A)`` for ``i >= j`` and 0 above; a chunk's end state is ``sum_j
exp(sum_{j<k<=end} dt_k A) dt_j x_j B_j^T``; the chunk states are carried
forward by ``exp(sum over a chunk of dt A)``; and the carried state adds
``exp(sum_{k<=i} dt_k A) h_prev C_i``. The cumulative sums, the decays and
their exponentials are float32; the products take operands in ``x``'s dtype
(bf16 in training) and accumulate in float32; the state is float32. ``C
B^T`` is computed once a group, not once a head.

One algorithm, two lowerings (:func:`scan_lowering` picks by what the call
can see: backend, dtype, shapes):

* ``"xla"``: :func:`scan_einsum`, the form above as ``jnp.einsum`` with a
  ``lax.scan`` over the chunk states and autodiff's backward. Every array of
  it goes through HBM (the ``[Q, Q]`` decays of all heads alone are twice
  the bytes the scan needs to move); it is what a CPU runs, what shapes the
  kernels do not take run, and the unit tests' oracle.
* ``"pallas"``: two Mosaic kernels behind a ``jax.custom_vjp``
  (:func:`ssd_fwd`, :func:`ssd_bwd`, each a ``jax.jit`` of its own) that
  visit a sequence's chunks in order (the backward in reverse) with the
  state, or its cotangent, carried in VMEM. A grid step holds one chunk of
  16 heads (:data:`_HEADS_A_STEP`): it forms ``C B^T`` once a group and the
  running sum of ``dt A`` as a product with a triangle of ones, builds each
  head's decay tile in 128 x 128 pieces (the piece above the diagonal is
  never built; the mask stays in the exponent, so nothing overflows whatever
  the decays), multiplies the heads of a lane block in one matmul, and reads
  ``x``, ``dt``, ``B``, ``C`` and writes ``y`` once. The forward that is
  differentiated also writes each chunk's incoming state (float32), which
  the backward reads instead of running the recurrence again. The backward
  rebuilds the tiles, and gets the running sums' cotangent from two
  identities that need no ``[Q, Q]`` reduction: ``sum_j dM_ij M_ij = <dy_i,
  y_i - D x_i>`` and ``sum_i dM_ij M_ij = <dt_j x_j, d(dt x)_j>``. Outside
  the kernels stay ``dt A``, the transposes of ``dt`` to a row a head and
  back, and the sums over chunks that give ``A``'s and ``D``'s cotangents:
  two megabytes a layer.
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
# the decay tile is built in pieces of this side; a lane block of ``x``
_SB = 128
# heads a grid step holds, most first: a step's own work (the running sums,
# the rows' transposes, the pipeline's turn) costs about 1.4 us on a v5e
# whatever it holds (the Granite cell's forward alone: 0.46 ms a layer at 16,
# 0.55 at 8, 0.65 at 4), and the body is unrolled over the step's heads, so
# more of them is more to trace and to compile
_HEADS_A_STEP = (16, 8, 4, 2, 1)
# a step's blocks at 16 heads of 64 and a chunk of 256, each pipelined twice:
# x, y (and dy, dx) 0.5 MB, the chunk's states 0.5 MB in float32; scratch:
# the carried state (or its cotangent) of all heads 2 MB, C B^T and its
# cotangent 0.25 MB each: 5 MB forward, 8 MB backward, beside what Mosaic
# spills of a lane block's [256, 128] float32 values
_VMEM_LIMIT = 48 * 1024 * 1024
# x, B and C reach the kernels as slices of one wider array (the mixer's
# convolution output): XLA may fuse the slice into the operand's read
# instead of copying 33.5 MB a layer through HBM first
_FUSE_FWD = [True, False, False, True, True, False]

# ---------------------------------------------------------------------------
# which lowering: from the call's own facts
# ---------------------------------------------------------------------------

def _heads_a_step(H: int, P: int, G: int) -> Optional[int]:
    """Heads of one grid step: whole lane blocks of ``x``, inside one group."""
    R = H // G
    return next((hb for hb in _HEADS_A_STEP
                 if R % hb == 0 and (hb * P) % _SB == 0), None)


def _shapes_taken(Q: int, H: int, P: int, G: int, N: int) -> str:
    """Why the kernels do not take these shapes; "" where they do."""
    if Q % _SB:
        return f"a chunk of {Q} is not whole tiles of {_SB}"
    if P not in (64, 128):
        return f"heads of {P} channels (the kernels: 64 or 128)"
    if N % _SB:
        return f"a state of {N} is not whole lanes of {_SB}"
    if H % G:
        return f"{H} heads do not divide into {G} groups"
    if _heads_a_step(H, P, G) is None:
        return f"{H // G} heads a group of {P} do not fill lane blocks"
    return ""


def scan_lowering(Q: int, H: int, P: int, G: int, N: int, dtype, *,
                  tpu: Optional[bool] = None) -> Tuple[str, str]:
    """``("pallas" | "xla", why)`` for one scan at chunk ``Q``: the kernels
    where they were measured (a TPU, bf16 operands, a chunk of whole 128
    tiles, heads of 64 or 128 channels in whole lane blocks inside a group,
    a state of whole lanes), the einsum form everywhere else."""
    if tpu is None:
        tpu = _on_tpu()
    if not tpu:
        return "xla", "not a TPU backend"
    if jnp.dtype(dtype) != jnp.bfloat16:
        return "xla", f"{jnp.dtype(dtype).name} operands (the kernels: bf16)"
    why = _shapes_taken(Q, H, P, G, N)
    return ("xla", why) if why else ("pallas", "")


# ---------------------------------------------------------------------------
# the einsum form
# ---------------------------------------------------------------------------

def _pad_to_chunks(T: int, Q: int, *arrays):
    """``arrays`` [B, T, ...] padded along T to whole chunks (zeros: a step of
    ``dt = 0`` passes the state through and adds nothing)."""
    pad = -T % Q
    if pad:
        arrays = tuple(
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in arrays)
    return arrays


def scan_einsum(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                C: jax.Array, D: jax.Array, chunk: int) -> jax.Array:
    """:func:`ssd_scan` as einsums; the backward is autodiff's."""
    b, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    R, Q = H // G, int(chunk)
    x, dt, B, C = _pad_to_chunks(T, Q, x, dt, B, C)
    Tp = x.shape[1]
    c = Tp // Q
    dt = dt.astype(F32)
    # [b, c, G, R, Q]: a position's log-decay, and its running sum in a chunk
    a = (dt * A.astype(F32)).reshape(b, c, Q, G, R).transpose(0, 1, 3, 4, 2)
    cum = jnp.cumsum(a, axis=-1)
    xg = x.reshape(b, c, Q, G, R, P)
    dtx = (xg.astype(F32) * dt.reshape(b, c, Q, G, R, 1)).astype(x.dtype)
    Bc, Cc = B.reshape(b, c, Q, G, N), C.reshape(b, c, Q, G, N)

    # inside a chunk: (C B^T o L)(dt x); the mask goes into the exponent, so
    # that no position above the diagonal overflows (or leaves a NaN behind
    # in the backward)
    cb = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc, preferred_element_type=F32)
    seen = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.exp(jnp.where(seen, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    m = (cb[:, :, :, None] * decay).astype(x.dtype)
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", m, dtx,
                   preferred_element_type=F32)

    # each chunk's end state, as if it had started from zero
    to_end = jnp.exp(cum[..., -1:] - cum)                   # [b, c, G, R, Q]
    xw = (dtx.astype(F32) * to_end.transpose(0, 1, 4, 2, 3)[..., None]
          ).astype(x.dtype)
    states = jnp.einsum("bcjgrp,bcjgn->bcgrpn", xw, Bc,
                        preferred_element_type=F32)

    # the states carried from chunk to chunk: h_prev[c] is what chunk c
    # starts from
    total = jnp.exp(cum[..., -1])                           # [b, c, G, R]

    def carry(h, sc):
        s, t = sc
        return h * t[..., None, None] + s, h

    _, h_prev = jax.lax.scan(
        carry, jnp.zeros((b, G, R, P, N), F32),
        (states.transpose(1, 0, 2, 3, 4, 5), total.transpose(1, 0, 2, 3)))
    h_prev = h_prev.transpose(1, 0, 2, 3, 4, 5)             # [b, c, G, R, P, N]
    y_prev = jnp.einsum("bcign,bcgrpn->bcigrp", Cc, h_prev.astype(x.dtype),
                        preferred_element_type=F32)
    y = y + y_prev * jnp.exp(cum).transpose(0, 1, 4, 2, 3)[..., None]
    y = y + xg.astype(F32) * D.astype(F32).reshape(G, R, 1)
    return y.reshape(b, Tp, H, P)[:, :T].astype(x.dtype)


# ---------------------------------------------------------------------------
# the kernels' shared pieces
# ---------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_TN = (((0,), (0,)), ((), ()))      # a^T @ b


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=F32)


def _columns(rows):
    """[hb, Q] -> [Q, 128]: lane ``l`` holds row ``l % hb`` as a column (the
    rows stacked to 128 sublanes, then one transpose a 128 x 128 piece)."""
    return jnp.concatenate([rows] * (_SB // rows.shape[0]), axis=0).T


def _by_lane(rows, heads, P):
    """[Q, 128] whose lane ``l`` holds row ``heads[l // P]`` of ``rows``
    [hb, Q] as a column: a per-head, per-position factor laid out like a lane
    block of ``x`` (each head's row spread over ``P`` sublanes, then one
    transpose a 128 x 128 piece: cheaper than broadcasting columns along
    lanes and selecting between the heads)."""
    Q = rows.shape[1]
    return jnp.concatenate(
        [jnp.broadcast_to(rows[k:k + 1], (P, Q)) for k in heads], axis=0).T


def _by_row(col, heads, P):
    """[128, 1] whose row ``r`` holds ``col[heads[r // P]]`` (col [hb, 1]): a
    per-head factor laid out like a lane block's rows of the state. Every
    head goes through a select, the last one too: Mosaic cannot broadcast one
    element along sublanes and lanes at once, which is what a lone head's
    factor times the state would fold to."""
    row = jax.lax.broadcasted_iota(jnp.int32, (_SB, 1), 0)
    out = jnp.zeros((_SB, 1), F32)
    for t in range(len(heads) - 1, -1, -1):
        out = jnp.where(row < (t + 1) * P, col[heads[t]:heads[t] + 1], out)
    return out


def _segment(t, n, P):
    """[1, 128] mask of the lanes of head ``t`` of ``n`` in a lane block;
    None where the block is one head's."""
    if n == 1:
        return None
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _SB), 1)
    return (lane >= t * P) & (lane < (t + 1) * P)


def _each_heads_lanes(parts, P):
    """[Q, 128] that takes head ``t``'s lanes from ``parts[t]``: the heads of
    a lane block, each computed over all 128 lanes, put side by side."""
    out = parts[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _SB), 1)
    for t in range(len(parts) - 2, -1, -1):
        out = jnp.where(lane < (t + 1) * P, parts[t], out)
    return out


def _decay_tiles(cum_c, cum_r, k, nq):
    """Head ``k``'s decay ``exp(cum_i - cum_j)`` for ``i >= j`` (0 above the
    diagonal, by a mask in the exponent) as the live 128 x 128 pieces
    ``[ib][jb]``, ``jb <= ib``, float32."""
    tri = (jax.lax.broadcasted_iota(jnp.int32, (_SB, _SB), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (_SB, _SB), 1))
    tiles = []
    for ib in range(nq):
        col = cum_c[ib * _SB:(ib + 1) * _SB, k:k + 1]
        row = []
        for jb in range(ib + 1):
            d = col - cum_r[k:k + 1, jb * _SB:(jb + 1) * _SB]
            if ib == jb:
                d = jnp.where(tri, d, -jnp.inf)
            row.append(jnp.exp(d))
        tiles.append(row)
    return tiles


def _exact_dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """:func:`_dot` of a float32 array and a 0/1 matrix (bf16), either way
    round: the float32 one goes in as three bf16 pieces that add up to it,
    so every product is exact and the sum is float32's whatever the matmul
    unit rounds its operands to."""
    split_a = a.dtype == F32
    val, out = (a if split_a else b), None
    for _ in range(3):
        piece = val.astype(jnp.bfloat16)
        part = _dot(piece, b, dims) if split_a else _dot(a, piece, dims)
        out = part if out is None else out + part
        val = val - piece.astype(F32)
    return out


def _running_sums(rows, from_end: bool = False):
    """[hb, Q] -> each row's running sum along Q (position i: everything up
    to i; ``from_end``: everything from i on), as a product with a
    triangle of ones."""
    Q = rows.shape[1]
    j = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    tri = ((j >= i) if from_end else (j <= i)).astype(jnp.bfloat16)
    return _exact_dot(rows, tri)


def _chunk_facts(dtr_ref, ar_ref):
    """A step's per-head rows [hb, Q] and what follows from them: ``dt``, the
    running sum of ``dt A`` (also as columns [Q, 128], for the tiles), the
    decay to the chunk's end and from its start; the whole chunk's decay
    [hb, 1]."""
    cum_r = _running_sums(ar_ref[...])
    last = cum_r[:, -1:]
    return (dtr_ref[...], cum_r, _columns(cum_r), jnp.exp(last - cum_r),
            jnp.exp(cum_r), jnp.exp(last))


def _masked_products(cb_ref, decays, dtype, ib):
    """Row block ``ib`` of each head's ``(C B^T) o L`` in ``dtype``, the
    heads of a lane block one under the other [heads 128, (ib + 1) 128]:
    one matmul's rows, so that what they multiply is loaded once."""
    rows_ = slice(ib * _SB, (ib + 1) * _SB)
    return jnp.concatenate([jnp.concatenate(
        [(cb_ref[rows_, jb * _SB:(jb + 1) * _SB] * decay[ib][jb]).astype(dtype)
         for jb in range(ib + 1)], axis=1) for decay in decays], axis=0)


def _heads_apart(stacked, n):
    """The heads' [128, .] pieces of a stacked product."""
    return [stacked[t * _SB:(t + 1) * _SB] for t in range(n)]


def _each_heads_rows(stacked_blocks, n):
    """Stacked products of successive row blocks -> each head's blocks one
    under the other."""
    apart = [_heads_apart(block, n) for block in stacked_blocks]
    return [jnp.concatenate([a[t] for a in apart], axis=0) for t in range(n)]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, dtr_ref, ar_ref, b_ref, c_ref, dl_ref, y_ref, *rest,
                Q: int, P: int, hb: int, steps_a_group: int, states: bool):
    """One chunk of ``hb`` heads. ``rest``: the incoming states' block (where
    ``states``), then scratch: ``C B^T`` [Q, Q] and the carried state
    [steps, hb P, N], both float32."""
    hp_ref = rest[0] if states else None
    cb_ref, h_ref = rest[-2:]
    ci, s = pl.program_id(1), pl.program_id(2)
    nq, per, dtype = Q // _SB, _SB // P, x_ref.dtype

    @pl.when(s % steps_a_group == 0)
    def _group():
        cb_ref[...] = _dot(c_ref[...], b_ref[...], _NT)

    @pl.when(ci == 0)
    def _start():
        h_ref[s] = jnp.zeros(h_ref.shape[1:], F32)

    dt_r, cum_r, cum_c, te_r, e_r, g = _chunk_facts(dtr_ref, ar_ref)
    bm, cm = b_ref[...], c_ref[...]
    for lb in range(hb * P // _SB):
        lanes = slice(lb * _SB, (lb + 1) * _SB)
        heads = list(range(lb * per, (lb + 1) * per))
        xf = x_ref[:, lanes].astype(F32)
        dtx = (xf * _by_lane(dt_r, heads, P)).astype(dtype)
        xw = (dtx.astype(F32) * _by_lane(te_r, heads, P)).astype(dtype)
        hprev = h_ref[s, lanes, :]
        if states:
            hp_ref[lanes, :] = hprev
        acc = _dot(cm, hprev.astype(dtype), _NT) * _by_lane(e_r, heads, P) \
            + xf * dl_ref[:, lanes]
        decays = [_decay_tiles(cum_c, cum_r, k, nq) for k in heads]
        ys = _each_heads_rows(
            [_dot(_masked_products(cb_ref, decays, dtype, ib),
                  dtx[:(ib + 1) * _SB]) for ib in range(nq)], per)
        y_ref[:, lanes] = (acc + _each_heads_lanes(ys, P)).astype(y_ref.dtype)
        h_ref[s, lanes, :] = hprev * _by_row(g, heads, P) + _dot(xw, bm, _TN)


def _rows_of(a, b, c, Q, HS, hb):
    """[b, c Q, H] -> [b, c, HS, hb, Q]: each head's positions of a chunk as
    a row."""
    return a.reshape(b, c, Q, HS, hb).transpose(0, 1, 3, 4, 2)


def _prepare(x, dt, A, B, C, D, Q):
    """The kernels' operands from the scan's: padded to whole chunks, ``x``
    with its heads side by side in lanes, ``dt`` and ``dt A`` as rows, ``D``
    a lane a channel."""
    b, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    hb = _heads_a_step(H, P, G)
    x, dt, B, C = _pad_to_chunks(T, Q, x, dt, B, C)
    Tp = x.shape[1]
    c, HS = Tp // Q, H // hb
    dtr = _rows_of(dt.astype(F32), b, c, Q, HS, hb)
    ar = dtr * A.astype(F32).reshape(HS, hb, 1)
    dl = jnp.repeat(D.astype(F32), P).reshape(1, H * P)
    return (x.reshape(b, Tp, H * P), dtr, ar, B.reshape(b, Tp, G * N),
            C.reshape(b, Tp, G * N), dl), (b, T, Tp, H, P, G, N, hb, c, HS)


def _specs(Q, P, N, hb, R, flip=None):
    """Block specs of a step's operands over the grid (sequence, chunk,
    heads' step); ``flip`` (the chunks there are) turns the chunk axis
    around, for the backward."""
    W = hb * P

    def ch(c):
        return c if flip is None else flip - 1 - c

    lanes = pl.BlockSpec((None, Q, W), lambda b, c, s: (b, ch(c), s))
    rows = pl.BlockSpec((None, None, None, hb, Q),
                        lambda b, c, s: (b, ch(c), s, 0, 0))
    group = pl.BlockSpec((None, Q, N),
                         lambda b, c, s: (b, ch(c), (s * hb) // R))
    chan = pl.BlockSpec((1, W), lambda b, c, s: (0, s))
    state = pl.BlockSpec((None, None, W, N),
                         lambda b, c, s: (b, ch(c), s, 0))
    return lanes, rows, group, chan, state


@functools.partial(jax.jit, static_argnames=("chunk", "states", "interpret"))
def ssd_fwd(x, dt, A, B, C, D, *, chunk: int, states: bool,
            interpret: bool = False):
    """``y`` [B, T, H, P] and, where ``states``, each chunk's incoming state
    [B, c, H P, N] float32 (else None)."""
    Q = chunk
    ops, (b, T, Tp, H, P, G, N, hb, c, HS) = _prepare(x, dt, A, B, C, D, Q)
    lanes, rows, group, chan, state = _specs(Q, P, N, hb, H // G)
    out_shape = [jax.ShapeDtypeStruct((b, Tp, H * P), x.dtype)]
    out_specs = [lanes]
    if states:
        out_shape.append(jax.ShapeDtypeStruct((b, c, H * P, N), F32))
        out_specs.append(state)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, Q=Q, P=P, hb=hb,
                          steps_a_group=(H // G) // hb, states=states),
        grid=(b, c, HS),
        in_specs=[lanes, rows, rows, group, group, chan],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((Q, Q), F32),
                        pltpu.VMEM((HS, hb * P, N), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            allow_input_fusion=_FUSE_FWD,
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*ops)
    y = out[0].reshape(b, Tp, H, P)[:, :T]
    return y, (out[1] if states else None)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _head_sums(val, lb, per, hb, P):
    """[hb, Q]: the sum of ``val`` [Q, 128] over each head's lanes, as the
    rows of lane block ``lb``'s heads (zeros elsewhere)."""
    k = jax.lax.broadcasted_iota(jnp.int32, (hb, _SB), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (hb, _SB), 1)
    pick = (k == lb * per + lane // P).astype(jnp.bfloat16)
    return _exact_dot(pick, val, _NT)


def _bwd_kernel(x_ref, dtr_ref, ar_ref, b_ref, c_ref, dl_ref, dy_ref,
                hp_ref, dx_ref, ddt_ref, da_ref, db_ref, dc_ref, dd_ref,
                cb_ref, dcb_ref, dbacc_ref, dcacc_ref, dh_ref, *,
                Q: int, P: int, hb: int, steps_a_group: int):
    """One chunk of ``hb`` heads, the chunks visited last first. Scratch: ``C
    B^T`` and its cotangent [Q, Q], the carried parts of ``B``'s and ``C``'s
    cotangents [Q, N], the cotangent of the state this chunk leaves
    [steps, hb P, N]; all float32."""
    ci, s = pl.program_id(1), pl.program_id(2)
    nq, per, dtype = Q // _SB, _SB // P, x_ref.dtype

    @pl.when(s % steps_a_group == 0)
    def _group():
        cb_ref[...] = _dot(c_ref[...], b_ref[...], _NT)
        dcb_ref[...] = jnp.zeros((Q, Q), F32)
        dbacc_ref[...] = jnp.zeros(dbacc_ref.shape, F32)
        dcacc_ref[...] = jnp.zeros(dcacc_ref.shape, F32)

    @pl.when(ci == 0)
    def _start():
        dh_ref[s] = jnp.zeros(dh_ref.shape[1:], F32)

    dt_r, cum_r, cum_c, te_r, e_r, g = _chunk_facts(dtr_ref, ar_ref)
    bm, cm = b_ref[...], c_ref[...]
    ddt_rows = jnp.zeros((hb, Q), F32)
    dcum_rows = jnp.zeros((hb, Q), F32)
    at_end = (jax.lax.broadcasted_iota(jnp.int32, (hb, Q), 1) == Q - 1)
    head_row = jax.lax.broadcasted_iota(jnp.int32, (hb, Q), 0)
    for lb in range(hb * P // _SB):
        lanes = slice(lb * _SB, (lb + 1) * _SB)
        heads = list(range(lb * per, (lb + 1) * per))
        xf = x_ref[:, lanes].astype(F32)
        dy = dy_ref[:, lanes]
        dyf = dy.astype(F32)
        dt_l, te_l, e_l = (_by_lane(v, heads, P) for v in (dt_r, te_r, e_r))
        dtx = (xf * dt_l).astype(dtype)
        dtxf = dtx.astype(F32)
        xw = (dtxf * te_l).astype(dtype)
        hprev = hp_ref[lanes, :]
        dhn = dh_ref[s, lanes, :]
        hprev_b, dhn_b = hprev.astype(dtype), dhn.astype(dtype)
        # the carried part of y, and what the state's cotangent sends back
        ypart = _dot(cm, hprev_b, _NT) * e_l
        du = _dot(bm, dhn_b, _NT) * te_l
        dye = (dyf * e_l).astype(dtype)
        # what the decays to the chunk's end and the whole chunk's decay owe
        # the running sum's last position, from the very numbers that are
        # taken from the positions' own sums below (they cancel to the bit)
        to_end = jnp.sum(dtxf * du, axis=0, keepdims=True)
        dhg = dhn * _by_row(g, heads, P)
        whole = jnp.sum(dhg * hprev, axis=1, keepdims=True)
        segs = [_segment(t, per, P) for t in range(per)]
        # each head's dy with the other heads' lanes cleared, so that a
        # product over all 128 lanes is that head's alone
        dys = [dy if seg is None else jnp.where(seg, dy, 0).astype(dtype)
               for seg in segs]
        decays = [_decay_tiles(cum_c, cum_r, k, nq) for k in heads]
        ms = [_masked_products(cb_ref, decays, dtype, ib)
              for ib in range(nq)]
        for ib in range(nq):
            upto = (ib + 1) * _SB
            rows_ = slice(ib * _SB, upto)
            dm = _dot(jnp.concatenate([d[rows_] for d in dys], axis=0),
                      dtx[:upto], _NT)
            dcb_ref[rows_, :upto] += sum(
                piece * jnp.concatenate(decay[ib], axis=1)
                for piece, decay in zip(_heads_apart(dm, per), decays))
        ys = _each_heads_rows(
            [_dot(ms[ib], dtx[:(ib + 1) * _SB]) for ib in range(nq)], per)
        # M^T dy by column block: the heads' blocks side by side
        # [(nq - jb) 128, heads 128], so that dy is loaded once
        dus = _each_heads_rows(
            [_dot(jnp.concatenate([jnp.concatenate(_heads_apart(
                ms[ib][:, jb * _SB:(jb + 1) * _SB], per), axis=1)
                for ib in range(jb, nq)], axis=0), dy[jb * _SB:], _TN)
             for jb in range(nq)], per)
        for t, k in enumerate(heads):
            left = jnp.sum(to_end if segs[t] is None
                           else jnp.where(segs[t], to_end, 0.0),
                           keepdims=True) \
                + jnp.sum(whole[t * P:(t + 1) * P], keepdims=True)
            dcum_rows = dcum_rows + jnp.where(
                at_end & (head_row == k), left, 0.0)
        ypart = ypart + _each_heads_lanes(ys, P)
        du = du + _each_heads_lanes(dus, P)
        dx_ref[:, lanes] = (du * dt_l + dyf * dl_ref[:, lanes]
                            ).astype(dx_ref.dtype)
        ddt_rows = ddt_rows + _head_sums(du * xf, lb, per, hb, P)
        dcum_rows = dcum_rows + _head_sums(dyf * ypart - dtxf * du, lb, per,
                                           hb, P)
        dd_ref[:, lanes] = jnp.sum(dyf * xf, axis=0, keepdims=True)
        dh_ref[s, lanes, :] = dhg + _dot(dye, cm, _TN)
        dbacc_ref[...] += _dot(xw, dhn_b)
        dcacc_ref[...] += _dot(dye, hprev_b)
    ddt_ref[...] = ddt_rows
    # a_k = dt_k A feeds the running sum of every later position
    da_ref[...] = _running_sums(dcum_rows, from_end=True)

    @pl.when(s % steps_a_group == steps_a_group - 1)
    def _group_end():
        dcb = dcb_ref[...].astype(dtype)
        dc_ref[...] = (_dot(dcb, bm) + dcacc_ref[...]).astype(dc_ref.dtype)
        db_ref[...] = (_dot(dcb, cm, _TN) + dbacc_ref[...]
                       ).astype(db_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_bwd(x, dt, A, B, C, D, hp, dy, *, chunk: int,
            interpret: bool = False):
    """The six cotangents of :func:`ssd_fwd`'s ``y`` from ``dy`` and the
    chunks' incoming states ``hp``."""
    Q = chunk
    ops, (b, T, Tp, H, P, G, N, hb, c, HS) = _prepare(x, dt, A, B, C, D, Q)
    dtr = ops[1]
    dy, = _pad_to_chunks(T, Q, dy)
    lanes, rows, group, chan, state = _specs(Q, P, N, hb, H // G, flip=c)
    sums = pl.BlockSpec((None, None, 1, hb * P),
                        lambda b, c_, s: (b, c - 1 - c_, 0, s))
    dx, ddt, da, dB, dC, dD = pl.pallas_call(
        functools.partial(_bwd_kernel, Q=Q, P=P, hb=hb,
                          steps_a_group=(H // G) // hb),
        grid=(b, c, HS),
        in_specs=[lanes, rows, rows, group, group, chan, lanes, state],
        out_specs=[lanes, rows, rows, group, group, sums],
        out_shape=[jax.ShapeDtypeStruct((b, Tp, H * P), x.dtype),
                   jax.ShapeDtypeStruct(dtr.shape, F32),
                   jax.ShapeDtypeStruct(dtr.shape, F32),
                   jax.ShapeDtypeStruct((b, Tp, G * N), B.dtype),
                   jax.ShapeDtypeStruct((b, Tp, G * N), C.dtype),
                   jax.ShapeDtypeStruct((b, c, 1, H * P), F32)],
        scratch_shapes=[pltpu.VMEM((Q, Q), F32), pltpu.VMEM((Q, Q), F32),
                        pltpu.VMEM((Q, N), F32), pltpu.VMEM((Q, N), F32),
                        pltpu.VMEM((HS, hb * P, N), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            allow_input_fusion=_FUSE_FWD + [False, False],
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*ops, dy.reshape(b, Tp, H * P), hp)
    Af = A.astype(F32).reshape(HS, hb, 1)
    ddt = (ddt + da * Af).transpose(0, 1, 4, 2, 3).reshape(b, Tp, H)
    dA = jnp.sum(da * dtr, axis=(0, 1, 4)).reshape(H)
    dD = jnp.sum(dD.reshape(b * c, H, P), axis=(0, 2))
    return (dx.reshape(b, Tp, H, P)[:, :T], ddt[:, :T].astype(dt.dtype),
            dA.astype(A.dtype), dB.reshape(b, Tp, G, N)[:, :T],
            dC.reshape(b, Tp, G, N)[:, :T], dD.astype(D.dtype))


# ---------------------------------------------------------------------------
# the scan
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan_pallas(x, dt, A, B, C, D, chunk, interpret):
    return ssd_fwd(x, dt, A, B, C, D, chunk=chunk, states=False,
                   interpret=interpret)[0]


def _scan_pallas_fwd(x, dt, A, B, C, D, chunk, interpret):
    y, hp = ssd_fwd(x, dt, A, B, C, D, chunk=chunk, states=True,
                    interpret=interpret)
    return y, (x, dt, A, B, C, D, hp)


def _scan_pallas_bwd(chunk, interpret, res, dy):
    lowerings.count("ssm_scan", "pallas")     # the kernels' own backward
    return ssd_bwd(*res, dy, chunk=chunk, interpret=interpret)


_scan_pallas.defvjp(_scan_pallas_fwd, _scan_pallas_bwd)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, D: jax.Array, chunk: int,
             interpret: Optional[bool] = None) -> jax.Array:
    """x [B, T, H, P], dt [B, T, H] (after softplus), A [H] (negative), B and
    C [B, T, G, N] (each group serves H / G heads), D [H] -> y [B, T, H, P]
    in ``x``'s dtype. A ``T`` that is not a multiple of ``chunk`` is padded
    with steps of ``dt = 0`` (the state passes through them unchanged and
    they add nothing to it) and the padding's outputs are dropped.
    ``interpret`` is the kernels' test handle (None: ask
    :func:`scan_lowering`; True: the kernels, interpreted, in any float
    dtype, for shapes they take)."""
    H, P = x.shape[2:]
    G, N = B.shape[2:]
    if interpret is None:
        lowering, _ = scan_lowering(int(chunk), H, P, G, N, x.dtype)
    else:
        why = _shapes_taken(int(chunk), H, P, G, N)
        if why:
            raise ValueError(f"the scan's kernels do not take {why}")
        lowering = "pallas"
    # a scan by the lowering it took: one for a scan, one more for a Pallas
    # scan's backward (the einsum form's is autodiff's)
    lowerings.count("ssm_scan", lowering)
    if lowering == "xla":
        return scan_einsum(x, dt, A, B, C, D, chunk)
    return _scan_pallas(x, dt, A, B, C, D, int(chunk), bool(interpret))
