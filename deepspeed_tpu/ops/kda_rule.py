"""The delta rule with a decay a key channel (Kimi Delta Attention, Kimi
Linear, arXiv:2510.26692), in chunks.

Per head, over a state ``S`` [dk, dv] that starts at zero, with a decay
``exp(g_t)`` in (0, 1] for each of the ``dk`` key channels and a step
``beta_t``::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

(``q`` arrives scaled). ``ops/delta_rule.py``'s rule is the case ``g``
constant over a head's channels. Chunked, with ``G`` [C, dk] the running sum
of ``g`` from the chunk's start and ``S_0`` the incoming state::

    A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)  (i > j)
    (I + A) U = diag(beta) (V - (K o exp(G)) S_0)
    O    = (Q o exp(G)) S_0 + [sum_c q_ic k_jc exp(G_ic - G_jc), i >= j] U
    S_C  = Diag(exp(G_C)) S_0 + (K o exp(G_C - G))^T U

so with ``T = (I + A)^{-1}`` (``delta_rule.unit_lower_inverse``: substitution
inside diagonal blocks of 16, then the joins, with a backward of its own),
``U = T diag(beta) V - (T diag(beta) (K o exp(G))) S_0``: one state read and
one state update a chunk; only the states are sequential.

With a decay a channel ``exp(G_i - G_j)`` is no scalar a pair of positions
that could multiply ``K K^T`` and ``Q K^T`` afterwards: it goes into the
operands. Rows are taken in blocks of 16 (:data:`BLOCK`); for the rows ``i``
of a block whose first row is ``s``, ``exp(G_i - G_j) = exp(G_i - G_s)
exp(G_s - G_j)``: the first factor is at most 1, the second at most 1 for the
columns ``j <= s`` of earlier blocks and at most ``exp(15 |g|_max)`` for the
block's own columns, which float32 (and bf16, whose exponent is float32's)
holds because the published gate is bounded below (``kda_lower_bound`` -5:
``exp(75)``); :func:`chunked_kda_rule` refuses no ``g``, it is the model's
gate (``models/kda.py``) that keeps the bound. Columns after the block are
masked in the exponent (``-inf``), so that nothing above the diagonal
overflows or leaves a NaN in the backward. Every other decay of the chunked
form, ``exp(G)``, ``exp(G_C - G)`` and ``exp(G_C)``, is at most 1.

Precision, as ``ops/delta_rule.py``'s: the running sums of ``g`` (a product
with a triangle of ones at the highest precision: XLA's ``cumsum`` is a
``reduce-window`` on a TPU), every decay and the triangular inverse are
float32, every ratio of decays is one exponential of a difference, never a
quotient of two; the products take operands in ``v``'s dtype (bf16 in
training) and accumulate in float32.

One algorithm, two lowerings (:func:`kda_lowering` picks by what the call
can see: backend, dtype, widths):

* ``"xla"``: :func:`kda_einsum`, the form above as ``jnp.einsum`` with a
  ``lax.scan`` over the chunk states and autodiff's backward (but the
  inverse's), every array of it through HBM: the columns' decays against
  each block's first row alone are ``[B, N, H, 4, C, dk]`` a layer, written,
  kept for the backward and read back. It is what a CPU runs, what float32
  and widths the kernels do not take run, and the unit tests' oracle.
* ``"pallas"``: two Mosaic kernels behind a ``jax.custom_vjp``
  (:func:`kda_fwd`, :func:`kda_bwd`, each a ``jax.jit`` of its own), in the
  shape of ``ops/delta_rule.py``'s and on its helpers (the substitution on
  16 x 16 diagonal blocks, the two joins, the layout constants, the norms of
  q and k): a grid step holds eight chunks of one head, two by two, with the
  state, or its cotangent, carried in VMEM across the chunk axis. With keys
  and values of 128 a head is one lane tile of ``[B, T, H d]``, so the
  ``BlockSpec`` cuts a head's block of q, k, v, ``g`` and o and nothing is
  copied inside. What a step builds and keeps in VMEM, a pair of chunks at
  a time: the running sums ``G`` [C, dk] (a product with a triangle of ones,
  float32-exact), the rows' and the columns' decays by the einsum form's own
  factoring (only the column blocks at or before a row block: 10 of the 16
  block pairs of a chunk; the later ones are left zero, not built and
  masked), the decayed operands rounded where :func:`kda_einsum` rounds
  them, the ``[C, C]`` tiles ``A``, ``Q K^T``, ``T``, and ``U``; a pair's
  two ``[C, C]`` tiles lie side by side in the lanes of one ``[C, 2 C]``
  array, and the product of a block of rows takes both chunks' rows against
  both chunks' columns at once, the other chunk's lanes dropped. The forward
  that is differentiated also writes each chunk's incoming state (float32),
  which the backward reads instead of running the recurrence again; its
  output and those states carry ``RULE_CHECKPOINT_NAMES`` like the einsum
  form's and the scalar rule's, so that a policy that keeps the named
  residuals keeps them. The backward visits the chunks last first, rebuilds a
  chunk's decays, ``T`` and ``U``, and gives ``dg`` a key channel: every
  decayed operand ``x exp(+-G)`` sends ``+-`` itself times its cotangent to
  ``G`` (no array a pair of positions), then one reversed running sum.
  Outside the kernels stay the gate's ``g`` and ``beta`` themselves
  (``models/kda.py``); ``beta`` [B, T, H] goes in as it is, every head's
  column held across the grid's head axis. The kernels' own layout,
  ``[B, T, H d]``, is an entry of its own (:func:`kda_rule_lanes`, which
  ``models/kda.py`` calls): a neighbour that works on ``[B, T, H, d]``
  (tiled over the heads: other bytes on a TPU) costs a copy each way, and
  :func:`chunked_kda_rule` is that entry between two reshapes.

The rules traced are counted by lowering (``ops/lowerings.py``, site
``kda_scan``) for the step-program table: one for a rule, one more for the
kernels' own backward.
"""

from __future__ import annotations

import functools
from types import SimpleNamespace
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.accelerator.real_accelerator import on_tpu as _on_tpu
from deepspeed_tpu.ops import lowerings
from deepspeed_tpu.ops.delta_rule import (CHUNK, _CHUNKS_A_STEP, _PAIR,
                                          _apart, _block_inverses, _call,
                                          _halves, _highest, _iotas, _joined,
                                          _layout_constants, _pack,
                                          _packed_iotas, _read_rows,
                                          _unit_back, unit_heads,
                                          unit_lower_inverse)
from deepspeed_tpu.ops.ssd_scan import (_NT, _TN, _dot, _exact_dot,
                                        _pad_to_chunks)
from deepspeed_tpu.runtime.activation_checkpointing import (
    RULE_CHECKPOINT_NAMES)

F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
#: rows that share a reference row for their decays: inside a block the
#: second factor of a decay can reach ``exp((BLOCK - 1) |g|_max)``
BLOCK = 16


def kda_einsum(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
               beta: jax.Array) -> jax.Array:
    """:func:`chunked_kda_rule` as einsums; the backward is autodiff's."""
    B, T, H, dk = q.shape
    dv, C, R, dt = v.shape[-1], CHUNK, CHUNK // BLOCK, v.dtype
    q, k, v, g, beta = _pad_to_chunks(T, C, q, k, v, g, beta)
    N = q.shape[1] // C
    dot = functools.partial(jnp.einsum, preferred_element_type=F32)
    # [B, N, H, C, .]: a chunk of a head is one matrix
    qc, kc, vc, gc = (a.reshape(B, N, C, H, -1).transpose(0, 1, 3, 2, 4)
                      for a in (q, k, v, g.astype(F32)))
    bc = beta.astype(F32).reshape(B, N, C, H).transpose(0, 1, 3, 2)
    pos = jnp.arange(C)
    cum = jnp.einsum("ij,bnhjd->bnhid",
                     (pos[:, None] >= pos[None, :]).astype(F32), gc,
                     precision=_HIGHEST)                        # G_i
    qf, kf, vf = qc.astype(F32), kc.astype(F32), vc.astype(F32)
    # the rows of block I against its first row s, [B, N, H, R, BLOCK, dk]
    # (<= 1), and every column against each block's first row,
    # [B, N, H, R, C, dk] (the block's own columns up to exp(15 |g|); the
    # columns after it masked in the exponent)
    blocks = cum.reshape(B, N, H, R, BLOCK, dk)
    first = blocks[..., :1, :]                                  # G_s
    rows = jnp.exp(blocks - first)
    ahead = (pos[None, :] // BLOCK > jnp.arange(R)[:, None])[..., None]
    cols = (kf[..., None, :, :] * jnp.exp(jnp.where(
        ahead, -jnp.inf, first - cum[..., None, :, :]))).astype(dt)

    def decayed(x):
        """``sum_c x_ic k_jc exp(G_ic - G_jc)`` [B, N, H, C, C], right where
        ``i >= j``."""
        mine = (x.reshape(B, N, H, R, BLOCK, dk) * rows).astype(dt)
        return dot("bnhrid,bnhrjd->bnhrij", mine, cols).reshape(
            B, N, H, C, C)

    a = jnp.where(pos[:, None] > pos[None, :],
                  bc[..., :, None] * decayed(kf), 0.0)
    qk = jnp.where(pos[:, None] >= pos[None, :], decayed(qf), 0.0).astype(dt)
    t = unit_lower_inverse(a).astype(dt)
    since = jnp.exp(cum)                                        # exp(G_i)
    u0 = dot("bnhij,bnhjd->bnhid", t, (vf * bc[..., None]).astype(dt))
    w = dot("bnhij,bnhjd->bnhid", t,
            (kf * since * bc[..., None]).astype(dt)).astype(dt)
    k_end = (kf * jnp.exp(cum[..., -1:, :] - cum)).astype(dt)
    whole = jnp.exp(cum[..., -1, :])                            # [B, N, H, dk]

    def chunk_step(s, xs):
        u0_c, w_c, k_c, whole_c = xs
        u = (u0_c - dot("bhid,bhde->bhie", w_c, s.astype(dt))).astype(dt)
        new = s * whole_c[..., None] + dot("bhid,bhie->bhde", k_c, u)
        return new, (s, u)

    _, (s_in, u) = jax.lax.scan(
        chunk_step, jnp.zeros((B, H, dk, dv), F32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (u0, w, k_end, whole)))
    s_in = checkpoint_name(jnp.moveaxis(s_in, 0, 1), RULE_CHECKPOINT_NAMES[1])
    u = jnp.moveaxis(u, 0, 1)
    o = dot("bnhij,bnhje->bnhie", qk, u) \
        + dot("bnhid,bnhde->bnhie", (qf * since).astype(dt), s_in.astype(dt))
    o = o.transpose(0, 1, 3, 2, 4).reshape(B, N * C, H, dv)[:, :T].astype(dt)
    return checkpoint_name(o, RULE_CHECKPOINT_NAMES[0])


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

#: (keys, values) a head: what the kernels were built, tested and measured
#: for; a head is then one lane tile of ``[B, T, H d]`` and the ``BlockSpec``
#: cuts it
_WIDTHS = ((128, 128),)


def _lanes_sum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _pair_facts(q_in, k_in, g, beta, dt):
    """What a pair of chunks needs that no state enters, forward and backward
    alike. ``q_in``, ``k_in``: :func:`delta_rule._read_rows` of the pair's
    rows; ``g`` [2 C, dk] float32; ``beta`` [2 C, 1]. The running sums of
    ``g`` inside each chunk (one product with a triangle of ones), the decays
    of the state's read and update, and, a block of :data:`BLOCK` rows each,
    the einsum form's decayed operands: the block's rows of q and k against
    its first row (``mine``, both chunks' side by side in sublanes) and the
    columns of the blocks up to it against that row (``theirs``, the later
    blocks' left zero: they are not built), whose products are the pair's
    packed ``Q K^T`` (masked to i >= j) and ``K K^T`` and the strictly lower
    ``A``."""
    C, b = CHUNK, BLOCK
    qf, kf = q_in[0].astype(F32), k_in[0].astype(F32)
    dk = g.shape[1]
    r2, c2 = _iotas((_PAIR, _PAIR))
    cum = _exact_dot((((r2 >> 6) == (c2 >> 6)) & (r2 >= c2)).astype(
        jnp.bfloat16), g)                                       # G_i
    row, col, left = _packed_iotas()
    first_lanes = _iotas((b, _PAIR))[1] < C
    mine, theirs, row_decays, col_decays, qk, kk = [], [], [], [], [], []
    for r in range(C // b):
        mq, mk, cols, rd, cd = [], [], [], [], []
        for lo in (0, C):
            at = lo + r * b
            first = cum[at:at + 1]                              # G_s
            rd.append(jnp.exp(cum[at:at + b] - first))
            cd.append(jnp.exp(first - cum[lo:at + b]))
            mq.append((qf[at:at + b] * rd[-1]).astype(dt))
            mk.append((kf[at:at + b] * rd[-1]).astype(dt))
            cols.append((kf[lo:at + b] * cd[-1]).astype(dt))
            if at + b < lo + C:
                cols.append(jnp.zeros((lo + C - at - b, dk), dt))
        mine.append(jnp.concatenate(mq + mk, axis=0))           # [4 b, dk]
        theirs.append(jnp.concatenate(cols, axis=0))            # [2 C, dk]
        row_decays.append(rd)
        col_decays.append(cd)
        both = _dot(mine[-1], theirs[-1], _NT)                  # [4 b, 2 C]
        qk.append(jnp.where(first_lanes, both[:b], both[b:2 * b]))
        kk.append(jnp.where(first_lanes, both[2 * b:3 * b], both[3 * b:]))
    qk, kk = jnp.concatenate(qk, axis=0), jnp.concatenate(kk, axis=0)
    ends = jnp.concatenate(
        [jnp.broadcast_to(cum[lo + C - 1:lo + C], (C, dk)) for lo in (0, C)],
        axis=0)                                                 # G_C
    return SimpleNamespace(
        qf=qf, kf=kf, q_norm=q_in[1], k_norm=k_in[1], beta=beta, cum=cum,
        since=jnp.exp(cum), to_end=jnp.exp(ends - cum),
        mine=mine, theirs=theirs, row_decays=row_decays,
        col_decays=col_decays, kk=kk,
        qk=jnp.where(row >= col, qk, 0.0),
        a=jnp.where(row > col, _pack(beta, left) * kk, 0.0))


def _whole(cum, width):
    """``exp(G_C)`` of a pair's two chunks as the columns that scale a state
    [dk, width] each (every lane the channel's total: a product with a 0/1
    matrix puts the row of sums down the sublanes, float32-exact)."""
    at, lane = _iotas((_PAIR, 2 * width))
    pick = (at == jnp.where(lane < width, CHUNK - 1, _PAIR - 1)).astype(
        jnp.bfloat16)
    both = jnp.exp(_exact_dot(cum, pick, _TN))                  # [dk, 2 dv]
    return both[:, :width], both[:, width:]


def _step_facts(q_ref, k_ref, g_ref, b_ref, h, pairs, unit, dt):
    """:func:`_pair_facts` of each of a step's pairs; ``b_ref`` holds every
    head's ``beta`` [L, H], of which lane ``h`` is the step's."""
    length, eps = unit or (None, None)
    lane = _iotas(b_ref.shape)[1]
    beta = _lanes_sum(jnp.where(lane == h, b_ref[...], 0.0))    # [L, 1]
    facts = []
    for p in range(pairs):
        rows = slice(p * _PAIR, (p + 1) * _PAIR)
        facts.append(_pair_facts(
            _read_rows(q_ref, rows, eps, length, dt),
            _read_rows(k_ref, rows, eps, 1.0, dt), g_ref[rows, :],
            beta[rows], dt))
    return facts


def _state_operands(f, t, vf, dt):
    """A pair's operands of the state's read and update, as the einsum form
    rounds them: ``beta v`` and ``beta exp(G) k`` (what ``T`` multiplies),
    ``u0``, ``w``, ``k exp(G_C - G)`` and ``q exp(G)``."""
    vb = (vf * f.beta).astype(dt)
    kb = (f.kf * f.since * f.beta).astype(dt)
    return (vb, kb, _dot(t, vb), _dot(t, kb).astype(dt),
            (f.kf * f.to_end).astype(dt), (f.qf * f.since).astype(dt))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, fold_ref, spread_ref,
                unfold_ref, o_ref, *rest, pairs: int, states: bool, unit):
    """``pairs`` pairs of chunks of one head (the ``BlockSpec`` cut the
    head's lane tile). ``rest``: the incoming states' block [2 pairs, dk, dv]
    (where ``states``), then scratch: every head's carried state [H, dk, dv]
    float32. ``unit`` (q's length, eps): q and k arrive as the convolutions
    left them."""
    sin_ref = rest[0] if states else None
    s_ref = rest[-1]
    C, dt = CHUNK, v_ref.dtype
    n, h = pl.program_id(1), pl.program_id(2)

    @pl.when(n == 0)
    def _start():
        s_ref[h] = jnp.zeros(s_ref.shape[1:], F32)

    facts = _step_facts(q_ref, k_ref, g_ref, b_ref, h, pairs, unit, dt)
    left = _packed_iotas()[2]
    blocks = _block_inverses([f.a for f in facts], fold_ref, spread_ref,
                             unfold_ref)
    s = s_ref[h]
    for p, f in enumerate(facts):
        rows = slice(p * _PAIR, (p + 1) * _PAIR)
        t = _apart(_joined(f.a, blocks[p]).astype(dt), left)    # [2 C, 2 C]
        _, _, u0, w, k_end, qs = _state_operands(
            f, t, v_ref[rows, :].astype(F32), dt)
        whole = _whole(f.cum, s.shape[1])
        us, from_state = [], []
        # only the states are sequential: a chunk after the other
        for half, (w_c, q_c, u0_c, k_c) in enumerate(zip(
                _halves(w), _halves(qs), _halves(u0), _halves(k_end))):
            if states:
                sin_ref[2 * p + half] = s
            ws = _dot(jnp.concatenate([w_c, q_c], axis=0), s.astype(dt))
            us.append((u0_c - ws[:C]).astype(dt))
            from_state.append(ws[C:])
            s = s * whole[half] + _dot(k_c, us[-1], _TN)
        o_ref[rows, :] = (
            _dot(_apart(f.qk.astype(dt), left), jnp.concatenate(us, axis=0))
            + jnp.concatenate(from_state, axis=0)).astype(o_ref.dtype)
    s_ref[h] = s


def _prepare(q, k, v, g, beta):
    """The kernels' operands from the rule's, all with the heads side by side
    in lanes as the projections wrote them (q, k, ``g`` [B, T, H dk], v
    [B, T, H dv], ``beta`` [B, T, H]): padded to whole grid steps (positions
    of ``k = 0``, ``g = 0``), ``g`` and ``beta`` float32."""
    B, T, H = beta.shape
    dk, dv = q.shape[-1] // H, v.shape[-1] // H
    pairs = min(_CHUNKS_A_STEP // 2, -(-T // _PAIR))
    q, k, v, g, beta = _pad_to_chunks(T, pairs * _PAIR, q, k, v, g, beta)
    consts = tuple(jnp.asarray(m, jnp.bfloat16)
                   for m in _layout_constants(2 * pairs))
    return (q, k, v, g.astype(F32), beta.astype(F32)) + consts, \
        (B, T, q.shape[1], H, dk, dv, pairs)


def _specs(H, dk, dv, pairs, flip=None):
    """Block specs over the grid (sequence, step of ``pairs`` pairs of
    chunks, head): a head's lane tile of the keys' and the values' arrays,
    every head's ``beta`` (held across the head axis), a head's states;
    ``flip`` (the steps there are) turns the steps around, for the
    backward."""
    L = pairs * _PAIR

    def st(n):
        return n if flip is None else flip - 1 - n

    keys = pl.BlockSpec((None, L, dk), lambda b, n, h: (b, st(n), h))
    vals = pl.BlockSpec((None, L, dv), lambda b, n, h: (b, st(n), h))
    steps = pl.BlockSpec((None, L, H), lambda b, n, h: (b, st(n), 0))
    state = pl.BlockSpec((None, None, 2 * pairs, dk, dv),
                         lambda b, n, h: (b, h, st(n), 0, 0))
    consts = [pl.BlockSpec(m.shape, lambda b, n, h: (0, 0))
              for m in _layout_constants(2 * pairs)]
    return keys, vals, steps, state, consts


@functools.partial(jax.jit, static_argnames=("states", "unit", "interpret"))
def kda_fwd(q, k, v, g, beta, *, states: bool, unit=None,
            interpret: bool = False):
    """``o`` [B, T, H dv] from q, k, ``g`` [B, T, H dk], v [B, T, H dv] and
    ``beta`` [B, T, H] and, where ``states``, each chunk's incoming state
    [B, H, N, dk, dv] float32 (else None)."""
    ops, (B, T, Tp, H, dk, dv, pairs) = _prepare(q, k, v, g, beta)
    L = pairs * _PAIR
    keys, vals, steps, state, consts = _specs(H, dk, dv, pairs)
    out_shape = [jax.ShapeDtypeStruct(ops[2].shape, v.dtype)]
    out_specs = [vals]
    if states:
        out_shape.append(
            jax.ShapeDtypeStruct((B, H, Tp // CHUNK, dk, dv), F32))
        out_specs.append(state)
    out = _call(
        functools.partial(_fwd_kernel, pairs=pairs, states=states, unit=unit),
        Tp // L, B, H,
        in_specs=[keys, keys, vals, keys, steps] + consts,
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((H, dk, dv), F32)],
        interpret=interpret)(*ops)
    return out[0][:, :T], (out[1] if states else None)


def _tile_rows(x, left):
    """A packed tile's sums over each tile's lanes, as the pair's column
    [2 C, 1]."""
    return jnp.concatenate([_lanes_sum(jnp.where(left, x, 0.0)),
                            _lanes_sum(jnp.where(left, 0.0, x))], axis=0)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, fold_ref, spread_ref,
                unfold_ref, do_ref, sin_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                db_ref, ds_ref, *, pairs: int, unit):
    """``pairs`` pairs of chunks of one head, the steps and a step's chunks
    visited last first. Scratch: the cotangent of the state each head's
    visited chunks leave [H, dk, dv] float32. A chunk's decays, ``T`` and
    ``U`` are rebuilt from the operands and its saved incoming state. The
    cotangent of the running sums ``G`` has no array a decayed operand: an
    operand ``x exp(+-G)`` whose cotangent is ``d`` sends ``+- x exp(+-G) d``,
    which is ``+- x`` times what it sends to ``x``; the reference rows of the
    blocks cancel; the chunk's total takes what ``exp(G_C - G)`` and
    ``exp(G_C)`` send. ``g`` then feeds the running sum of every later
    position of its chunk."""
    C, b, dt = CHUNK, BLOCK, v_ref.dtype
    n, h = pl.program_id(1), pl.program_id(2)

    @pl.when(n == 0)
    def _start():
        ds_ref[h] = jnp.zeros(ds_ref.shape[1:], F32)

    facts = _step_facts(q_ref, k_ref, g_ref, b_ref, h, pairs, unit, dt)
    row, col, left = _packed_iotas()
    first_lanes = _iotas((b, _PAIR))[1] < C
    blocks = _block_inverses([f.a for f in facts], fold_ref, spread_ref,
                             unfold_ref)
    r2, c2 = _iotas((_PAIR, _PAIR))
    later = (((r2 >> 6) == (c2 >> 6)) & (r2 <= c2)).astype(jnp.bfloat16)
    pos = _iotas((_PAIR, 1))[0]
    head = _iotas((_PAIR, db_ref.shape[1]))[1] == h
    ds = ds_ref[h]
    ones = jnp.ones((2 * b, ds.shape[1]), jnp.bfloat16)
    for p in range(pairs - 1, -1, -1):
        f = facts[p]
        rows = slice(p * _PAIR, (p + 1) * _PAIR)
        t_f = _joined(f.a, blocks[p])
        t = _apart(t_f.astype(dt), left)
        do, vf = do_ref[rows, :], v_ref[rows, :].astype(F32)
        # the forward's values again
        vb, kb, u0, w, k_end, qs = _state_operands(f, t, vf, dt)
        whole = _whole(f.cum, ds.shape[1])
        states = [sin_ref[2 * p], sin_ref[2 * p + 1]]
        us = [(u0_c - _dot(w_c, s.astype(dt))).astype(dt)
              for u0_c, w_c, s in zip(_halves(u0), _halves(w), states)]
        u = jnp.concatenate(us, axis=0)
        # o = qk u + qs s;  s' = s whole + k_end^T u;  u = u0 - w s
        dqkm = _pack(_dot(do, u, _NT), left)
        du_in = _dot(_apart(f.qk.astype(dt), left), do, _TN)
        dus, dk_ends, backs, d_wholes = [None] * 2, [None] * 2, [None] * 2, \
            [None] * 2
        for half in (1, 0):
            hs = slice(half * C, (half + 1) * C)
            sb, dsb = states[half].astype(dt), ds.astype(dt)
            du_c = (du_in[hs] + _dot(k_end[hs], dsb)).astype(dt)
            dk_ends[half] = _dot(us[half], dsb, _NT)
            # what q s and w s send to q exp(G) and to w
            backs[half] = _dot(jnp.concatenate([do[hs], du_c], axis=0), sb,
                               _NT)                             # [2 C, dk]
            last = half * C + C - 1
            d_wholes[half] = _exact_dot(ones, ds * states[half], _NT)[:1] \
                * jnp.exp(f.cum[last:last + 1])
            ds = ds * whole[half] + _dot(
                jnp.concatenate([qs[hs], w[hs]], axis=0),
                jnp.concatenate([do[hs], -du_c], axis=0), _TN)
            dus[half] = du_c
        du = jnp.concatenate(dus, axis=0)
        dk_end = jnp.concatenate(dk_ends, axis=0)
        dqs = jnp.concatenate([x[:C] for x in backs], axis=0)
        dw = (-jnp.concatenate([x[C:] for x in backs], axis=0)).astype(dt)
        # u0 = T (beta v);  w = T (beta exp(G) k)
        d_t = _apart(_pack(_dot(du, vb, _NT) + _dot(dw, kb, _NT), left), left)
        dvb, dkb = _dot(t, du, _TN), _dot(t, dw, _TN)
        # T = (I + A)^{-1}: dA = -T^T dT T^T, strictly lower
        t_f = _apart(t_f, left)
        da = jnp.where(row > col, -_pack(_highest(
            _highest(t_f, d_t, _TN), t_f, _NT), left), 0.0)
        # A = beta kk (below the diagonal); kk and qk a block of rows: the
        # block's rows times the columns up to it, both chunks' in one
        # product (the other chunk's lanes zero)
        dkk = da * _pack(f.beta, left)
        dqk = jnp.where(row >= col, dqkm, 0.0)
        y_q, y_k = [[], []], [[], []]
        y_cols = [[None] * (C // b), [None] * (C // b)]
        for r in range(C // b):
            blk = slice(r * b, (r + 1) * b)
            d_both = jnp.concatenate(
                [jnp.where(first_lanes, dqk[blk], 0.0),
                 jnp.where(first_lanes, 0.0, dqk[blk]),
                 jnp.where(first_lanes, dkk[blk], 0.0),
                 jnp.where(first_lanes, 0.0, dkk[blk])], axis=0).astype(dt)
            d_mine = _dot(d_both, f.theirs[r])                  # [4 b, dk]
            d_theirs = _dot(d_both, f.mine[r], _TN)             # [2 C, dk]
            for c in (0, 1):
                decay = f.row_decays[r][c]
                y_q[c].append(d_mine[c * b:(c + 1) * b] * decay)
                y_k[c].append(d_mine[(2 + c) * b:(3 + c) * b] * decay)
                y = d_theirs[c * C:c * C + (r + 1) * b] * f.col_decays[r][c]
                for m in range(r + 1):
                    piece = y[m * b:(m + 1) * b]
                    y_cols[c][m] = piece if y_cols[c][m] is None \
                        else y_cols[c][m] + piece
        y_q, y_k, y_cols = (jnp.concatenate(x[0] + x[1], axis=0)
                            for x in (y_q, y_k, y_cols))
        # what each decayed operand sends to its q or k, by the decay's sign
        dq = y_q + dqs * f.since
        up = y_k + dkb * (f.since * f.beta)
        y_end = dk_end * f.to_end
        down = y_cols + y_end
        spent = y_end * f.kf
        d_end = jnp.where(
            pos < C,
            jnp.sum(spent[:C], axis=0, keepdims=True) + d_wholes[0],
            jnp.sum(spent[C:], axis=0, keepdims=True) + d_wholes[1])
        dcum = f.qf * dq + f.kf * (up - down) \
            + jnp.where((pos & (C - 1)) == C - 1, d_end, 0.0)
        dbeta = _tile_rows(da * f.kk, left) + _lanes_sum(dvb * vf) \
            + _lanes_sum(dkb * f.kf * f.since)
        dq_ref[rows, :] = _unit_back(dq, f.q_norm, dt).astype(dq_ref.dtype)
        dk_ref[rows, :] = _unit_back(up + down, f.k_norm, dt).astype(
            dk_ref.dtype)
        dv_ref[rows, :] = (dvb * f.beta).astype(dv_ref.dtype)
        dg_ref[rows, :] = _exact_dot(later, dcum)
        db_ref[rows, :] = jnp.where(head, dbeta, db_ref[rows, :])
    ds_ref[h] = ds


@functools.partial(jax.jit, static_argnames=("unit", "interpret"))
def kda_bwd(q, k, v, g, beta, s_in, do, *, unit=None,
            interpret: bool = False):
    """The five cotangents of :func:`kda_fwd`'s ``o`` from ``do`` [B, T,
    H dv] and the chunks' incoming states ``s_in``, each as its operand
    came."""
    ops, (B, T, Tp, H, dk, dv, pairs) = _prepare(q, k, v, g, beta)
    L = pairs * _PAIR
    do, = _pad_to_chunks(T, L, do)
    keys, vals, steps, state, consts = _specs(H, dk, dv, pairs, flip=Tp // L)
    dq, dk_, dv_, dg, db = _call(
        functools.partial(_bwd_kernel, pairs=pairs, unit=unit),
        Tp // L, B, H,
        in_specs=[keys, keys, vals, keys, steps] + consts + [vals, state],
        out_specs=[keys, keys, vals, keys, steps],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in ops[:5]],
        scratch_shapes=[pltpu.VMEM((H, dk, dv), F32)],
        interpret=interpret)(*ops, do, s_in)
    return tuple(a[:, :T].astype(like.dtype) for a, like in zip(
        (dq, dk_, dv_, dg, db), (q, k, v, g, beta)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule_pallas(q, k, v, g, beta, unit, interpret):
    return kda_fwd(q, k, v, g, beta, states=False, unit=unit,
                   interpret=interpret)[0]


def _rule_pallas_fwd(q, k, v, g, beta, unit, interpret):
    o, s_in = kda_fwd(q, k, v, g, beta, states=True, unit=unit,
                      interpret=interpret)
    # named as the einsum form names them, so that a policy that keeps the
    # named residuals keeps the kernel's and no second forward is run
    o = checkpoint_name(o, RULE_CHECKPOINT_NAMES[0])
    s_in = checkpoint_name(s_in, RULE_CHECKPOINT_NAMES[1])
    return o, (q, k, v, g, beta, s_in)


def _rule_pallas_bwd(unit, interpret, res, do):
    lowerings.count("kda_scan", "pallas")     # the kernels' own backward
    return kda_bwd(*res, do, unit=unit, interpret=interpret)


_rule_pallas.defvjp(_rule_pallas_fwd, _rule_pallas_bwd)


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------

def _shapes_taken(dk: int, dv: int) -> str:
    """Why the kernels do not take these shapes; "" where they do."""
    if CHUNK != 64:
        return f"chunks of {CHUNK} (the kernels: 64)"
    if (dk, dv) not in _WIDTHS:
        return (f"keys of {dk} and values of {dv} (the kernels: "
                + ", ".join(f"{a} / {b}" for a, b in _WIDTHS) + ")")
    return ""


def kda_lowering(T: int, H: int, dk: int, dv: int, dtype, *,
                 tpu: Optional[bool] = None) -> Tuple[str, str]:
    """``("pallas" | "xla", why)`` for one rule over ``T`` positions of ``H``
    heads: the kernels where they were measured (a TPU, bf16 ``v``, keys and
    values of :data:`_WIDTHS`, chunks of 64), the einsum form everywhere
    else."""
    if tpu is None:
        tpu = _on_tpu()
    if not tpu:
        return "xla", "not a TPU backend"
    if jnp.dtype(dtype) != jnp.bfloat16:
        return "xla", f"{jnp.dtype(dtype).name} operands (the kernels: bf16)"
    why = _shapes_taken(dk, dv)
    return ("xla", why) if why else ("pallas", "")


def kda_rule_lanes(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                   beta: jax.Array, unit=None,
                   interpret: Optional[bool] = None) -> jax.Array:
    """:func:`chunked_kda_rule` on operands with the heads side by side in
    lanes, as the projections and the convolutions write them and as the
    kernels hold them: q, k, g [B, T, H dk], v [B, T, H dv], beta [B, T, H]
    -> o [B, T, H dv]. On a TPU ``[B, T, H, 128]`` is other bytes than
    ``[B, T, H 128]`` (the last two axes are what is tiled), and XLA does not
    always cancel a reshape to the one in front of a kernel against the
    reshape to the other inside it (``g``, made by a fusion of XLA's own,
    was copied before ``kda_bwd``): a caller that holds ``[B, T, H d]`` on
    both sides calls this entry and no array tiled over the heads exists."""
    _, T, H = beta.shape
    dk, dv = q.shape[-1] // H, v.shape[-1] // H
    if interpret is None:
        lowering, _ = kda_lowering(T, H, dk, dv, v.dtype)
    else:
        why = _shapes_taken(dk, dv)
        if why:
            raise ValueError(f"the rule's kernels do not take {why}")
        lowering = "pallas"
    # a rule by the lowering it took, as ``ops/delta_rule.py`` counts its own
    lowerings.count("kda_scan", lowering)
    if lowering == "pallas":
        return _rule_pallas(q, k, v, g, beta, unit, bool(interpret))
    q, k, v, g = (a.reshape(a.shape[:2] + (H, -1)) for a in (q, k, v, g))
    if unit is not None:
        q = unit_heads(q, unit[0], unit[1], v.dtype)
        k = unit_heads(k, 1.0, unit[1], v.dtype)
    o = kda_einsum(q, k, v, g, beta)
    return o.reshape(o.shape[:2] + (H * dv,))


def chunked_kda_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array, unit=None,
                     interpret: Optional[bool] = None) -> jax.Array:
    """q, k [B, T, H, dk] (``q`` scaled, both as the rule reads them), v
    [B, T, H, dv], g [B, T, H, dk] (each key channel's decay's logarithm,
    <= 0, and bounded below so that ``exp(15 |g|)`` is finite: the module's
    docstring) and beta [B, T, H] -> o [B, T, H, dv] in ``v``'s dtype. A ``T``
    that is not a multiple of ``delta_rule.CHUNK`` is padded with positions of
    ``k = 0``, ``g = 0`` and their outputs dropped. ``unit`` (q's length,
    eps): q and k arrive as the convolutions left them (float32) and each
    head's row is first scaled, q to that length and k to 1
    (``delta_rule.unit_heads``): the kernels do it on a head's rows in VMEM
    and hand back the cotangents of the rows as they arrived. ``interpret``
    is the kernels' test handle (None: ask :func:`kda_lowering`; True: the
    kernels, interpreted, in any float dtype, for shapes they take). A
    reshape of :func:`kda_rule_lanes`, which is the rule."""
    B, T, H, _ = q.shape
    o = kda_rule_lanes(*(a.reshape(B, T, -1) for a in (q, k, v, g)), beta,
                       unit=unit, interpret=interpret)
    return o.reshape(B, T, H, -1)
