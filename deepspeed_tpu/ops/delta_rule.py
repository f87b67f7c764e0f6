"""The gated delta rule of a linear-attention layer (Gated DeltaNet), in
chunks.

Per head, over a state ``S`` [dk, dv] that starts at zero, with a decay
``alpha_t = exp(g_t)`` in (0, 1] and a step ``beta_t``::

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

(``q`` arrives scaled). The chunked form computes the same ``o`` from
matmuls. Inside a chunk of ``C`` positions, with ``G_i`` the running sum of
``g`` from the chunk's start, write ``u_i = beta_i (v_i - alpha_i S_{i-1}^T
k_i)``, the value a position really writes. Unrolling the recurrence from the
chunk's incoming state ``S_0`` gives the WY / UT form::

    (I + A) U = diag(beta) V - diag(beta exp(G)) K S_0,
        A_ij = beta_i exp(G_i - G_j) <k_i, k_j>   for i > j, 0 elsewhere
    O   = diag(exp(G)) Q S_0 + (Q K^T o exp(G_i - G_j), i >= j) U
    S_C = exp(G_C) S_0 + (diag(exp(G_C - G)) K)^T U

so with ``T = (I + A)^{-1}`` (unit lower triangular), ``U = T diag(beta) V -
(T diag(beta exp(G)) K) S_0``: one state read and one state update a chunk.
Only the states are sequential.

Precision: the running sums of ``g``, every decay and the triangular inverse
are float32, and every ratio of decays is ``exp(G_i - G_j)`` with ``i >= j``
(the mask goes into the exponent), never a quotient of two exponentials; the
products take operands in ``v``'s dtype (bf16 in training) and accumulate in
float32.

``T`` is found by substitution, not by a series (``sum (-A)^n`` cancels
catastrophically once ``beta <k_i, k_j>`` nears 1): rows one after the other
inside diagonal blocks of 16, 15 vector steps over all blocks at once, then
the blocks joined two by two, ``[[P, 0], [R, Q]]^{-1} =
[[P^{-1}, 0], [-Q^{-1} R P^{-1}, Q^{-1}]]``. Its backward is its own: ``dA =
-T^T dT T^T``, so that no loop is differentiated.

One algorithm, two lowerings (:func:`rule_lowering` picks by what the call
can see: backend, dtype, widths):

* ``"xla"``: :func:`rule_einsum`, the form above as ``jnp.einsum`` with a
  ``lax.scan`` over the chunk states and autodiff's backward (but the
  inverse's). Every array of it goes through HBM; it is what a CPU runs, what
  float32 and widths the kernels do not take run, and the unit tests' oracle.
* ``"pallas"``: two Mosaic kernels behind a ``jax.custom_vjp``
  (:func:`rule_fwd`, :func:`rule_bwd`, each a ``jax.jit`` of its own) that
  visit a sequence's chunks in order (the backward in reverse) with the
  state, or its cotangent, carried in VMEM. A grid step holds eight chunks of
  one head, two by two: a pair's ``[C, C]`` tiles (decays, ``K K^T``, ``Q
  K^T``, ``A``, ``T``) lie side by side in the lanes of one ``[C, 2 C]``
  array, so that the vector unit works on full registers, and enter the
  products as one block-diagonal ``[2 C, 2 C]`` matrix over the pair's 128
  positions. The running sums of ``g`` are a product with a triangle of ones
  (float32-exact); the 16 x 16 diagonal blocks of all eight chunks are
  inverted by substitution on one strip, 15 vector steps for all of them; the
  two joins are float32 products. q, k, v arrive and o leaves as the
  projections write them, ``[B, T, H d]``: with 15 heads of 96 no head block
  of whole lane tiles divides the arrays, so a block is the full width, held
  across the grid's head axis, and the step's head is cut out at its lane
  offset inside VMEM. Where the caller asks (``unit``), the norms a delta
  layer puts on a head's q and k are taken there too, on the float32 rows
  the convolutions left, and the backward hands out the cotangents of those
  rows: a ``[B, T, H, 96]`` view of them costs XLA a copy of the array each
  way, which were a third of the scope's time around the kernels. At keys
  and values of 128 a head's columns are whole lane tiles: a block is then
  the head's own columns and nothing is cut out or copied in VMEM; and there
  q and k may have fewer heads than v (``Hk`` key heads, value head ``i``
  reading key head ``i // (H / Hk)``, Qwen3-Next's 16 for 32): the grid's
  value heads that share a key head follow each other, so its q and k block
  stays in VMEM for them, and the backward adds their dq and dk in the
  output block that stays with it. No ``[B, T, H, dk]`` copy of q or k
  exists in HBM. The
  forward that is differentiated also writes each chunk's incoming state
  (float32), which the backward reads instead of running the recurrence
  again; its output and those states carry a
  ``checkpoint_name`` each, so that a policy that keeps the einsum form's
  products keeps them (``runtime/activation_checkpointing.py``). The backward
  rebuilds a chunk's decays, ``T`` and ``U`` and carries the state's
  cotangent. Outside the kernels stay the transposes of ``g`` and ``beta``
  to a row a head and back.

The rules traced are counted by lowering (``ops/lowerings.py``, site
``delta_scan``) for the step-program table: one for a rule, one more for the
kernels' own backward.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.accelerator.real_accelerator import on_tpu as _on_tpu
from deepspeed_tpu.ops import lowerings
from deepspeed_tpu.ops.ssd_scan import (_NT, _TN, _dot, _exact_dot,
                                        _pad_to_chunks)
from deepspeed_tpu.runtime.activation_checkpointing import (
    RULE_CHECKPOINT_NAMES)

F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
#: positions a chunk holds: the family's kernels' choice (``fla``); no
#: published key names it and no model here asks for another
CHUNK = 64
#: the side of the diagonal blocks inverted row by row
_BASE = 16

def _rows_in_turn(a: jax.Array) -> jax.Array:
    """``(I + a)^{-1}`` of strictly lower triangular ``a`` [..., n, n] by
    forward substitution: row i of the inverse is ``e_i - a[i] T``, which
    reads the rows before it (``a[i, j]`` is 0 from j = i on)."""
    n = a.shape[-1]
    eye = jnp.broadcast_to(jnp.eye(n, dtype=F32), a.shape)

    def row(i, t):
        a_i = jax.lax.dynamic_index_in_dim(a, i, axis=a.ndim - 2,
                                           keepdims=False)      # [..., n]
        new = -jnp.sum(a_i[..., :, None] * t, axis=-2)          # [..., n]
        new = new + (jnp.arange(n) == i).astype(F32)
        return jax.lax.dynamic_update_index_in_dim(t, new, i, a.ndim - 2)

    return jax.lax.fori_loop(1, n, row, eye)


def _inverse(a: jax.Array) -> jax.Array:
    n = a.shape[-1]
    if n <= _BASE or n % 2:
        return _rows_in_turn(a)
    h = n // 2
    # the two diagonal blocks as one batch
    both = _inverse(jnp.stack([a[..., :h, :h], a[..., h:, h:]]))
    p, q = both[0], both[1]
    r = -jnp.matmul(jnp.matmul(q, a[..., h:, :h], precision=_HIGHEST), p,
                    precision=_HIGHEST)
    return jnp.concatenate(
        [jnp.concatenate([p, jnp.zeros_like(r)], axis=-1),
         jnp.concatenate([r, q], axis=-1)], axis=-2)


@jax.custom_vjp
def unit_lower_inverse(a: jax.Array) -> jax.Array:
    """``(I + a)^{-1}`` for ``a`` [..., n, n] float32 whose entries on and
    above the diagonal are zero."""
    return _inverse(a)


def _inverse_fwd(a):
    t = _inverse(a)
    return t, t


def _inverse_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    da = -jnp.matmul(jnp.matmul(tt, dt, precision=_HIGHEST), tt,
                     precision=_HIGHEST)
    n = t.shape[-1]
    return (jnp.where(jnp.tril(jnp.ones((n, n), bool), -1), da, 0.0),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def rule_einsum(q: jax.Array, k: jax.Array, v: jax.Array,
                g: jax.Array, beta: jax.Array) -> jax.Array:
    """:func:`chunked_delta_rule` as einsums; the backward is autodiff's."""
    B, T, H, dk = q.shape
    dv, C, dt = v.shape[-1], CHUNK, v.dtype
    q, k, v, g, beta = _pad_to_chunks(T, C, q, k, v, g, beta)
    N = q.shape[1] // C
    dot = functools.partial(jnp.einsum, preferred_element_type=F32)
    # [B, N, H, C, .]: a chunk of a head is one matrix
    qc, kc, vc = (a.reshape(B, N, C, H, -1).transpose(0, 1, 3, 2, 4)
                  for a in (q, k, v))
    gc, bc = (a.astype(F32).reshape(B, N, C, H).transpose(0, 1, 3, 2)
              for a in (g, beta))
    cum = jnp.cumsum(gc, axis=-1)                               # G_i
    since_start = jnp.exp(cum)                                  # exp(G_i)
    seen = jnp.tril(jnp.ones((C, C), bool))
    ratio = jnp.exp(jnp.where(seen, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                        # i >= j
    kk = dot("bnhid,bnhjd->bnhij", kc, kc)
    a = jnp.where(jnp.tril(jnp.ones((C, C), bool), -1),
                  bc[..., :, None] * ratio * kk, 0.0)
    t = unit_lower_inverse(a).astype(dt)
    kf, vf = kc.astype(F32), vc.astype(F32)
    u0 = dot("bnhij,bnhjd->bnhid", t, (vf * bc[..., None]).astype(dt))
    w = dot("bnhij,bnhjd->bnhid", t,
            (kf * (bc * since_start)[..., None]).astype(dt)).astype(dt)
    k_end = (kf * jnp.exp(cum[..., -1:] - cum)[..., None]).astype(dt)
    whole = jnp.exp(cum[..., -1])                               # [B, N, H]

    def chunk_step(s, xs):
        u0_c, w_c, k_c, whole_c = xs
        u = (u0_c - dot("bhid,bhde->bhie", w_c, s.astype(dt))).astype(dt)
        new = s * whole_c[..., None, None] + dot("bhid,bhie->bhde", k_c, u)
        return new, (s, u)

    _, (s_in, u) = jax.lax.scan(
        chunk_step, jnp.zeros((B, H, dk, dv), F32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (u0, w, k_end, whole)))
    s_in, u = jnp.moveaxis(s_in, 0, 1), jnp.moveaxis(u, 0, 1)
    qk = (dot("bnhid,bnhjd->bnhij", qc, kc) * ratio).astype(dt)
    o = dot("bnhij,bnhje->bnhie", qk, u) \
        + dot("bnhid,bnhde->bnhie", qc, s_in.astype(dt)) \
        * since_start[..., None]
    return o.transpose(0, 1, 3, 2, 4).reshape(B, N * C, H, dv)[:, :T] \
        .astype(dt)


# ---------------------------------------------------------------------------
# the kernels' shared pieces
# ---------------------------------------------------------------------------

#: chunks a grid step takes (a step's own cost is about 1.4 us, a chunk of
#: one head's work under 1 us); its body is unrolled over them, two by two
_CHUNKS_A_STEP = 8
#: (keys, values) a head: what the kernels were built, tested and (the
#: first and the last) measured for; widths need only be whole sublane tiles
_WIDTHS = ((96, 192), (64, 128), (32, 64), (128, 128))
_VMEM_LIMIT = 64 * 1024 * 1024
#: two chunks' positions: the rows of a pair's operands, the lanes of its
#: packed [C, C] tiles
_PAIR = 2 * CHUNK


def _highest(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                               preferred_element_type=F32)


def _iotas(shape):
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _packed_iotas():
    """Row and column of a packed tile [C, 2 C] (a pair's two [C, C] tiles
    side by side in lanes) inside its own tile, and which lanes are the
    first chunk's."""
    row, lane = _iotas((CHUNK, _PAIR))
    return row, lane & (CHUNK - 1), lane < CHUNK


def _pack(x, left):
    """[2 C, 2 C] (or a column [2 C, 1]) -> [C, 2 C]: the two diagonal
    blocks side by side (a column: each chunk's over its tile's lanes)."""
    return jnp.where(left, x[:CHUNK], x[CHUNK:])


def _apart(x, left):
    """Packed [C, 2 C] -> [2 C, 2 C] block diagonal: a pair's two tiles as
    one matrix over the pair's positions."""
    return jnp.concatenate([jnp.where(left, x, jnp.zeros_like(x)),
                            jnp.where(left, jnp.zeros_like(x), x)], axis=0)


@functools.lru_cache(maxsize=None)
def _layout_constants(nb: int):
    """0/1 matrices (numpy, bf16-exact) that move the chunks' 16 x 16
    diagonal blocks between a pair's packed tile and one [C, 128] strip of
    all ``nb`` chunks (lane ``c 16 + j``: chunk c's column j inside its
    block; sublane ``m 16 + i``: block m's row i):

    * ``fold`` [nb C, 128]: row ``c C + m 16 + j`` -> lane ``c 16 + j``;
    * ``spread`` [128, 15 x 128]: lane ``c 16 + j`` -> for step j, every lane
      ``c 16 + x`` of piece j (a block's column j as that step's factor of
      each of its columns);
    * ``unfold`` [128, nb C]: lane ``c 16 + x`` -> lanes ``c C + m 16 + x``
      of every m.
    """
    C, b = CHUNK, _BASE
    lane = np.arange(128)
    tile = np.arange(nb * C)
    fold = ((tile[:, None] // C == lane[None, :] // b)
            & (tile[:, None] % b == lane[None, :] % b))
    piece = np.arange((b - 1) * 128)
    spread = ((lane[:, None] // b == (piece[None, :] % 128) // b)
              & (lane[:, None] % b == piece[None, :] // 128))
    return (fold.astype(np.float32), spread.astype(np.float32),
            fold.T.astype(np.float32))


def _block_inverses(a_list, fold_ref, spread_ref, unfold_ref):
    """``(I + a)^{-1}`` of the 16 x 16 diagonal blocks of each pair's packed
    ``a`` [C, 2 C] (float32, strictly lower), packed alike: substitution in
    float32, column by column, on one strip that holds every chunk's blocks
    side by side (:func:`_layout_constants`), so that a step is three vector
    operations on eight registers for all of them."""
    C, b = CHUNK, _BASE
    row, col, _ = _packed_iotas()
    own = (row >> 4) == (col >> 4)
    strip = None
    for p, a in enumerate(a_list):
        part = _exact_dot(jnp.where(own, a, 0.0),
                          fold_ref[p * _PAIR:(p + 1) * _PAIR, :])
        strip = part if strip is None else strip + part
    factors = _exact_dot(strip, spread_ref[...])        # [C, 15 x 128]
    r, l = _iotas((C, 128))
    t = ((r & (b - 1)) == (l & (b - 1))).astype(F32)
    for j in range(b - 1):
        # row j of every block is final: take it out of the rows below
        rows = jnp.concatenate(
            [jnp.broadcast_to(t[m * b + j:m * b + j + 1, :], (b, 128))
             for m in range(C // b)], axis=0)
        t = t - factors[:, j * 128:(j + 1) * 128] * rows
    back = _exact_dot(t, unfold_ref[...])               # [C, nb C]
    return [jnp.where(own, back[:, p * _PAIR:(p + 1) * _PAIR], 0.0)
            for p in range(len(a_list))]


def _joined(a, d):
    """``(I + a)^{-1}`` (packed [C, 2 C]) from the inverses ``d`` of its
    16 x 16 diagonal blocks: blocks joined two by two, twice, ``[[P, 0],
    [R, Q]]^{-1} = [[P^{-1}, 0], [-Q^{-1} R P^{-1}, Q^{-1}]]``, float32
    products (a pair's two tiles in one product: the packed tile times the
    block-diagonal form of the other factor)."""
    row, col, left = _packed_iotas()
    side = _BASE
    while side < CHUNK:
        n = side.bit_length()
        # the lower left quarter of each block of twice the side
        below = ((row >> n) == (col >> n)) & ((row >> (n - 1))
                                             != (col >> (n - 1)))
        r = jnp.where(below, a, 0.0)
        d = d - _highest(_highest(d, _apart(r, left)), _apart(d, left))
        side *= 2
    return d


def _pair_sums(g_ref, b_ref):
    """From a head's rows [pairs, 2 C] of ``g`` and ``beta`` (a row: a pair
    of chunks): the running sums of ``g`` inside each chunk as rows
    [pairs, 2 C] and as columns [2 C, pairs], ``beta`` as columns, each
    position's own chunk's total as columns, and the first and the second
    chunk's total as rows of equal entries [pairs, 256]. The sums are one
    product with a triangle of ones; the other forms are copies of it, taken
    by products with 0/1 matrices (float32-exact, so that ``G_i - G_i`` is 0
    to the bit and nothing is transposed)."""
    r, c = _iotas((_PAIR, _PAIR))
    same = (r >> 6) == (c >> 6)
    bf = jnp.bfloat16
    last = (c & (CHUNK - 1)) == CHUNK - 1
    at = _iotas((_PAIR, 256))[0]
    cum_r = _exact_dot(g_ref[...], (same & (r <= c)).astype(bf))
    eye = (r == c).astype(bf)
    return (cum_r, _exact_dot(eye, cum_r, _NT),
            _exact_dot(eye, b_ref[...], _NT),
            _exact_dot((same & last).astype(bf), cum_r, _NT),
            (_exact_dot(cum_r, (at == CHUNK - 1).astype(bf)),
             _exact_dot(cum_r, (at == _PAIR - 1).astype(bf))))


def _pair_tiles(q, k, cum_r, cum_c, beta_c):
    """A pair's decays ``exp(G_i - G_j)`` (i >= j; 0 above, by a mask in the
    exponent), ``Q K^T``, ``K K^T`` and the strictly lower ``A``, float32,
    packed [C, 2 C]; ``q``, ``k`` [2 C, dk], ``cum_r`` [1, 2 C], ``cum_c``
    and ``beta_c`` [2 C, 1]."""
    row, col, left = _packed_iotas()
    ratio = jnp.exp(jnp.where(row >= col, _pack(cum_c, left) - cum_r,
                              -jnp.inf))
    both = _dot(jnp.concatenate([q, k], axis=0), k, _NT)       # [4 C, 2 C]
    qk, kk = _pack(both[:_PAIR], left), _pack(both[_PAIR:], left)
    return ratio, qk, kk, jnp.where(
        row > col, _pack(beta_c, left) * ratio * kk, 0.0)


def _one_head(refs, scratch, h, H):
    """The step's head ``h`` of each full-width block ``refs`` copied to its
    scratch: with every head held no head block of whole lane tiles divides
    the arrays (15 x 96 columns are 11.25 tiles), so a block is the array's
    width, held across the grid's head axis, and the head is cut out at its
    lane offset, one branch a head."""
    for i in range(H):
        @pl.when(h == i)
        def _copy(i=i):
            for ref, mine in zip(refs, scratch):
                w = mine.shape[1]
                mine[...] = ref[:, i * w:(i + 1) * w]
    return scratch


def _heads_back(refs, scratch, h, H):
    """The head's results from their scratch to its lanes of the full-width
    blocks."""
    for i in range(H):
        @pl.when(h == i)
        def _out(i=i):
            for ref, mine in zip(refs, scratch):
                w = mine.shape[1]
                ref[:, i * w:(i + 1) * w] = mine[...]


def _halves(x):
    return x[:CHUNK], x[CHUNK:]


def _whole_tiles(dk: int, dv: int) -> bool:
    """Whether a head's columns of q, k and v are whole lane tiles: a block
    is then one head's, cut by the block spec and not in VMEM."""
    return dk % 128 == 0 and dv % 128 == 0


def unit_heads(x: jax.Array, scale: float, eps: float, dtype) -> jax.Array:
    """x [..., d] -> each row scaled to length ``scale``, ``x rsqrt(sum x^2
    + eps) scale`` in ``x``'s dtype, rounded to ``dtype``: the norm a delta
    layer puts on a head's q and k."""
    return (x * (jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)
                 * scale)).astype(dtype)


def _read_rows(ref, rows, eps, length, dt):
    """A pair's rows of q or k as the rule reads them, in ``dt``, and what
    the norm's cotangent needs. ``eps`` None: they arrive normed (and that is
    None). Else they arrive as the convolutions left them and are scaled
    here to ``length`` (:func:`unit_heads`, a head's rows in VMEM); the
    cotangent needs the rows at length 1 and each row's factor, float32."""
    x = ref[rows, :]
    if eps is None:
        return x, None
    x = x.astype(F32)
    r = jax.lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + eps)
    return (x * (r * length)).astype(dt), (x * r, r * length)


def _step_tiles(qh_ref, kh_ref, g_ref, b_ref, pairs, unit, dt):
    """What a step's chunks need that no state enters, forward and backward
    alike: the rows' sums (:func:`_pair_sums`) and, a pair each, q and k as
    :func:`_read_rows` gives them and the tiles (:func:`_pair_tiles`)."""
    sums = _pair_sums(g_ref, b_ref)
    cum_r, cum_c, beta_c = sums[:3]
    length, eps = unit or (None, None)
    q_in, k_in, tiles = [], [], []
    for p in range(pairs):
        rows = slice(p * _PAIR, (p + 1) * _PAIR)
        q_in.append(_read_rows(qh_ref, rows, eps, length, dt))
        k_in.append(_read_rows(kh_ref, rows, eps, 1.0, dt))
        tiles.append(_pair_tiles(q_in[p][0], k_in[p][0], cum_r[p:p + 1, :],
                                 cum_c[:, p:p + 1], beta_c[:, p:p + 1]))
    return sums, q_in, k_in, tiles


def _unit_back(d, normed, dt):
    """The cotangent of the rows as they arrived from that of the rows the
    rule read (rounded to ``dt``, as the rule hands it out)."""
    if normed is None:
        return d
    hat, factor = normed
    d = d.astype(dt).astype(F32)
    return factor * (d - hat * jnp.sum(d * hat, axis=1, keepdims=True))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, fold_ref, spread_ref,
                unfold_ref, o_ref, *rest, pairs: int, H: int, states: bool,
                unit):
    """``pairs`` pairs of chunks of one head. ``rest``: the incoming states'
    block [2 pairs, dk, dv] (where ``states``), then scratch: every head's
    carried state [H, dk, dv] float32 and (where the blocks are the arrays'
    full width) the head's q, k, v and o. ``unit`` (q's length, eps): q and k
    arrive as the convolutions left them."""
    sin_ref = rest[0] if states else None
    s_ref, *mine = rest[1 if states else 0:]
    C, dt = CHUNK, v_ref.dtype
    n, h = pl.program_id(1), pl.program_id(2)
    if mine:
        qh_ref, kh_ref, vh_ref = _one_head((q_ref, k_ref, v_ref), mine[:3],
                                           h, H)
        oh_ref = mine[3]
    else:                       # the blocks are the head's own columns
        qh_ref, kh_ref, vh_ref, oh_ref = q_ref, k_ref, v_ref, o_ref

    @pl.when(n == 0)
    def _start():
        s_ref[h] = jnp.zeros(s_ref.shape[1:], F32)

    (_, cum_c, beta_c, total_c, total_r), q_in, k_in, tiles = _step_tiles(
        qh_ref, kh_ref, g_ref, b_ref, pairs, unit, dt)
    left = _packed_iotas()[2]
    blocks = _block_inverses([t[3] for t in tiles], fold_ref, spread_ref,
                             unfold_ref)
    s = s_ref[h]
    dv = s.shape[1]
    for p in range(pairs):
        rows = slice(p * _PAIR, (p + 1) * _PAIR)
        ratio, qk, _, a = tiles[p]
        t = _apart(_joined(a, blocks[p]).astype(dt), left)      # [2 C, 2 C]
        q, kf, vf = q_in[p][0], k_in[p][0].astype(F32), \
            vh_ref[rows, :].astype(F32)
        b_c, since = beta_c[:, p:p + 1], jnp.exp(cum_c[:, p:p + 1])
        u0 = _dot(t, (vf * b_c).astype(dt))
        w = _dot(t, (kf * (b_c * since)).astype(dt)).astype(dt)
        k_end = (kf * jnp.exp(total_c[:, p:p + 1] - cum_c[:, p:p + 1])
                 ).astype(dt)
        us, from_state = [], []
        # only the states are sequential: a chunk after the other
        for half, (w_c, q_c, u0_c, k_c) in enumerate(zip(
                _halves(w), _halves(q), _halves(u0), _halves(k_end))):
            if states:
                sin_ref[2 * p + half] = s
            ws = _dot(jnp.concatenate([w_c, q_c], axis=0), s.astype(dt))
            us.append((u0_c - ws[:C]).astype(dt))
            from_state.append(ws[C:])
            s = s * jnp.exp(total_r[half][p:p + 1, :dv]) \
                + _dot(k_c, us[-1], _TN)
        oh_ref[rows, :] = (
            _dot(_apart((qk * ratio).astype(dt), left),
                 jnp.concatenate(us, axis=0))
            + jnp.concatenate(from_state, axis=0) * since
        ).astype(oh_ref.dtype)
    s_ref[h] = s
    if mine:
        _heads_back((o_ref,), (oh_ref,), h, H)


def _prepare(q, k, v, g, beta):
    """The kernels' operands from the rule's: padded to whole grid steps
    (positions of ``k = 0``, ``g = 0``), q, k and v with the heads side by
    side in lanes as the projections wrote them, ``g`` and ``beta`` as
    float32 rows a head and pair of chunks [B, H, steps, pairs, 2 C]. q and
    k may have fewer heads than v (``Hk``: a whole number of value heads to
    each)."""
    B, T, Hk, dk = q.shape
    H, dv = v.shape[-2:]
    pairs = min(_CHUNKS_A_STEP // 2, -(-T // _PAIR))
    L = pairs * _PAIR
    q, k, v, g, beta = _pad_to_chunks(T, L, q, k, v, g, beta)
    Tp = q.shape[1]

    def lanes(a):
        return a.reshape(B, Tp, -1)

    def rows(a):
        return a.astype(F32).transpose(0, 2, 1).reshape(B, H, Tp // L, pairs,
                                                        _PAIR)

    consts = tuple(jnp.asarray(m, jnp.bfloat16)
                   for m in _layout_constants(2 * pairs))
    return (lanes(q), lanes(k), lanes(v), rows(g), rows(beta)) + consts, \
        (B, T, Tp, H, Hk, dk, dv, pairs)


def _specs(H, Hk, dk, dv, pairs, flip=None):
    """Block specs over the grid (sequence, step of ``pairs`` pairs of
    chunks, value head); ``flip`` (the steps there are) turns the steps
    around, for the backward. A block of q, k or v is the arrays' full width
    (the kernel cuts its head out), or where a head's columns are whole lane
    tiles the head's own: q's and k's then the key head's that serves the
    step's value head."""
    L = pairs * _PAIR

    def st(n):
        return n if flip is None else flip - 1 - n

    if _whole_tiles(dk, dv):
        rep = H // Hk
        keys = pl.BlockSpec((None, L, dk),
                            lambda b, n, h: (b, st(n), h // rep))
        vals = pl.BlockSpec((None, L, dv), lambda b, n, h: (b, st(n), h))
    else:
        keys = pl.BlockSpec((None, L, H * dk), lambda b, n, h: (b, st(n), 0))
        vals = pl.BlockSpec((None, L, H * dv), lambda b, n, h: (b, st(n), 0))
    rows = pl.BlockSpec((None, None, None, pairs, _PAIR),
                        lambda b, n, h: (b, h, st(n), 0, 0))
    state = pl.BlockSpec((None, None, 2 * pairs, dk, dv),
                         lambda b, n, h: (b, h, st(n), 0, 0))
    consts = [pl.BlockSpec(m.shape, lambda b, n, h: (0, 0))
              for m in _layout_constants(2 * pairs)]
    return keys, vals, rows, state, consts


def _head_scratch(L, whole, *like):
    """A head's columns of a step's blocks ``like`` [B, T, H w]; none where
    the blocks are a head's own (``whole``)."""
    return [] if whole else [pltpu.VMEM((L, a.shape[-1]), a.dtype)
                             for a in like]


def _call(kernel, steps, B, H, **kw):
    return pl.pallas_call(
        kernel, grid=(B, steps, H),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT), **kw)


@functools.partial(jax.jit, static_argnames=("states", "unit", "interpret"))
def rule_fwd(q, k, v, g, beta, *, states: bool, unit=None,
             interpret: bool = False):
    """``o`` [B, T, H, dv] and, where ``states``, each chunk's incoming
    state [B, H, N, dk, dv] float32 (else None)."""
    ops, (B, T, Tp, H, Hk, dk, dv, pairs) = _prepare(q, k, v, g, beta)
    L = pairs * _PAIR
    keys, vals, rows, state, consts = _specs(H, Hk, dk, dv, pairs)
    out_shape = [jax.ShapeDtypeStruct(ops[2].shape, v.dtype)]
    out_specs = [vals]
    if states:
        out_shape.append(
            jax.ShapeDtypeStruct((B, H, Tp // CHUNK, dk, dv), F32))
        out_specs.append(state)
    out = _call(
        functools.partial(_fwd_kernel, pairs=pairs, H=H, states=states,
                          unit=unit), Tp // L, B, H,
        in_specs=[keys, keys, vals, rows, rows] + consts,
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((H, dk, dv), F32)]
        + _head_scratch(L, _whole_tiles(dk, dv), q[..., 0, :], k[..., 0, :],
                        v[..., 0, :], v[..., 0, :]),
        interpret=interpret)(*ops)
    return out[0].reshape(B, Tp, H, dv)[:, :T], (out[1] if states else None)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, fold_ref, spread_ref,
                unfold_ref, do_ref, sin_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                db_ref, ds_ref, *mine, pairs: int, H: int, rep: int, unit):
    """``pairs`` pairs of chunks of one head, the steps and a step's chunks
    visited last first. Scratch: the cotangent of the state each head's
    visited chunks leave [H, dk, dv] float32 and (where the blocks are the
    arrays' full width) the head's q, k, v, do and dq, dk, dv. A chunk's
    decays, ``T`` and ``U`` are rebuilt from the operands and its saved
    incoming state. ``rep`` value heads read one key head: they follow each
    other on the grid, the block of dq and of dk stays in VMEM for them, the
    first writes it and the others add to it (float32 where q and k arrive
    float32, as under ``unit``)."""
    C, dt = CHUNK, v_ref.dtype
    n, h = pl.program_id(1), pl.program_id(2)
    if mine:
        qh_ref, kh_ref, vh_ref, doh_ref = _one_head(
            (q_ref, k_ref, v_ref, do_ref), mine[:4], h, H)
        dqh_ref, dkh_ref, dvh_ref = outs = mine[4:]
    else:                       # the blocks are the head's own columns
        qh_ref, kh_ref, vh_ref, doh_ref = q_ref, k_ref, v_ref, do_ref
        dqh_ref, dkh_ref, dvh_ref = dq_ref, dk_ref, dv_ref
    # whether this value head is the first of its key head's
    first = None if rep == 1 else (h % rep) == 0

    def onto_key_head(ref, rows, d):
        if first is not None:
            d = d + jnp.where(first, 0.0, ref[rows, :].astype(F32))
        ref[rows, :] = d.astype(ref.dtype)

    @pl.when(n == 0)
    def _start():
        ds_ref[h] = jnp.zeros(ds_ref.shape[1:], F32)

    (_, cum_c, beta_c, total_c, total_r), q_in, k_in, tiles = _step_tiles(
        qh_ref, kh_ref, g_ref, b_ref, pairs, unit, dt)
    row, col, left = _packed_iotas()
    blocks = _block_inverses([t[3] for t in tiles], fold_ref, spread_ref,
                             unfold_ref)
    r2, c2 = _iotas((_PAIR, _PAIR))
    lane = _iotas((_PAIR, pairs))[1]
    pos = _iotas((_PAIR, 1))[0]
    lanes_sum = functools.partial(jnp.sum, axis=1, keepdims=True)

    def tile_rows(x):
        """A packed tile's sums over each tile's lanes, as the pair's
        column [2 C, 1]."""
        first = lanes_sum(jnp.where(left, x, 0.0))
        return jnp.concatenate([first, lanes_sum(x) - first], axis=0)

    dcum_cols = jnp.zeros((_PAIR, pairs), F32)
    dbeta_cols = jnp.zeros((_PAIR, pairs), F32)
    ds = ds_ref[h]
    dv_w = ds.shape[1]
    for p in range(pairs - 1, -1, -1):
        rows = slice(p * _PAIR, (p + 1) * _PAIR)
        ratio, qk, kk, a = tiles[p]
        t_f = _joined(a, blocks[p])
        t = _apart(t_f.astype(dt), left)
        q, k, do = q_in[p][0], k_in[p][0], doh_ref[rows, :]
        kf, vf, dof = k.astype(F32), vh_ref[rows, :].astype(F32), \
            do.astype(F32)
        b_c, g_c = beta_c[:, p:p + 1], cum_c[:, p:p + 1]
        since, to_end = jnp.exp(g_c), jnp.exp(total_c[:, p:p + 1] - g_c)
        # the forward's values again
        vb, kb = (vf * b_c).astype(dt), (kf * (b_c * since)).astype(dt)
        u0 = _dot(t, vb)
        w = _dot(t, kb).astype(dt)
        k_end = (kf * to_end).astype(dt)
        states, us, from_state = [], [], []
        for half, (w_c, q_c, u0_c) in enumerate(zip(
                _halves(w), _halves(q), _halves(u0))):
            states.append(sin_ref[2 * p + half])
            ws = _dot(jnp.concatenate([w_c, q_c], axis=0),
                      states[-1].astype(dt))
            us.append((u0_c - ws[:C]).astype(dt))
            from_state.append(ws[C:])
        u, qs = jnp.concatenate(us, axis=0), jnp.concatenate(from_state,
                                                             axis=0)
        qkm_f = qk * ratio
        qkm = _apart(qkm_f.astype(dt), left)
        # o = qkm u + (q s) since;  s' = s whole + k_end^T u
        dqkm = _pack(_dot(do, u, _NT), left)
        dqs = (dof * since).astype(dt)
        du_in = _dot(qkm, do, _TN)
        dus, dk_ends, backs, d_totals = [None] * 2, [None] * 2, [None] * 2, \
            [None] * 2
        for half in (1, 0):
            hs = slice(half * C, (half + 1) * C)
            sb, dsb = states[half].astype(dt), ds.astype(dt)
            du_c = (du_in[hs] + _dot(k_end[hs], dsb)).astype(dt)
            dk_ends[half] = _dot(us[half], dsb, _NT)
            # u = u0 - w s: what q s and w s send to q and w
            backs[half] = _dot(jnp.concatenate([dqs[hs], du_c], axis=0), sb,
                               _NT)                             # [2 C, dk]
            d_totals[half] = jnp.sum(lanes_sum(ds * states[half]), axis=0,
                                     keepdims=True) \
                * jnp.exp(total_c[hs, p:p + 1][:1])
            ds = ds * jnp.exp(total_r[half][p:p + 1, :dv_w]) + _dot(
                jnp.concatenate([q[hs], w[hs]], axis=0),
                jnp.concatenate([dqs[hs], -du_c], axis=0), _TN)
            dus[half] = du_c
        du = jnp.concatenate(dus, axis=0)
        dk_end = jnp.concatenate(dk_ends, axis=0)
        dw = (-jnp.concatenate([b[C:] for b in backs], axis=0)).astype(dt)
        # u0 = T (beta v);  w = T (beta exp(G) k)
        d_t = _apart(_pack(_dot(du, vb, _NT) + _dot(dw, kb, _NT), left), left)
        dvb, dkb = _dot(t, du, _TN), _dot(t, dw, _TN)
        # T = (I + A)^{-1}: dA = -T^T dT T^T, strictly lower
        t_f = _apart(t_f, left)
        da = jnp.where(row > col, -_pack(_highest(
            _highest(t_f, d_t, _TN), t_f, _NT), left), 0.0)
        # A = beta ratio kk (below the diagonal); qkm = qk ratio
        dkk = (da * _pack(b_c, left) * ratio).astype(dt)
        dqk = (dqkm * ratio).astype(dt)
        z = da * a + dqkm * qkm_f
        z_cols = lanes_sum(jnp.where(r2 == c2, jnp.sum(
            z, axis=0, keepdims=True), 0.0))
        both = jnp.concatenate([_apart(dqk, left), _apart(dkk, left)],
                               axis=0)                          # [4 C, 2 C]
        onto_k = _dot(both, k)                                  # [4 C, dk]
        m = lanes_sum(dkb * kf)
        spent = lanes_sum(dk_end * kf) * to_end
        d_total = jnp.where(
            pos < C,
            jnp.sum(spent[:C], axis=0, keepdims=True) + d_totals[0],
            jnp.sum(spent[C:], axis=0, keepdims=True) + d_totals[1])
        dcum = tile_rows(z) - z_cols \
            + (lanes_sum(dof * qs) + m * b_c) * since - spent \
            + jnp.where((pos & (C - 1)) == C - 1, d_total, 0.0)
        dbeta = tile_rows(da * (ratio * kk)) + lanes_sum(dvb * vf) + m * since
        onto_key_head(dqh_ref, rows, _unit_back(
            onto_k[:_PAIR] + jnp.concatenate([b[:C] for b in backs], axis=0),
            q_in[p][1], dt))
        onto_key_head(dkh_ref, rows, _unit_back(
            onto_k[_PAIR:] + _dot(both, jnp.concatenate([q, k], axis=0), _TN)
            + dkb * (b_c * since) + dk_end * to_end, k_in[p][1], dt))
        dvh_ref[rows, :] = (dvb * b_c).astype(dvh_ref.dtype)
        dcum_cols = jnp.where(lane == p, dcum, dcum_cols)
        dbeta_cols = jnp.where(lane == p, dbeta, dbeta_cols)
    ds_ref[h] = ds
    # g feeds the running sum of every later position of its chunk
    dg_ref[...] = _exact_dot(
        dcum_cols, (((r2 >> 6) == (c2 >> 6)) & (r2 >= c2)).astype(
            jnp.bfloat16), _TN)
    db_ref[...] = _exact_dot(dbeta_cols, (r2 == c2).astype(jnp.bfloat16), _TN)
    if mine:
        _heads_back((dq_ref, dk_ref, dv_ref), outs, h, H)


@functools.partial(jax.jit, static_argnames=("unit", "interpret"))
def rule_bwd(q, k, v, g, beta, s_in, do, *, unit=None,
             interpret: bool = False):
    """The five cotangents of :func:`rule_fwd`'s ``o`` from ``do`` and the
    chunks' incoming states ``s_in``."""
    ops, (B, T, Tp, H, Hk, dk, dv, pairs) = _prepare(q, k, v, g, beta)
    L = pairs * _PAIR
    do, = _pad_to_chunks(T, L, do)
    keys, vals, rows, state, consts = _specs(H, Hk, dk, dv, pairs,
                                             flip=Tp // L)
    heads = [a[..., 0, :] for a in (q, k, v)]
    whole = _whole_tiles(dk, dv)
    dq, dk_, dv_, dg, db = _call(
        functools.partial(_bwd_kernel, pairs=pairs, H=H, rep=H // Hk,
                          unit=unit),
        Tp // L, B, H,
        in_specs=[keys, keys, vals, rows, rows] + consts + [vals, state],
        out_specs=[keys, keys, vals, rows, rows],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in ops[:5]],
        scratch_shapes=[pltpu.VMEM((H, dk, dv), F32)]
        + _head_scratch(L, whole, *heads, heads[2])
        + _head_scratch(L, whole, *heads),
        interpret=interpret)(*ops, do.reshape(B, Tp, H * dv), s_in)

    def lanes(a, w):
        return a.reshape(B, Tp, -1, w)[:, :T]

    def rows_back(a, like):
        return a.reshape(B, H, Tp).transpose(0, 2, 1)[:, :T].astype(like.dtype)

    return (lanes(dq, dk), lanes(dk_, dk), lanes(dv_, dv),
            rows_back(dg, g), rows_back(db, beta))


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule_pallas(q, k, v, g, beta, unit, interpret):
    return rule_fwd(q, k, v, g, beta, states=False, unit=unit,
                    interpret=interpret)[0]


def _rule_pallas_fwd(q, k, v, g, beta, unit, interpret):
    o, s_in = rule_fwd(q, k, v, g, beta, states=True, unit=unit,
                       interpret=interpret)
    # the kernel's products are what a policy that keeps dots would have
    # kept of the einsum form: named, so that it can keep them too and the
    # recomputed region holds no second forward
    o = checkpoint_name(o, RULE_CHECKPOINT_NAMES[0])
    s_in = checkpoint_name(s_in, RULE_CHECKPOINT_NAMES[1])
    return o, (q, k, v, g, beta, s_in)


def _rule_pallas_bwd(unit, interpret, res, do):
    lowerings.count("delta_scan", "pallas")   # the kernels' own backward
    return rule_bwd(*res, do, unit=unit, interpret=interpret)


_rule_pallas.defvjp(_rule_pallas_fwd, _rule_pallas_bwd)


def _shapes_taken(dk: int, dv: int, H: int = 1, key_heads: int = 1) -> str:
    """Why the kernels do not take these shapes; "" where they do."""
    if CHUNK != 64:
        return f"chunks of {CHUNK} (the kernels: 64)"
    if (dk, dv) not in _WIDTHS:
        return (f"keys of {dk} and values of {dv} (the kernels: "
                + ", ".join(f"{a} / {b}" for a, b in _WIDTHS) + ")")
    if key_heads != H and not _whole_tiles(dk, dv):
        return (f"{key_heads} key heads for {H} value heads at {dk} / {dv} "
                f"(the kernels share a key head's q and k where a head's "
                f"columns are whole lane tiles: 128 / 128)")
    return ""


def rule_lowering(T: int, H: int, dk: int, dv: int, dtype, *,
                  tpu: Optional[bool] = None,
                  key_heads: Optional[int] = None) -> Tuple[str, str]:
    """``("pallas" | "xla", why)`` for one rule over ``T`` positions of ``H``
    (value) heads whose q and k have ``key_heads`` (None: as many): the
    kernels where they were measured (a TPU, bf16 operands, the key and value
    widths of :data:`_WIDTHS`, fewer key heads than value heads only at
    128 / 128, chunks of 64), the einsum form everywhere else."""
    if tpu is None:
        tpu = _on_tpu()
    if not tpu:
        return "xla", "not a TPU backend"
    if jnp.dtype(dtype) != jnp.bfloat16:
        return "xla", f"{jnp.dtype(dtype).name} operands (the kernels: bf16)"
    why = _shapes_taken(dk, dv, H, key_heads or H)
    return ("xla", why) if why else ("pallas", "")


def chunked_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array,
                       g: jax.Array, beta: jax.Array, unit=None,
                       interpret: Optional[bool] = None) -> jax.Array:
    """q, k [B, T, Hk, dk] (``q`` scaled, both as the rule reads them), v
    [B, T, H, dv], g [B, T, H] (the decay's logarithm, <= 0) and beta
    [B, T, H] -> o [B, T, H, dv] in ``v``'s dtype. ``Hk`` divides ``H``:
    value head ``i`` reads key head ``i // (H / Hk)`` (the kernels read a
    key head's q and k once for its value heads; the einsum form repeats
    them). A ``T`` that is not a
    multiple of :data:`CHUNK` is padded with positions of ``k = 0``, ``g = 0``
    (the state passes through them unchanged) and their outputs dropped.
    ``unit`` (q's length, eps): q and k arrive as the convolutions left them
    (float32) and the rule first scales each head's row, q to that length
    and k to 1 (:func:`unit_heads`, rounded to ``v``'s dtype): the kernels do
    it on a head's rows in VMEM, where a ``[B, T, H, dk]`` view of 96-wide
    heads costs XLA a copy of the array each way. ``interpret`` is the
    kernels' test handle (None: ask :func:`rule_lowering`; True: the
    kernels, interpreted, in any float dtype, for shapes they take)."""
    B, T, Hk, dk = q.shape
    H, dv = v.shape[-2:]
    if H % Hk or k.shape != q.shape:
        raise ValueError(f"q {q.shape} and k {k.shape} for v {v.shape}: as "
                         f"many key heads each, a divisor of the value heads")
    if interpret is None:
        lowering, _ = rule_lowering(T, H, dk, dv, v.dtype, key_heads=Hk)
    else:
        why = _shapes_taken(dk, dv, H, Hk)
        if why:
            raise ValueError(f"the rule's kernels do not take {why}")
        lowering = "pallas"
    # a rule by the lowering it took (``ops/ssd_scan.py`` counts its scans
    # the same way)
    lowerings.count("delta_scan", lowering)
    # the rows of q and k the rule reads: a key head's once in the kernels,
    # once a value head in the einsum form, which repeats them
    lowerings.count("delta_qk_rows", lowering,
                    2 * B * T * (Hk if lowering == "pallas" else H))
    if lowering == "pallas":
        return _rule_pallas(q, k, v, g, beta, unit, bool(interpret))
    if unit is not None:
        q = unit_heads(q, unit[0], unit[1], v.dtype)
        k = unit_heads(k, 1.0, unit[1], v.dtype)
    if Hk != H:
        q, k = (jnp.repeat(a, H // Hk, axis=2) for a in (q, k))
    return rule_einsum(q, k, v, g, beta)
