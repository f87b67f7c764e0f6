"""Attention over the keys a learned indexer picks for each query (DeepSeek
sparse attention: the lightning indexer of DeepSeek-V3.2-Exp), the exact
selection of each query's ``topk`` keys, and the loss that trains the indexer
beside the model: one ``jax.custom_vjp``, in query tiles, with no
``[heads, T, T]`` array forward or backward. The selection and the attention
over the set are plain ``jax.numpy``; the indexer's weighted head-score sum
and the gradient of its loss are two Mosaic kernels where the platform and
the shapes allow, and ``jax.numpy`` lines elsewhere.

For one row, queries ``q`` [T, H, d] and keys and values ``k``, ``v``
[T, K, d] (grouped: key-value head g serves query heads g H/K ..), the
indexer's queries ``qi`` [T, J, c], its one key head ``ki`` [T, c] and its
head weights ``wi`` [T, J] (float32)::

    I[t, s]  = sum_j wi[t, j] relu(qi[t, j] . ki[s])          s <= t
    S_t      = the topk keys s <= t of largest I[t, s] (all of them where
               t < topk; a tie at the threshold goes to the lower position)
    a[t,h,s] = softmax_{s in S_t}(q[t, h] . k[s, g(h)] / sqrt(d))
    o[t, h]  = sum_{s in S_t} a[t, h, s] v[s, g(h)]
    p[t, s]  = (1 / H) sum_h a[t, h, s]
    kl       = sum_t sum_{s in S_t} p[t, s] (log p[t, s]
                                             - log softmax_{S_t}(I[t, .])[s])

``o`` gets its gradient through the softmax over the set and none through
the set; ``kl`` gives ``qi``, ``ki`` and ``wi`` their gradient (``p`` is a
constant to it) and ``q``, ``k``, ``v`` none. The indexer's products are
rounded to the compute dtype (the head scores ``qi . ki``) and summed over the
heads in float32.

**The form.** Query tiles of ``tile`` rows, in up to :data:`GROUPS` runs of
tiles: a run's tiles score the keys up to the run's last position (a static
length, so that the first tiles do not pay for the whole row), the attention
loops over key tiles up to the query tile's own (a dynamic trip count). For a
tile: the indexer's weighted sum of head scores ``I`` [tile, S]; the
threshold, the k-th largest of each row, by 32 counts of ``score >= candidate``
over the bits of the float (no sort: the count is exact, and so is the set),
and only where a row has more scores at the threshold than places left, a
second search over the position that admits the lower ones; one pass over the
key tiles for each head's log-sum-exp, a second for the output and ``p``; then
the indexer's loss and, ``p`` being a constant, its whole gradient (``d kl / d
I = softmax_S(I) - p``), which the forward keeps as residuals: the backward
multiplies them by the loss's cotangent. The backward of the attention reads
the set from a bit-packed mask [T, S / 8] the forward left.

**The indexer's two lowerings** (:func:`index_lowering` picks by what the
call can see: backend, dtype, shapes; ``lowerings`` site ``dsa`` counts an
op by the one it took):

* ``"jnp"``: :func:`index_scores` and :func:`index_grads`, einsums. The
  heads' relu'd scores ``r`` [tile, J, S] are an array in the compute dtype:
  written, read back for the weighted sum, kept across the attention for
  the loss, whose ``d_r`` is a second array of that size read by two
  products. What a CPU runs, what float32 and shapes the kernels do not take
  run, and the unit tests' oracle.
* ``"pallas"``: :func:`dsa_index_fwd` and :func:`dsa_index_bwd`, a grid over
  the key tiles of one query tile, the tile's index a scalar-prefetch
  operand: a key tile after the query tile's own is not computed (``select``
  masks it; the forward writes zeros there) and, its index map clamped to
  the last live tile, not fetched. A head's scores [tile, tile] exist only
  in VMEM, forward and backward: the product accumulated in float32 and
  rounded to the compute dtype as the einsum's output is, ``relu``, times
  ``wi[:, j]`` and summed over the heads in float32 (``I``, 4 bytes a pair,
  the one array that leaves); the gradient kernel rebuilds them the same way
  from ``qi`` and ``ki``, forms ``d_r_j = d_scores wi_j [x_j > 0]`` in the
  compute dtype and accumulates ``dqi`` over the key tiles in VMEM, writes
  each key tile's ``dki`` once and ``dwi = sum_s d_scores relu(x_j)`` from
  lane-partial float32 sums. ``qi`` goes in as [T, J c], the heads side by
  side in lanes; with heads of 64 a lane tile holds two, and so that no head
  is cut out of a lane tile the keys go in as two copies [S, 128], each
  zero in the other head's lanes (:func:`lane_keys`): a product with a copy
  is one head's scores at the MXU's full depth, ``d_r_j`` times a copy lands
  in the head's own lanes of ``dqi``, and ``d_r_j^T`` times the lane tile of
  ``qi`` holds ``dki``'s part in the head's lanes (the other half is
  dropped: a 64-wide product costs a 128-wide one on this MXU either way).
  The same arithmetic as the lines but the order of the float32 sums over
  the heads and the key tiles, and ``dwi``'s product, float32 here where the
  einsum rounds ``d_scores`` to bf16 on a TPU.

What a recomputation policy may keep of the forward is named
(:data:`RESIDUAL_NAMES`), as ``ops/flash_attention.py`` names its own.
"""

from __future__ import annotations

import math
from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.accelerator.real_accelerator import on_tpu as _on_tpu
from deepspeed_tpu.ops import lowerings
from deepspeed_tpu.ops.ssd_scan import _NT, _TN, _dot

F32 = jnp.float32
#: the runs of query tiles, each scoring keys up to its own last position
GROUPS = 4
#: the queries of a row whose sets the forward also hands out, packed, for
#: whoever checks the selection (the step record's ``dsa_probe_sets``)
PROBES = 8
#: the ``checkpoint_name`` of what the backward reads of the forward beside
#: its inputs: the output, the heads' log-sum-exps, the packed set and the
#: indexer's gradients to a unit cotangent (literals, as
#: ``runtime/activation_checkpointing.py`` lists them)
RESIDUAL_NAMES = ("dsa_out", "dsa_lse", "dsa_set", "dsa_index_grads")


def selected_share(T: int, topk: int) -> float:
    """Selected (query, key) pairs over causal pairs of a row of ``T``."""
    k = min(int(topk), int(T))
    return (k * (k + 1) / 2 + (T - k) * k) / (T * (T + 1) / 2)


def probe_positions(T: int, n: int = PROBES) -> List[int]:
    """The last query of each of ``n`` equal stretches of a row of ``T``."""
    return [(j + 1) * T // n - 1 for j in range(n)]


def runs(T: int, tile: int, groups: int = GROUPS) -> List[Tuple[int, int]]:
    """``[(first tile, tiles)]`` of the runs of a row of ``T``."""
    n = T // tile
    g = max(1, min(groups, n))
    cuts = [round(j * n / g) for j in range(g + 1)]
    return [(lo, hi - lo) for lo, hi in zip(cuts, cuts[1:]) if hi > lo]


def sortable(x: jax.Array) -> jax.Array:
    """float32 -> uint32, order-preserving (``-0.0`` read as ``0.0``); no
    finite float maps to 0."""
    x = jnp.where(x == 0, 0.0, x)
    b = lax.bitcast_convert_type(x, jnp.int32)
    key = b ^ ((b >> 31) & jnp.int32(0x7FFFFFFF))
    return lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(0x80000000)


def kth_largest(u: jax.Array, k: int) -> jax.Array:
    """The k-th largest of each row of ``u`` [n, S] uint32, S >= k: the
    largest ``x`` with ``count(u >= x) >= k``, built bit by bit."""
    def bit(i, x):
        cand = x | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(u >= cand[:, None], axis=-1, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, x)

    return lax.fori_loop(0, 32, bit, jnp.zeros(u.shape[:1], jnp.uint32))


def _tie_cut(eq: jax.Array, need: jax.Array) -> jax.Array:
    """The position up to which a row's scores at the threshold are taken,
    ``eq`` [n, S] marking them and ``need`` [n] >= 1 the places left: the
    position of the ``need``-th of them."""
    S = eq.shape[-1]
    pos = jnp.arange(S, dtype=jnp.int32)
    bits = max(1, (S - 1).bit_length())

    def bit(i, p):
        cand = p | (jnp.int32(1) << (bits - 1 - i))
        below = jnp.sum(eq & (pos[None] < cand[:, None]), axis=-1,
                        dtype=jnp.int32)
        return jnp.where(below < need, cand, p)

    return lax.fori_loop(0, bits, bit, jnp.zeros(eq.shape[:1], jnp.int32))


def select(scores: jax.Array, q_pos: jax.Array, topk: int) -> jax.Array:
    """The set as a mask [n, S]: for the query at position ``q_pos[i]`` the
    ``topk`` keys ``s <= q_pos[i]`` of largest ``scores[i, s]``, ties to the
    lower position; every such key where there are no more than ``topk``."""
    n, S = scores.shape
    pos = jnp.arange(S, dtype=jnp.int32)
    causal = pos[None] <= q_pos[:, None]
    if S <= topk:
        return causal
    u = jnp.where(causal, sortable(scores), jnp.uint32(0))
    full = q_pos + 1 > topk
    tau = jnp.where(full, kth_largest(u, topk), jnp.uint32(0))
    gt = u > tau[:, None]
    eq = (u == tau[:, None]) & causal
    need = topk - jnp.sum(gt, axis=-1, dtype=jnp.int32)
    tied = full & (jnp.sum(eq, axis=-1, dtype=jnp.int32) > need)
    cut = lax.cond(jnp.any(tied),
                   lambda: jnp.where(tied, _tie_cut(eq, jnp.maximum(need, 1)),
                                     S),
                   lambda: jnp.full((n,), S, jnp.int32))
    return gt | (eq & (pos[None] <= cut[:, None]))


def index_scores(qi: jax.Array, ki: jax.Array, wi: jax.Array):
    """``(I [n, S] float32, the heads' relu'd scores [n, J, S])`` of the
    indexer's queries ``qi`` [n, J, c] against its keys ``ki`` [S, c]."""
    r = jax.nn.relu(jnp.einsum("qjc,sc->qjs", qi, ki))
    return jnp.einsum("qjs,qj->qs", r.astype(F32), wi.astype(F32)), r


def index_grads(d_scores: jax.Array, r: jax.Array, qi: jax.Array,
                ki: jax.Array, wi: jax.Array):
    """``(dqi [n, J, c], dki [S, c], dwi [n, J])``, float32, from ``d_scores``
    [n, S] float32, the gradient by ``I``, and :func:`index_scores`' ``r``:
    the heads' cotangent ``d_r`` in the compute dtype, three products."""
    d_r = (d_scores[:, None, :] * wi.astype(F32)[:, :, None]
           * (r > 0)).astype(r.dtype)
    dqi = jnp.einsum("qjs,sc->qjc", d_r, ki, preferred_element_type=F32)
    dki = jnp.einsum("qjs,qjc->sc", d_r, qi, preferred_element_type=F32)
    return dqi, dki, jnp.einsum("qs,qjs->qj", d_scores, r.astype(F32))


# ---------------------------------------------------------------------------
# the indexer's kernels
# ---------------------------------------------------------------------------

_LANES = 128
#: the heads the kernels were built, tested and measured for: two of them
#: fill a lane tile of ``qi`` [T, J c]
_HEAD = 64
#: the most heads a query tile may have (the cell's 16 are 1024 columns):
#: what the gradient kernel holds in VMEM grows with them
_MAX_HEADS = 32
#: the gradient kernel holds a query tile's ``qi`` twice, its gradient three
#: times and a lane tile of partial sums a head (14.5 MB at 512 x 16 x 64
#: beside 3 MB of a tile pair's values): over Mosaic's default 16 MiB scope
_VMEM_LIMIT = 64 * 1024 * 1024


def _shapes_taken(tile: int, J: int, c: int) -> str:
    """Why the kernels do not take these shapes; "" where they do."""
    if tile % _LANES:
        return f"tiles of {tile} (the kernels: a multiple of {_LANES})"
    if c != _HEAD or J % 2 or J > _MAX_HEADS:
        return (f"{J} heads of {c} (the kernels: pairs of heads of {_HEAD}, "
                f"at most {_MAX_HEADS})")
    return ""


def index_lowering(tile: int, J: int, c: int, dtype, *,
                   tpu: Optional[bool] = None) -> Tuple[str, str]:
    """``("pallas" | "jnp", why)`` for the indexer's scores and their
    gradient over tiles of ``tile`` queries and keys: the kernels where they
    were measured (a TPU, bf16 operands, shapes :func:`_shapes_taken`
    accepts), the ``jax.numpy`` lines everywhere else."""
    if tpu is None:
        tpu = _on_tpu()
    if not tpu:
        return "jnp", "not a TPU backend"
    if jnp.dtype(dtype) != jnp.bfloat16:
        return "jnp", f"{jnp.dtype(dtype).name} operands (the kernels: bf16)"
    why = _shapes_taken(tile, J, c)
    return ("jnp", why) if why else ("pallas", "")


def lane_keys(ki: jax.Array) -> jax.Array:
    """``ki`` [S, 64] -> [2, S, 128]: copy h holds the keys in lanes 64 h ..
    64 (h + 1) and zeros in the other half, so that a lane tile of ``qi``
    [n, J c] (two heads side by side) times copy h is the h-th head's scores
    alone, at the MXU's full depth, and no head is cut out of a lane tile."""
    return jnp.stack([jnp.pad(ki, ((0, 0), (0, _HEAD))),
                      jnp.pad(ki, ((0, 0), (_HEAD, 0)))])


def _head_scores_relu(q_ref, k_ref, j: int):
    """``relu(qi_j . ki)`` of the query tile's head ``j`` against the key
    tile, [tile, tk] float32 holding values of the compute dtype: the
    product accumulated in float32 and rounded as the einsum's output is.
    Also the head's lane tile of ``qi`` and its copy of the keys."""
    qp = q_ref[:, j // 2 * _LANES:(j // 2 + 1) * _LANES]
    kk = k_ref[j % 2]
    x = _dot(qp, kk, _NT)
    return jnp.maximum(x, 0.0).astype(q_ref.dtype).astype(F32), qp, kk


def _index_fwd_kernel(i_ref, q_ref, k_ref, w_ref, o_ref):
    """One key tile of a query tile's weighted head-score sum. ``i_ref``:
    the query tile's index (scalar prefetch); key tiles after it are zeros
    (``select`` masks them) and, their index map clamped, not fetched."""
    kj = pl.program_id(0)

    @pl.when(kj > i_ref[0])
    def _dead():
        o_ref[...] = jnp.zeros(o_ref.shape, F32)

    @pl.when(kj <= i_ref[0])
    def _live():
        w = w_ref[...]
        acc = None
        for j in range(w.shape[1]):
            r, _, _ = _head_scores_relu(q_ref, k_ref, j)
            term = r * w[:, j:j + 1]
            acc = term if acc is None else acc + term
        o_ref[...] = acc


def _index_bwd_kernel(i_ref, q_ref, k_ref, w_ref, ds_ref, dq_ref, dk_ref,
                      dw_ref, dq_acc, dw_acc):
    """One key tile of a query tile's gradient of the indexer's loss: the
    head scores rebuilt as the forward built them, ``d_r_j = d_scores wi_j
    [x_j > 0]`` in the compute dtype, and its three products. ``dq_acc``
    [tile, J c] and ``dw_acc`` [J, tile, 128] (a head's ``sum_s d_scores
    relu(x_j)`` in lane-partial sums) are carried over the key tiles and
    written out at the query tile's own, the last that is live; ``dk_ref``
    is this key tile's [tk, 128]: the even heads' sum in the first 64 lanes,
    the odd heads' in the others (the caller adds the halves)."""
    kj, i = pl.program_id(0), i_ref[0]
    heads, dt = w_ref.shape[1], q_ref.dtype

    @pl.when(kj == 0)
    def _start():
        dq_acc[...] = jnp.zeros(dq_acc.shape, F32)
        dw_acc[...] = jnp.zeros(dw_acc.shape, F32)

    @pl.when(kj > i)
    def _dead():
        dk_ref[...] = jnp.zeros(dk_ref.shape, F32)

    @pl.when(kj <= i)
    def _live():
        w, ds = w_ref[...], ds_ref[...]
        dk = [None, None]
        for j in range(heads):
            r, qp, kk = _head_scores_relu(q_ref, k_ref, j)
            d_r = jnp.where(r > 0, ds * w[:, j:j + 1], 0.0).astype(dt)
            # the head's own half of the lane tile: the other is zero
            mine = _dot(d_r, kk)                            # [tile, 128]
            dq = mine if j % 2 == 0 else dq + mine
            if j % 2:
                dq_acc[:, j // 2 * _LANES:(j // 2 + 1) * _LANES] += dq
            # the head's half is its own, the other its neighbour's: dropped
            theirs = _dot(d_r, qp, _TN)                     # [tk, 128]
            dk[j % 2] = theirs if j < 2 else dk[j % 2] + theirs
            pw = ds * r
            dw_acc[j] += sum(pw[:, n:n + _LANES]
                             for n in range(0, pw.shape[1], _LANES))
        lane = lax.broadcasted_iota(jnp.int32, dk_ref.shape, 1)
        dk_ref[...] = jnp.where(lane < _HEAD, dk[0], dk[1])

    @pl.when(kj == i)
    def _finish():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)
        lane = lax.broadcasted_iota(jnp.int32, dw_ref.shape, 1)
        out = jnp.zeros(dw_ref.shape, F32)
        for j in range(heads):
            out = jnp.where(lane == j, jnp.sum(dw_acc[j], axis=1,
                                               keepdims=True), out)
        dw_ref[...] = out


def _index_specs(tile: int, W: int, J: int):
    """Block specs over the grid of key tiles, the query tile's index ``i``
    prefetched: the query tile's rows of ``qi`` [T, J c] and ``wi`` [T, J];
    the key tile's rows of the two lane copies and its columns of an operand
    over the keys (the last live tile's again after it: nothing is fetched
    for a dead step); a key tile's columns of a result over the keys."""
    rows = lambda width: pl.BlockSpec(  # noqa: E731
        (tile, width), lambda kj, i: (i[0], 0))
    live = lambda kj, i: jnp.minimum(kj, i[0])  # noqa: E731
    keys = pl.BlockSpec((2, tile, _LANES), lambda kj, i: (0, live(kj, i), 0))
    read = pl.BlockSpec((tile, tile), lambda kj, i: (0, live(kj, i)))
    written = pl.BlockSpec((tile, tile), lambda kj, i: (0, kj))
    return rows(W), keys, rows(J), read, written


def _index_call(kernel, n_keys: int, interpret: bool, name: str, in_specs,
                out_specs, out_shape, scratch_shapes=()):
    return pl.pallas_call(
        kernel, grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_keys,), in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes),
        out_shape=out_shape, compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name)


@partial(jax.jit, static_argnames=("tile", "keys", "interpret"))
def dsa_index_fwd(i, qi, kk, wi, *, tile: int, keys: int,
                  interpret: bool = False):
    """``I`` [tile, keys] float32 of query tile ``i`` of a row: ``qi``
    [T, J c] (the heads side by side), ``kk`` :func:`lane_keys` of the row's
    keys, ``wi`` [T, J] float32; the columns of key tiles after the query
    tile's own are zeros."""
    q, k, w, _, written = _index_specs(tile, qi.shape[1], wi.shape[1])
    return _index_call(
        _index_fwd_kernel, keys // tile, interpret, "dsa_index_fwd",
        in_specs=[q, k, w], out_specs=written,
        out_shape=jax.ShapeDtypeStruct((tile, keys), F32))(
            i.reshape(1), qi, kk, wi)


@partial(jax.jit, static_argnames=("tile", "interpret"))
def dsa_index_bwd(i, qi, kk, wi, d_scores, *, tile: int,
                  interpret: bool = False):
    """``(dqi_t [tile, J c] in qi's dtype, dki [keys, c] float32, dwi_t
    [tile, J] float32)`` of query tile ``i`` from ``d_scores`` [tile, keys]
    float32, the gradient of the loss by ``I``: ``dki`` is the tile's
    contribution to every key it may see, zero after them."""
    keys, W, J = d_scores.shape[1], qi.shape[1], wi.shape[1]
    q, k, w, read, _ = _index_specs(tile, W, J)
    held = lambda width: pl.BlockSpec(  # noqa: E731
        (tile, width), lambda kj, i: (0, 0))
    dq, dk, dw = _index_call(
        _index_bwd_kernel, keys // tile, interpret, "dsa_index_bwd",
        in_specs=[q, k, w, read],
        out_specs=[held(W), pl.BlockSpec((tile, _LANES),
                                         lambda kj, i: (kj, 0)), held(J)],
        out_shape=[jax.ShapeDtypeStruct((tile, W), qi.dtype),
                   jax.ShapeDtypeStruct((keys, _LANES), F32),
                   jax.ShapeDtypeStruct((tile, J), F32)],
        scratch_shapes=[pltpu.VMEM((tile, W), F32),
                        pltpu.VMEM((J, tile, _LANES), F32)])(
            i.reshape(1), qi, kk, wi, d_scores)
    return dq, dk[:, :_HEAD] + dk[:, _HEAD:], dw


def _pack(mask: jax.Array) -> jax.Array:
    """bool [n, S] -> uint8 [n, S / 8], bit b of byte i is column 8 i + b."""
    n, S = mask.shape
    m = mask.reshape(n, S // 8, 8).astype(jnp.uint8)
    return jnp.sum(m << jnp.arange(8, dtype=jnp.uint8), axis=-1,
                   dtype=jnp.uint8)


def _unpack(bits: jax.Array) -> jax.Array:
    n, w = bits.shape
    m = (bits[:, :, None] >> jnp.arange(8, dtype=jnp.uint8)) & jnp.uint8(1)
    return m.reshape(n, w * 8).astype(bool)


def _by_kv_head(x: jax.Array, K: int) -> jax.Array:
    """[n, H, d] -> [K, G, n, d], query heads by the key-value head that
    serves them."""
    n, H, d = x.shape
    return x.reshape(n, K, H // K, d).transpose(1, 2, 0, 3)


def _from_kv_head(x: jax.Array) -> jax.Array:
    K, G, n, d = x.shape
    return x.transpose(2, 0, 1, 3).reshape(n, K * G, d)


def _head_scores(qg: jax.Array, kc: jax.Array, scale: float) -> jax.Array:
    """[K, G, n, d] x [K, m, d] -> float32 [K, G, n, m]."""
    return jnp.einsum("kgqd,ksd->kgqs", qg, kc,
                      preferred_element_type=F32) * scale


def _row_forward(q, k, v, qi, ki, wi, *, topk: int, tile: int,
                 interpret: Optional[bool]):
    """One row's forward: ``(o [T, H, d], kl, lse [T, H] float32, the set
    packed [T, T / 8], d kl / d (qi, ki, wi))``. ``interpret``: None for the
    ``jax.numpy`` lines of the indexer's scores and their gradient, else
    the kernels (True: interpreted)."""
    T, H, d = q.shape
    K = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    kt, vt = k.transpose(1, 0, 2), v.transpose(1, 0, 2)        # [K, T, d]
    outs = []
    dki = jnp.zeros(ki.shape, F32)
    kernels = interpret is not None
    if kernels:
        with jax.named_scope("dsa_indexer"):
            qi_rows, kk, wi = qi.reshape(T, -1), lane_keys(ki), wi.astype(F32)
    for first, n_tiles in runs(T, tile):
        S = (first + n_tiles) * tile

        def one_tile(dki, i, S=S):
            t0 = i * tile
            q_pos = t0 + jnp.arange(tile, dtype=jnp.int32)
            chunks = i + 1                  # key tiles up to the query's own
            with jax.named_scope("dsa_indexer"):
                if kernels:
                    scores = dsa_index_fwd(i, qi_rows, kk, wi, tile=tile,
                                           keys=S, interpret=interpret)
                else:
                    qi_t = lax.dynamic_slice_in_dim(qi, t0, tile)
                    wi_t = lax.dynamic_slice_in_dim(wi, t0, tile)
                    scores, r = index_scores(qi_t, ki[:S], wi_t)
            with jax.named_scope("dsa_select"):
                sel = select(scores, q_pos, topk)
                packed = jnp.zeros((tile, T // 8), jnp.uint8
                                   ).at[:, :S // 8].set(_pack(sel))
            with jax.named_scope("dsa_attend"):
                qg = _by_kv_head(lax.dynamic_slice_in_dim(q, t0, tile), K)
                G = qg.shape[1]

                def logits(j):
                    kc = lax.dynamic_slice_in_dim(kt, j * tile, tile, axis=1)
                    m = lax.dynamic_slice_in_dim(sel, j * tile, tile, axis=1)
                    return jnp.where(m[None, None],
                                     _head_scores(qg, kc, scale), -jnp.inf), m

                def lse_pass(j, ml):
                    m_run, l_run = ml
                    s, _ = logits(j)
                    m_new = jnp.maximum(m_run, s.max(axis=-1))
                    # (a key tile may hold none of a row's set)
                    safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                    l_run = l_run * jnp.exp(m_run - safe) + jnp.exp(
                        s - safe[..., None]).sum(axis=-1)
                    return m_new, l_run

                m_run, l_run = lax.fori_loop(
                    0, chunks, lse_pass,
                    (jnp.full((K, G, tile), -jnp.inf, F32),
                     jnp.zeros((K, G, tile), F32)))
                lse = m_run + jnp.log(l_run)                   # [K, G, tile]

                def out_pass(j, acc_p):
                    acc, p = acc_p
                    s, _ = logits(j)
                    a = jnp.exp(s - lse[..., None])
                    vc = lax.dynamic_slice_in_dim(vt, j * tile, tile, axis=1)
                    acc = acc + jnp.einsum(
                        "kgqs,ksd->kgqd", a.astype(v.dtype), vc,
                        preferred_element_type=F32)
                    p = lax.dynamic_update_slice_in_dim(
                        p, a.sum(axis=(0, 1)) / H, j * tile, axis=1)
                    return acc, p

                acc, p = lax.fori_loop(
                    0, chunks, out_pass,
                    (jnp.zeros((K, G, tile, d), F32),
                     jnp.zeros((tile, S), F32)))
                o_t = _from_kv_head(acc).astype(q.dtype)
                lse_t = lse.transpose(2, 0, 1).reshape(tile, H)
            with jax.named_scope("dsa_loss"):
                masked = jnp.where(sel, scores, -jnp.inf)
                log_q = scores - jax.nn.logsumexp(masked, axis=-1,
                                                  keepdims=True)
                on = sel & (p > 0)
                kl = jnp.sum(jnp.where(on, p * (jnp.log(jnp.where(
                    on, p, 1.0)) - log_q), 0.0))
                d_scores = jnp.where(sel, jnp.exp(jnp.where(
                    sel, log_q, 0.0)) - p, 0.0)               # [tile, S]
                if kernels:
                    dqi_t, dki_t, dwi_t = dsa_index_bwd(
                        i, qi_rows, kk, wi, d_scores, tile=tile,
                        interpret=interpret)
                    dqi_t = dqi_t.reshape((tile,) + qi.shape[1:])
                else:
                    dqi_t, dki_t, dwi_t = index_grads(d_scores, r, qi_t,
                                                      ki[:S], wi_t)
                dki = dki.at[:S].add(dki_t)
            return dki, (o_t, lse_t, packed, kl, dqi_t.astype(qi.dtype),
                         dwi_t)

        dki, out = lax.scan(one_tile, dki,
                            first + jnp.arange(n_tiles, dtype=jnp.int32))
        outs.append(out)
    o, lse, packed, kl, dqi, dwi = (
        jnp.concatenate([x[n] for x in outs]) for n in range(6))
    flat = lambda x: x.reshape((T,) + x.shape[2:])  # noqa: E731
    return (flat(o), kl.sum(), flat(lse), flat(packed),
            (flat(dqi), dki.astype(ki.dtype), flat(dwi)))


def _row_backward(q, k, v, o, lse, packed, do, *, tile: int):
    """One row's ``(dq, dk, dv)`` of the attention over the packed set."""
    T, H, d = q.shape
    K = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    kt, vt = k.transpose(1, 0, 2), v.transpose(1, 0, 2)

    def one_tile(dkv, i):
        t0 = i * tile
        cut = lambda x: lax.dynamic_slice_in_dim(x, t0, tile)  # noqa: E731
        qg, dog = _by_kv_head(cut(q), K), _by_kv_head(cut(do), K)
        G = qg.shape[1]
        lse_g = cut(lse).reshape(tile, K, G).transpose(1, 2, 0)
        delta = jnp.sum(dog.astype(F32) * _by_kv_head(cut(o), K).astype(F32),
                        axis=-1)                               # [K, G, tile]
        bits = cut(packed)

        def pair(j, carry):
            dqg, dk, dv = carry
            kc = lax.dynamic_slice_in_dim(kt, j * tile, tile, axis=1)
            vc = lax.dynamic_slice_in_dim(vt, j * tile, tile, axis=1)
            m = _unpack(lax.dynamic_slice_in_dim(bits, j * tile // 8,
                                                 tile // 8, axis=1))
            s = jnp.where(m[None, None], _head_scores(qg, kc, scale),
                          -jnp.inf)
            a = jnp.exp(s - lse_g[..., None])
            dp = jnp.einsum("kgqd,ksd->kgqs", dog, vc,
                            preferred_element_type=F32)
            ds = (a * (dp - delta[..., None]) * scale).astype(q.dtype)
            dqg = dqg + jnp.einsum("kgqs,ksd->kgqd", ds, kc,
                                   preferred_element_type=F32)
            add = lambda x, y: lax.dynamic_update_slice_in_dim(  # noqa: E731
                x, lax.dynamic_slice_in_dim(x, j * tile, tile, axis=1) + y,
                j * tile, axis=1)
            dk = add(dk, jnp.einsum("kgqs,kgqd->ksd", ds, qg,
                                    preferred_element_type=F32))
            dv = add(dv, jnp.einsum("kgqs,kgqd->ksd", a.astype(q.dtype), dog,
                                    preferred_element_type=F32))
            return dqg, dk, dv

        dqg, dk, dv = lax.fori_loop(
            0, i + 1, pair, (jnp.zeros((K, G, tile, d), F32),) + dkv)
        return (dk, dv), _from_kv_head(dqg).astype(q.dtype)

    zeros = jnp.zeros((K, T, d), F32)
    with jax.named_scope("dsa_attend"):
        (dk, dv), dq = lax.scan(one_tile, (zeros, zeros),
                                jnp.arange(T // tile, dtype=jnp.int32))
    return (dq.reshape(T, H, d), dk.transpose(1, 0, 2).astype(k.dtype),
            dv.transpose(1, 0, 2).astype(v.dtype))


def tile_for(T: int, topk: int, tile: int) -> int:
    """The tile a row of ``T`` runs in: ``tile``, or the whole row where that
    is shorter; raises where tiles do not cover the row or the set's mask
    does not pack."""
    tile = min(int(tile), int(T))
    if T % tile or tile % 8:
        raise ValueError(
            f"the selected-key attention runs in tiles of {tile} queries and "
            f"keys, a multiple of 8, that cover the row: T={T} is not a "
            f"multiple")
    if topk < 1:
        raise ValueError(f"topk={topk} keys a query")
    return tile


@partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def dsa_attention(q, k, v, qi, ki, wi, topk: int, tile: int,
                  interpret: Optional[bool] = None):
    """``(o [B, T, H, d], kl [B], probes [B, PROBES, T / 8] uint8)`` of the
    module docstring, for rows ``q`` [B, T, H, d], ``k``, ``v`` [B, T, K, d],
    ``qi`` [B, T, J, c], ``ki`` [B, T, c], ``wi`` [B, T, J] float32; ``tile``
    as :func:`tile_for` gives it. ``probes`` are the sets of the queries at
    :func:`probe_positions`, bit s % 8 of byte s // 8 key s: no part of the
    arithmetic. ``interpret`` is the indexer kernels' test handle (None: ask
    :func:`index_lowering`; True: the kernels, interpreted, in any float
    dtype, for shapes they take)."""
    return _dsa_fwd(q, k, v, qi, ki, wi, topk, tile, interpret)[0]


def _dsa_fwd(q, k, v, qi, ki, wi, topk, tile, interpret):
    J, c = qi.shape[2:]
    if interpret is None:
        lowering, _ = index_lowering(tile, J, c, qi.dtype)
    else:
        why = _shapes_taken(tile, J, c)
        if why:
            raise ValueError(f"the indexer's kernels do not take {why}")
        lowering = "pallas"
    # an op by the lowering its indexer took, as ``ops/kda_rule.py`` counts
    lowerings.count("dsa", lowering)
    how = bool(interpret) if lowering == "pallas" else None
    o, kl, lse, packed, grads = lax.map(
        lambda row: _row_forward(*row, topk=topk, tile=tile, interpret=how),
        (q, k, v, qi, ki, wi))
    o = checkpoint_name(o, RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, RESIDUAL_NAMES[1])
    packed = checkpoint_name(packed, RESIDUAL_NAMES[2])
    grads = jax.tree_util.tree_map(
        lambda g: checkpoint_name(g, RESIDUAL_NAMES[3]), grads)
    probes = packed[:, jnp.asarray(probe_positions(q.shape[1]))]
    return (o, kl, probes), (q, k, v, o, lse, packed, grads)


def _dsa_bwd(topk, tile, interpret, res, cot):
    q, k, v, o, lse, packed, (dqi, dki, dwi) = res
    do, dkl, _ = cot
    dq, dk, dv = lax.map(
        lambda row: _row_backward(*row, tile=tile),
        (q, k, v, o, lse, packed, do))
    with jax.named_scope("dsa_loss"):
        times = lambda g: (g.astype(F32) * dkl.reshape(  # noqa: E731
            (-1,) + (1,) * (g.ndim - 1))).astype(g.dtype)
        return dq, dk, dv, times(dqi), times(dki), times(dwi)


dsa_attention.defvjp(_dsa_fwd, _dsa_bwd)
