"""A router's choice of ``k`` experts a token, without a sort.

``topk_select(x, k, groups)`` over scores ``x`` [..., E] gives ``lax.top_k(x,
k)[1]`` element for element: the ``k`` largest by value descending, of equal
values the lower index first, in XLA's total order of floats (``-0.0`` below
``0.0``). With ``groups = (n_group, topk_group)`` above (1, 1) the choice is
DeepSeek-V3's group-limited one: the experts in ``n_group`` groups of ``E /
n_group`` neighbours, a group's score the sum of its two largest, the
``topk_group`` best groups kept (of equal scores the lower group), the other
groups' experts at ``-inf`` for the ``k``; the second result is then ``keep``
[..., n_group], true where a token kept the group. Nothing of the selection
carries a gradient: the kernel takes ``stop_gradient`` of its operand.

Why an op. On a TPU ``lax.top_k`` is a full two-operand sort of each token's
scores (0.66 ms for [8192, 512] on a v5e, whatever ``k``), and the group limit
is two more. What a router needs is ``k`` rounds of "the maximum, the lowest
index at the maximum, that one out": 8 indices of 512 scores that are 2 KB a
token.

One algorithm, two lowerings (:func:`topk_lowering` picks by what the call can
see: backend, dtype, widths, and the ``(E, k)`` at which the kernel was
measured faster than the sort):

* ``"xla"``: :func:`topk_select_xla`, ``lax.top_k`` and the group limit's
  ``jax.numpy`` lines. What a CPU runs, what a router narrower than a lane
  tile runs, and the unit tests' oracle.
* ``"pallas"``: one Mosaic kernel (:func:`select_rounds`, a ``jax.jit`` of its
  own) whose grid step is a tile of rows. The tile's scores become int32 keys
  in the floats' total order and are transposed into VMEM scratch, experts
  down the sublanes and rows along the lanes, so that a maximum over the
  experts is an elementwise maximum of vregs and no round reduces across
  lanes. A round goes down the experts in chunks, takes the last round's
  choice out (a key no float has, not ``-inf``: a row with fewer than ``k``
  finite scores still gives ``lax.top_k``'s answer) and keeps each sublane's
  first largest; the chunk's sublanes then settle value, then index. The
  group limit is the same two steps over a group's rows, then ``topk_group``
  rounds over the ``n_group`` sums. The indices leave as ``[k, rows]``, lane
  dense, and are transposed outside.

The ops traced are counted by lowering (``ops/lowerings.py``, site
``moe_topk``) for the step-program table.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.accelerator.real_accelerator import on_tpu as _on_tpu
from deepspeed_tpu.ops import lowerings

I32 = jnp.int32
#: the experts come in whole lane tiles (they are transposed a tile a time)
_LANES = 128
# a grid step's rows, the most of these that divide the rows, and the experts
# a round's running maximum goes down at a time ([_CHUNK, rows] is that many
# independent chains of compare and select). On a v5e at [8192, 512], k = 8
# under the group limit (8, 4): 0.082 ms at (512, 16), 0.089 at (256, 32),
# 0.103 at (128, 32), 0.102 at (256, 64), 0.099 at (1024, 16); with the
# experts left on the lanes (a round two lane reductions a row) 0.27-0.75
_TILE_ROWS = (512, 256, 128)
_CHUNK = 16
# the key (:func:`_keys`) of an expert already taken, below the key of every
# float but the NaN of all ones; and ``-inf``'s
_TAKEN = -2 ** 31
_NEG_INF = -2 ** 31 + 0x7FFFFF
# the widest router and the most rounds the kernel was measured at
_MOST_EXPERTS = 1024
_MOST_ROUNDS = 32


def topk_select_xla(x: jax.Array, k: int, groups: Tuple[int, int] = (1, 1)):
    """:func:`topk_select` as ``lax.top_k`` and ``jax.numpy`` lines: two
    sorts and a compare of the kept groups against ``arange(n_group)`` for
    the limit, one sort for the ``k``."""
    keep = None
    if tuple(groups) != (1, 1):
        n_group, topk_group = groups
        grouped = x.reshape(*x.shape[:-1], n_group, x.shape[-1] // n_group)
        score = lax.top_k(grouped, 2)[0].sum(-1)              # [..., n]
        _, best = lax.top_k(score, topk_group)
        keep = (best[..., None] == jnp.arange(n_group, dtype=best.dtype)) \
            .any(axis=-2)                                     # [..., n]
        x = jnp.where(keep[..., None], grouped, -jnp.inf).reshape(x.shape)
    return lax.top_k(x, k)[1], keep


# ---------------------------------------------------------------------------
# which lowering: from the call's own facts
# ---------------------------------------------------------------------------

def _tile_rows(T: int) -> Optional[int]:
    return next((r for r in _TILE_ROWS if T % r == 0), None)


def _shapes_taken(T: int, E: int, k: int, groups: Tuple[int, int]) -> str:
    """Why the kernel does not take these shapes; "" where it does."""
    n_group, topk_group = groups
    if E % _LANES:
        return f"{E} experts, not whole lane tiles of {_LANES}"
    if E > _MOST_EXPERTS:
        return f"{E} experts (the kernel: at most {_MOST_EXPERTS})"
    if not 1 <= k <= min(E, _MOST_ROUNDS):
        return f"k = {k} (the kernel: 1 to {min(E, _MOST_ROUNDS)} rounds)"
    if _tile_rows(T) is None:
        return f"{T} rows are not whole tiles of {_TILE_ROWS[-1]}"
    if (n_group, topk_group) != (1, 1) and (
            E % n_group or (E // n_group) % 8 or E // n_group < 2
            or not 1 <= topk_group <= n_group):
        return (f"groups {n_group, topk_group} over {E} experts (the "
                "kernel: groups of whole sublane tiles of 8)")
    return ""


def topk_lowering(T: int, E: int, k: int, groups: Tuple[int, int], dtype, *,
                  tpu: Optional[bool] = None) -> Tuple[str, str]:
    """``("pallas" | "xla", why)`` for the ``k`` of ``E`` scores of each of
    ``T`` rows: the kernel where it was measured faster than the sort (a
    TPU, float32 scores, experts in whole lane tiles, rows in whole tiles),
    ``lax.top_k`` everywhere else."""
    if tpu is None:
        tpu = _on_tpu()
    if not tpu:
        return "xla", "not a TPU backend"
    if jnp.dtype(dtype) != jnp.float32:
        return "xla", f"{jnp.dtype(dtype).name} scores (the kernel: float32)"
    why = _shapes_taken(T, E, k, tuple(groups))
    return ("xla", why) if why else ("pallas", "")


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

def _flip(b):
    """int32 bits of a float32 <-> an int32 whose order is the floats' total
    order (``-0.0`` below ``0.0``, a NaN of positive sign above ``inf``):
    its own inverse."""
    return b ^ ((b >> 31) & I32(0x7FFFFFFF))


def _keys(x):
    return _flip(lax.bitcast_convert_type(x, I32))


def _floats(key):
    return lax.bitcast_convert_type(_flip(key), jnp.float32)


def _top(a):
    return jnp.max(a, axis=0, keepdims=True)


def _first_at_top(key, pos, none):
    """Down the sublanes of ``key`` [n, R]: the least ``pos`` that holds the
    largest [1, R]."""
    return jnp.min(jnp.where(key == _top(key), pos, none), axis=0,
                   keepdims=True)


def _kept_groups(key_ref, n_group: int, topk_group: int):
    """The group limit over the keys [E, R] in scratch: ``keep`` [n_group, R]
    int32 (1 where the row kept the group), the other groups' keys set to
    ``-inf``'s."""
    E, R = key_ref.shape
    size = E // n_group
    group = lax.broadcasted_iota(I32, (n_group, R), 0)
    score = jnp.full((n_group, R), _TAKEN, I32)
    for g in range(n_group):
        blk = key_ref[g * size:(g + 1) * size, :]
        m1 = _top(blk)
        at = blk == m1
        twice = jnp.sum(at.astype(I32), axis=0, keepdims=True) >= 2
        m2 = jnp.where(twice, m1, _top(jnp.where(at, _TAKEN, blk)))
        score = jnp.where(group == g, _keys(_floats(m1) + _floats(m2)),
                          score)
    keep = jnp.zeros((n_group, R), I32)
    for _ in range(topk_group):
        best = _first_at_top(score, group, n_group)
        keep = jnp.where(group == best, 1, keep)
        score = jnp.where(group == best, _TAKEN, score)
    for g in range(n_group):
        kept = _top(jnp.where(group == g, keep, 0)) > 0       # [1, R]
        key_ref[g * size:(g + 1) * size, :] = jnp.where(
            kept, key_ref[g * size:(g + 1) * size, :], _NEG_INF)
    return keep


def _select_kernel(x_ref, idx_ref, *rest, k: int, groups: Tuple[int, int]):
    """One tile of rows: ``x_ref`` [R, E] float32 -> ``idx_ref`` [k up to
    whole sublane tiles, R] (and ``keep_ref`` [n_group, R] under a group
    limit); ``key_ref`` [E, R] is scratch."""
    key_ref = rest[-1]
    R, E = x_ref.shape
    for e in range(0, E, _LANES):
        for r in range(0, R, _LANES):
            key_ref[e:e + _LANES, r:r + _LANES] = _keys(
                x_ref[r:r + _LANES, e:e + _LANES]).T
    if groups != (1, 1):
        rest[0][...] = _kept_groups(key_ref, *groups)

    chunk = min(_CHUNK, E)
    sub = lax.broadcasted_iota(I32, (chunk, R), 0)
    place = lax.broadcasted_iota(I32, idx_ref.shape, 0)

    def one_round(j, carry):
        last, out = carry          # the last round's choice [1, R], -1 at first
        gone = last - sub          # == c chunk where the chunk holds it
        best = jnp.full((chunk, R), _TAKEN, I32)
        chunk_of = jnp.zeros((chunk, R), I32)
        for c in range(E // chunk):
            rows = slice(c * chunk, (c + 1) * chunk)
            key = jnp.where(gone == c * chunk, _TAKEN, key_ref[rows, :])
            key_ref[rows, :] = key
            more = key > best      # down the experts: the first largest stays
            best = jnp.where(more, key, best)
            chunk_of = jnp.where(more, c, chunk_of)
        chosen = _first_at_top(best, chunk_of * chunk + sub, E)
        return chosen, jnp.where(place == j, chosen, out)

    _, out = lax.fori_loop(
        0, k, one_round,
        (jnp.full((1, R), -1, I32), jnp.zeros(idx_ref.shape, I32)))
    idx_ref[...] = out


@functools.partial(jax.jit, static_argnames=("k", "groups", "interpret"))
def select_rounds(x, *, k: int, groups: Tuple[int, int] = (1, 1),
                  interpret: bool = False):
    """``x`` [T, E] float32 -> ``(idx [T, k] int32, keep [T, n_group] bool or
    None)``."""
    T, E = x.shape
    R = _tile_rows(T)
    grouped = groups != (1, 1)
    down = lambda n: pl.BlockSpec((n, R), lambda i: (0, i))  # noqa: E731
    k_up = -(-k // 8) * 8
    out = pl.pallas_call(
        functools.partial(_select_kernel, k=k, groups=groups),
        grid=(T // R,),
        in_specs=[pl.BlockSpec((R, E), lambda i: (i, 0))],
        out_specs=[down(k_up)] + [down(groups[0])] * grouped,
        out_shape=[jax.ShapeDtypeStruct((k_up, T), I32)]
        + [jax.ShapeDtypeStruct((groups[0], T), I32)] * grouped,
        scratch_shapes=[pltpu.VMEM((E, R), I32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x)
    return out[0][:k].T, (out[1].T > 0 if grouped else None)


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------

def topk_select(x: jax.Array, k: int, groups: Tuple[int, int] = (1, 1),
                interpret: Optional[bool] = None):
    """x [..., E] -> ``(lax.top_k(x, k)[1] [..., k], keep [..., n_group] or
    None)``, the ``k`` among the kept groups' experts where ``groups`` is
    above (1, 1). ``interpret`` is the kernel's test handle (None: ask
    :func:`topk_lowering`; True: the kernel, interpreted, for shapes it
    takes)."""
    groups = tuple(int(g) for g in groups)
    lead, E = x.shape[:-1], x.shape[-1]
    T = math.prod(lead)
    if interpret is None:
        lowering, _ = topk_lowering(T, E, k, groups, x.dtype)
    else:
        why = _shapes_taken(T, E, k, groups)
        if why:
            raise ValueError(f"the selection kernel does not take {why}")
        lowering = "pallas"
    lowerings.count("moe_topk", lowering)
    if lowering == "xla":
        return topk_select_xla(x, k, groups)
    # indices and a mask: nothing to differentiate (``lax.top_k``'s indices
    # carry no gradient either, and off the kernel the trace is the one it was)
    idx, keep = select_rounds(
        lax.stop_gradient(x).reshape(T, E).astype(jnp.float32), k=k,
        groups=groups, interpret=bool(interpret))
    return (idx.reshape(*lead, k),
            None if keep is None else keep.reshape(*lead, groups[0]))
