"""Block-diffusion training (SDAR, arXiv:2510.06303; the training form is
BD3-LM's, arXiv:2503.09573): autoregressive across blocks of ``B`` tokens,
masked diffusion inside a block.

A row of ``L`` tokens ``x0`` is trained as one row of ``2L`` positions,
``[xt ; x0]``: the noised copy (a position is the mask token with probability
``t`` of its block, ``runtime/data_pipeline/block_noise.py``) and then the
clean one, both at positions ``0..L-1``. With ``beta(i) = (i mod L) // B`` a
query ``i`` sees the key ``j`` where::

    noised i, noised j :  beta(j) == beta(i)     its own noised block
    noised i, clean  j :  beta(j) <  beta(i)     the clean blocks before it
    clean  i, noised j :  never
    clean  i, clean  j :  beta(j) <= beta(i)     block-causal

so the noised block ``b`` reads what a model that decodes block ``b`` after
the clean blocks before it would read, and one forward trains every block.
The loss is over the noised half alone (``models/transformer.py:lm_loss``).

:func:`attention` runs the mask with no ``[2L, 2L]`` and no ``[L, L]`` array:
each half is one call of the flash kernels over the clean keys under the
diagonal rounded to blocks (``ops/flash_attention.py``: ``diag``; the clean
queries never fetch a noised key), and the noised half's own-block term (``B``
keys a query) is a third call over the noised keys under the block-diagonal
band, the row cut into segments of :data:`OWN_SEGMENT` positions that no
block straddles (a segment is one tile: no dead grid step), merged with the
second call's result through the two log-sum-exps, exactly
(:func:`merge`). :func:`dense_attention` is the same mask as a dense softmax,
for a backend or a mesh the kernels do not run on.
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops import flash_attention as fa

#: the scopes of the two parts, nested in ``attn`` (``STEP_SCOPES``)
CROSS_SCOPE, OWN_SCOPE = "bd_cross", "bd_own"
#: the first positions of each half whose mixer output the step record
#: carries on its own (:func:`early_ms`)
EARLY = 64
#: the own-block call runs the noised half in segments of up to this many
#: positions, each a sequence of its own: a block's keys lie in its queries'
#: segment, so the segments' block-diagonal bands are the row's
OWN_SEGMENT = 256


def row_positions(rows: int, L: int) -> jax.Array:
    """``[rows, 2L]``: the positions of a ``[noised ; clean]`` row."""
    p = jnp.arange(L, dtype=jnp.int32)
    return jnp.broadcast_to(jnp.concatenate([p, p])[None], (rows, 2 * L))


def mask(L: int, block: int) -> jax.Array:
    """The ``[2L, 2L]`` mask of the module docstring as booleans (query,
    key): for a dense softmax at small sizes, and for tests."""
    i = jnp.arange(2 * L)
    beta = (i % L) // block
    noised = i < L
    qn, kn = noised[:, None], noised[None, :]
    qb, kb = beta[:, None], beta[None, :]
    return jnp.where(qn, jnp.where(kn, kb == qb, kb < qb),
                     ~kn & (kb <= qb))


def dense_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    block: int) -> jax.Array:
    """q [b, 2L, H, d], k, v [b, 2L, K, d] -> [b, 2L, H, d] by a dense
    softmax under :func:`mask` (scores ``[2L, 2L]`` a head)."""
    from deepspeed_tpu.models.transformer import repeat_kv

    L, d = q.shape[1] // 2, q.shape[-1]
    k, v = repeat_kv(k, v, q.shape[2])
    s = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32) \
        / math.sqrt(d)
    s = jnp.where(mask(L, block)[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", p, v)


def _weights(lse_a: jax.Array, lse_b: jax.Array):
    """The share of set a, [b, T, H] float32, from the two log-sum-exps
    [b, H, T, 1]: ``sigmoid(lse_a - lse_b)`` (a set with no key, ``NEG_INF``:
    0)."""
    la, lb = (x[..., 0].transpose(0, 2, 1).astype(jnp.float32)
              for x in (lse_a, lse_b))
    return jax.nn.sigmoid(la - lb)


@jax.custom_vjp
def merge(out_a: jax.Array, lse_a: jax.Array, out_b: jax.Array,
          lse_b: jax.Array) -> jax.Array:
    """The attention over the union of two disjoint key sets from each
    set's result: out [b, T, H, d] and its log-sum-exp [b, H, T, 1] (a set
    with no key for a query: 0 and ``NEG_INF``, which weigh nothing):
    ``w out_a + (1 - w) out_b``, ``w`` the softmax over the two log-sum-exps,
    in float32. Its own derivative rule, so that a pass reads each array
    once: the backward is one pass over the cotangent and the two results
    (``d lse_a = -d lse_b = w (1 - w) sum_d g (out_a - out_b)``), and nothing
    is kept for it but the operands."""
    return _merge_fwd(out_a, lse_a, out_b, lse_b)[0]


def _merge_fwd(out_a, lse_a, out_b, lse_b):
    w = _weights(lse_a, lse_b)[..., None]
    out = w * out_a.astype(jnp.float32) + (1.0 - w) * out_b.astype(
        jnp.float32)
    return out.astype(out_a.dtype), (out_a, lse_a, out_b, lse_b)


def _merge_bwd(res, g):
    out_a, lse_a, out_b, lse_b = res
    w = _weights(lse_a, lse_b)
    gf = g.astype(jnp.float32)
    dw = jnp.sum(gf * (out_a.astype(jnp.float32)
                       - out_b.astype(jnp.float32)), axis=-1)   # [b, T, H]
    dl = (dw * w * (1.0 - w)).transpose(0, 2, 1)[..., None]
    return ((w[..., None] * gf).astype(out_a.dtype), dl.astype(lse_a.dtype),
            ((1.0 - w)[..., None] * gf).astype(out_b.dtype),
            (-dl).astype(lse_b.dtype))


merge.defvjp(_merge_fwd, _merge_bwd)


def own_block_attention(q: jax.Array, k: jax.Array, v: jax.Array, block: int,
                        *, interpret: Optional[bool] = None):
    """``(out [b, L, H, d], lse [b, H, L, 1])`` of the noised queries q over
    the ``block`` keys k, v [b, L, K, d] of their own noised block: the
    flash kernels under the block-diagonal band (``DIAG_OWN``), the row as
    ``L / segment`` sequences of one tile each."""
    b, L, H, d = q.shape
    seg = fa._pick_block(L, max(OWN_SEGMENT, block))
    cut = lambda x: x.reshape((b * (L // seg), seg) + x.shape[2:])  # noqa
    out, lse = fa.flash_attention_lse(
        cut(q), cut(k), cut(v), causal=True, diag=(block, fa.DIAG_OWN),
        block_q=seg, block_k=seg, interpret=interpret)
    lse = lse.reshape(b, L // seg, H, seg, 1).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, L, H, d), lse.reshape(b, H, L, 1)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, block: int, *,
              interpret: Optional[bool] = None) -> jax.Array:
    """q [b, 2L, H, d], k, v [b, 2L, K, d] (the ``[noised ; clean]`` row,
    roped) -> [b, 2L, H, d] under the module docstring's mask: the two
    calls over the clean keys (scope ``bd_cross``), the own-block call and
    the merge (``bd_own``)."""
    L = q.shape[1] // 2
    qn, qc = q[:, :L], q[:, L:]
    kn, kc = k[:, :L], k[:, L:]
    vn, vc = v[:, :L], v[:, L:]
    with jax.named_scope(CROSS_SCOPE):
        out_c = fa.flash_attention(
            qc, kc, vc, causal=True, diag=(block, fa.DIAG_UPTO),
            interpret=interpret)
        out_x, lse_x = fa.flash_attention_lse(
            qn, kc, vc, causal=True, diag=(block, fa.DIAG_BEFORE),
            interpret=interpret)
    with jax.named_scope(OWN_SCOPE):
        out_o, lse_o = own_block_attention(qn, kn, vn, block,
                                           interpret=interpret)
        return jnp.concatenate([merge(out_x, lse_x, out_o, lse_o), out_c],
                               axis=1)


def early_ms(mix: jax.Array) -> jax.Array:
    """[2]: the mean square of a layer's mixer output ``mix`` [b, 2L, D]
    over the first :data:`EARLY` positions of the noised half and of the
    clean half: where a block's few keys are a large share of a query's set,
    so that a mask that is off by a block shows in the step record."""
    L = mix.shape[1] // 2
    n = min(EARLY, L)
    sq = jnp.square(mix.astype(jnp.float32))
    return jnp.stack([jnp.mean(sq[:, :n]), jnp.mean(sq[:, L:L + n])])


def mask_pairs(L: int, block: int) -> int:
    """The (query, key) pairs a head keeps in a row of ``L`` tokens:
    ``L^2 + L B`` (clean-clean ``B^2 nb (nb + 1) / 2``, noised-clean ``B^2 nb
    (nb - 1) / 2``, the own blocks ``nb B^2``)."""
    return L * L + L * block


def kernel_tiles(L: int, block: int, block_q: int = fa.DEFAULT_BLOCK_Q,
                 block_k: int = fa.DEFAULT_BLOCK_K) -> Dict[str, object]:
    """What the flash kernels do with a head of one row of ``L`` tokens under
    the three rounded diagonals, by the kernels' own predicates: under each
    diagonal's label the tiles by arm and the crossed tiles' sub-blocks
    (forward and fused backward take the same ones; the own-block call's are
    its segments' summed), and ``pairs_worked``, the (query, key) pairs of
    the whole tiles and live sub-blocks of the three calls, beside
    ``pairs_kept`` (:func:`mask_pairs`)."""
    bq, bk = fa._pick_block(L, block_q), fa._pick_block(L, block_k)
    seg = fa._pick_block(L, max(OWN_SEGMENT, block))
    out: Dict[str, object] = {}
    worked = 0
    for mode, T, tq, tk, times in (
            (fa.DIAG_UPTO, L, bq, bk, 1), (fa.DIAG_BEFORE, L, bq, bk, 1),
            (fa.DIAG_OWN, seg, seg, seg, L // seg)):
        diag = (block, mode)
        sub = fa._pick_block(tq, fa._SUB), fa._pick_block(tk, fa._SUB)
        arms = {k: n * times for k, n in fa._tile_arms(
            T, T, tq, tk, True, None, 0, sub, diag).items()}
        out[fa.diag_label(diag)] = arms
        worked += (arms["unmasked"] * tq * tk
                   + arms["sub_live"] * sub[0] * sub[1])
    return dict(out, pairs_worked=worked, pairs_kept=mask_pairs(L, block))
