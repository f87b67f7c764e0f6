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

:func:`attention` runs the mask with no ``[2L, 2L]`` and no ``[L, L]`` array,
in two calls of the flash kernels, one a half, over the clean keys under the
diagonal rounded to blocks (``ops/flash_attention.py``: ``diag``; the clean
queries never fetch a noised key). The noised half's call takes the noised
keys and values as a second key source (``k_own``, ``v_own``): a q-tile's
own noised tile is one more operand block of a grid step the call has, worked
under the block-diagonal band in the sub-blocks that keep a pair, into the
running maximum, sum and accumulator the clean tiles are worked into. One
softmax over both key sets, forward and backward: no log-sum-exp leaves the
call and nothing is merged outside it; the two halves' results go on as two
arrays. :func:`dense_attention` is the same mask as a dense softmax, for a
backend or a mesh the kernels do not run on.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops import flash_attention as fa

#: the scope of the two flash calls, nested in ``attn`` (``STEP_SCOPES``)
CROSS_SCOPE = "bd_cross"
#: the first positions of each half whose mixer output the step record
#: carries on its own (:func:`early_ms`)
EARLY = 64


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


def attention(q: jax.Array, k: jax.Array, v: jax.Array, block: int, *,
              block_q: int = fa.DEFAULT_BLOCK_Q,
              block_k: int = fa.DEFAULT_BLOCK_K,
              interpret: Optional[bool] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """q [b, 2L, H, d], k, v [b, 2L, K, d] (the ``[noised ; clean]`` row,
    roped) -> the two halves' results under the module docstring's mask,
    ``(noised, clean)``, [b, L, H, d] each: a call of the flash kernels a
    half (scope ``bd_cross``), the noised half's with its own noised block as
    the second key source. They are not joined here: a caller that projects
    each to the model's width first joins arrays half as wide, in its own
    layout (joined as the kernels leave them, heads first, the row cost a
    pass over 134 MB a layer and pass, and a wider relayout after it:
    PERF.md section 6, PR 65). ``block_q``, ``block_k``: the kernels' tile,
    as :func:`kernel_tiles` takes it."""
    L = q.shape[1] // 2
    qn, qc = q[:, :L], q[:, L:]
    kn, kc = k[:, :L], k[:, L:]
    vn, vc = v[:, :L], v[:, L:]
    with jax.named_scope(CROSS_SCOPE):
        tile = dict(block_q=block_q, block_k=block_k, interpret=interpret)
        out_c = fa.flash_attention(
            qc, kc, vc, causal=True, diag=(block, fa.DIAG_UPTO), **tile)
        out_n = fa.flash_attention(
            qn, kc, vc, k_own=kn, v_own=vn, causal=True,
            diag=(block, fa.DIAG_BEFORE), **tile)
    return out_n, out_c


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
    """What the flash kernels do with a head of one row of ``L`` tokens in
    the two calls, by the kernels' own predicates: under each rounded
    diagonal's label the tiles over the clean keys by arm and the crossed
    tiles' sub-blocks (forward and fused backward take the same ones), under
    the band's label the noised call's own tiles (one a q-tile, on a grid
    step it has) and theirs, and ``pairs_worked``, the (query, key) pairs of
    the whole tiles and live sub-blocks of both calls, beside ``pairs_kept``
    (:func:`mask_pairs`)."""
    bq, bk = fa._pick_block(L, block_q), fa._pick_block(L, block_k)
    sub = fa._pick_block(bq, fa._SUB), fa._pick_block(bk, fa._SUB)
    own_edge = fa._pick_block(bq, fa._OWN_SUB)
    out: Dict[str, object] = {}
    worked = 0
    for mode in (fa.DIAG_UPTO, fa.DIAG_BEFORE, fa.DIAG_OWN):
        diag = (block, mode)
        if mode == fa.DIAG_OWN:
            arms, edge = fa._own_arms(L, bq, diag), (own_edge, own_edge)
        else:
            arms, edge = fa._tile_arms(L, L, bq, bk, True, None, 0, sub,
                                       diag), sub
        out[fa.diag_label(diag)] = arms
        worked += (arms["unmasked"] * bq * bk
                   + arms["sub_live"] * edge[0] * edge[1])
    return dict(out, pairs_worked=worked, pairs_kept=mask_pairs(L, block))
