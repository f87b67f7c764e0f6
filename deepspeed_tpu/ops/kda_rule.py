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

One lowering today, ``"xla"``: the form above as ``jnp.einsum`` with a
``lax.scan`` over the chunk states and autodiff's backward (but the
inverse's), every array of it through HBM. Its output and the chunks'
incoming states carry ``RULE_CHECKPOINT_NAMES`` like the scalar rule's, so
that a policy that keeps the named residuals keeps them. Counted at site
``kda_scan`` (``ops/lowerings.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from deepspeed_tpu.ops import lowerings
from deepspeed_tpu.ops.delta_rule import (CHUNK, unit_heads,
                                          unit_lower_inverse)
from deepspeed_tpu.ops.ssd_scan import _pad_to_chunks
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


def chunked_kda_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array, unit=None) -> jax.Array:
    """q, k [B, T, H, dk] (``q`` scaled, both as the rule reads them), v
    [B, T, H, dv], g [B, T, H, dk] (each key channel's decay's logarithm,
    <= 0, and bounded below so that ``exp(15 |g|)`` is finite: the module's
    docstring) and beta [B, T, H] -> o [B, T, H, dv] in ``v``'s dtype. A ``T``
    that is not a multiple of ``delta_rule.CHUNK`` is padded with positions of
    ``k = 0``, ``g = 0`` and their outputs dropped. ``unit`` (q's length,
    eps): q and k arrive as the convolutions left them (float32) and each
    head's row is first scaled, q to that length and k to 1
    (``delta_rule.unit_heads``)."""
    # the one lowering the rule has (``ROADMAP.md`` keeps its kernels)
    lowerings.count("kda_scan", "xla")
    if unit is not None:
        q = unit_heads(q, unit[0], unit[1], v.dtype)
        k = unit_heads(k, 1.0, unit[1], v.dtype)
    return kda_einsum(q, k, v, g, beta)
