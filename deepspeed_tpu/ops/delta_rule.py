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
Only the states are sequential: a ``lax.scan`` over the chunks carries ``S``
in float32 and hands out each chunk's ``U``; the outputs are then products
over all chunks at once.

Precision: the running sums of ``g``, every decay and the triangular inverse
are float32, and every ratio of decays is ``exp(G_i - G_j)`` with ``i >= j``
(the mask goes into the exponent), never a quotient of two exponentials; the
products take operands in ``v``'s dtype (bf16 in training) and accumulate in
float32.

``T`` is found by substitution, not by a series (``sum (-A)^n`` cancels
catastrophically once ``beta <k_i, k_j>`` nears 1): rows one after the other
inside diagonal blocks of 16, a ``fori_loop`` of 15 vector steps over all
blocks at once, then the blocks joined two by two, ``[[P, 0], [R, Q]]^{-1} =
[[P^{-1}, 0], [-Q^{-1} R P^{-1}, Q^{-1}]]``. Its backward is its own: ``dA =
-T^T dT T^T``, so that no loop is differentiated.

This is the ``"xla"`` lowering, the one there is; :func:`lowerings` counts the
rules traced, for the step-program table.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.ssd_scan import _pad_to_chunks

F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
#: positions a chunk holds: the family's kernels' choice (``fla``); no
#: published key names it and no model here asks for another
CHUNK = 64
#: the side of the diagonal blocks inverted row by row
_BASE = 16

# rules by the lowering they took, counted when traced (``ops/ssd_scan.py``
# keeps the same count of its scans)
_LOWERINGS = {"xla": 0}


def lowerings() -> dict:
    return dict(_LOWERINGS)


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


def chunked_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array,
                       g: jax.Array, beta: jax.Array) -> jax.Array:
    """q, k [B, T, H, dk] (``q`` scaled, both as the rule reads them), v
    [B, T, H, dv], g [B, T, H] (the decay's logarithm, <= 0) and beta
    [B, T, H] -> o [B, T, H, dv] in ``v``'s dtype. A ``T`` that is not a
    multiple of :data:`CHUNK` is padded with positions of ``k = 0``, ``g = 0``
    (the state passes through them unchanged) and their outputs dropped."""
    _LOWERINGS["xla"] += 1
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
