"""The chunked scan of a Mamba-2 (state-space duality) layer, and the causal
depthwise convolution in front of it. Plain ``jax.numpy`` / ``lax``: the
backward is autodiff's (under the block's recomputation only one layer's
residuals are alive at a time; ``PERF.md`` keeps the bytes).

Per head, over a state ``h`` [P, N] that starts at zero::

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T        y_t = h_t C_t + D x_t

The chunked form computes the same ``y`` from matmuls. Within a chunk of
``Q`` positions ``y = ((C B^T) o L)(dt x)`` with ``L_ij = exp(sum_{j<k<=i}
dt_k A)`` for ``i >= j`` and 0 above; a chunk's end state is ``sum_j
exp(sum_{j<k<=end} dt_k A) dt_j x_j B_j^T``; the chunk states are carried
forward by ``exp(sum over a chunk of dt A)`` in a ``lax.scan`` over the
chunks; and the carried state adds ``exp(sum_{k<=i} dt_k A) h_prev C_i``.
The cumulative sums, the decays and their exponentials are float32; the
products take operands in ``x``'s dtype (bf16 in training) and accumulate in
float32. ``C B^T`` is computed once a group, not once a head.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal convolution over the last ``K`` positions: x
    [B, T, C], w [K, C], b [C]; ``y_t = b + sum_k w[k] x_{t-(K-1)+k}`` with
    zeros before the sequence's start. ``K`` shifted multiply-adds in
    float32, returned in float32 (the caller's activation fuses in)."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(F32), ((0, 0), (K - 1, 0), (0, 0)))
    y = b.astype(F32)
    for k in range(K):
        y = y + xp[:, k:k + T] * w[k].astype(F32)
    return y


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, D: jax.Array, chunk: int) -> jax.Array:
    """x [B, T, H, P], dt [B, T, H] (after softplus), A [H] (negative), B and
    C [B, T, G, N] (each group serves H / G heads), D [H] -> y [B, T, H, P]
    in ``x``'s dtype. A ``T`` that is not a multiple of ``chunk`` is padded
    with steps of ``dt = 0`` (the state passes through them unchanged and
    they add nothing to it) and the padding's outputs are dropped."""
    b, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    R, Q = H // G, int(chunk)
    pad = -T % Q
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                       for a in (x, dt, B, C))
    c = (T + pad) // Q
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
    return y.reshape(b, T + pad, H, P)[:, :T].astype(x.dtype)
