"""Ring attention over a sequence-parallel mesh axis.

The reference has no ring attention (its long-context members are Ulysses all-to-all
and FPDT chunking — SURVEY.md §5.7); this is the TPU-idiomatic context-parallel
member: KV chunks rotate around the ``sp`` ring via ``lax.ppermute`` (ICI
neighbor exchange), each step folding a chunk into an online-softmax accumulator —
FPDT's chunked online softmax (``sequence/fpdt_layer.py:135``) with the host-offload
stream replaced by the ring.

Call **inside** ``shard_map`` with the sequence dim sharded over ``axis``. Layout:
q/k/v ``[B, T_local, H, d]``. Causality uses global positions, so contiguous
(non-permuted) sequence sharding is assumed.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _chunk_scores(q, k, scale):
    return jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                      k.astype(jnp.float32)) * scale


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, axis: str = "sp",
                   causal: bool = True,
                   window: Optional[int] = None) -> jax.Array:
    """Exact attention over the full (ring-distributed) sequence. ``window``
    masks keys more than window-1 positions behind each query (global
    positions — chunks rotate with their ring source index)."""
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    B, Tl, H, d = q.shape
    K = k.shape[2]
    if K != H:  # GQA: expand once, locally
        rep = H // K
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = 1.0 / math.sqrt(d)
    perm = [(i, (i + 1) % n) for i in range(n)]

    q_pos = idx * Tl + jnp.arange(Tl)  # global positions of local queries

    def step(carry, s):
        k_cur, v_cur, m, l, acc = carry
        src = (idx - s) % n  # rank whose kv chunk we currently hold
        kv_pos = src * Tl + jnp.arange(Tl)
        scores = _chunk_scores(q, k_cur, scale)  # [B, H, Tl, Tl]
        if causal or window is not None:
            mask = (q_pos[:, None] >= kv_pos[None, :]) if causal else True
            if window is not None:
                mask = mask & (kv_pos[None, :] > q_pos[:, None] - window)
            scores = jnp.where(mask[None, None], scores, NEG_INF)
        m_cur = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(scores - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bhts,bshd->bthd", p, v_cur.astype(jnp.float32))
        acc_new = acc * corr.transpose(0, 2, 1, 3) + pv
        k_nxt = lax.ppermute(k_cur, axis, perm)
        v_nxt = lax.ppermute(v_cur, axis, perm)
        return (k_nxt, v_nxt, m_new, l_new, acc_new), None

    # mark the fresh accumulators as device-varying over the ring axis so the scan
    # carry type matches the computed updates (shard_map vma check)
    m0 = lax.pcast(jnp.full((B, H, Tl, 1), NEG_INF, jnp.float32), axis, to="varying")
    l0 = lax.pcast(jnp.zeros((B, H, Tl, 1), jnp.float32), axis, to="varying")
    acc0 = lax.pcast(jnp.zeros((B, Tl, H, d), jnp.float32), axis, to="varying")
    (k_f, v_f, m, l, acc), _ = lax.scan(step, (k, v, m0, l0, acc0), jnp.arange(n))
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1, 3)  # [B, Tl, H, 1]
    return (acc / denom).astype(q.dtype)


def ring_attention_spmd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        segment_ids: Optional[jax.Array] = None,
                        window: Optional[int] = None) -> jax.Array:
    """``attention_impl="ring"``: engine-selectable context parallelism.

    Self-enters a shard_map manual over ``sp`` (sequence dim sharded, batch and
    head axes GSPMD-auto) so the model can pick ring attention from inside the
    engine's jit — the long-context path of BASELINE.md without hand-rolled
    shard_map at the call site. No head-divisibility constraint (works for any
    GQA layout). Falls back to dense attention off-mesh."""
    if segment_ids is not None:
        raise NotImplementedError("ring attention does not take segment_ids")
    from deepspeed_tpu.sequence.layer import sp_shard_map

    mesh = jax.sharding.get_abstract_mesh()
    if mesh is not None and not mesh.empty:
        parent_manual = set(getattr(mesh, "manual_axes", ()) or ())
        sp_live = "sp" in mesh.axis_names and mesh.shape["sp"] > 1
        if sp_live and parent_manual and "sp" not in parent_manual:
            # XLA cannot yet transpose (differentiate) a ppermute ring nested
            # inside another manual region — the pipeline's pp shard_map.
            raise NotImplementedError(
                "attention_impl='ring' cannot run inside the pipeline region "
                "(nested-manual ppermute has no transpose); use "
                "attention_impl='ulysses' when composing sp with pp")

    out = sp_shard_map(
        lambda a, b, c: ring_attention(a, b, c, axis="sp", causal=causal,
                                       window=window),
        q, k, v)
    if out is not None:
        return out
    from deepspeed_tpu.models.transformer import get_attention_impl

    kw = {} if window is None else {"window": window}
    return get_attention_impl("auto")(q, k, v, causal=causal, **kw)
