"""Pallas flash attention (causal, GQA) — forward + backward TPU kernels.

Parity target: the reference's fused attention kernels
(``csrc/transformer/inference/csrc/softmax.cu`` + the FlashAttention path used by
Ulysses/FPDT, ``deepspeed/sequence/fpdt_layer.py:135`` chunked online softmax). Here it
is a first-class Pallas TPU kernel:

* grid ``(B, H, num_q_blocks, num_kv_blocks)`` with the KV loop as the innermost grid
  dimension; running max/denominator live in VMEM scratch across KV steps (online
  softmax — the same math as FPDT's ``_fpdt_general_attn_forward`` chunk loop, but on
  one chip's MXU instead of a CUDA stream pipeline);
* causal block skipping: fully-masked KV blocks are predicated out with ``pl.when``;
* GQA folded into the BlockSpec index maps (KV head = Q head // group);
* fp32 accumulation, bf16 inputs; logsumexp saved for the backward;
* backward = two kernels (dq over q-blocks; dk/dv over kv-blocks) using the saved
  logsumexp, the standard flash-attention-2 recurrence.

The public entry ``flash_attention(q, k, v, causal=True)`` takes ``[B, T, H, d]`` /
``[B, S, K, d]`` (model layout) and is differentiable via ``jax.custom_vjp``. On
non-TPU backends it falls back to the XLA reference implementation automatically.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.accelerator.real_accelerator import on_tpu as _on_tpu

DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _block_live(causal, window, q_start, k_start, block_q, block_k):
    """Per-tile liveness predicate for ``pl.when`` (q_start/k_start are traced
    program-id products): dead when entirely above the causal diagonal or
    entirely older than the sliding window. Callers fold any static
    rel_offset (a global q-position shift for chunk-pair masking) into
    q_start before calling — same convention as _bwd_mask."""
    live = True
    if causal:
        live = k_start <= q_start + block_q - 1
    if window is not None:
        in_win = k_start + block_k - 1 >= q_start - (window - 1)
        live = in_win if live is True else jnp.logical_and(live, in_win)
    return live


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                scale: float, causal: bool, window, block_q: int,
                block_k: int, rel_offset: int = 0):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q + rel_offset
    k_start = ik * block_k
    live = _block_live(causal, window, q_start, k_start, block_q, block_k)

    @pl.when(live)
    def _compute():
        # MXU wants low-precision inputs with fp32 accumulation: keep q/k/v in
        # their storage dtype (bf16) and set preferred_element_type — an fp32
        # cast before the dot would run the MXU at a fraction of its bf16 rate.
        q = q_ref[0, 0]                      # [bq, d]
        k = k_ref[0, 0]                      # [bk, d]
        v = v_ref[0, 0]                      # [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if causal or window is not None:
            # rows+q_start >= cols+k_start  ⟺  rows-cols >= k_start-q_start:
            # the iota difference is block-invariant, only the scalar threshold
            # moves, which keeps the per-block VPU mask work to compare+select
            diff = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                    - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
            keep = (diff >= k_start - q_start) if causal else True
            if window is not None:  # mistral/qwen2 sliding window
                keep = keep & (diff <= window - 1 + k_start - q_start)
            s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[:, :1]                 # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                # [bq, bk] fp32
        corr = jnp.exp(m_prev - m_new)        # [bq, 1]
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc_scr[:] / denom).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[:, :1] + jnp.log(denom)


def _fwd_pallas(q, k, v, *, scale, causal, window, block_q, block_k,
                interpret, rel_offset=0):
    B, H, T, d = q.shape
    S, K = k.shape[2], k.shape[1]
    rep = H // K
    nq, nk = T // block_q, S // block_k
    grid = (B, H, nq, nk)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               window=window, block_q=block_q, block_k=block_k,
                               rel_offset=rel_offset)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, iq, ik: (b, h // rep, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, iq, ik: (b, h // rep, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, d), q.dtype),
            jax.ShapeDtypeStruct((B, H, T, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _bwd_mask(s, causal, window, q_start, k_start):
    # callers fold any static rel_offset into q_start
    if not causal and window is None:
        return s
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + q_start
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + k_start
    keep = (rows >= cols) if causal else True
    if window is not None:
        keep = keep & (rows - cols <= window - 1)
    return jnp.where(keep, s, NEG_INF)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr, *,
                   scale, causal, window, block_q, block_k, rel_offset=0):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    q_start, k_start = iq * block_q + rel_offset, ik * block_k

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    live = _block_live(causal, window, q_start, k_start, block_q, block_k)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]                   # [bq, 1]
        delta = delta_ref[0, 0]               # [bq, 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _bwd_mask(s, causal, window, q_start, k_start)
        p = jnp.exp(s - lse)                  # [bq, bk]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(k.dtype)
        dq_scr[:] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *,
                    scale, causal, window, block_q, block_k, rel_offset=0):
    ik, iq = pl.program_id(2), pl.program_id(3)  # kv-blocks outer, q-blocks inner
    nq = pl.num_programs(3)
    q_start, k_start = iq * block_q + rel_offset, ik * block_k

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    live = _block_live(causal, window, q_start, k_start, block_q, block_k)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _bwd_mask(s, causal, window, q_start, k_start)
        p = jnp.exp(s - lse)                   # [bq, bk]
        pc = p.astype(do.dtype)
        dv_scr[:] += jax.lax.dot_general(
            pc, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_scr[:] += scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_pallas(q, k, v, out, lse, do, *, scale, causal, window, block_q,
                block_k, interpret, dlse=None, rel_offset=0):
    B, H, T, d = q.shape
    S, K = k.shape[2], k.shape[1]
    rep = H // K
    nq, nk = T // block_q, S // block_k
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
                    keepdims=True)  # [B,H,T,1]
    if dlse is not None:
        # lse cotangent (the lse-returning variant): d lse/d s = p, so the
        # extra term p*dlse folds into the kernels' ds = p*(dp - delta) as
        # delta' = delta - dlse — no kernel change
        delta = delta - dlse.astype(jnp.float32)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          rel_offset=rel_offset),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, iq, ik: (b, h // rep, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, iq, ik: (b, h // rep, ik, 0)),
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dk/dv accumulate over q blocks, per Q-head; GQA-sum folded after.
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          rel_offset=rel_offset),
        grid=(B, H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, ik, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, ik, iq: (b, h // rep, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, ik, iq: (b, h // rep, ik, 0)),
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, ik, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, ik, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, ik, iq: (b, h, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, ik, iq: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, ik, iq: (b, h, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, d), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, d), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    if rep > 1:  # GQA: sum over the query-head group
        dk = dk_h.reshape(B, K, rep, S, d).sum(axis=2).astype(k.dtype)
        dv = dv_h.reshape(B, K, rep, S, d).sum(axis=2).astype(v.dtype)
    else:
        dk, dv = dk_h, dv_h
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API with custom VJP, model layout [B, T, H, d]
# ---------------------------------------------------------------------------

def _pick_block(n: int, preferred: int) -> int:
    b = min(preferred, n)
    while n % b != 0:
        b //= 2
    return max(b, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, block_q, block_k, interpret):
    out, _ = _flash_fwd(q, k, v, causal, window, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    scale = 1.0 / math.sqrt(q.shape[-1])
    out, lse = _fwd_pallas(q, k, v, scale=scale, causal=causal, window=window,
                           block_q=block_q, block_k=block_k, interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    scale = 1.0 / math.sqrt(q.shape[-1])
    dq, dk, dv = _bwd_pallas(q, k, v, out, lse, do, scale=scale, causal=causal,
                             window=window, block_q=block_q, block_k=block_k,
                             interpret=interpret)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_lse(q, k, v, causal, window, block_q, block_k, interpret,
               rel_offset=0):
    out_lse, _ = _flash_lse_fwd(q, k, v, causal, window, block_q, block_k,
                                interpret, rel_offset)
    return out_lse


def _flash_lse_fwd(q, k, v, causal, window, block_q, block_k, interpret,
                   rel_offset=0):
    scale = 1.0 / math.sqrt(q.shape[-1])
    out, lse = _fwd_pallas(q, k, v, scale=scale, causal=causal,
                           window=window, block_q=block_q, block_k=block_k,
                           interpret=interpret, rel_offset=rel_offset)
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd(causal, window, block_q, block_k, interpret, rel_offset,
                   res, ct):
    do, dlse = ct
    q, k, v, out, lse = res
    scale = 1.0 / math.sqrt(q.shape[-1])
    dq, dk, dv = _bwd_pallas(q, k, v, out, lse, do, scale=scale,
                             causal=causal, window=window, block_q=block_q,
                             block_k=block_k, interpret=interpret, dlse=dlse,
                             rel_offset=rel_offset)
    return dq, dk, dv


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_lse(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        window: Optional[int] = None,
                        rel_offset: int = 0,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: Optional[bool] = None):
    """Flash attention that ALSO returns the log-sum-exp rows, fully
    differentiable in both outputs: ``(out [B,T,H,d], lse [B,H,T,1])``.

    The lse output is what makes chunked/merged attention composable
    (sequence/fpdt.py pair merge; flash-decode-style split reductions):
    two chunk results merge exactly via
    ``m=max(l1,l2); o=(e^{l1-m} o1 + e^{l2-m} o2)/(e^{l1-m}+e^{l2-m})``.
    GQA is native — k/v keep their K heads, the kernel maps query head h
    to kv head h//(H/K).

    ``rel_offset`` (STATIC) shifts every q row's global position by that
    many tokens relative to k row 0 — with ``causal``/``window`` this masks
    a (q-chunk, kv-chunk) pair at chunk distance ``rel_offset`` exactly as
    the full sequence would (the fused FPDT tier's sliding-window path)."""
    if interpret is None:
        interpret = not _on_tpu()
    T, S = q.shape[1], k.shape[1]
    bq = _pick_block(T, block_q)
    bk = _pick_block(S, block_k)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out, lse = _flash_lse(qt, kt, vt, causal, window, bq, bk, interpret,
                          int(rel_offset))
    return out.transpose(0, 2, 1, 3), lse


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
                    segment_ids=None, window: Optional[int] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Flash attention over model-layout tensors q[B,T,H,d], k/v[B,S,K,d].

    ``window`` masks keys more than ``window-1`` positions behind each query
    (mistral/qwen2 sliding-window attention); fully-out-of-window KV blocks
    are skipped, so compute scales with ``T*window`` instead of ``T*S``."""
    if segment_ids is not None:
        from deepspeed_tpu.models.transformer import xla_attention

        return xla_attention(q, k, v, causal=causal, segment_ids=segment_ids,
                             window=window)
    T, S = q.shape[1], k.shape[1]
    if window is not None:
        if not causal:
            raise ValueError("sliding window implies causal attention")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if T != S:
            # the block mask is start-aligned (row==col on the diagonal);
            # an S != T cache layout needs the end-aligned offset the dense
            # decode path applies — route those through the cache attention
            raise ValueError(
                f"windowed flash attention requires T == S (got T={T}, "
                f"S={S}); use the KV-cache decode path for ragged shapes")
    if interpret is None:
        interpret = not _on_tpu()
    bq = _pick_block(T, block_q)
    bk = _pick_block(S, block_k)
    qt = q.transpose(0, 2, 1, 3)  # [B, H, T, d]
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _flash(qt, kt, vt, causal, window, bq, bk, interpret)
    out = out.transpose(0, 2, 1, 3)
    # Named so remat policies can pin the kernel's output: attention is
    # VPU-bound (~5-10% MFU ceiling at trainable seq lens on v5e) and must
    # never be recomputed in the backward pass.
    return checkpoint_name(out, "flash_attn_out")
