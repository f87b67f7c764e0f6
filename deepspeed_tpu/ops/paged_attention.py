"""Pallas paged attention over a blocked KV pool (FastGen ragged kernel parity).

Parity target: ``deepspeed/inference/v2/kernels/ragged_ops/`` — ``blocked_flash``
(flash attention over paged KV blocks), ``atom_builder`` (the packed row of
atoms) and ``v2/ragged/kv_cache.py`` (the block pool). TPU-native design:

* the KV cache is a **global pool of fixed-size blocks**, stacked over layers
  and lane-folded: ``[L, num_blocks+1, block_size, K*d]``, shared by all
  sequences — HBM footprint is proportional to allocated blocks, not
  ``max_sequences × max_seq_len``. Physical blocks 0..num_blocks-1 are
  allocator-owned; the LAST block is a scratch block that padded lanes write
  into.
* ``block_tables[b, i]`` maps logical block *i* of slot *b* to its physical
  block. The kernels read the table through **scalar prefetch** and stream
  the blocks of a flat WORK LIST of (atom, block-group) items with manual
  async copies (see "Ragged atom kernels" below): the TPU analog of
  blocked_flash's block-table indirection.
* a step's own tokens attend from VMEM, so every layer's KV append hoists out
  of the layer scan into ONE in-place XLA scatter computed from the same
  tables (:func:`packed_kv_append`, :func:`packed_kv_append_quant`) — rotary
  is applied by the model before the append.
* every kernel has an XLA twin with the same signature and numerics
  (:func:`xla_ragged_attention`, :func:`xla_decode_partials`):
  :func:`attention_kernel_path` names which one a geometry takes.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.accelerator.real_accelerator import on_tpu as _on_tpu

NEG_INF = -1e30


def decode_kernel_support() -> Tuple[Optional[str], str]:
    """How the fused Pallas decode kernel can run on this backend:
    ``("native", why)`` on TPU (Mosaic lowering), ``("interpret", why)`` on
    CPU (the CI parity mode), ``(None, why)`` anywhere else — the engine
    logs ``why`` and falls back to ``decode_kernel: xla``. A runtime that
    cannot initialise raises."""
    backend = jax.default_backend()
    if backend == "tpu":
        return "native", "TPU backend: Mosaic lowering available"
    if backend == "cpu":
        return "interpret", "CPU backend: Pallas interpret mode"
    return None, (f"backend {backend!r} has no Pallas TPU lowering "
                  f"(only tpu/native and cpu/interpret are supported)")


def attention_kernel_path(d: int, bs: int, tq: int, kernel: str = "pallas",
                          interpret: Optional[bool] = None
                          ) -> Tuple[str, str]:
    """Which implementation the paged attention entry points run for head
    dim ``d``, block size ``bs`` and atom width ``tq``: ``("pallas", why)``
    or ``("xla", why)`` — the dense-gather twin, numerically identical.
    Mosaic wants 128-lane-aligned DMA chunks and reshapes; geometries off
    the serving sweet spot (small head_dim models, tiny test configs) take
    the twin. The dispatch below and the engine's ``kernel_paths`` record
    both read this one rule."""
    if _check_kernel(kernel):
        return "xla", "kernel='xla' requested"
    if interpret is None:
        interpret = not _on_tpu()
    if interpret:
        return "pallas", "interpret mode takes every geometry"
    if d % 128:
        return "xla", f"head_dim {d} is not a multiple of the 128 lanes"
    if bs % 8:
        return "xla", f"block_size {bs} is not a multiple of 8 sublanes"
    if tq > 1 and bs % 128:
        return "xla", (f"block_size {bs} is not a multiple of 128 "
                       f"(prefill atoms read whole 128-row blocks)")
    return "pallas", "Mosaic lowering"


def _check_kernel(kernel: str) -> bool:
    """Validate a ``kernel=`` selector; True when the XLA twin was asked
    for explicitly (the Pallas work-list kernel is the default)."""
    if kernel not in ("pallas", "xla"):
        raise ValueError(f"kernel must be 'pallas' or 'xla', got {kernel!r}")
    return kernel == "xla"


# ---------------------------------------------------------------------------
# Ragged atom kernels (FastGen atom_builder/blocked_flash parity, decode-fast)
#
# The serving-throughput path. An atom is one whole scheduled chunk (decode
# step = 1-token atom, prefill chunk = up to MAX_ATOM tokens). Two kernels,
# each shaped for its region's bottleneck:
#
# * DECODE (tq == 1): HBM-latency-bound — per-atom serial block streaming
#   leaves the memory system idle between tiny DMAs (measured ~10 ms flat in
#   occupancy on v5e, ~10x off the KV-bandwidth roofline). The kernel below
#   runs a flat WORK LIST of (atom, block-group) items: each item issues
#   ``_DECODE_G`` per-block async copies concurrently (blocks are table-
#   indirected, so no single large DMA is possible — the win is G copies in
#   flight per item) and the pipeline keeps ``_DMA_DEPTH`` item-fetches in
#   flight ACROSS atoms, so transfers never serialize behind compute.
#   All GQA heads are computed in ONE MXU matmul per item via a zero-padded
#   [H, K*d] query ("q_big": head h occupies lane block h//rep, zeros
#   elsewhere — the K-fold FLOPs waste is ~free, decode is bandwidth-bound).
#   The atom's own token is merged OUTSIDE the kernel from the returned
#   (acc, m, l) partials — flash-decode's split-reduction, with the self
#   token as the extra partial.
# * PREFILL (tq > 1): split reduction. A work-list kernel (same machinery
#   as decode, per-kv-head [R=tq*rep, G*bs] tiles) streams the PAST blocks
#   into (acc, m, l) partials; a REAL flash tile (same structure as the
#   training kernel in ops/flash_attention.py) runs the intra-atom causal
#   attention with its online-softmax scratch SEEDED from those partials —
#   so chunked prefill hits training-class efficiency and the merge costs
#   one scratch init instead of an XLA pass.
#
# Both kernels read the pools STACKED across layers ([L, nbp1, bs, K, d] in
# ANY/HBM memory, a traced layer index picks the layer) — threading
# per-layer pool slices through the model's lax.scan would materialize a
# full pool copy per layer (measured ~12 ms/step of pure copies on v5e).
# The (K, d) axes are folded to K*d lanes at the kernel boundary: every DMA
# chunk is a [bs, K*d] tile — sub-tile row DMAs crash the Mosaic toolchain
# and tiny-sublane chunks are slow.
# ---------------------------------------------------------------------------

# (the atom-width cap lives on TransformerLM.MAX_ATOM — the engine chunking
# and the VMEM-bounded kernel tile share that single constant)

_DECODE_G = 8       # KV blocks per decode work item (one DMA pair per item)
_PAST_G = 2         # KV blocks per prefill-past work item (bigger per-block
                    # compute; smaller groups keep VMEM under the 16MB cap)
_DMA_DEPTH = 3      # work-item fetches kept in flight across the work list
# The past kernel keeps (m, l, acc) for all H*tq rows of an atom in VMEM:
# 18.3 MiB at H=32/K=8/d=128 with the widest atom (tq=256), over Mosaic's
# 16 MiB default scoped limit — the chip's compiler refuses it. v5e has
# 128 MiB of VMEM; raise the kernel's own limit so every tq <= MAX_ATOM
# the engine can schedule compiles.
_PAST_VMEM_LIMIT = 64 * 1024 * 1024


def _worklist_helpers(n_items, NG, G, bs, nb_max, slot_ref, nblk_ref, lo_ref,
                      ng_ref, bt_ref, li_ref, kpool, vpool, kbuf, vbuf, dsem,
                      spool=None, sbuf=None):
    """Shared work-list DMA machinery: item j = G consecutive logical KV
    blocks of atom j//NG, streamed from the STACKED pool layer li. With an
    int8 pool, ``spool`` [L, nbp1, 1, 2*bs] carries the per-token
    dequant scales (k in lanes [0,bs), v in [bs,2bs)) — one extra f32 row
    copy per block.

    Every copy is paired with a per-block validity predicate (from
    ``nblk_ref``, computed host-side by the same ``_past_ranges`` call that
    produced ``ng_ref`` — a single source of truth) and the call sites gate
    start()/wait() on it: an atom's tail group only streams its REAL
    blocks. Unguarded, the clipped tail re-read the last block G-ish times
    — at 512-token contexts that was ~1.8x the useful KV bytes, and the
    decode kernel is pure KV bandwidth."""

    def item_dmas(j, dst):
        jc = jnp.clip(j, 0, n_items - 1)
        aj = jc // NG
        gj = jax.lax.rem(jc, NG)
        slot = slot_ref[aj]
        li = li_ref[0]
        nblk = nblk_ref[aj]
        copies = []
        for gg in range(G):
            ok = gj * G + gg < nblk
            lb = jnp.clip(lo_ref[aj] + gj * G + gg, 0, nb_max - 1)
            bid = bt_ref[slot, lb]
            copies.append((pltpu.make_async_copy(
                kpool.at[li, bid], kbuf.at[dst, pl.ds(gg * bs, bs)],
                dsem.at[dst, 0, gg]), ok))
            copies.append((pltpu.make_async_copy(
                vpool.at[li, bid], vbuf.at[dst, pl.ds(gg * bs, bs)],
                dsem.at[dst, 1, gg]), ok))
            if spool is not None:
                # sbuf rows are [1, 2bs] leading-dim slices (Mosaic requires
                # minor-dim slices be tile-aligned; a [G, 2bs] row pick
                # along dim 1 is not)
                copies.append((pltpu.make_async_copy(
                    spool.at[li, bid], sbuf.at[dst * G + gg],
                    dsem.at[dst, 2, gg]), ok))
        return copies

    def item_active(j):
        jc = jnp.clip(j, 0, n_items - 1)
        return (j < n_items) & (jax.lax.rem(jc, NG) < ng_ref[jc // NG])

    return item_dmas, item_active


def _gated_dmas(copies, op):
    """start()/wait() each (copy, valid) pair under its own predicate."""
    for c, ok in copies:
        @pl.when(ok)
        def _go(c=c):
            getattr(c, op)()


def _past_ranges(atom_pos0, row_pos, bs, nb_max, G, window):
    """(pos0, lo block, valid block count, group count >= 1) of each atom's
    visible past range. ``row_pos`` (>= pos0) is the query row's global
    position — it trails the sliding window; ``pos0`` is the pool frontier
    (tokens < pos0 cached). ``nblk`` feeds the kernels' per-copy DMA gate —
    computed HERE, once, so the gate can never disagree with ``ng``."""
    pos0 = atom_pos0.astype(jnp.int32)
    if window is not None:
        lo = jnp.maximum((row_pos.astype(jnp.int32) - (window - 1)) // bs, 0)
    else:
        lo = jnp.zeros_like(pos0)
    nblk = jnp.where(
        pos0 > 0,
        jnp.maximum(jnp.minimum((pos0 - 1) // bs, nb_max - 1) - lo + 1, 0), 0)
    ng = jnp.maximum(-(-nblk // G), 1).astype(jnp.int32)
    return pos0, lo.astype(jnp.int32), nblk.astype(jnp.int32), ng


def _quantize_q_rows(q):
    """Per-row (last-axis) int8 fake-quant of a query tensor. Returns
    (q_int8, scale) — the ONE definition of the int8-KV decode path's q-hat
    semantics, shared by the kernel wrapper and its XLA twin so they stay
    bit-identical."""
    qf = q.astype(jnp.float32)
    qs = jnp.maximum(jnp.max(jnp.abs(qf), axis=-1, keepdims=True) / 127.0,
                     1e-12)
    qi = jnp.clip(jnp.round(qf / qs), -127, 127)
    return qi.astype(jnp.int8), qs


def _unpack_int4_lanes(packed_i8, K: int, d: int):
    """[R, K*d/2] packed int8 bytes → [R, K*d] int4 values as bf16.

    Lane pairing is GLOBAL — byte lane j holds features j (low nibble) and
    j + K*d/2 (high) — so the unpack is one 128-aligned lane concat
    (per-head pairing would need d/2-lane slices, which Mosaic will not
    lower; the cost is that an int4 pool cannot be lane-sharded over tp —
    the engine guards that combination). i32 shifts sign-extend the nibbles
    for free (Mosaic legalizes i32 but not i8 vector shifts); this replaced
    a float floor/divide unpack whose VPU cost outweighed the byte saving
    (see ops/quant_matmul.py _qmm_body for the same rework)."""
    del K, d
    b32 = packed_i8.astype(jnp.int32)
    lo = ((b32 << 28) >> 28).astype(jnp.bfloat16)
    hi = (b32 >> 4).astype(jnp.bfloat16)
    return jnp.concatenate([lo, hi], axis=-1)


def _decode_kernel(*refs, scale: float, bs: int, K: int, rep: int,
                   nb_max: int, NG: int, window, quantized: bool,
                   kv_bits: int = 8):
    """One work item = G consecutive past-KV blocks of one decode atom."""
    if quantized and kv_bits == 8:
        # int8 pool + int8 q: the score dot runs on the int8 MXU and the K
        # tile is never converted — the convert of the whole [G*bs, K*d]
        # tile was ~30% of the int8 decode step (the kernel sat at ~430
        # GB/s effective vs the bf16 kernel's ~590)
        (li_ref, slot_ref, pos0_ref, row_ref, lo_ref, nblk_ref, ng_ref,
         bt_ref, q_ref, qs_ref, kpool, vpool, spool, acc_ref, m_ref, l_ref,
         kbuf, vbuf, sbuf, dsem, m_scr, l_scr, acc_scr) = refs
    elif quantized:
        (li_ref, slot_ref, pos0_ref, row_ref, lo_ref, nblk_ref, ng_ref,
         bt_ref, q_ref, kpool, vpool, spool, acc_ref, m_ref, l_ref,
         kbuf, vbuf, sbuf, dsem, m_scr, l_scr, acc_scr) = refs
        qs_ref = None
    else:
        (li_ref, slot_ref, pos0_ref, row_ref, lo_ref, nblk_ref, ng_ref,
         bt_ref, q_ref, kpool, vpool, acc_ref, m_ref, l_ref,
         kbuf, vbuf, dsem, m_scr, l_scr, acc_scr) = refs
        spool = sbuf = qs_ref = None
    i = pl.program_id(0)
    n_items = pl.num_programs(0)
    G, DEPTH = _DECODE_G, _DMA_DEPTH
    H = q_ref.shape[1]
    d = q_ref.shape[2] // K       # NOT from the pool: int4 packs its lanes
    a = i // NG
    g = jax.lax.rem(i, NG)
    item_dmas, item_active = _worklist_helpers(
        n_items, NG, G, bs, nb_max, slot_ref, nblk_ref, lo_ref, ng_ref,
        bt_ref, li_ref, kpool, vpool, kbuf, vbuf, dsem, spool, sbuf)

    @pl.when(i == 0)
    def _warmup():
        # gated DMAs leave tail slots untouched, so stale VMEM must start
        # finite: p~0 x NaN garbage would poison the pv@vb contraction
        kbuf[:] = jnp.zeros_like(kbuf)
        vbuf[:] = jnp.zeros_like(vbuf)
        if sbuf is not None:
            sbuf[:] = jnp.zeros_like(sbuf)
        for joff in range(DEPTH):
            @pl.when(item_active(joff))
            def _issue(_j=joff):
                _gated_dmas(item_dmas(_j, _j % DEPTH), "start")

    @pl.when(g == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    active = g < ng_ref[a]

    @pl.when(active)
    def _compute():
        dst = jax.lax.rem(i, DEPTH)
        _gated_dmas(item_dmas(i, dst), "wait")
        qb = q_ref[0]                            # [H, K*d] zero-padded
        if quantized:                 # int rows, per-token dequant scales
            sc = sbuf[pl.ds(dst * G, G), 0]      # [G, 2*bs] f32
            sck = sc[:, :bs].reshape(1, G * bs)
            scv = sc[:, bs:].reshape(1, G * bs)
        if quantized and kv_bits == 8:
            # qb int8 [H, K*d], kb raw int8: exact integer dot, dequant on
            # the [H, G*bs] scores (q row scale x per-token k scale)
            s = jax.lax.dot_general(qb, kbuf[dst], (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.int32)
            s = (s.astype(jnp.float32) * (qs_ref[0][:, :1] * scale)) * sck
            vb = vbuf[dst].astype(jnp.bfloat16)
        else:
            if quantized:             # int4: nibble-unpack, global pairing
                kb = _unpack_int4_lanes(kbuf[dst], K, d).astype(qb.dtype)
                vb = _unpack_int4_lanes(vbuf[dst], K, d).astype(qb.dtype)
            else:
                kb = kbuf[dst]                   # [G*bs, K*d]
                vb = vbuf[dst]
            s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) \
                * scale
            if quantized:
                s = s * sck
        pos0 = pos0_ref[a]
        colpos = ((lo_ref[a] + g * G) * bs
                  + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
        keep = colpos < pos0
        if window is not None:
            keep = keep & (colpos > row_ref[a] - window)
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            l_scr[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        pv = (p * scv if quantized else p).astype(vb.dtype)
        ob = jax.lax.dot_general(pv, vb, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        # head-select the GQA group's lane block out of [H, K*d]
        obh = ob.reshape(H, K, d)
        sel = (jax.lax.broadcasted_iota(jnp.int32, (H, K, 1), 1)
               == jax.lax.broadcasted_iota(jnp.int32, (H, K, 1), 0) // rep)
        acc_scr[:] = acc_scr[:] * corr + jnp.sum(
            jnp.where(sel, obh, 0.0), axis=1)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    # refill the pipeline AFTER the compute consumed this slot's buffers —
    # item i+DEPTH reuses slot i%DEPTH. Outside the `active` guard: an
    # inactive item must still issue its successor or a gap in the work
    # list would starve the pipeline.
    @pl.when(item_active(i + DEPTH))
    def _prefetch():
        _gated_dmas(item_dmas(i + DEPTH, jax.lax.rem(i + DEPTH, DEPTH)),
                    "start")

    @pl.when(g == ng_ref[a] - 1)
    def _finalize():
        acc_ref[0] = acc_scr[:]
        m_ref[0] = m_scr[:]
        l_ref[0] = l_scr[:]


def decode_pool_partials(q, k_pool, v_pool, layer, block_tables, atom_slot,
                         atom_pos0, *, window=None, row_pos=None,
                         interpret=None, kv_scale=None, kv_bits: int = 8,
                         kernel: str = "pallas"):
    """(acc, m, l) flash-decode partials of each decode row's attention over
    its POOL-cached past (positions < pos0). ``row_pos`` is the query's true
    position (defaults to pos0) — it only matters for sliding windows, e.g.
    in the fused loop where rows advance while the pool frontier stays put.
    q [A, H, d]; pools STACKED lane-folded [L, nbp1, bs, K*d] — bf16, or
    int8/int4 (``kv_bits``; int4 packs lane j with j + K*d/2 per byte) with
    ``kv_scale`` [L, nbp1, 1, 2*bs] per-token dequant scales.
    ``kernel='xla'`` (``inference.decode_kernel``) routes straight to the
    dense-gather twin — same math, for A/B benching and as the logged
    fallback when Pallas is unavailable.
    Returns fp32 acc [A, H, d] (unnormalized), m/l [A, H]."""
    if interpret is None:
        interpret = not _on_tpu()
    A, H, d = q.shape
    lane_mul = 2 if (kv_scale is not None and kv_bits == 4) else 1
    bs, K = k_pool.shape[2], k_pool.shape[3] * lane_mul // d
    rep = H // K
    nb_max = block_tables.shape[1]
    scale = 1.0 / math.sqrt(d)
    quantized = kv_scale is not None
    if row_pos is None:
        row_pos = atom_pos0
    if attention_kernel_path(d, bs, 1, kernel, interpret)[0] == "xla":
        return xla_decode_partials(q, k_pool, v_pool, layer, block_tables,
                                   atom_slot, atom_pos0, window=window,
                                   row_pos=row_pos, kv_scale=kv_scale,
                                   kv_bits=kv_bits)
    G = _DECODE_G
    NG = max(1, -(-nb_max // G))
    pos0, lo, nblk, ng = _past_ranges(atom_pos0, row_pos, bs, nb_max, G,
                                      window)

    # zero-padded q_big: head h lives in lane block h//rep
    hsel = (jnp.arange(K)[None, :] == (jnp.arange(H) // rep)[:, None])
    q_big = jnp.where(hsel[None, :, :, None], q[:, :, None, :], 0)
    q_big = q_big.reshape(A, H, K * d)
    if q_big.dtype not in (jnp.bfloat16, jnp.float32):
        q_big = q_big.astype(jnp.bfloat16)
    q_int = quantized and kv_bits == 8
    if q_int:
        # per-(atom, head) int8 q for the integer score dot; the zero
        # padding survives exactly (0/scale == 0)
        q_big, qs = _quantize_q_rows(q_big)
        qs_pad = jnp.broadcast_to(qs, (A, H, 128)).astype(jnp.float32)

    kernel = functools.partial(
        _decode_kernel, scale=scale, bs=bs, K=K, rep=rep, nb_max=nb_max,
        NG=NG, window=window, quantized=quantized, kv_bits=kv_bits)
    kd_lanes = k_pool.shape[3]          # K*d, or K*d/2 for the int4 pool
    in_specs = [
        pl.BlockSpec((1, H, K * d), lambda i, *_: (i // NG, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    scratch = [
        pltpu.VMEM((_DMA_DEPTH, G * bs, kd_lanes), k_pool.dtype),
        pltpu.VMEM((_DMA_DEPTH, G * bs, kd_lanes), v_pool.dtype),
        pltpu.SemaphoreType.DMA((_DMA_DEPTH, 3 if quantized else 2, G)),
        pltpu.VMEM((H, 128), jnp.float32),
        pltpu.VMEM((H, 128), jnp.float32),
        pltpu.VMEM((H, d), jnp.float32),
    ]
    operands = [q_big, k_pool, v_pool]
    if q_int:
        in_specs.insert(1, pl.BlockSpec((1, H, 128),
                                        lambda i, *_: (i // NG, 0, 0)))
        operands.insert(1, qs_pad)
    if quantized:
        in_specs.insert(4 if q_int else 3, pl.BlockSpec(memory_space=pl.ANY))
        scratch.insert(2, pltpu.VMEM((_DMA_DEPTH * G, 1, 2 * bs),
                                     jnp.float32))
        operands.append(kv_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(A * NG,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, H, d), lambda i, *_: (i // NG, 0, 0)),
            pl.BlockSpec((1, H, 128), lambda i, *_: (i // NG, 0, 0)),
            pl.BlockSpec((1, H, 128), lambda i, *_: (i // NG, 0, 0)),
        ],
        scratch_shapes=scratch,
    )
    acc, m_p, l_p = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((A, H, d), jnp.float32),
            jax.ShapeDtypeStruct((A, H, 128), jnp.float32),
            jax.ShapeDtypeStruct((A, H, 128), jnp.float32),
        ],
        interpret=interpret,
    )(layer.reshape(1).astype(jnp.int32), atom_slot.astype(jnp.int32), pos0,
      row_pos.astype(jnp.int32), lo, nblk, ng,
      block_tables.astype(jnp.int32), *operands)
    return acc, m_p[..., 0], l_p[..., 0]


def _unpack_int4_lanes_xla(packed, K: int, d: int):
    """[..., K*d/2] int8 packed → [..., K*d] f32 int4 values (XLA-side twin
    of :func:`_unpack_int4_lanes`, same global lane pairing; int8 shifts
    are fine outside Mosaic)."""
    del K, d
    lo = ((packed << 4).astype(jnp.int8) >> 4).astype(jnp.float32)
    hi = (packed >> 4).astype(jnp.float32)
    return jnp.concatenate([lo, hi], axis=-1)


def xla_decode_partials(q, k_pool, v_pool, layer, block_tables, atom_slot,
                        atom_pos0, *, window=None, row_pos=None,
                        kv_scale=None, kv_bits: int = 8):
    """Dense-gather reference/fallback for :func:`decode_pool_partials`
    (pools stacked lane-folded [L, nbp1, bs, K*d])."""
    A, H, d = q.shape
    lane_mul = 2 if (kv_scale is not None and kv_bits == 4) else 1
    bs, K = k_pool.shape[2], k_pool.shape[3] * lane_mul // d
    rep = H // K
    if row_pos is None:
        row_pos = atom_pos0
    kp = jax.lax.dynamic_index_in_dim(k_pool, layer, keepdims=False)
    vp = jax.lax.dynamic_index_in_dim(v_pool, layer, keepdims=False)
    bt = block_tables[atom_slot]                            # [A, nb_max]
    S = bt.shape[1] * bs
    if kv_scale is not None and kv_bits == 4:
        kd = _unpack_int4_lanes_xla(kp[bt], K, d).reshape(A, S, K, d)
        vd = _unpack_int4_lanes_xla(vp[bt], K, d).reshape(A, S, K, d)
    else:
        kd = kp[bt].reshape(A, S, K, d)
        vd = vp[bt].reshape(A, S, K, d)
    if kv_scale is not None:                    # int pool: dequant per token
        sc = jax.lax.dynamic_index_in_dim(kv_scale, layer, keepdims=False)
        sc = sc[bt][..., 0, :]                  # [A, nb_max, 2*bs]
        sck = sc[..., :bs].reshape(A, S)
        scv = sc[..., bs:].reshape(A, S)
        kd = kd.astype(jnp.float32) * sck[..., None, None]
        vd = vd.astype(jnp.float32) * scv[..., None, None]
        if kv_bits == 8:
            # mirror the kernel's int8 q (per-(atom, head) scale) so the
            # twin computes the same q-hat semantics
            qi, qs = _quantize_q_rows(q)
            q = qi.astype(jnp.float32) * qs
    if K != H:
        kd = jnp.repeat(kd, rep, axis=2)
        vd = jnp.repeat(vd, rep, axis=2)
    s = jnp.einsum("ahd,ashd->ahs", q.astype(jnp.float32),
                   kd.astype(jnp.float32)) / math.sqrt(d)
    col = jnp.arange(S)[None, None, :]
    keep = col < atom_pos0[:, None, None]
    if window is not None:
        keep = keep & (col > row_pos[:, None, None] - window)
    s = jnp.where(keep, s, NEG_INF)
    m = jnp.max(s, axis=-1)                                 # [A, H]
    p = jnp.exp(s - m[..., None])
    p = jnp.where(keep, p, 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("ahs,ashd->ahd", p, vd.astype(jnp.float32))
    return acc, m, l


def decode_pool_partials_tp(q, k_pool, v_pool, layer, block_tables,
                            atom_slot, atom_pos0, axis: str = "tp",
                            window=None, row_pos=None, kv_scale=None,
                            kv_bits: int = 8, kernel: str = "pallas"):
    """Tensor-parallel :func:`decode_pool_partials` (heads embarrassingly
    parallel: q on H, pools on K, partials out on H; per-token int8 scales
    replicated)."""
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or axis not in mesh.axis_names \
            or mesh.shape[axis] <= 1:
        return decode_pool_partials(q, k_pool, v_pool, layer, block_tables,
                                    atom_slot, atom_pos0, window=window,
                                    row_pos=row_pos, kv_scale=kv_scale,
                                    kv_bits=kv_bits, kernel=kernel)
    if row_pos is None:
        row_pos = atom_pos0

    if kv_scale is None:
        kv_scale = jnp.zeros((0,), jnp.float32)   # sentinel: bf16 pool
    elif kv_scale.ndim != 4:
        raise ValueError(
            f"kv_scale must be [L, nb+1, 1, 2*block_size], got "
            f"{kv_scale.shape}")

    def shard_fn(q, kp, vp, lay, bt, a_s, a_p, rp, sc):
        return decode_pool_partials(
            q, kp, vp, lay, bt, a_s, a_p, window=window, row_pos=rp,
            kv_scale=sc if sc.ndim == 4 else None, kv_bits=kv_bits,
            kernel=kernel)

    return jax.shard_map(
        shard_fn,
        in_specs=(P(None, axis, None), P(None, None, None, axis),
                  P(None, None, None, axis), P(), P(None, None),
                  P(None), P(None), P(None),
                  P(None, None, None, None) if kv_scale.ndim == 4
                  else P(None)),
        out_specs=(P(None, axis, None), P(None, axis), P(None, axis)),
        check_vma=False,
    )(q, k_pool, v_pool, layer, block_tables, atom_slot, atom_pos0, row_pos,
      kv_scale)


def _decode_attention(q, k_self, v_self, k_pool, v_pool, layer, block_tables,
                      atom_slot, atom_pos0, atom_len, *, window, interpret,
                      kv_scale=None, kv_bits: int = 8):
    """Decode-row attention: pool partials + self token merged outside
    (flash-decode split reduction). Shapes: q/k_self/v_self [A, H|K, d];
    pools STACKED lane-folded [L, nbp1, bs, K*d], ``layer`` picks the
    layer."""
    A, H, d = q.shape
    K = k_self.shape[-2]
    rep = H // K
    scale = 1.0 / math.sqrt(d)
    acc, m_k, l_k = decode_pool_partials(
        q, k_pool, v_pool, layer, block_tables, atom_slot, atom_pos0,
        window=window, interpret=interpret, kv_scale=kv_scale,
        kv_bits=kv_bits)

    # merge the self token (its position == pos0: always causal-visible and
    # inside any window)
    qf = q.astype(jnp.float32)
    ks = jnp.repeat(k_self.astype(jnp.float32), rep, axis=1)    # [A, H, d]
    vs = jnp.repeat(v_self.astype(jnp.float32), rep, axis=1)
    s_self = jnp.sum(qf * ks, axis=-1) * scale                  # [A, H]
    m2 = jnp.maximum(m_k, s_self)
    c_k = jnp.exp(m_k - m2)
    c_s = jnp.exp(s_self - m2)
    denom = jnp.maximum(l_k * c_k + c_s, 1e-30)
    out = (acc * c_k[..., None] + vs * c_s[..., None]) / denom[..., None]
    out = jnp.where(atom_len[:, None, None] > 0, out, 0)
    return out.astype(q.dtype)


def _past_kernel(*refs, scale: float, bs: int, tq: int, K: int, rep: int,
                 nb_max: int, NG: int, window, quantized: bool,
                 kv_bits: int = 8):
    """Prefill-past partials: one work item = G past blocks of one chunk
    atom, per-kv-head score/update loops over [R=tq*rep, G*bs] tiles."""
    if quantized:
        (li_ref, slot_ref, pos0_ref, lo_ref, nblk_ref, ng_ref, bt_ref,
         q_ref, kpool, vpool, spool, acc_ref, m_ref, l_ref,
         kbuf, vbuf, sbuf, dsem, m_scr, l_scr, acc_scr) = refs
    else:
        (li_ref, slot_ref, pos0_ref, lo_ref, nblk_ref, ng_ref, bt_ref,
         q_ref, kpool, vpool, acc_ref, m_ref, l_ref,
         kbuf, vbuf, dsem, m_scr, l_scr, acc_scr) = refs
        spool = sbuf = None
    i = pl.program_id(0)
    n_items = pl.num_programs(0)
    G, DEPTH = _PAST_G, _DMA_DEPTH
    # NOT from the pool lane width: the int4 pool packs two lanes per byte
    d = q_ref.shape[-1]
    R = tq * rep
    a = i // NG
    g = jax.lax.rem(i, NG)
    item_dmas, item_active = _worklist_helpers(
        n_items, NG, G, bs, nb_max, slot_ref, nblk_ref, lo_ref, ng_ref,
        bt_ref, li_ref, kpool, vpool, kbuf, vbuf, dsem, spool, sbuf)

    @pl.when(i == 0)
    def _warmup():
        # stale VMEM must start finite under gated DMAs (see _decode_kernel)
        kbuf[:] = jnp.zeros_like(kbuf)
        vbuf[:] = jnp.zeros_like(vbuf)
        if sbuf is not None:
            sbuf[:] = jnp.zeros_like(sbuf)
        for joff in range(DEPTH):
            @pl.when(item_active(joff))
            def _issue(_j=joff):
                _gated_dmas(item_dmas(_j, _j % DEPTH), "start")

    @pl.when(g == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    active = g < ng_ref[a]

    @pl.when(active)
    def _compute():
        dst = jax.lax.rem(i, DEPTH)
        _gated_dmas(item_dmas(i, dst), "wait")
        pos0 = pos0_ref[a]
        colpos = ((lo_ref[a] + g * G) * bs
                  + jax.lax.broadcasted_iota(jnp.int32, (R, G * bs), 1))
        keep = colpos < pos0
        if window is not None:
            rowpos = (pos0 + jax.lax.broadcasted_iota(
                jnp.int32, (R, G * bs), 0) // rep)
            keep = keep & (colpos > rowpos - window)
        if quantized:
            sc = sbuf[pl.ds(dst * G, G), 0]                   # [G, 2*bs]
            sck = sc[:, :bs].reshape(1, G * bs)
            scv = sc[:, bs:].reshape(1, G * bs)
        if quantized and kv_bits == 4:
            # unpack the whole [G*bs, K*d/2] tile once (global lane
            # pairing), then per-head slabs slice the unpacked lanes
            kfull = _unpack_int4_lanes(kbuf[dst], K, d)
            vfull = _unpack_int4_lanes(vbuf[dst], K, d)
        for kk in range(K):
            qk = q_ref[0, kk]                    # [R, d]
            if quantized and kv_bits == 4:
                kslab = kfull[:, kk * d:(kk + 1) * d].astype(qk.dtype)
                vslab = vfull[:, kk * d:(kk + 1) * d].astype(qk.dtype)
            else:
                kslab = kbuf[dst, :, kk * d:(kk + 1) * d]
                vslab = vbuf[dst, :, kk * d:(kk + 1) * d]
                if quantized:
                    kslab = kslab.astype(qk.dtype)
                    vslab = vslab.astype(qk.dtype)
            s = jax.lax.dot_general(
                qk, kslab, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [R, G*bs]
            if quantized:
                s = s * sck
            s = jnp.where(keep, s, NEG_INF)
            m_prev = m_scr[kk, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[kk] = jnp.broadcast_to(
                l_scr[kk, :, :1] * corr + jnp.sum(p, axis=1, keepdims=True),
                l_scr.shape[1:])
            pv = (p * scv if quantized else p).astype(vslab.dtype)
            acc_scr[kk] = acc_scr[kk] * corr + jax.lax.dot_general(
                pv, vslab, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[kk] = jnp.broadcast_to(m_new, m_scr.shape[1:])

    @pl.when(item_active(i + DEPTH))
    def _prefetch():
        _gated_dmas(item_dmas(i + DEPTH, jax.lax.rem(i + DEPTH, DEPTH)),
                    "start")

    @pl.when(g == ng_ref[a] - 1)
    def _finalize():
        acc_ref[0] = acc_scr[:]
        m_ref[0] = m_scr[:]
        l_ref[0] = l_scr[:]


def _self_kernel(len_ref, q_ref, k_ref, v_ref, m0_ref, l0_ref, a0_ref, o_ref,
                 m_scr, l_scr, acc_scr, *, scale: float, block_q: int,
                 block_k: int, window, has_past: bool):
    """Intra-atom causal flash over the chunk's own (right-padded) tokens,
    optionally seeded from the past kernel's partials — the second half of
    the flash-decode split reduction, fused into the flash epilogue."""
    a, iq, ik = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    alen = len_ref[a]

    @pl.when(ik == 0)
    def _init():
        if has_past:
            m_scr[:] = m0_ref[0, 0]
            l_scr[:] = l0_ref[0, 0]
            acc_scr[:] = a0_ref[0, 0]
        else:
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

    live = jnp.logical_and(ik * block_k <= iq * block_q + block_q - 1,
                           ik * block_k < alen)
    if window is not None:
        live = jnp.logical_and(
            live, ik * block_k + block_k - 1 >= iq * block_q - (window - 1))

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        row = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = (col <= row) & (col < alen)
        if window is not None:
            keep = keep & (col > row - window)
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            l_scr[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(ik == nk - 1)
    def _finalize():
        out = acc_scr[:] / jnp.maximum(l_scr[:, :1], 1e-30)
        row_ok = (iq * block_q
                  + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
                  < alen)
        o_ref[0, 0] = jnp.where(row_ok, out, 0).astype(o_ref.dtype)


def _prefill_attention(q, k_self, v_self, k_pool, v_pool, layer,
                       block_tables, atom_slot, atom_pos0, atom_len, tq, *,
                       window, interpret, no_past=False, kv_scale=None,
                       kv_bits: int = 8):
    """Chunk-atom attention = past work-list partials + seeded self flash.
    Pools stacked lane-folded [L, nbp1, bs, K*d] (bf16, or int8/int4 +
    ``kv_scale``)."""
    N, H, d = q.shape
    lane_mul = 2 if (kv_scale is not None and kv_bits == 4) else 1
    bs, K = k_pool.shape[2], k_pool.shape[3] * lane_mul // d
    rep = H // K
    A = N // tq
    R = tq * rep
    nb_max = block_tables.shape[1]
    scale = 1.0 / math.sqrt(d)
    quantized = kv_scale is not None

    if not no_past:
        G = _PAST_G
        NG = max(1, -(-nb_max // G))
        # the OLDEST query row (position pos0) governs the window's lo block
        pos0, lo, nblk, ng = _past_ranges(atom_pos0, atom_pos0, bs,
                                          nb_max, G, window)
        # q in per-kv-head row blocks: [A, K, R=tq*rep, d], row r=(t, rr)
        qk = (q.reshape(A, tq, K, rep, d).transpose(0, 2, 1, 3, 4)
              .reshape(A, K, R, d))
        kernel = functools.partial(
            _past_kernel, scale=scale, bs=bs, tq=tq, K=K, rep=rep,
            nb_max=nb_max, NG=NG, window=window, quantized=quantized,
            kv_bits=kv_bits)
        in_specs = [
            pl.BlockSpec((1, K, R, d), lambda i, *_: (i // NG, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ]
        kd_lanes = k_pool.shape[3]     # K*d, or K*d/2 for the int4 pool
        scratch = [
            pltpu.VMEM((_DMA_DEPTH, G * bs, kd_lanes), k_pool.dtype),
            pltpu.VMEM((_DMA_DEPTH, G * bs, kd_lanes), v_pool.dtype),
            pltpu.SemaphoreType.DMA((_DMA_DEPTH, 3 if quantized else 2, G)),
            pltpu.VMEM((K, R, 128), jnp.float32),
            pltpu.VMEM((K, R, 128), jnp.float32),
            pltpu.VMEM((K, R, d), jnp.float32),
        ]
        operands = [qk, k_pool, v_pool]
        if quantized:
            in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
            scratch.insert(2, pltpu.VMEM((_DMA_DEPTH * G, 1, 2 * bs),
                                         jnp.float32))
            operands.append(kv_scale)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(A * NG,),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, K, R, d), lambda i, *_: (i // NG, 0, 0, 0)),
                pl.BlockSpec((1, K, R, 128), lambda i, *_: (i // NG, 0, 0, 0)),
                pl.BlockSpec((1, K, R, 128), lambda i, *_: (i // NG, 0, 0, 0)),
            ],
            scratch_shapes=scratch,
        )
        acc_p, m_p, l_p = pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((A, K, R, d), jnp.float32),
                jax.ShapeDtypeStruct((A, K, R, 128), jnp.float32),
                jax.ShapeDtypeStruct((A, K, R, 128), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_PAST_VMEM_LIMIT),
            interpret=interpret,
        )(layer.reshape(1).astype(jnp.int32), atom_slot.astype(jnp.int32),
          pos0, lo, nblk, ng, block_tables.astype(jnp.int32), *operands)

        def to_hq(x):  # [A, K, (tq, rep), c] -> [A, H=K*rep, tq, c]
            c = x.shape[-1]
            return (x.reshape(A, K, tq, rep, c).transpose(0, 1, 3, 2, 4)
                    .reshape(A, H, tq, c))
        m0, l0, a0 = to_hq(m_p), to_hq(l_p), to_hq(acc_p)
    else:
        # dummy inits of the right block shape (the kernel ignores them)
        m0 = l0 = jnp.zeros((A, H, tq, 128), jnp.float32)
        a0 = jnp.zeros((A, H, tq, d), jnp.float32)

    bk = 128 if not interpret else bs
    bq = tq
    while bq > 256 or tq % bq:
        bq //= 2
    tq_pad = -(-tq // bk) * bk
    pad = [(0, 0), (0, tq_pad - tq), (0, 0), (0, 0)]
    # the atom's own KV stays in compute precision (never quantized)
    ks4 = (jnp.pad(k_self.reshape(A, tq, K, d), pad).astype(q.dtype)
           .transpose(0, 2, 1, 3))
    vs4 = (jnp.pad(v_self.reshape(A, tq, K, d), pad).astype(q.dtype)
           .transpose(0, 2, 1, 3))
    q4 = q.reshape(A, tq, H, d).transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _self_kernel, scale=scale, block_q=bq, block_k=bk, window=window,
        has_past=not no_past)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(A, H, tq // bq, tq_pad // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda a, h, iq, ik, *_: (a, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda a, h, iq, ik, *_: (a, h // rep, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda a, h, iq, ik, *_: (a, h // rep, ik, 0)),
            pl.BlockSpec((1, 1, bq, 128),
                         lambda a, h, iq, ik, *_: (a, h, iq, 0)),
            pl.BlockSpec((1, 1, bq, 128),
                         lambda a, h, iq, ik, *_: (a, h, iq, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda a, h, iq, ik, *_: (a, h, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda a, h, iq, ik, *_: (a, h, iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((A, H, tq, d), q.dtype),
        interpret=interpret,
    )(atom_len.astype(jnp.int32), q4, ks4, vs4, m0, l0, a0)
    return out.transpose(0, 2, 1, 3).reshape(N, H, d)


def ragged_paged_attention(q: jax.Array, k_self: jax.Array, v_self: jax.Array,
                           k_pool: jax.Array, v_pool: jax.Array,
                           block_tables: jax.Array, atom_slot: jax.Array,
                           atom_pos0: jax.Array, atom_len: jax.Array,
                           tq: int, window: Optional[int] = None,
                           interpret: Optional[bool] = None,
                           layer: Optional[jax.Array] = None,
                           no_past: bool = False,
                           kv_scale: Optional[jax.Array] = None,
                           kv_bits: int = 8,
                           kernel: str = "pallas") -> jax.Array:
    """Attention over atoms of the packed token row.

    ``q``/``k_self``/``v_self``: [N, H|K, d] with N = n_atoms*tq; atom ``a``
    covers rows [a*tq, a*tq+atom_len[a]) — consecutive positions
    ``atom_pos0[a]+i`` of sequence slot ``atom_slot[a]``. The atom's own KV
    (``k_self``/``v_self``) never goes through the pools — the pools only
    need tokens of PREVIOUS put()s (positions < atom_pos0), so the step's
    appends happen after the fact, in one hoisted scatter.

    Pools may be per-layer [nbp1, bs, K, d] or STACKED [L, nbp1, bs, K, d]
    with ``layer`` (traced scalar) selecting the layer — the stacked form is
    the fast path: the model passes the whole cache straight through every
    layer of its scan and the kernels index it in HBM, so no per-layer pool
    slice is ever materialized. ``no_past=True`` (static) skips the past
    kernel when the engine knows every chunk starts at position 0.
    Dispatches to the decode work-list kernel (tq == 1) or the
    past+self-flash pair (tq > 1); see the section comment above.
    ``kernel='xla'`` forces the dense-gather reference path for every atom
    (``inference.decode_kernel`` — A/B benching and the no-Pallas
    fallback). Returns [N, H, d]."""
    if interpret is None:
        interpret = not _on_tpu()
    N, H, d = q.shape
    K = k_self.shape[-2]
    if k_pool.ndim == 5:                  # unfolded stacked [L,nbp1,bs,K,d]
        k_pool = k_pool.reshape(*k_pool.shape[:3], K * d)
        v_pool = v_pool.reshape(*v_pool.shape[:3], K * d)
    elif k_pool.shape[-1] == d and k_pool.shape[-2] == K:
        # per-layer unfolded [nbp1, bs, K, d] (tests / direct calls)
        k_pool = k_pool.reshape(1, *k_pool.shape[:2], K * d)
        v_pool = v_pool.reshape(1, *v_pool.shape[:2], K * d)
        layer = jnp.zeros((), jnp.int32)
    if layer is None:
        raise ValueError("stacked pools need a layer index")
    bs = k_pool.shape[2]
    if attention_kernel_path(d, bs, tq, kernel, interpret)[0] == "xla":
        kp = jax.lax.dynamic_index_in_dim(k_pool, layer, keepdims=False)
        vp = jax.lax.dynamic_index_in_dim(v_pool, layer, keepdims=False)
        if kv_scale is not None and kv_bits == 4:
            kp = _unpack_int4_lanes_xla(kp, K, d)
            vp = _unpack_int4_lanes_xla(vp, K, d)
        kp = kp.reshape(*kp.shape[:2], K, d)
        vp = vp.reshape(*vp.shape[:2], K, d)
        if kv_scale is not None:                # dequant dense for fallback
            sc = jax.lax.dynamic_index_in_dim(kv_scale, layer,
                                              keepdims=False)[:, 0]
            kp = kp.astype(jnp.float32) * sc[:, :bs, None, None]
            vp = vp.astype(jnp.float32) * sc[:, bs:, None, None]
            kp = kp.astype(q.dtype)
            vp = vp.astype(q.dtype)
        return xla_ragged_attention(
            q, k_self, v_self, kp, vp, block_tables, atom_slot,
            atom_pos0, atom_len, tq, window=window)
    if tq == 1:
        return _decode_attention(q, k_self, v_self, k_pool, v_pool, layer,
                                 block_tables, atom_slot, atom_pos0,
                                 atom_len, window=window, interpret=interpret,
                                 kv_scale=kv_scale, kv_bits=kv_bits)
    return _prefill_attention(q, k_self, v_self, k_pool, v_pool, layer,
                              block_tables, atom_slot, atom_pos0, atom_len,
                              tq, window=window, interpret=interpret,
                              no_past=no_past, kv_scale=kv_scale,
                              kv_bits=kv_bits)


def ragged_paged_attention_tp(q: jax.Array, k_self: jax.Array,
                              v_self: jax.Array, k_pool: jax.Array,
                              v_pool: jax.Array, block_tables: jax.Array,
                              atom_slot: jax.Array, atom_pos0: jax.Array,
                              atom_len: jax.Array, tq: int,
                              axis: str = "tp",
                              window: Optional[int] = None,
                              layer: Optional[jax.Array] = None,
                              no_past: bool = False,
                              kv_scale: Optional[jax.Array] = None,
                              kv_bits: int = 8,
                              kernel: str = "pallas") -> jax.Array:
    """Tensor-parallel :func:`ragged_paged_attention`: heads embarrassingly
    parallel, q sharded on H, the atom KV and pools on K under shard_map
    (int8 per-token scales replicated)."""
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or axis not in mesh.axis_names \
            or mesh.shape[axis] <= 1:
        return ragged_paged_attention(q, k_self, v_self, k_pool, v_pool,
                                      block_tables, atom_slot, atom_pos0,
                                      atom_len, tq, window=window,
                                      layer=layer, no_past=no_past,
                                      kv_scale=kv_scale, kv_bits=kv_bits,
                                      kernel=kernel)
    tp = mesh.shape[axis]
    H = q.shape[1]
    d = q.shape[2]
    K = k_self.shape[-2]
    assert H % tp == 0 and K % tp == 0, (
        f"tp={tp} must divide num_heads={H} and num_kv_heads={K}")
    if k_pool.ndim == 5:                       # unfolded stacked
        pool_spec = P(None, None, None, axis, None)
    elif k_pool.shape[-1] == d and k_pool.shape[-2] == K:
        pool_spec = P(None, None, axis, None)  # per-layer unfolded
    else:
        pool_spec = P(None, None, None, axis)  # stacked lane-folded
    if layer is None:
        layer = jnp.zeros((), jnp.int32)

    if kv_scale is None:
        kv_scale = jnp.zeros((0,), jnp.float32)   # sentinel: bf16 pool
    elif kv_scale.ndim != 4:
        raise ValueError(
            f"kv_scale must be [L, nb+1, 1, 2*block_size], got "
            f"{kv_scale.shape}")

    def shard_fn(q, ks, vs, kp, vp, bt, a_s, a_p, a_l, lay, sc):
        return ragged_paged_attention(q, ks, vs, kp, vp, bt, a_s, a_p, a_l,
                                      tq, window=window, layer=lay,
                                      no_past=no_past,
                                      kv_scale=sc if sc.ndim == 4 else None,
                                      kv_bits=kv_bits, kernel=kernel)

    return jax.shard_map(
        shard_fn,
        in_specs=(P(None, axis, None), P(None, axis, None),
                  P(None, axis, None), pool_spec, pool_spec,
                  P(None, None), P(None), P(None), P(None), P(),
                  P(None, None, None, None) if kv_scale.ndim == 4
                  else P(None)),
        out_specs=P(None, axis, None),
        check_vma=False,
    )(q, k_self, v_self, k_pool, v_pool, block_tables, atom_slot, atom_pos0,
      atom_len, layer, kv_scale)


def packed_kv_append(pool: jax.Array, new_rows: jax.Array,
                     block_tables: jax.Array, tok_slot: jax.Array,
                     tok_pos: jax.Array,
                     valid: Optional[jax.Array] = None) -> jax.Array:
    """Write per-token KV rows for ALL layers into the stacked pool with one
    in-place scatter (free under buffer donation — the per-layer scatter
    inside a scan copies the whole pool every layer instead).

    ``pool``: lane-folded [L, nb+1, bs, K*d] (or unfolded [L, nb+1, bs, K,
    d]); ``new_rows``: [L, N, K, d] or [L, N, K*d]; metadata [N]. Invalid
    rows are dropped (out-of-bounds index + mode='drop')."""
    unfolded_shape = pool.shape if pool.ndim == 5 else None
    if unfolded_shape:
        pool = pool.reshape(*pool.shape[:3], -1)
    L, nbp1, bs, KD = pool.shape
    N = new_rows.shape[1]
    rows = new_rows.reshape(L, N, KD)
    bt_rows = block_tables[tok_slot]                          # [N, nb_max]
    logical = jnp.clip(tok_pos // bs, 0, bt_rows.shape[1] - 1)
    phys = jnp.take_along_axis(bt_rows, logical[:, None], axis=1)[:, 0]
    off = tok_pos % bs
    li = jnp.arange(L, dtype=jnp.int32)[:, None]
    idx = (li * nbp1 + phys[None, :]) * bs + off[None, :]     # [L, N]
    if valid is not None:
        # one-past-the-end is definitively out of bounds → mode='drop'
        # discards the row (negative indices would WRAP, not drop)
        idx = jnp.where(valid[None, :], idx, L * nbp1 * bs)
    flat = pool.reshape(L * nbp1 * bs, KD)
    flat = flat.at[idx.reshape(-1)].set(
        rows.reshape(L * N, KD).astype(pool.dtype),
        mode="drop", unique_indices=True)
    out = flat.reshape(pool.shape)
    if unfolded_shape:
        out = out.reshape(unfolded_shape)
    return out


def packed_kv_append_quant(pool: jax.Array, scale_pool: jax.Array,
                           new_rows: jax.Array, block_tables: jax.Array,
                           tok_slot: jax.Array, tok_pos: jax.Array,
                           which: int,
                           valid: Optional[jax.Array] = None,
                           bits: int = 8):
    """Quantize-and-append per-token KV rows into an int8/int4 pool.

    ``pool`` int8 [L, nb+1, bs, K*d] (int8) or [L, nb+1, bs, K*d/2]
    (int4: lane j paired with j + K*d/2 per byte, see
    :func:`_unpack_int4_lanes`); ``scale_pool`` f32 [L, nb+1, 1, 2*bs]
    holding per-token dequant scales (k rows in lanes [0, bs), v in
    [bs, 2bs) — ``which`` 0/1 selects the half); ``new_rows`` float
    [L, N, K, d] or [L, N, K*d] (either form — the int4 lane pairing is
    GLOBAL, byte j = features j and j + K*d/2, so only the flattened K*d
    width matters). Known accuracy limit at ``bits=4``: the single
    per-token amax spans every kv head's features, so one outlier head
    costs the rest resolution (15 levels); the upgrade path is per-head K
    scales (``kv_scale`` lanes [K, 2*bs], score dequant per (row-block,
    column)) — V scales must stay per-token because the pv contraction
    mixes columns before the per-head output lanes separate. Each row is
    quantized ONCE with
    its own amax/qmax scale and never requantized — per-token granularity
    is what makes incremental block filling exact. Under tensor
    parallelism the amax over the (sharded) head dim is an automatic GSPMD
    all-reduce, so every shard records the same scale.
    Returns (pool, scale_pool)."""
    L, nbp1, bs, _lanes = pool.shape
    N = new_rows.shape[1]
    KD = (new_rows.shape[-1] * new_rows.shape[-2]
          if new_rows.ndim == 4 else new_rows.shape[-1])
    rows = new_rows.reshape(L, N, KD).astype(jnp.float32)
    qmax = 7.0 if bits == 4 else 127.0
    sc = jnp.maximum(jnp.max(jnp.abs(rows), axis=-1) / qmax, 1e-8)  # [L, N]
    qrows = jnp.clip(jnp.round(rows / sc[..., None]), -qmax, qmax) \
        .astype(jnp.int8)
    if bits == 4:
        # global lane pairing: byte j = (feature j, feature j + KD/2)
        lo = qrows[..., :KD // 2]
        hi = qrows[..., KD // 2:]
        qrows = (((lo.astype(jnp.int32) & 0xF)
                  | ((hi.astype(jnp.int32) & 0xF) << 4))
                 .astype(jnp.int8))
    KD_pool = _lanes
    bt_rows = block_tables[tok_slot]
    logical = jnp.clip(tok_pos // bs, 0, bt_rows.shape[1] - 1)
    phys = jnp.take_along_axis(bt_rows, logical[:, None], axis=1)[:, 0]
    off = tok_pos % bs
    li = jnp.arange(L, dtype=jnp.int32)[:, None]
    idx = (li * nbp1 + phys[None, :]) * bs + off[None, :]
    sidx = (li * nbp1 + phys[None, :]) * (2 * bs) + which * bs + off[None, :]
    if valid is not None:
        idx = jnp.where(valid[None, :], idx, L * nbp1 * bs)
        sidx = jnp.where(valid[None, :], sidx, L * nbp1 * 2 * bs)
    flat = pool.reshape(L * nbp1 * bs, KD_pool)
    flat = flat.at[idx.reshape(-1)].set(qrows.reshape(L * N, KD_pool),
                                        mode="drop", unique_indices=True)
    sflat = scale_pool.reshape(L * nbp1 * 2 * bs)
    sflat = sflat.at[sidx.reshape(-1)].set(sc.reshape(-1), mode="drop",
                                           unique_indices=True)
    return flat.reshape(pool.shape), sflat.reshape(scale_pool.shape)


def xla_ragged_attention(q, k_self, v_self, k_pool, v_pool, block_tables,
                         atom_slot, atom_pos0, atom_len, tq, window=None):
    """Dense-gather reference for :func:`ragged_paged_attention` (parity
    tests; pools hold only PAST tokens, the atom's own KV comes from
    ``k_self``/``v_self``)."""
    N, H, d = q.shape
    bs, K = k_pool.shape[1], k_pool.shape[2]
    A = N // tq
    S = block_tables.shape[1] * bs
    rep = H // K
    bt = block_tables[atom_slot]                              # [A, nb_max]
    k_dense = k_pool[bt].reshape(A, S, K, d)
    v_dense = v_pool[bt].reshape(A, S, K, d)
    ks = k_self.reshape(A, tq, K, d)
    vs = v_self.reshape(A, tq, K, d)
    k_all = jnp.concatenate([k_dense, ks], axis=1)            # [A, S+tq, K, d]
    v_all = jnp.concatenate([v_dense, vs], axis=1)
    if K != H:
        k_all = jnp.repeat(k_all, rep, axis=2)
        v_all = jnp.repeat(v_all, rep, axis=2)
    qa = q.reshape(A, tq, H, d)
    s = jnp.einsum("athd,ashd->ahts", qa, k_all,
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    row = (atom_pos0[:, None] + jnp.arange(tq)[None, :])[:, None, :, None]
    colpos = jnp.concatenate(
        [jnp.arange(S)[None, :] + jnp.zeros((A, 1), jnp.int32),
         atom_pos0[:, None] + jnp.arange(tq)[None, :]],
        axis=1)[:, None, None, :]                             # [A,1,1,S+tq]
    is_past = (jnp.arange(S + tq) < S)[None, None, None, :]
    keep = jnp.where(is_past, colpos < atom_pos0[:, None, None, None],
                     colpos <= row)
    keep = keep & (jnp.arange(tq)[None, None, :, None]
                   < atom_len[:, None, None, None])
    col_valid = jnp.where(
        is_past, True,
        (jnp.arange(S + tq) - S)[None, None, None, :]
        < atom_len[:, None, None, None])
    keep = keep & col_valid
    if window is not None:
        keep = keep & (colpos > row - window)
    s = jnp.where(keep, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("ahts,ashd->athd", p, v_all)
    out = jnp.where((jnp.arange(tq) < atom_len[:, None])[:, :, None, None],
                    out, 0)
    return out.reshape(N, H, d)
