"""Continuous-batching inference engine (FastGen parity).

Parity target: ``deepspeed/inference/v2/engine_v2.py`` ``InferenceEngineV2`` — ``put``
(:107: one step over a ragged batch of prompt chunks + decode tokens), ``query``/
``flush`` scheduling surface, backed by the blocked KV allocator.

Device-side execution is **paged**: the KV cache is a global pool of fixed-size
blocks (``[L, num_blocks+1, block_size, K, d]``) and each sequence owns only the
blocks its length requires — HBM footprint follows allocated blocks, not
``max_sequences × max_seq_len`` (the waste FastGen's paged KV exists to remove,
``v2/ragged/kv_cache.py``). The ``BlockedAllocator``'s block ids ARE the
physical pool indices; host-side scheduling builds the block tables the Pallas
paged-attention kernel (``ops/paged_attention.py``) consumes via scalar
prefetch.
"""

from __future__ import annotations

import os
import time
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.quant import QUANT_LEAVES
from deepspeed_tpu.inference.ragged import (CapacityError, PrefixCache,
                                            SequenceManager)
from deepspeed_tpu.observability.events import get_bus
from deepspeed_tpu.models.transformer import TransformerLM
from deepspeed_tpu.utils.logging import log_dist

# packed-row atom layout (atom_builder parity): 1-token chunks are decode
# atoms; longer chunks each occupy one whole-chunk atom of bucketed width
_MIN_TILE = 32


class _PausedSeq:
    """Host-side record of a PREEMPTED (paused) sequence: the tier-store
    keys holding its demoted KV pages, the frontier to restore, and the
    committed-token history the flush would otherwise discard. Store keys
    are NEGATIVE so they can never collide with the prefix cache's
    non-negative promote handles in a shared tier store."""

    __slots__ = ("uid", "keys", "seen", "hist", "paused_t", "resuming",
                 "adopted", "durable", "manifest_path")

    def __init__(self, uid: int, keys, seen: int, hist):
        self.uid = uid
        self.keys = list(keys)
        self.seen = int(seen)
        self.hist = hist
        self.paused_t = time.perf_counter()
        self.resuming = False
        # cross-replica migration state: `adopted` marks a record whose
        # entries came from ANOTHER replica's manifest (its tier reads
        # fault through the migrate site, not the resume site); `durable`/
        # `manifest_path` are the donor-side crash backup to reclaim when
        # the record dies locally (resume, cancel, expire)
        self.adopted = False
        self.durable = None
        self.manifest_path = None


class InferenceEngineV2:
    def __init__(self, model: TransformerLM, params=None, max_sequences: int = 8,
                 max_seq_len: Optional[int] = None, block_size: int = 128,
                 num_blocks: Optional[int] = None, topology=None,
                 mesh: Optional[dict] = None, kv_dtype: str = "bf16",
                 weight_dtype: str = "bf16", prefix_cache=None,
                 speculative=None, decode_kernel: str = "pallas",
                 moe_kernel: Optional[str] = None,
                 moe_a2a_bits: Optional[int] = None,
                 moe_a2a_slice: Optional[int] = None,
                 moe_replica_slots: int = 0):
        import functools

        from jax.sharding import NamedSharding, PartitionSpec as P

        from deepspeed_tpu.parallel import build_mesh
        from deepspeed_tpu.parallel import sharding as shd
        from deepspeed_tpu.utils.compile_cache import place_compile_cache

        place_compile_cache()
        if hasattr(model, "_one_pass_only"):
            # before any pool is built: R caches a layer and early exit, or
            # a recurrent state beside the pages, are not here (PERF.md,
            # open questions)
            model._one_pass_only("InferenceEngineV2 (one key-value cache a "
                                 "layer, one set of logits a token)")
        self.module = model
        self.cfg = model.cfg
        self.max_seq_len = max_seq_len or self.cfg.max_seq_len
        if topology is None:
            from deepspeed_tpu.config.config import MeshConfig

            topology = build_mesh(MeshConfig(**(mesh or {})))
        self.topology = topology
        self.mesh = self.topology.mesh
        self.state = SequenceManager(max_sequences, self.max_seq_len, block_size,
                                     num_blocks=num_blocks)
        # TP-sharded params (reference InferenceEngineV2 TP via sharded model
        # implementations, v2/model_implementations/sharding/)
        specs = model.param_specs() if hasattr(model, "param_specs") else None
        spec_tree = shd.zero_param_specs(
            jax.eval_shape(model.init, jax.random.key(0)), specs, self.topology,
            stage=0)
        self.param_sharding = shd.named(self.topology, spec_tree)
        cdt = jnp.dtype(self.cfg.dtype)

        def _serve_cast(tree):
            # inference holds weights in the compute dtype: fp32 masters would
            # otherwise be re-read AND re-cast every step (3x the HBM traffic
            # of the matmuls themselves on a bf16 model)
            return jax.tree_util.tree_map(
                lambda p: p.astype(cdt) if p.dtype == jnp.float32 else p, tree)

        with jax.sharding.set_mesh(self.mesh):
            if params is None:
                params = jax.jit(
                    lambda k: _serve_cast(model.init(k)),
                    out_shardings=self.param_sharding)(jax.random.key(0))
            else:
                params = jax.jit(_serve_cast,
                                 out_shardings=self.param_sharding)(params)
        if weight_dtype not in ("bf16", "int8", "int4"):
            raise ValueError(f"weight_dtype must be bf16|int8|int4, got "
                             f"{weight_dtype!r}")
        self.weight_dtype = weight_dtype
        if weight_dtype != "bf16":
            # decode is weight-bandwidth-bound: swap the big matmul leaves
            # (layer stack + an int copy of the LM head table) for packed
            # QuantizedWeight nodes — every forward path picks them up
            # through the model's linear() seam, cutting decode HBM reads
            # 2x (int8) / 4x (int4). The embedding GATHER keeps the bf16
            # table (it reads B rows/step, not the full [V, D]).
            params = self._quantize_weights(
                params, bits=4 if weight_dtype == "int4" else 8)
            # the quantizer restructures the served tree (fused wqkv/
            # w_gateup, QuantizedWeight leaves, popped lm_head) — the spec
            # tree computed above no longer matches and must not be
            # re-applied to self.params
            self.param_sharding = None
        self.params = params
        self.timing: Dict[str, float] = {}
        self._obs = None  # opt-in inference/* registry stream; enable_metrics
        # causal event bus (observability.tracing) — cached ref; the
        # singleton is mutated in place by configure_tracing, so a
        # disabled bus costs one attribute check per dispatch
        self._ebus = get_bus()
        self.block_size = block_size
        self.nb_max = -(-self.max_seq_len // block_size)  # logical blocks/slot
        if kv_dtype not in ("bf16", "int8", "int4"):
            raise ValueError(f"kv_dtype must be bf16|int8|int4, got "
                             f"{kv_dtype!r}")
        self.kv_dtype = kv_dtype
        if kv_dtype == "int4" and "tp" in self.mesh.axis_names \
                and self.mesh.shape["tp"] > 1:
            # the int4 pool's byte lanes pair feature j with j + K*d/2
            # (the only Mosaic-lowerable pairing), so lane-sharding it over
            # tp would split pairs across shards
            raise ValueError("kv_dtype='int4' does not compose with tp>1 "
                             "(use int8 KV under tensor parallelism)")
        # ---- decode attention kernel selection (inference.decode_kernel):
        # "pallas" = the fused work-list flash-decode kernel (native on TPU,
        # interpret mode on CPU CI), "xla" = the dense-gather reference twin.
        # Resolved ONCE here — the choice is baked into the step jits below,
        # so a backend with no Pallas lowering falls back to xla with one
        # logged warning instead of failing at trace time.
        if decode_kernel not in ("pallas", "xla"):
            raise ValueError(f"decode_kernel must be 'pallas' or 'xla', got "
                             f"{decode_kernel!r}")
        self.decode_kernel_reason = ""
        if decode_kernel == "pallas":
            from deepspeed_tpu.ops import paged_attention as _pa

            mode, reason = _pa.decode_kernel_support()
            if mode is None:
                import logging

                log_dist(f"decode_kernel: Pallas unavailable ({reason}); "
                         f"falling back to the XLA reference path",
                         level=logging.WARNING)
                decode_kernel, mode = "xla", "xla"
                self.decode_kernel_reason = reason
            self.decode_kernel_mode = mode   # native | interpret | xla
        else:
            self.decode_kernel_mode = "xla"
        self.decode_kernel = decode_kernel
        # what each compiled step really runs: the ops keep geometry rules
        # that route to an XLA twin (head_dim % 128, > 256 rows, ...), so
        # "pallas was asked for" is not "pallas runs"
        self.kernel_paths = self._resolve_kernel_paths(max_sequences)
        att, why = self.kernel_paths["decode"]["attention"]
        if self.decode_kernel_mode != "xla" and att == "xla-twin":
            self.decode_kernel_mode, self.decode_kernel_reason = "xla", why
        log_dist("kernel paths: " + "; ".join(
            f"{step} attention={v['attention'][0]} ({v['attention'][1]})"
            + "".join(f" {n}={c[0]} ({c[1]})"
                      for n, c in v["dequant_matmul"].items())
            for step, v in self.kernel_paths.items()))
        # ---- MoE expert-parallel serving (moe.kernel / a2a wire / AutoEP).
        # Mirrors the decode-kernel selection above: the grouped-GEMM
        # kernel is resolved ONCE (probe + one logged fallback warning) and
        # baked into the step jits via the model's moe_fn seam; the a2a
        # wire format rides the same partial. Placement state starts at the
        # natural layout and is rewritten by rebalance_moe().
        self.moe_kernel = None
        self.moe_kernel_reason = ""
        self._moe_ep = False
        self._moe_assign = None
        self._moe_slots = 0
        self._moe_tracker = None
        if getattr(self.cfg, "num_experts", 1) > 1 and \
                getattr(self.cfg, "moe_dispatch", "capacity") == "grouped":
            from deepspeed_tpu.moe import sharded_moe as _moe

            want = moe_kernel if moe_kernel is not None else \
                getattr(self.cfg, "moe_kernel", "ragged")
            self.moe_kernel, self.moe_kernel_reason = \
                _moe.resolve_moe_kernel(want)
            self._moe_a2a_bits = int(
                moe_a2a_bits if moe_a2a_bits is not None
                else getattr(self.cfg, "moe_a2a_bits", 0) or 0)
            self._moe_a2a_slice = int(
                moe_a2a_slice if moe_a2a_slice is not None
                else getattr(self.cfg, "moe_a2a_slice", 0) or 0)
            # baked into every step jit below through the moe_fn attribute
            # (tracing is lazy, so this must land before the first dispatch)
            model.moe_fn = functools.partial(
                _moe.grouped_moe_mlp_block, kernel=self.moe_kernel,
                a2a_bits=self._moe_a2a_bits, a2a_slice=self._moe_a2a_slice)
            self._moe_ep = ("ep" in self.mesh.axis_names
                            and self.mesh.shape["ep"] > 1)
            if self._moe_ep and moe_replica_slots > 0:
                self._moe_expand_placement(moe_replica_slots)
        self.num_blocks = self.state.allocator.num_blocks
        cache = model.init_paged_kv_cache(
            self.num_blocks, block_size, quantize=kv_dtype != "bf16",
            bits=4 if kv_dtype == "int4" else 8)
        # pool sharded over tp on the lane-folded kv-head dim
        # ([L, nb+1, bs, K*d]: contiguous d-lanes per kv head);
        # per-token int8 scales replicated (identical on every shard)
        kv_spec = shd.filter_spec(P(None, None, None, "tp"),
                                  self.mesh.axis_names)
        cache_spec = {"k": kv_spec, "v": kv_spec}
        if "kv_scale" in cache:
            cache_spec["kv_scale"] = P(None, None, None, None)
        self.cache = jax.device_put(
            cache, {k: NamedSharding(self.mesh, s)
                    for k, s in cache_spec.items()})
        self._pos = np.zeros((max_sequences,), np.int32)
        # pin the output cache to the SAME sharding as the input: an
        # XLA-chosen output spec would change the next call's signature
        # and retrace/recompile every step program once per alternation
        kv_out = {k: NamedSharding(self.mesh, s)
                  for k, s in cache_spec.items()}
        self._kv_out = kv_out       # reused by the tier-promote scatter
        # donate the pool: the step returns the updated {'k','v'} dict and
        # self.cache is immediately reassigned — without donation XLA would
        # double-buffer the whole pool and copy all unchanged blocks.
        # The kernel choice rides a keyword-bound partial so the
        # positional donate/static indices stay valid (a traced string
        # argument would not jit)
        self._fwd_packed = functools.partial(
            model.forward_with_packed_cache,
            decode_kernel=self.decode_kernel)
        self._step_packed = jax.jit(self._fwd_packed,
                                    donate_argnums=(2,),
                                    static_argnums=(8, 9, 10),
                                    out_shardings=(None, kv_out))
        self._decode_loop = jax.jit(self._multi_decode,
                                    donate_argnums=(1,),
                                    static_argnums=(6, 9, 10, 11),
                                    out_shardings=(None, kv_out))
        # fused promote-prologue twins of the two decode dispatches,
        # built lazily on the first fenced step (they close over
        # whether the pool carries int8 scales)
        self._decode_loop_fused = None
        self._step_packed_fused = None
        self._prefill_step = jax.jit(self._prefill_impl,
                                     donate_argnums=(3,),
                                     out_shardings=(None, kv_out))
        log_dist(f"paged KV pool: {self.num_blocks} blocks x {block_size} "
                 f"tokens ({self.cache['k'].nbytes * 2 / 1e6:.0f} MB), "
                 f"mesh={self.topology}")
        # ---- prefix-cache KV reuse + n-gram speculative decoding ----------
        from deepspeed_tpu.config.config import (PrefixCacheConfig,
                                                 SpeculativeConfig)

        def _coerce(cls, v):
            if v is None or isinstance(v, cls):
                return v if v is not None else cls()
            if isinstance(v, bool):
                return cls(enabled=v)
            return cls(**dict(v))

        self.prefix_cfg = _coerce(PrefixCacheConfig, prefix_cache)
        self.spec_cfg = _coerce(SpeculativeConfig, speculative)
        self.prefix_cache: Optional[PrefixCache] = None
        # tiered KV spill state (inference.prefix_cache.tiers): the store
        # holding demoted blocks' pages, the queue of promotions awaiting
        # their device upload, and the per-tier promote-latency histograms
        self._tier_store = None
        self._promote_q: list = []
        self._promote_ms = None
        self._promote_step = None   # lazy: tiers branch or first pause
        # serving preemption (pause/resume) state: paused-request KV parks
        # in the SAME tier store as demoted prefix blocks; uploads ride the
        # same promote fence. Negative keys namespace them apart.
        self._paused: Dict[int, _PausedSeq] = {}
        self._pause_q: list = []        # resume uploads awaiting the fence
        self._resume_failed: list = []  # uids whose resume tier read failed
        self._pause_key = -1
        # pinned-host budget used when the pause path must create its own
        # store (prefix tiers off); the serving layer overrides from
        # serving.slo.pause_host_mb before the first pause
        self.pause_store_mb = 64.0
        # shared migration namespace (serving.migration.shared_nvme_path,
        # set by the serving layer before the first pause): gives the
        # pause store an NVMe tier so paused KV can be exported durably
        # and adopted by sibling replicas
        self.migration_nvme_path = ""
        if self.prefix_cfg.enabled:
            from deepspeed_tpu.observability import get_registry

            r = get_registry()
            inst = {
                "hits": r.counter("inference/prefix_cache_hits",
                                  "requests that attached a cached prefix"),
                "misses": r.counter("inference/prefix_cache_misses",
                                    "prefix lookups that matched nothing"),
                "hit_tokens": r.counter(
                    "inference/prefix_cache_hit_tokens",
                    "prompt tokens served from cached KV (prefill skipped)"),
                "evictions": r.counter(
                    "inference/prefix_cache_evictions",
                    "cached blocks evicted (LRU, under pool pressure)"),
                "blocks": r.gauge("inference/prefix_cache_blocks",
                                  "blocks currently held by the prefix tree"),
            }
            tiers = self.prefix_cfg.tiers
            if tiers.enabled:
                inst["tier_hits_hbm"] = r.counter(
                    "inference/prefix_cache_tier_hits",
                    "cached blocks served per tier on a radix match",
                    labels={"tier": "hbm"})
            self.prefix_cache = PrefixCache(
                self.state.allocator, max_blocks=self.prefix_cfg.max_blocks,
                instruments=inst)
            self.state.prefix_cache = self.prefix_cache
            if tiers.enabled:
                from deepspeed_tpu.inference.kv_tier import KVTierStore

                tier_inst = {}
                for t in ("host", "nvme"):
                    tier_inst[t] = {
                        "hits": r.counter(
                            "inference/prefix_cache_tier_hits",
                            "cached blocks served per tier on a radix match",
                            labels={"tier": t}),
                        "misses": r.counter(
                            "inference/prefix_cache_tier_misses",
                            "tier entries lost or unreadable (recomputed)",
                            labels={"tier": t}),
                        "demotions": r.counter(
                            "inference/prefix_cache_tier_demotions",
                            "cache blocks demoted into the tier",
                            labels={"tier": t}),
                        "bytes": r.gauge(
                            "inference/prefix_cache_tier_bytes",
                            "KV bytes resident in the tier",
                            labels={"tier": t}),
                    }
                self._promote_ms = {
                    t: r.histogram(
                        "inference/prefix_cache_tier_promote_ms",
                        "demoted-block promote latency: tier fetch start "
                        "to pool upload dispatched", labels={"tier": t})
                    for t in ("host", "nvme")}
                self._tier_store = KVTierStore(
                    host_mb=tiers.host_mb, nvme_path=tiers.nvme_path,
                    promote_depth=tiers.promote_depth,
                    nvme_max_mb=tiers.nvme_max_mb,
                    nvme_ttl_s=tiers.nvme_ttl_s,
                    instruments=tier_inst)
                self.prefix_cache.attach_tier_store(self._tier_store,
                                                    self._extract_blocks)
                self._promote_step = jax.jit(self._promote_impl,
                                             donate_argnums=(0,),
                                             out_shardings=self._kv_out)
        # per-uid committed-token history: needed to key prefix publication
        # and to self-draft n-grams; None when both features are off so the
        # hot path pays nothing
        self._hist: Optional[Dict[int, np.ndarray]] = (
            {} if (self.prefix_cfg.enabled or self.spec_cfg.enabled)
            else None)
        self.spec_stats: Dict[str, int] = {
            "rounds": 0, "drafted": 0, "accepted": 0, "emitted": 0,
            "fallback_steps": 0,
            # verify rounds run through the fused Pallas kernel (the same
            # _step_packed jit as put) — lets benches attribute spec wins
            # to the kernel vs the scheduling
            "fused": 1 if self.decode_kernel == "pallas" else 0,
        }
        # standalone promote-scatter dispatches absorbed into a fused
        # decode/step prologue (surfacing in tier_report)
        self._fused_saved_dispatches = 0

    _QUANT_LEAVES = QUANT_LEAVES

    def _resolve_kernel_paths(self, max_sequences: int) -> Dict[str, dict]:
        """Per compiled step kind (``decode``: 1-token atoms, one row per
        sequence; ``prefill``: the widest atom), which of {pallas-native,
        pallas-interpret, xla-twin} attention and each quantized projection
        take, and why — from the ops' own geometry rules, at the unsharded
        shapes."""
        from deepspeed_tpu.models.transformer import QuantizedWeight
        from deepspeed_tpu.ops.paged_attention import attention_kernel_path
        from deepspeed_tpu.ops.quant_matmul import quant_matmul_path

        interpret = self.decode_kernel_mode != "native"
        pallas = "pallas-interpret" if interpret else "pallas-native"
        qleaves = [
            (jax.tree_util.keystr(path[-1:]), leaf) for path, leaf in
            jax.tree_util.tree_flatten_with_path(
                self.params,
                is_leaf=lambda x: isinstance(x, QuantizedWeight))[0]
            if isinstance(leaf, QuantizedWeight)]
        itemsize = jnp.dtype(self.cfg.dtype).itemsize
        out = {}
        for step, tq, rows in (
                ("decode", 1, max(8, 1 << (max_sequences - 1).bit_length())),
                ("prefill", self.module.MAX_ATOM, self.module.MAX_ATOM)):
            path, why = attention_kernel_path(
                self.cfg.head_dim, self.block_size, tq, self.decode_kernel,
                interpret)
            mm = {}
            for name, qw in qleaves:
                F = qw.scales.shape[-1]
                bf, mwhy = quant_matmul_path(
                    rows, qw.din, F, qw.din // qw.scales.shape[-2], itemsize)
                mm[name] = (pallas if bf else "xla-twin", mwhy)
            out[step] = {
                "rows": rows,
                "attention": (pallas if path == "pallas" else "xla-twin", why),
                "dequant_matmul": mm}
        return out

    def _quantize_weights(self, params, bits: int):
        from deepspeed_tpu.inference.quant import quantize_serving_params

        return quantize_serving_params(params, self.cfg, bits, self.mesh)

    def enable_metrics(self, registry=None) -> None:
        """Opt-in ``inference/*`` registry stream for the packed put path
        (host-build and device+fetch latency histograms, token counter).
        Off by default: the put loop is the decode hot path, and disabled
        means literally one ``is None`` check per put."""
        from deepspeed_tpu.observability import get_registry

        r = registry if registry is not None else get_registry()
        self._obs = {
            "put_host_ms": r.histogram(
                "inference/put_host_ms",
                "put(): host batch building (ms)"),
            "put_fetch_ms": r.histogram(
                "inference/put_fetch_ms",
                "put(): device step + logits D2H (ms)"),
            "tokens": r.counter("inference/tokens",
                                "tokens pushed through put()"),
            "decode_dispatches": r.counter(
                "inference/decode_dispatches",
                "fused decode-scan device dispatches (decode_batch)"),
            "decode_tokens": r.counter(
                "inference/decode_tokens",
                "tokens generated by decode_batch scans"),
            "decode_fetch_ms": r.histogram(
                "inference/decode_fetch_ms",
                "decode_batch: device scan + token D2H (ms)"),
            "decode_prologue_promotes": r.counter(
                "inference/decode_prologue_promotes",
                "tier promotions folded into a fused step prologue"),
        }
        if getattr(self.cfg, "num_experts", 1) > 1:
            from deepspeed_tpu.moe import balancer as _bal
            from deepspeed_tpu.moe import sharded_moe as _moe

            self._moe_tracker = _bal.ExpertLoadTracker(
                self.cfg.num_experts, registry=r)
            _moe.set_expert_tracker(self._moe_tracker)
            self._obs["moe_rebalances"] = r.counter(
                "moe/rebalances", "applied expert placement rebalances")

    # ---- AutoEP expert placement (moe/balancer.py) -----------------------
    def _moe_place(self, mlp, assign, prev_assign):
        """Gather the layer-stacked expert leaves into physical slot order
        (expert axis 1 — axis 0 is the layer scan) and attach the routing
        tables broadcast over layers, re-pinned to each leaf's sharding."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from deepspeed_tpu.moe import balancer as _bal

        E = self.cfg.num_experts
        ep = self.mesh.shape["ep"]
        new = _bal.apply_placement(
            {n: v for n, v in mlp.items()
             if n not in ("place_dest", "place_slot", "place_nrep")},
            assign, E, ep, prev_assign=prev_assign, expert_axis=1)
        L = self.cfg.num_layers
        out = {}
        for name, leaf in new.items():
            if name in ("place_dest", "place_slot", "place_nrep"):
                # tables ride the layer scan like every other leaf: L
                # identical copies (int32, KBs), replicated over the mesh
                t = jnp.broadcast_to(leaf[None], (L,) + leaf.shape)
                out[name] = jax.device_put(
                    jnp.asarray(t), NamedSharding(
                        self.mesh, P(*([None] * (leaf.ndim + 1)))))
            elif name in mlp and hasattr(mlp[name], "sharding"):
                out[name] = jax.device_put(leaf, mlp[name].sharding)
            else:
                out[name] = leaf
        return out

    def _moe_expand_placement(self, replica_slots: int) -> None:
        """Grow the expert grid to ``ceil(E/ep) + replica_slots`` physical
        slots per shard at the natural (round-robin) assignment — the spare
        slots start as extra replicas so the FIRST rebalance is a pure
        re-placement, never a retrace (table shapes are static in R)."""
        E = self.cfg.num_experts
        ep = self.mesh.shape["ep"]
        slots = -(-E // ep) + int(replica_slots)
        assign = [i % E for i in range(ep * slots)]
        mlp = self.params["layers"]["mlp"]
        placed = self._moe_place(
            {n: v for n, v in mlp.items() if n != "router"}, assign, None)
        placed["router"] = mlp["router"]
        self.params["layers"]["mlp"] = placed
        # the served tree no longer matches the init-time spec tree (the
        # expert axis grew and table leaves appeared) — same rule as the
        # quantizer restructuring above
        self.param_sharding = None
        self._moe_assign = assign
        self._moe_slots = slots

    def rebalance_moe(self, counts=None, min_gain: float = 0.0):
        """Re-place (and re-replicate) experts from observed load — the
        AutoEP control step. Safe at any step boundary: the swap happens
        between dispatches, replicas are exact weight copies, and every
        routed pair still reaches its expert, so greedy outputs are
        bit-identical across the event (asserted by the moe-storm drill).

        ``counts`` defaults to the metrics tracker's current window
        (``enable_metrics`` must be on in that case); the tracker window
        resets after planning so the next decision sees fresh traffic.
        Returns the applied :class:`~deepspeed_tpu.moe.balancer.
        RebalancePlan`, or ``None`` when below ``min_gain`` or not serving
        expert-parallel MoE.
        """
        from deepspeed_tpu.moe import balancer as _bal

        if not self._moe_ep or self._moe_assign is None:
            return None
        if counts is None:
            if self._moe_tracker is None:
                raise ValueError("rebalance_moe() needs counts= or "
                                 "enable_metrics() for the load tracker")
            counts = self._moe_tracker.snapshot()
            self._moe_tracker.reset()
        ep = self.mesh.shape["ep"]
        plan = _bal.plan_rebalance(counts, ep, self._moe_slots,
                                   prev_assign=self._moe_assign)
        if plan.moved_slots == 0 or \
                plan.imbalance_before - plan.imbalance_after <= min_gain:
            return None
        mlp = self.params["layers"]["mlp"]
        placed = self._moe_place(
            {n: v for n, v in mlp.items() if n != "router"},
            plan.assign, self._moe_assign)
        placed["router"] = mlp["router"]
        self.params["layers"]["mlp"] = placed
        self._moe_assign = plan.assign
        if self._obs is not None and "moe_rebalances" in self._obs:
            self._obs["moe_rebalances"].inc()
        log_dist(f"moe rebalance: imbalance "
                 f"{plan.imbalance_before:.2f} -> {plan.imbalance_after:.2f} "
                 f"(bound {plan.bound:.2f}), {plan.moved_slots} slots moved, "
                 f"nrep={plan.nrep}")
        return plan

    # ---- scheduling surface (engine_v2.py:184 parity) --------------------
    def query(self, uid: int, n_tokens: int) -> bool:
        return self.state.can_schedule(uid, n_tokens)

    def flush(self, uids: Sequence[int]) -> None:
        for uid in uids:
            seq = self.state.sequences.get(uid)
            if seq is not None:
                self._pos[seq.slot] = 0
            self.state.flush(uid)
            if self._hist is not None:
                self._hist.pop(uid, None)
            if self._paused:
                # a PAUSED request resolving terminal (expire/cancel/drain)
                # flushes through the same path a live one does — its
                # parked tier entries must go with it or the store leaks
                self._drop_paused(uid)

    # ---- prefix-cache KV reuse -------------------------------------------
    def prefix_attach(self, uid: int, tokens) -> int:
        """Attach the longest cached full-block prefix of ``tokens`` to the
        FRESH sequence ``uid`` (shared blocks, reference taken) and position
        it so the engine prefills only the uncached suffix. Capped at
        ``len(tokens) - 1`` so at least one token always runs through the
        model (the forward that yields the first next-token logits); the
        partial tail block is recomputed rather than copied — logical
        copy-on-write without a device block copy. Returns matched tokens
        (0 = miss or feature off)."""
        if self.prefix_cache is None or uid in self.state.sequences:
            return 0
        toks = np.atleast_1d(np.asarray(tokens, np.int32))
        if len(toks) < 2:
            return 0
        blocks, n = self.prefix_cache.acquire(toks, max_tokens=len(toks) - 1)
        recs: list = []
        try:
            # collect any promotions this acquire started, whatever
            # happens next: their uploads fence at the next device
            # dispatch, and an attach failure must re-demote them (their
            # pool blocks hold garbage until uploaded)
            recs = self.prefix_cache.drain_promotes()
            if n == 0:
                return 0
            seq = self.state.attach_prefix(uid, blocks, n)
        except BaseException:
            # slot exhaustion (or any attach failure): give back acquire's
            # references before surfacing — leaked refs would pin the
            # blocks (refcount >= 2) out of the evictable set forever
            if blocks:
                self.state.allocator.free(blocks)
            if recs:
                self.prefix_cache.cancel_promotes(recs)
            raise
        self._promote_q.extend(recs)
        self._pos[seq.slot] = n
        if self._hist is not None:
            self._hist[uid] = toks[:n].copy()
        bus = self._ebus
        if bus.enabled and (n or recs):
            # the uid <-> KV-tier join point: a warm-but-demoted prefix
            # attaching here is the event that explains a cheap TTFT
            bus.instant("engine", "prefix_attach",
                        args={"uid": int(uid), "hit_tokens": int(n),
                              "promotes": len(recs)})
            if recs:
                bus.instant("kv_tier", "promote_attach",
                            args={"uid": int(uid), "blocks": len(recs),
                                  "tiers": sorted({r.tier for r in recs})})
        return n

    def _commit(self, uid: int, fed) -> None:
        """Commit one scheduled chunk: advance ``seen_tokens``, extend the
        per-uid token history, and publish newly completed full blocks to
        the prefix tree (shared from then on; never written again — decode
        continues past them, block-aligned)."""
        self.state.commit(uid)
        if self._hist is not None:
            arr = np.atleast_1d(np.asarray(fed, np.int32))
            h = self._hist.get(uid)
            self._hist[uid] = (arr.copy() if h is None
                               else np.concatenate([h, arr]))
        if self.prefix_cache is not None:
            seq = self.state.sequences.get(uid)
            if seq is not None:
                n_full = seq.seen_tokens // self.block_size
                if n_full > seq.published:
                    self.prefix_cache.insert(
                        self._hist[uid][:n_full * self.block_size],
                        seq.blocks[:n_full])
                    seq.published = n_full

    def prefix_cache_report(self) -> Optional[Dict]:
        return (None if self.prefix_cache is None
                else self.prefix_cache.report())

    # ---- tiered KV spill (inference.prefix_cache.tiers) ------------------
    def _extract_blocks(self, blocks: Sequence[int]) -> list:
        """Fetch the listed pool blocks' KV pages to host in ONE gather +
        one transfer (the demote path's device read; per-block fetches
        would pay a dispatch round-trip each). Returns one
        ``{part: ndarray}`` payload per block."""
        idx = jnp.asarray(np.asarray(blocks, np.int32))
        grab = {"k": self.cache["k"][:, idx], "v": self.cache["v"][:, idx]}
        if "kv_scale" in self.cache:
            grab["kv_scale"] = self.cache["kv_scale"][:, idx]
        pages = jax.device_get(grab)
        return [{name: arr[:, i] for name, arr in pages.items()}
                for i in range(len(blocks))]

    def _promote_impl(self, cache, idx, kp, vp, sp=None):
        """One scatter folds every pending promote's pages back into the
        pool (padding rows land on the scratch block). Donated + pinned to
        the pool's sharding like every other step program."""
        out = {"k": cache["k"].at[:, idx].set(kp),
               "v": cache["v"].at[:, idx].set(vp)}
        if sp is not None:
            out["kv_scale"] = cache["kv_scale"].at[:, idx].set(sp)
        return out

    def _flush_promotes(self) -> None:
        """The promote-completion fence: upload every queued promotion's
        payload into its pool block BEFORE the next device step can read
        it. Called at every dispatch site; the NVMe ticket reads started at
        attach time overlap all host-side batch building in between. A
        payload whose tier read failed is zero-filled (loudly) — the
        sequence computes on zeros rather than on whatever the evicted
        block left behind. Pending RESUME uploads (paused requests) ride
        the same fence first — their blocks must also be whole before any
        attention read."""
        if self._pause_q:
            self._flush_pause_promotes()
        recs, self._promote_q = self._promote_q, []
        if not recs:
            return
        bus = self._ebus
        if not bus.enabled:
            return self._flush_promotes_impl(recs)
        with bus.span("engine", "promote_fence",
                      args={"pending": len(recs)}):
            return self._flush_promotes_impl(recs)

    def _build_promote_payloads(self, recs):
        """Stale-filter the promote records and materialise their scatter
        payloads (fetch waits happen here). Returns ``(recs, failed, idx,
        kp, vp, sp)`` ready for :meth:`_promote_impl` — whether that runs
        standalone or as a fused step prologue — or ``None`` when every
        record was stale."""
        stale = [r for r in recs if r.epoch != self.prefix_cache.epoch]
        if stale:
            # a clear() between attach and this fence released these
            # records' blocks — by now they may belong to another
            # sequence, so the payloads must NOT be scattered. Their store
            # entries are ours to drop too: the nodes were promoted
            # (handle already cleared), so the tree's clear() could not
            # reach these keys.
            for rec in stale:
                rec.fetch.release()
                self._tier_store.discard(rec.key)
            recs = [r for r in recs if r.epoch == self.prefix_cache.epoch]
            if not recs:
                return None
        n = len(recs)
        npad = max(4, 1 << (n - 1).bit_length())
        kt = self.cache["k"]
        kp = np.zeros((kt.shape[0], npad) + kt.shape[2:], kt.dtype)
        vp = np.zeros_like(kp)
        sp = None
        if "kv_scale" in self.cache:
            st = self.cache["kv_scale"]
            sp = np.zeros((st.shape[0], npad) + st.shape[2:], st.dtype)
        idx = np.full((npad,), self.num_blocks, np.int32)  # pad -> scratch
        failed = []
        for i, rec in enumerate(recs):
            idx[i] = rec.block
            try:
                parts = rec.fetch.wait()
            except Exception as e:
                # not just IO errors: a lazy NVMe fetch submits its read
                # INSIDE wait() (pool.get / swap_in_start can raise under
                # the very host-memory pressure that put us in this tier).
                # Zero-fill and keep going — letting the exception out here
                # would strand every later record unreleased and unuploaded
                import logging

                log_dist(f"kv tier: promote read failed for block "
                         f"{rec.block} ({e}); zero-filling",
                         level=logging.WARNING)
                self._tier_store.count_miss(rec.tier)
                failed.append(rec)
                continue
            kp[:, i] = parts["k"]
            vp[:, i] = parts["v"]
            if sp is not None:
                sp[:, i] = parts["kv_scale"]
        return recs, failed, idx, kp, vp, sp

    def _finish_promotes(self, recs, failed) -> None:
        """Post-upload bookkeeping shared by the standalone scatter and the
        fused prologue: return the fetch loans, drop the store entries,
        observe promote latency, publish the uploaded nodes."""
        now = time.perf_counter()
        for rec in recs:
            rec.fetch.release()
            self._tier_store.discard(rec.key)
            if self._promote_ms is not None and rec not in failed:
                # failed reads are counted as tier misses, not promotes —
                # observing them would pollute the latency an operator
                # uses to size promote_depth / host_mb
                self._promote_ms[rec.tier].observe(
                    (now - rec.fetch.t_start) * 1e3)
        self.prefix_cache.mark_uploaded(recs)
        for rec in failed:
            # the zero-filled block serves ONLY the in-flight acquirer:
            # published, every future match would read zeros as KV and
            # the next demotion would persist them into the tier
            self.prefix_cache.drop_failed_promote(rec.node)

    def _flush_promotes_impl(self, recs) -> None:
        built = self._build_promote_payloads(recs)
        if built is None:
            return
        recs, failed, idx, kp, vp, sp = built
        try:
            with jax.sharding.set_mesh(self.mesh):
                if sp is None:
                    self.cache = self._promote_step(
                        self.cache, jnp.asarray(idx), jnp.asarray(kp),
                        jnp.asarray(vp))
                else:
                    self.cache = self._promote_step(
                        self.cache, jnp.asarray(idx), jnp.asarray(kp),
                        jnp.asarray(vp), jnp.asarray(sp))
        except BaseException:
            # upload never happened: re-demote onto the still-intact tier
            # entries so the blocks (garbage) leave the tree and the
            # fetch loans return to the pool, then surface the failure
            self.prefix_cache.cancel_promotes(recs)
            raise
        self._finish_promotes(recs, failed)

    # ---- fused promote prologue (decode_kernel='pallas') -----------------
    def _fence_promotes(self):
        """The dispatch-site promote fence. With the fused kernel active the
        pending prefix promotions do NOT get their own donated scatter —
        their payloads are returned here and the caller threads them into
        the upcoming step's fused prologue (one dispatch instead of two).
        Pending RESUME uploads always flush standalone first: a failed
        resume read unwinds the whole resume rather than zero-filling, a
        policy the prologue (which must always dispatch) cannot express.
        Returns ``(recs, failed, idx, kp, vp, sp)`` or ``None`` (nothing to
        fuse — already flushed, stale, or the xla path is active)."""
        if self._pause_q:
            self._flush_pause_promotes()
        if self.decode_kernel != "pallas":
            self._flush_promotes()
            return None
        recs, self._promote_q = self._promote_q, []
        if not recs:
            return None
        return self._build_promote_payloads(recs)

    def _psp(self, sp):
        """The fused jits take the scale payload positionally; a scale-less
        pool passes this zero-size sentinel (dead-code under jit)."""
        return (jnp.asarray(sp) if sp is not None
                else jnp.zeros((0,), jnp.float32))

    def _finish_fused_promotes(self, recs, failed) -> None:
        self._finish_promotes(recs, failed)
        self._fused_saved_dispatches += 1
        if self._obs is not None:
            self._obs["decode_prologue_promotes"].inc(float(len(recs)))
        bus = self._ebus
        if bus.enabled:
            bus.instant("engine", "promote_fence_fused",
                        args={"promotes": len(recs),
                              "failed": len(failed)})

    def _get_decode_loop_fused(self):
        if self._decode_loop_fused is None:
            has_sc = "kv_scale" in self.cache

            def fused(params, cache, pidx, pkp, pvp, psp, bt, slots, pos0,
                      tok0, steps, valid, rng, temperature, top_k, top_p):
                cache = self._promote_impl(cache, pidx, pkp, pvp,
                                           psp if has_sc else None)
                return self._multi_decode(params, cache, bt, slots, pos0,
                                          tok0, steps, valid, rng,
                                          temperature, top_k, top_p)

            self._decode_loop_fused = jax.jit(
                fused, donate_argnums=(1,), static_argnums=(10, 13, 14, 15),
                out_shardings=(None, self._kv_out))
        return self._decode_loop_fused

    def _get_step_packed_fused(self):
        if self._step_packed_fused is None:
            has_sc = "kv_scale" in self.cache

            def fused(params, tok_ids, cache, pidx, pkp, pvp, psp, bt,
                      tok_slot, tok_pos, valid, gidx, dr, tile, no_past):
                cache = self._promote_impl(cache, pidx, pkp, pvp,
                                           psp if has_sc else None)
                return self._fwd_packed(params, tok_ids, cache, bt,
                                        tok_slot, tok_pos, valid, gidx,
                                        dr, tile, no_past)

            self._step_packed_fused = jax.jit(
                fused, donate_argnums=(2,), static_argnums=(12, 13, 14),
                out_shardings=(None, self._kv_out))
        return self._step_packed_fused

    def tier_report(self) -> Optional[Dict]:
        """Tier-store snapshot + pending promote depth (None = tiers off)."""
        if self._tier_store is None:
            return None
        return {**self._tier_store.report(),
                "pending_promotes": len(self._promote_q),
                "paused_requests": len(self._paused),
                "pending_resumes": len(self._pause_q),
                "fused_prologue_dispatches_saved":
                    self._fused_saved_dispatches}

    # ---- serving preemption: pause / resume through the tier store -------
    def _ensure_pause_store(self):
        """The pause path's tier store + promote jit, created on first use
        when ``inference.prefix_cache.tiers`` is off (paused KV then lives
        in an engine-private host-only store; the prefix cache never sees
        it)."""
        if self._tier_store is None:
            from deepspeed_tpu.inference.kv_tier import KVTierStore

            self._tier_store = KVTierStore(
                host_mb=float(self.pause_store_mb),
                nvme_path=self.migration_nvme_path or "")
        elif self.migration_nvme_path:
            # store created before the serving layer set the shared path
            # (or by prefix tiers without NVMe): late-attach; no-op when a
            # swapper already exists
            self._tier_store.attach_nvme(self.migration_nvme_path)
        if self._promote_step is None:
            self._promote_step = jax.jit(self._promote_impl,
                                         donate_argnums=(0,),
                                         out_shardings=self._kv_out)
        return self._tier_store

    def is_paused(self, uid: int) -> bool:
        return uid in self._paused

    def paused_blocks(self, uid: int) -> int:
        """Pool blocks a paused uid needs back to resume (0 = not paused)."""
        rec = self._paused.get(uid)
        return 0 if rec is None else len(rec.keys)

    def can_resume(self, uid: int) -> bool:
        """Capacity probe: a free slot + enough free-or-evictable blocks to
        re-materialise the paused sequence."""
        rec = self._paused.get(uid)
        if rec is None or rec.resuming:
            return False
        return (bool(self.state._free_slots)
                and len(rec.keys) <= self.state._available_blocks())

    def pause_request(self, uid: int) -> bool:
        """PREEMPT a live sequence: demote its KV pages into the tier store
        (exactly the prefix-demotion byte path) and free its HBM blocks +
        slot through the normal flush mechanics. Returns False — with NO
        side effects — when the uid has no pausable state (unknown, already
        paused, mid-step, nothing in KV yet) or the store cannot hold the
        pages; the caller falls back to a plain shed."""
        seq = self.state.sequences.get(uid)
        if seq is None or uid in self._paused or seq.in_flight:
            return False
        seen = int(seq.seen_tokens)
        if seen <= 0:
            return False
        t0 = time.perf_counter()
        nb = -(-seen // self.block_size)
        blocks = seq.blocks[:nb]
        store = self._ensure_pause_store()
        payloads = self._extract_blocks(blocks)
        keys = []
        for parts in payloads:
            key = self._pause_key
            self._pause_key -= 1
            if not store.put(key, parts):
                for k in keys:
                    store.discard(k)
                return False
            keys.append(key)
        hist = None
        if self._hist is not None:
            hist = self._hist.get(uid)
        self._paused[uid] = _PausedSeq(uid, keys, seen, hist)
        # release HBM + slot the same way a terminal flush does (shared
        # prefix blocks just lose this sequence's reference — the snapshot
        # above captured their bytes, so resume never depends on the tree)
        self._pos[seq.slot] = 0
        self.state.flush(uid)
        if self._hist is not None:
            self._hist.pop(uid, None)
        bus = self._ebus
        if bus.enabled:
            bus.instant("kv_tier", "pause",
                        args={"uid": int(uid), "blocks": nb,
                              "seen_tokens": seen,
                              "ms": round((time.perf_counter() - t0) * 1e3,
                                          3)})
        return True

    def resume_request(self, uid: int) -> bool:
        """Begin resuming a paused uid: fresh slot + freshly allocated
        blocks, tier reads started; the payload upload fences before the
        next device step (the :meth:`_flush_promotes` discipline). Returns
        False when there is no capacity yet (try again later) — or when
        the parked entries were lost, in which case the uid is also queued
        on the resume-failure list (:meth:`flush_resumes` drains it) so
        the serving layer sheds it retryably instead of retrying forever."""
        rec = self._paused.get(uid)
        if rec is None or rec.resuming or self._tier_store is None:
            return False
        if not self.can_resume(uid):
            return False
        store = self._tier_store
        try:
            seq = self.state.restore(uid, len(rec.keys), rec.seen)
        except (RuntimeError, ValueError):
            return False
        fetches = []
        store.begin_chain(rec.keys)
        try:
            for key in rec.keys:
                f = store.fetch_start(key)
                if f is None:         # entry dropped under store pressure
                    raise KeyError(key)
                fetches.append(f)
        except BaseException:
            for f in fetches:
                f.release()
            store.end_chain()
            # the parked KV is gone: unwind the restore completely (the
            # request must never see zeroed KV) and report the loss
            self._pos[seq.slot] = 0
            self.state.flush(uid)
            self._drop_paused(uid)
            self._resume_failed.append(uid)
            return False
        store.end_chain()
        rec.resuming = True
        self._pos[seq.slot] = rec.seen
        if self._hist is not None and rec.hist is not None:
            self._hist[uid] = rec.hist
        self._pause_q.append((uid, rec, list(seq.blocks), fetches))
        bus = self._ebus
        if bus.enabled:
            bus.instant("kv_tier", "resume_start",
                        args={"uid": int(uid), "blocks": len(rec.keys),
                              "seen_tokens": rec.seen})
        return True

    # ---- cross-replica migration: durable export / adopt -----------------
    def export_paused(self, uid: int, tag: str, shared_path: str,
                      keep: bool = True) -> Optional[str]:
        """Write a durable, portable resume manifest for a PAUSED uid onto
        the shared migration namespace; returns the manifest path (None =
        not exportable: unknown or mid-resume uid, no NVMe-backed store,
        or the store's NVMe namespace is not the shared one). ``tag`` must
        be fleet-unique — callers build it from the replica name +
        incarnation + uid. With ``keep`` (the crash-backup path) the donor
        retains its parked entries and reclaims the durable copy when the
        record dies locally; ``keep=False`` (voluntary rebalance)
        transfers ownership to the manifest, so the donor's local flush
        leaves the durable files for the adopting sibling."""
        rec = self._paused.get(uid)
        if rec is None or rec.resuming:
            return None
        if rec.manifest_path is not None:
            path = rec.manifest_path            # idempotent re-export
            if not keep:
                # a crash backup already exists; rebalance just transfers
                # ownership — the donor's local flush must now LEAVE the
                # durable files + manifest for the adopting sibling
                rec.durable = None
                rec.manifest_path = None
            return path
        store = self._tier_store
        if store is None or store.swapper is None:
            return None
        if os.path.realpath(store.swapper.swap_dir) != os.path.realpath(
                os.path.join(shared_path, "kv")):
            # the store spills somewhere siblings cannot see (prefix tiers
            # on a private path): a manifest would point at air
            return None
        from deepspeed_tpu.inference.kv_tier import write_manifest
        from deepspeed_tpu.resilience.faults import get_injector

        inj = get_injector()
        t0 = time.perf_counter()
        entries = store.export_durable(rec.keys, tag)
        try:
            if inj:
                # the crash window the manifest protocol closes: KV bytes
                # durable, manifest not yet committed → orphaned files the
                # TTL sweep reclaims, never a manifest pointing at air
                inj.on_pause_export(str(tag))
            hist = rec.hist
            payload = {
                "uid": str(tag),
                "seen_tokens": int(rec.seen),
                "hist": ([] if hist is None
                         else [int(t) for t in np.asarray(hist).tolist()]),
                "entries": entries,
            }
            path = write_manifest(shared_path, payload)
        except BaseException:
            store.drop_durable(entries)
            raise
        if inj:
            inj.maybe_tear_manifest(path, str(tag))
        if keep:
            rec.durable = entries
            rec.manifest_path = path
        bus = self._ebus
        if bus.enabled:
            bus.instant("kv_tier", "pause_export",
                        args={"uid": int(uid), "tag": str(tag),
                              "entries": len(entries), "keep": bool(keep),
                              "ms": round((time.perf_counter() - t0) * 1e3,
                                          3)})
        return path

    def adopt_paused(self, uid: int, payload: Dict,
                     manifest_path: Optional[str] = None) -> None:
        """Register another replica's exported pause record under the
        LOCAL ``uid``: the manifest's durable entries become NVMe-tier
        entries of this engine's pause store, and the uid becomes
        resumable exactly like a locally-paused one — ``resume_request``
        promotes KV this replica never produced, through the same
        ``_flush_promotes`` fence. Raises on any validation failure
        (missing/torn durable files, store without the shared namespace)
        with the partial adopt fully unwound; the caller falls down the
        re-prefill ladder. ``manifest_path`` (the claimed manifest) is
        reclaimed when the record dies — after a successful resume, or
        with the adopted entries on failure."""
        if uid in self._paused or uid in self.state.sequences:
            raise ValueError(f"adopt_paused: uid {uid} already live")
        store = self._ensure_pause_store()
        if store.swapper is None:
            raise RuntimeError("adopt_paused requires a shared NVMe "
                               "namespace (serving.migration)")
        entries = payload.get("entries") or []
        seen = int(payload.get("seen_tokens", 0))
        if seen <= 0 or not entries:
            raise ValueError("adopt_paused: empty manifest payload")
        keys = []
        for _ in entries:
            keys.append(self._pause_key)
            self._pause_key -= 1
        store.adopt_durable(entries, keys)
        hist = payload.get("hist") or None
        rec = _PausedSeq(uid, keys, seen,
                         None if hist is None
                         else np.asarray(hist, np.int32))
        rec.adopted = True
        rec.manifest_path = manifest_path
        self._paused[uid] = rec
        bus = self._ebus
        if bus.enabled:
            bus.instant("kv_tier", "adopt",
                        args={"uid": int(uid),
                              "tag": str(payload.get("uid")),
                              "entries": len(keys), "seen_tokens": seen})

    def flush_resumes(self) -> list:
        """Force pending resume uploads NOW and return the uids whose tier
        read failed (drained). The batcher calls this right after
        ``resume_request`` so a failure is known BEFORE the request rejoins
        the plan; the dispatch-site fences also run it, so correctness
        never depends on the caller."""
        self._flush_pause_promotes()
        failed, self._resume_failed = self._resume_failed, []
        return failed

    def _unwind_resume(self, uid: int, fetches) -> None:
        """A resume that cannot complete: give back loans, blocks, slot and
        the parked entries; the uid lands on the resume-failure list."""
        for f in fetches:
            f.release()
        seq = self.state.sequences.get(uid)
        if seq is not None:
            self._pos[seq.slot] = 0
            self.state.flush(uid)
        if self._hist is not None:
            self._hist.pop(uid, None)
        self._drop_paused(uid)
        self._resume_failed.append(uid)

    def _flush_pause_promotes(self) -> None:
        """Upload every pending resume's parked pages into its new pool
        blocks. A failed tier read NEVER zero-fills here (unlike a prefix
        promote, which only costs recompute): a sequence resumed over
        zeros would decode garbage as its own past, so the whole resume is
        unwound instead and the uid reported failed."""
        pending, self._pause_q = self._pause_q, []
        if not pending:
            return
        from deepspeed_tpu.resilience.faults import get_injector

        import logging

        store = self._tier_store
        inj = get_injector()
        for j, (uid, rec, blocks, fetches) in enumerate(pending):
            n = len(blocks)
            kt = self.cache["k"]
            npad = max(4, 1 << (n - 1).bit_length())
            kp = np.zeros((kt.shape[0], npad) + kt.shape[2:], kt.dtype)
            vp = np.zeros_like(kp)
            sp = None
            if "kv_scale" in self.cache:
                st = self.cache["kv_scale"]
                sp = np.zeros((st.shape[0], npad) + st.shape[2:], st.dtype)
            idx = np.full((npad,), self.num_blocks, np.int32)
            failed = False
            for i, (key, fetch) in enumerate(zip(rec.keys, fetches)):
                try:
                    if inj:
                        tier = store.tier_of(key) or "host"
                        if rec.adopted:
                            # adopted KV faults through the migration site
                            # (a failed cross-replica read unwinds to the
                            # re-prefill ladder, not a plain resume shed)
                            inj.on_migrate_read(tier)
                        else:
                            inj.on_resume_read(tier)
                    parts = fetch.wait()
                except Exception as e:
                    log_dist(f"kv tier: resume read failed for uid {uid} "
                             f"key {key} ({e}); unwinding resume",
                             level=logging.WARNING)
                    failed = True
                    break
                idx[i] = blocks[i]
                kp[:, i] = parts["k"]
                vp[:, i] = parts["v"]
                if sp is not None:
                    sp[:, i] = parts["kv_scale"]
            if failed:
                self._unwind_resume(uid, fetches)
                continue
            try:
                with jax.sharding.set_mesh(self.mesh):
                    if sp is None:
                        self.cache = self._promote_step(
                            self.cache, jnp.asarray(idx), jnp.asarray(kp),
                            jnp.asarray(vp))
                    else:
                        self.cache = self._promote_step(
                            self.cache, jnp.asarray(idx), jnp.asarray(kp),
                            jnp.asarray(vp), jnp.asarray(sp))
            except BaseException:
                # upload never happened: unwind this uid, then surface —
                # the pool was not touched, later pendings re-queue
                self._unwind_resume(uid, fetches)
                self._pause_q = list(pending[j + 1:]) + self._pause_q
                raise
            for f in fetches:
                f.release()
            self._drop_paused(uid)      # parked copies now redundant
            bus = self._ebus
            if bus.enabled:
                bus.instant("kv_tier", "resume_upload",
                            args={"uid": int(uid), "blocks": n})

    def _drop_paused(self, uid: int) -> None:
        """Forget a pause record: purge any in-flight resume (releasing
        its loans) and discard the parked store entries. Idempotent."""
        rec = self._paused.pop(uid, None)
        if rec is None:
            return
        keep = []
        for item in self._pause_q:
            if item[0] == uid:
                for f in item[3]:
                    f.release()
            else:
                keep.append(item)
        self._pause_q = keep
        if self._tier_store is not None:
            for key in rec.keys:
                self._tier_store.discard(key)
            if rec.durable is not None:
                # donor-side crash backup: a local resume (or terminal
                # flush) makes the durable copy stale — reclaim it, or
                # manifests would advertise requests that no longer exist
                self._tier_store.drop_durable(rec.durable)
        if rec.manifest_path is not None:
            try:
                os.remove(rec.manifest_path)
            except OSError:
                pass                    # claimed/reclaimed by a sibling

    def close(self) -> None:
        """Idempotent teardown of host-side resources the engine stands up
        beside the device pool (today: the KV tier store's pinned buffers
        and AIO swapper). Safe to call on engines without tiers."""
        if self._promote_q:
            # never uploaded: drop the loans AND the nodes — the blocks
            # hold garbage, and the prefix cache stays usable after a
            # tier-only close(), so leaving them published would serve
            # zeroed/garbage KV to the next matching request
            for rec in self._promote_q:
                rec.fetch.release()
                if self.prefix_cache is not None:
                    self.prefix_cache.drop_failed_promote(rec.node)
            self._promote_q = []
        if self._pause_q:
            # in-flight resumes: release the loans; the pause records
            # below discard the parked entries themselves
            for _uid, _rec, _blocks, fetches in self._pause_q:
                for f in fetches:
                    f.release()
            self._pause_q = []
        for uid in list(self._paused):
            self._drop_paused(uid)
        if self._tier_store is not None:
            self._tier_store.close()
            self._tier_store = None
            if self.prefix_cache is not None:
                self.prefix_cache.tier_store = None
                self.prefix_cache.extract_fn = None

    # incremental block-table cache: rows refresh only when a sequence's
    # block count changed or its slot was reused (SequenceManager bumps
    # slot_generation on release) — a full rebuild per put() was
    # O(max_seqs x nb_max) of host work on the put critical path
    _bt_cache = None
    _bt_key = None

    def _block_tables(self) -> np.ndarray:
        """[max_sequences, nb_max] physical block ids. Invariant: rows of
        SCHEDULED slots are correct; a flushed slot's row keeps its stale
        ids until the slot is reused (only scheduled slots' rows are ever
        read — atoms/decode items index by live slot). Unused tail entries
        of a live row point at the scratch block."""
        if self._bt_cache is None:
            self._bt_cache = np.full(
                (self.state.max_sequences, self.nb_max), self.num_blocks,
                np.int32)
            self._bt_key = {}
        bt = self._bt_cache
        gen = self.state.slot_generation
        for seq in self.state.sequences.values():
            key = (gen[seq.slot], len(seq.blocks))
            if self._bt_key.get(seq.slot) != key:
                n = key[1]
                bt[seq.slot, :n] = seq.blocks
                bt[seq.slot, n:] = self.num_blocks
                self._bt_key[seq.slot] = key
        return bt

    def _multi_decode(self, params, cache, bt, slots, pos0, tok0, steps: int,
                      valid=None, rng=None, temperature: float = 0.0,
                      top_k: int = 0, top_p: float = 1.0):
        """``steps`` greedy-or-sampled decode iterations fused into ONE device
        program (lax.scan): the TPU analog of the reference v1 engine's
        CUDA-graph replay (inference/engine.py:497) — per-step host dispatch
        and transfers vanish, so decode throughput reflects the chip.
        ``temperature``/``top_k``/``top_p`` (static) select the v1 engine's
        ``sample_token`` math inside the loop; ``rng`` is the base PRNG key,
        folded per step (sampling adds one categorical over [B, V] per step
        — a rounding error next to the layer stack).

        The paged pool stays READ-ONLY across the whole scan: per-step
        appends would force XLA to snapshot-copy the pool at every Pallas
        read (~2 ms x layers x steps). New KV accumulates in a dense tail
        carry ([L, B, steps, K, d]) that attention treats as a third
        flash-decode segment, and ONE scatter folds it into the pool after
        the scan. ``valid`` masks bucket-padding rows (decode_batch pads B
        to powers of two so a draining batch does not recompile the scan
        per occupancy)."""
        import jax.numpy as jnp

        from deepspeed_tpu.ops.paged_attention import (
            packed_kv_append, packed_kv_append_quant)

        cfg = self.cfg
        B = tok0.shape[0]
        if valid is None:
            valid = jnp.ones((B,), bool)
        L, K, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
        cdt = jnp.dtype(cfg.dtype)
        tail0 = (jnp.zeros((L, B, steps, K, hd), cdt),
                 jnp.zeros((L, B, steps, K, hd), cdt))

        def step(carry, t):
            tk, tv, toks = carry
            logits, tail = self.module.forward_decode_tail(
                params, toks, cache, {"k": tk, "v": tv}, t, bt, slots, pos0,
                valid, decode_kernel=self.decode_kernel)
            if temperature > 0.0:
                from deepspeed_tpu.inference.engine import sample_token

                sub = jax.random.fold_in(rng, t)
                nxt = sample_token(logits, temperature, top_k, sub,
                                   top_p=top_p).astype(jnp.int32)
            else:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (tail["k"], tail["v"], nxt), nxt

        (tk, tv, _), out = jax.lax.scan(
            step, (*tail0, tok0), jnp.arange(steps, dtype=jnp.int32))
        # fold the tail into the pool: one scatter per pool for the whole
        # decode_batch call (row (b, s) -> slot[b] position pos0[b]+s)
        rows_k = tk.reshape(L, B * steps, K, hd)
        rows_v = tv.reshape(L, B * steps, K, hd)
        slot2 = jnp.repeat(slots, steps)
        pos2 = (pos0[:, None]
                + jnp.arange(steps, dtype=pos0.dtype)[None, :]).reshape(-1)
        valid2 = jnp.repeat(valid, steps)
        if "kv_scale" in cache:
            kvb = 4 if self.kv_dtype == "int4" else 8
            nk, sc1 = packed_kv_append_quant(cache["k"], cache["kv_scale"],
                                             rows_k, bt, slot2, pos2, 0,
                                             valid2, bits=kvb)
            nv, sc2 = packed_kv_append_quant(cache["v"], sc1, rows_v, bt,
                                             slot2, pos2, 1, valid2, bits=kvb)
            return out, {"k": nk, "v": nv, "kv_scale": sc2}
        nk = packed_kv_append(cache["k"], rows_k, bt, slot2, pos2, valid2)
        nv = packed_kv_append(cache["v"], rows_v, bt, slot2, pos2, valid2)
        return out, {"k": nk, "v": nv}          # out: [steps, B]

    def decode_batch(self, batch_uids: Sequence[int],
                     batch_tokens: Sequence[int], steps: int,
                     temperature: float = 0.0, top_k: int = 0,
                     top_p: float = 1.0, seed: int = 0,
                     speculative: Optional[bool] = None
                     ) -> Dict[int, np.ndarray]:
        """Advance every listed sequence ``steps`` tokens by on-device decode
        (greedy at ``temperature=0``, else the v1 engine's temperature/
        top-k/nucleus sampling), starting from each sequence's
        ``batch_tokens`` entry. Returns the generated tokens per uid
        ([steps] each). One dispatch + one fetch regardless of ``steps`` —
        the throughput serving mode.

        With ``inference.speculative`` enabled (overridable per call via
        ``speculative=``) greedy decode runs draft-verify rounds: up to
        ``max_draft`` tokens self-drafted by n-gram lookup in the sequence's
        own history, verified in one batched forward, the longest correct
        prefix accepted — token-identical output, fewer forward passes.
        Sampling always takes the fused-scan path."""
        spec = (self.spec_cfg.enabled if speculative is None
                else bool(speculative))
        bus = self._ebus
        if bus.enabled:
            with bus.span("engine", "decode_batch",
                          args={"uids": [int(u) for u in batch_uids],
                                "steps": int(steps), "spec": spec}):
                return self._decode_batch_dispatch(
                    batch_uids, batch_tokens, steps, temperature, top_k,
                    top_p, seed, spec)
        return self._decode_batch_dispatch(batch_uids, batch_tokens, steps,
                                           temperature, top_k, top_p, seed,
                                           spec)

    def _decode_batch_dispatch(self, batch_uids, batch_tokens, steps,
                               temperature, top_k, top_p, seed, spec):
        if spec and temperature == 0.0 and self._hist is not None:
            return self._decode_batch_spec(batch_uids, batch_tokens, steps)
        return self._decode_batch_scan(batch_uids, batch_tokens, steps,
                                       temperature, top_k, top_p, seed)

    def _decode_batch_scan(self, batch_uids: Sequence[int],
                           batch_tokens: Sequence[int], steps: int,
                           temperature: float = 0.0, top_k: int = 0,
                           top_p: float = 1.0, seed: int = 0
                           ) -> Dict[int, np.ndarray]:
        """The fused on-device decode scan (one dispatch for ``steps``)."""
        if not self.state.can_schedule_batch(batch_uids,
                                             [steps] * len(batch_uids)):
            raise CapacityError(batch_uids, [steps] * len(batch_uids),
                                "decode_batch")
        if self._moe_ep:
            from deepspeed_tpu.resilience.faults import get_injector

            inj = get_injector()
            if inj:
                # fires BEFORE any sequence state mutates: an injected a2a
                # failure unwinds to the batcher as a cleanly failed step
                inj.on_moe_dispatch("decode")
        descs = [self.state.schedule(uid, steps) for uid in batch_uids]
        B = len(descs)
        bpad = max(8, 1 << (B - 1).bit_length())  # bounded jit cache as B drains
        slots = np.zeros((bpad,), np.int32)
        slots[:B] = [d.slot for d in descs]
        pos0 = np.zeros((bpad,), np.int32)
        pos0[:B] = self._pos[slots[:B]]
        tok0 = np.zeros((bpad,), np.int32)
        tok0[:B] = np.asarray(batch_tokens, np.int32).reshape(B)
        valid = np.arange(bpad) < B
        fused = None
        if self._promote_q or self._pause_q:
            fused = self._fence_promotes()  # fence: no read of a promoted
        t_disp = time.perf_counter()        # block before its upload
        with jax.sharding.set_mesh(self.mesh):
            if fused is None:
                out, self.cache = self._decode_loop(
                    self.params, self.cache,
                    jnp.asarray(self._block_tables()),
                    jnp.asarray(slots), jnp.asarray(pos0),
                    jnp.asarray(tok0), steps, jnp.asarray(valid),
                    jax.random.key(seed), float(temperature), int(top_k),
                    float(top_p))
            else:
                # promotions ride the scan's prologue: one donated
                # dispatch scatters the payloads AND runs the decode loop
                recs, failed, idx, kp, vp, sp = fused
                try:
                    out, self.cache = self._get_decode_loop_fused()(
                        self.params, self.cache, jnp.asarray(idx),
                        jnp.asarray(kp), jnp.asarray(vp), self._psp(sp),
                        jnp.asarray(self._block_tables()),
                        jnp.asarray(slots), jnp.asarray(pos0),
                        jnp.asarray(tok0), steps, jnp.asarray(valid),
                        jax.random.key(seed), float(temperature),
                        int(top_k), float(top_p))
                except BaseException:
                    self.prefix_cache.cancel_promotes(recs)
                    raise
                self._finish_fused_promotes(recs, failed)
            toks = np.asarray(out)            # [steps, bpad]
        if self._obs is not None:
            self._obs["decode_dispatches"].inc(1.0)
            self._obs["decode_tokens"].inc(float(steps * B))
            self._obs["decode_fetch_ms"].observe(
                (time.perf_counter() - t_disp) * 1e3)
        for i, d in enumerate(descs):
            self._pos[d.slot] = d.seen_tokens + steps
            # fed tokens = the start token + all but the last output (the
            # scan feeds its own outputs; the final one's KV is not yet in)
            fed = (np.concatenate([tok0[i:i + 1], toks[:-1, i]])
                   if self._hist is not None else ())
            self._commit(d.uid, fed)
        return {d.uid: toks[:, i] for i, d in enumerate(descs)}

    # ---- n-gram speculative decoding (draft + batched verify) ------------
    def _draft(self, uids: Sequence[int], tokens: Sequence[int],
               caps: Sequence[int]) -> list:
        """Per-uid draft arrays from each sequence's own committed history
        plus the token about to be fed (prompt-lookup decoding)."""
        from deepspeed_tpu.inference.speculative import ngram_draft

        drafts = []
        for uid, t, cap in zip(uids, tokens, caps):
            seq = self.state.sequences.get(uid)
            room = self.max_seq_len - (seq.seen_tokens if seq else 0) - 1
            k = min(int(self.spec_cfg.max_draft), int(cap), room)
            h = self._hist.get(uid)
            hist = (np.concatenate([h, [t]]) if h is not None and h.size
                    else np.asarray([t], np.int32))
            drafts.append(ngram_draft(hist, self.spec_cfg.ngram, k)
                          if k > 0 else hist[:0])
        return drafts

    def draft_tokens(self, batch_uids: Sequence[int],
                     batch_tokens: Sequence[int],
                     max_drafts: Optional[Sequence[int]] = None) -> list:
        """Host-side n-gram drafts per uid (possibly empty arrays) — lets a
        caller route draft-less sequences through the ordinary decode path
        and pay the verify dispatch only where a draft exists."""
        if self._hist is None:
            raise ValueError("draft_tokens needs inference.speculative "
                             "(or prefix_cache) enabled on the engine")
        caps = (max_drafts if max_drafts is not None
                else [self.spec_cfg.max_draft] * len(batch_uids))
        return self._draft(batch_uids, batch_tokens, caps)

    def spec_decode_round(self, batch_uids: Sequence[int],
                          batch_tokens: Sequence[int],
                          max_drafts: Optional[Sequence[int]] = None,
                          drafts: Optional[list] = None):
        """One greedy draft-verify round for every listed sequence: draft up
        to ``min(max_draft, max_drafts[i])`` tokens by n-gram lookup (or
        take precomputed ``drafts``), verify all drafts in ONE batched
        forward, accept the longest prefix the model confirms (plus the
        model's own bonus token at the frontier). Returns
        ``({uid: emitted int32 array (1..K+1 tokens)}, info)`` where
        ``info`` carries the round's drafted/accepted/emitted counts — the
        acceptance-rate feed for ``serving/spec_*``."""
        if drafts is None:
            drafts = self.draft_tokens(batch_uids, batch_tokens, max_drafts)
        elif self._hist is None:
            raise ValueError("spec_decode_round needs inference.speculative "
                             "(or prefix_cache) enabled on the engine")
        return self._spec_verify(batch_uids, batch_tokens, drafts)

    def _pack_atoms(self, descs, chunks):
        """The packed two-region atom layout (decode rows, then pow2-wide
        tile atoms) shared by :meth:`put` and :meth:`_spec_verify` — the
        two MUST agree because they feed the same ``_step_packed`` jit.
        Returns ``(tok_ids, tok_slot, tok_pos, valid, starts, dr, tile,
        no_past)`` where ``starts[i]`` is the packed row of chunk ``i``'s
        first token."""
        items = list(enumerate(zip(descs, chunks)))
        dec = [(i, d, c) for i, (d, c) in items if len(c) == 1]
        big = [(i, d, c) for i, (d, c) in items if len(c) > 1]
        n_dec = len(dec)
        dr = max(8, 1 << (n_dec - 1).bit_length()) if n_dec else 0
        if big:
            longest = max(len(c) for _, _, c in big)
            tile = max(_MIN_TILE, 1 << (longest - 1).bit_length())
            tpad = 1 << (len(big) - 1).bit_length()
        else:
            tile, tpad = self.module.MAX_ATOM, 0
        npad = dr + tpad * tile
        tok_ids = np.zeros((npad,), np.int32)
        tok_slot = np.zeros((npad,), np.int32)
        tok_pos = np.zeros((npad,), np.int32)
        valid = np.zeros((npad,), bool)
        starts = np.zeros((len(descs),), np.int32)
        off = 0
        for i, d, c in dec:
            tok_ids[off] = c[0]
            tok_slot[off] = d.slot
            tok_pos[off] = d.seen_tokens
            valid[off] = True
            starts[i] = off
            off += 1
        off = dr
        for i, d, c in big:                  # one whole-chunk atom each
            tok_ids[off:off + len(c)] = c
            tok_slot[off:off + tile] = d.slot
            tok_pos[off:off + len(c)] = d.seen_tokens + np.arange(len(c))
            valid[off:off + len(c)] = True
            starts[i] = off
            off += tile
        # when every chunk atom starts at position 0 (fresh prefill) the
        # past kernel is statically skipped — the common first-put case
        no_past = all(d.seen_tokens == 0 for _, d, c in big)
        return tok_ids, tok_slot, tok_pos, valid, starts, dr, tile, no_past

    def _spec_verify(self, batch_uids, batch_tokens, drafts):
        bus = self._ebus
        if not bus.enabled:
            return self._spec_verify_impl(batch_uids, batch_tokens, drafts)
        with bus.span("engine", "spec_verify",
                      args={"uids": [int(u) for u in batch_uids],
                            "drafted": int(sum(len(d) for d in drafts))}):
            return self._spec_verify_impl(batch_uids, batch_tokens, drafts)

    def _spec_verify_impl(self, batch_uids, batch_tokens, drafts):
        """Verify per-sequence chunks ``[t0, d1..dk]`` in one packed step
        with logits gathered at EVERY chunk position, then accept greedily.
        KV for rejected drafts lands in the pool but the frontier
        (``seen_tokens``/``_pos``) only advances over accepted tokens, so
        later steps overwrite the stale rows before any read reaches them
        (pool reads are bounded by the frontier)."""
        chunks = [np.concatenate([[int(t)], np.asarray(d, np.int64)])
                  .astype(np.int32)
                  for t, d in zip(batch_tokens, drafts)]
        lens = [len(c) for c in chunks]
        if not self.state.can_schedule_batch(batch_uids, lens):
            raise CapacityError(batch_uids, lens, "spec verify round")
        descs = [self.state.schedule(uid, n)
                 for uid, n in zip(batch_uids, lens)]
        tok_ids, tok_slot, tok_pos, valid, starts, dr, tile, no_past = \
            self._pack_atoms(descs, chunks)
        # gather logits at EVERY chunk position (not just ends), chunk-major,
        # padded to a power of two so the jit cache stays bounded
        G = sum(lens)
        gpad = max(8, 1 << (G - 1).bit_length())
        gidx = np.zeros((gpad,), np.int32)
        goff = np.zeros((len(descs),), np.int32)
        g = 0
        for i, c in enumerate(chunks):
            goff[i] = g
            gidx[g:g + len(c)] = starts[i] + np.arange(len(c))
            g += len(c)
        fused = None
        if self._promote_q or self._pause_q:
            fused = self._fence_promotes()  # promote-completion fence
        with jax.sharding.set_mesh(self.mesh):
            if fused is None:
                logits, self.cache = self._step_packed(
                    self.params, jnp.asarray(tok_ids), self.cache,
                    jnp.asarray(self._block_tables()),
                    jnp.asarray(tok_slot), jnp.asarray(tok_pos),
                    jnp.asarray(valid), jnp.asarray(gidx), dr, tile,
                    no_past)
            else:
                recs, failed, idx, kp, vp, sp = fused
                try:
                    logits, self.cache = self._get_step_packed_fused()(
                        self.params, jnp.asarray(tok_ids), self.cache,
                        jnp.asarray(idx), jnp.asarray(kp), jnp.asarray(vp),
                        self._psp(sp), jnp.asarray(self._block_tables()),
                        jnp.asarray(tok_slot), jnp.asarray(tok_pos),
                        jnp.asarray(valid), jnp.asarray(gidx), dr, tile,
                        no_past)
                except BaseException:
                    self.prefix_cache.cancel_promotes(recs)
                    raise
                self._finish_fused_promotes(recs, failed)
            out = np.asarray(logits)                       # [gpad, V]
        results: Dict[int, np.ndarray] = {}
        info = {"drafted": int(G - len(descs)), "accepted": 0, "emitted": 0,
                "nonfinite_uids": []}
        for i, (d, c) in enumerate(zip(descs, chunks)):
            lg = out[goff[i]:goff[i] + len(c)]             # [len(c), V]
            if not np.all(np.isfinite(np.asarray(lg, np.float32))):
                # argmax over NaN would silently emit token 0; commit only
                # t0 (its KV is in the pool either way) and flag the uid so
                # the serving layer resolves it loudly like the put() path
                d.in_flight = 1
                self._pos[d.slot] = d.seen_tokens + 1
                self._commit(d.uid, c[:1])
                results[d.uid] = np.asarray([int(np.argmax(lg[0]))],
                                            np.int32)
                info["nonfinite_uids"].append(d.uid)
                info["emitted"] += 1
                continue
            emitted = [int(np.argmax(lg[0]))]
            j = 1
            while j < len(c) and int(c[j]) == emitted[-1]:
                emitted.append(int(np.argmax(lg[j])))
                j += 1
            m = len(emitted)        # fed tokens confirmed in KV: c[:m]
            d.in_flight = m
            self._pos[d.slot] = d.seen_tokens + m
            self._commit(d.uid, c[:m])
            results[d.uid] = np.asarray(emitted, np.int32)
            info["accepted"] += m - 1
            info["emitted"] += m
        self.spec_stats["rounds"] += 1
        self.spec_stats["drafted"] += info["drafted"]
        self.spec_stats["accepted"] += info["accepted"]
        self.spec_stats["emitted"] += info["emitted"]
        return results, info

    def _decode_batch_spec(self, batch_uids, batch_tokens, steps: int
                           ) -> Dict[int, np.ndarray]:
        """Greedy decode via draft-verify rounds; rounds where no sequence
        has a draft fall back to the fused scan (power-of-two step chunks,
        bounding compile churn). Output is token-identical to
        ``_decode_batch_scan`` — only the number of dispatches changes."""
        B = len(batch_uids)
        # same demand as the scan path: draft caps are remaining-1, so a
        # round schedules at most `remaining` tokens and the highest
        # position ever written is seen + steps - 1 — speculation changes
        # the number of dispatches, never the capacity contract
        if not self.state.can_schedule_batch(batch_uids, [steps] * B):
            raise CapacityError(batch_uids, [steps] * B, "decode_batch")
        out: Dict[int, list] = {u: [] for u in batch_uids}
        remaining = {u: steps for u in batch_uids}
        cur = {u: int(t) for u, t in zip(batch_uids, batch_tokens)}
        while True:
            live = [u for u in batch_uids if remaining[u] > 0]
            if not live:
                break
            caps = [remaining[u] - 1 for u in live]
            drafts = self._draft(live, [cur[u] for u in live], caps)
            if not any(len(d) for d in drafts):
                n = min(min(remaining[u] for u in live),
                        int(self.spec_cfg.fallback_steps))
                n = 1 << (n.bit_length() - 1)       # pow2: bounded jit cache
                res = self._decode_batch_scan(live,
                                              [cur[u] for u in live], n)
                self.spec_stats["fallback_steps"] += n
                for u in live:
                    toks = [int(t) for t in res[u]]
                    out[u].extend(toks)
                    remaining[u] -= n
                    cur[u] = toks[-1]
                continue
            res, _ = self._spec_verify(live, [cur[u] for u in live], drafts)
            for u in live:
                toks = [int(t) for t in res[u]]
                out[u].extend(toks)
                remaining[u] -= len(toks)
                cur[u] = toks[-1]
        return {u: np.asarray(out[u], np.int32) for u in batch_uids}

    def _fresh(self, uid: int) -> bool:
        seq = self.state.sequences.get(uid)
        return seq is None or self._pos[seq.slot] == 0

    def _prefill_impl(self, params, ids, lengths, cache, bt, slots):
        """Whole-prompt prefill + one-scatter pool append (jitted, cache
        donated — the model path never READS the pool, so the append stays
        in place)."""
        from deepspeed_tpu.ops.paged_attention import (
            packed_kv_append, packed_kv_append_quant)

        logits, kv = self.module.forward_prefill(params, ids, lengths)
        L = kv["k"].shape[0]
        Bp, T = ids.shape
        K, hd = self.cfg.num_kv_heads, self.cfg.head_dim
        rows_k = kv["k"].reshape(L, Bp * T, K, hd)
        rows_v = kv["v"].reshape(L, Bp * T, K, hd)
        slot2 = jnp.repeat(slots, T)
        pos2 = jnp.tile(jnp.arange(T, dtype=jnp.int32), Bp)
        valid2 = (jnp.arange(T)[None, :] < lengths[:, None]).reshape(-1)
        if "kv_scale" in cache:
            kvb = 4 if self.kv_dtype == "int4" else 8
            nk, sc1 = packed_kv_append_quant(cache["k"], cache["kv_scale"],
                                             rows_k, bt, slot2, pos2, 0,
                                             valid2, bits=kvb)
            nv, sc2 = packed_kv_append_quant(cache["v"], sc1, rows_v, bt,
                                             slot2, pos2, 1, valid2, bits=kvb)
            return logits, {"k": nk, "v": nv, "kv_scale": sc2}
        nk = packed_kv_append(cache["k"], rows_k, bt, slot2, pos2, valid2)
        nv = packed_kv_append(cache["v"], rows_v, bt, slot2, pos2, valid2)
        return logits, {"k": nk, "v": nv}

    # cap on bpad*T_pad per prefill step: bounds the [L, B, T, K, d] KV
    # stash forward_prefill materializes (~L*K*d*4B per token of transient
    # HBM) — larger fresh batches are split into successive steps
    PREFILL_BATCH_TOKENS = 16384

    def _prefill_whole(self, batch_uids: Sequence[int], chunks
                       ) -> Dict[int, np.ndarray]:
        """Fresh whole prompts: flash-prefill every prompt in one step."""
        t_entry = time.perf_counter()     # per-invocation host clock: the
        # grouped recursion below runs earlier groups' device steps to
        # completion, so timing must not be measured from put() entry
        if not self.state.can_schedule_batch(batch_uids,
                                             [len(c) for c in chunks]):
            raise CapacityError(batch_uids, [len(c) for c in chunks],
                                "whole-prompt prefill")
        longest = max(len(c) for c in chunks)
        T_pad0 = max(_MIN_TILE, 1 << (longest - 1).bit_length())
        group = max(1, self.PREFILL_BATCH_TOKENS // T_pad0)
        if len(batch_uids) > group:
            results: Dict[int, np.ndarray] = {}
            for i in range(0, len(batch_uids), group):
                results.update(self._prefill_whole(
                    batch_uids[i:i + group], chunks[i:i + group]))
            return results
        descs = [self.state.schedule(uid, len(c))
                 for uid, c in zip(batch_uids, chunks)]
        B = len(descs)
        bpad = 1 << (B - 1).bit_length()
        longest = max(len(c) for c in chunks)
        T_pad = max(_MIN_TILE, 1 << (longest - 1).bit_length())
        ids = np.zeros((bpad, T_pad), np.int32)
        lengths = np.zeros((bpad,), np.int32)
        slots = np.zeros((bpad,), np.int32)
        for i, (d, c) in enumerate(zip(descs, chunks)):
            ids[i, :len(c)] = c
            lengths[i] = len(c)
            slots[i] = d.slot
        if self._promote_q or self._pause_q:
            self._flush_promotes()      # promote-completion fence
        t_host = time.perf_counter()
        with jax.sharding.set_mesh(self.mesh):
            logits, self.cache = self._prefill_step(
                self.params, jnp.asarray(ids), jnp.asarray(lengths),
                self.cache, jnp.asarray(self._block_tables()),
                jnp.asarray(slots))
            t_disp = time.perf_counter()
            out = np.asarray(logits)
        self.timing = {
            "host_ms": (t_host - t_entry) * 1e3,
            "dispatch_ms": (t_disp - t_host) * 1e3,
            "fetch_ms": (time.perf_counter() - t_disp) * 1e3,
        }
        if self._obs is not None:
            # the whole-prompt fast path carries the TTFT-dominant puts —
            # it must feed the same inference/* stream as the packed path
            self._obs["put_host_ms"].observe(self.timing["host_ms"])
            self._obs["put_fetch_ms"].observe(self.timing["fetch_ms"])
            self._obs["tokens"].inc(float(sum(len(c) for c in chunks)))
        results: Dict[int, np.ndarray] = {}
        for i, (d, c) in enumerate(zip(descs, chunks)):
            results[d.uid] = out[i]
            self._pos[d.slot] = d.seen_tokens + len(c)
            self._commit(d.uid, c)
        return results

    # ---- one continuous-batching step (engine_v2.py:107 parity) ----------
    def put(self, batch_uids: Sequence[int], batch_tokens: Sequence[np.ndarray]
            ) -> Dict[int, np.ndarray]:
        """Advance every listed sequence by its token chunk; returns next-token
        logits per uid. Chunks may be whole prompts (prefill), single decode
        tokens, or anything between: the batch is one packed row of the
        scheduled tokens, each with its slot and position. With ``inference.prefix_cache``
        enabled, a fresh multi-token chunk first attaches any cached
        full-block prefix and only its uncached suffix is prefilled."""
        bus = self._ebus
        if not bus.enabled:
            return self._put_impl(batch_uids, batch_tokens)
        # the span carries the uid list: the request-track async events
        # join to these engine steps by uid (trace_drill's chain check)
        with bus.span("engine", "put", args={
                "uids": [int(u) for u in batch_uids],
                "tokens": int(sum(np.atleast_1d(np.asarray(t)).size
                                  for t in batch_tokens))}):
            return self._put_impl(batch_uids, batch_tokens)

    def _put_impl(self, batch_uids: Sequence[int],
                  batch_tokens: Sequence[np.ndarray]
                  ) -> Dict[int, np.ndarray]:
        assert len(batch_uids) == len(batch_tokens)
        t_put = time.perf_counter()
        self.timing = {}        # never report a previous put's numbers
        chunks = [np.atleast_1d(np.asarray(t)) for t in batch_tokens]
        if self._moe_ep:
            from deepspeed_tpu.resilience.faults import get_injector

            inj = get_injector()
            if inj:
                # before any sequence/prefix state mutates (see decode site)
                inj.on_moe_dispatch(
                    "prefill" if any(len(c) > 1 for c in chunks)
                    else "decode")
        if self.prefix_cache is not None \
                and any(len(c) > 1 and u not in self.state.sequences
                        for u, c in zip(batch_uids, chunks)) \
                and self.state.can_schedule_batch(
                    batch_uids, [len(c) for c in chunks]):
            # auto-attach only when the batch is schedulable COLD: put()
            # must stay side-effect-free when it raises CapacityError (a
            # rejected fresh uid must leave no sequence state behind), and
            # attaching strictly reduces demand, so a cold pass guarantees
            # every later capacity check in this call passes too. A batch
            # that fits only BECAUSE of the cache can prefix_attach()
            # explicitly first (the batcher does, at admission).
            trimmed = []
            for uid, c in zip(batch_uids, chunks):
                n = (self.prefix_attach(uid, c)
                     if len(c) > 1 and uid not in self.state.sequences
                     else 0)
                trimmed.append(c[n:] if n else c)
            chunks = trimmed
        if chunks and all(len(c) > 1 for c in chunks) \
                and max(len(c) for c in chunks) <= self.module.PREFILL_MAX \
                and all(self._fresh(uid) for uid in batch_uids):
            return self._prefill_whole(batch_uids, chunks)
        # chunked prefill (FastGen scheduling behavior): prompts longer
        # than one atom are fed in MAX_ATOM slices over internal steps.
        # JOINT capacity is checked for the WHOLE batch of prompts first
        # — a mid-prompt failure would otherwise leave sequences
        # half-prefilled with the pool partially consumed.
        cap = self.module.MAX_ATOM
        if any(len(c) > cap for c in chunks) and \
                not self.state.can_schedule_batch(
                    batch_uids, [len(c) for c in chunks]):
            raise CapacityError(batch_uids, [len(c) for c in chunks],
                                "joint chunked prefill")
        while any(len(c) > cap for c in chunks):
            sel = [(u, c[:cap]) for u, c in zip(batch_uids, chunks)
                   if len(c) > cap]
            self.put([u for u, _ in sel], [c for _, c in sel])
            chunks = [c[cap:] if len(c) > cap else c for c in chunks]
            # rebase the host clock: the sub-puts above ran device
            # steps to completion — without this, the final step's
            # host_ms would absorb their device+fetch time
            t_put = time.perf_counter()
        if not self.state.can_schedule_batch(batch_uids,
                                             [len(c) for c in chunks]):
            raise CapacityError(batch_uids, [len(c) for c in chunks])
        descs = [self.state.schedule(uid, len(toks))
                 for uid, toks in zip(batch_uids, chunks)]

        Bs = self.state.max_sequences

        # token-packed ragged batch (ragged_wrapper.py/atom_builder
        # parity): one row of the scheduled tokens in two regions —
        # decode steps as 1-token atoms, every longer chunk as ONE
        # whole-chunk atom (its KV blocks are DMA'd once; its own tokens
        # attend from VMEM so the step's appends hoist out of the layer
        # scan). Region sizes and the atom width are bucketed to powers
        # of two so the jit cache stays O(log^2) entries. Layout shared
        # with the spec-verify path via _pack_atoms.
        tok_ids, tok_slot, tok_pos, valid, starts, dr, tile, no_past = \
            self._pack_atoms(descs, chunks)
        gather_idx = np.zeros((Bs,), np.int32)
        for i, c in enumerate(chunks):       # chunk end → next-token
            gather_idx[i] = starts[i] + len(c) - 1
        fused = None
        if self._promote_q or self._pause_q:
            fused = self._fence_promotes()  # promote-completion fence
        t_host = time.perf_counter()
        with jax.sharding.set_mesh(self.mesh):
            if fused is None:
                logits, self.cache = self._step_packed(
                    self.params, jnp.asarray(tok_ids), self.cache,
                    jnp.asarray(self._block_tables()),
                    jnp.asarray(tok_slot), jnp.asarray(tok_pos),
                    jnp.asarray(valid), jnp.asarray(gather_idx), dr,
                    tile, no_past)
            else:
                recs, failed, idx, kp, vp, sp = fused
                try:
                    logits, self.cache = self._get_step_packed_fused()(
                        self.params, jnp.asarray(tok_ids), self.cache,
                        jnp.asarray(idx), jnp.asarray(kp),
                        jnp.asarray(vp), self._psp(sp),
                        jnp.asarray(self._block_tables()),
                        jnp.asarray(tok_slot), jnp.asarray(tok_pos),
                        jnp.asarray(valid), jnp.asarray(gather_idx),
                        dr, tile, no_past)
                except BaseException:
                    self.prefix_cache.cancel_promotes(recs)
                    raise
                self._finish_fused_promotes(recs, failed)
            t_disp = time.perf_counter()
            out = np.asarray(logits)
        t_fetch = time.perf_counter()
        # host scheduling vs dispatch vs device+transfer accounting:
        # host_ms is pure python/numpy batch building, dispatch_ms is
        # the async jit call (argument transfer + enqueue), fetch_ms
        # blocks on the device step + the logits D2H
        self.timing = {
            "host_ms": (t_host - t_put) * 1e3,
            "dispatch_ms": (t_disp - t_host) * 1e3,
            "fetch_ms": (t_fetch - t_disp) * 1e3,
        }
        if self._obs is not None:
            self._obs["put_host_ms"].observe(self.timing["host_ms"])
            self._obs["put_fetch_ms"].observe(self.timing["fetch_ms"])
            self._obs["tokens"].inc(float(sum(len(c) for c in chunks)))
        results: Dict[int, np.ndarray] = {}
        for i, (d, c) in enumerate(zip(descs, chunks)):
            results[d.uid] = out[i]
            self._pos[d.slot] = d.seen_tokens + len(c)
            self._commit(d.uid, c)
        return results
