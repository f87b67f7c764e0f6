"""The training engine.

Parity target: ``deepspeed/runtime/engine.py`` ``DeepSpeedEngine`` (:235) — the object
returned by ``initialize()`` that owns distributed setup, precision, ZeRO partitioning,
optimizer, data loader, LR schedule, checkpointing and logging, with the imperative
``forward() / backward() / step()`` training UX (:2675, :3066, :3241).

TPU-native design (NOT a port of the hook/stream machinery):

* **ZeRO = sharding layouts.** Stage 1/2/3 are expressed as ``NamedSharding`` choices
  for optimizer state / gradients / parameters over the ``fsdp`` mesh axis
  (``parallel/sharding.py``). XLA SPMD inserts and overlaps the all-gathers and
  reduce-scatters that ``stage_1_and_2.py``/``stage3.py`` orchestrate manually with
  grad hooks, IPG buckets and CUDA streams. There is no prefetch coordinator because
  the XLA latency-hiding scheduler plays that role over the scanned-layer structure.
* **forward/backward/step over jit.** JAX cannot split forward from backward, so
  ``forward`` runs a jitted ``value_and_grad`` and caches the micro-batch grads;
  ``backward`` folds them into the (sharded) accumulation buffer; ``step`` applies the
  optax update at the gradient-accumulation boundary. Semantics match the reference
  (loss scaling, clipping, GA boundary, overflow skip) with identical call patterns.
* **Precision.** Params are fp32 master weights (``bf16_optimizer.py:37`` parity);
  compute is bf16 by default; fp16 mode adds ``DynamicLossScaler``-equivalent state
  (``runtime/fp16/loss_scaler.py:187``) folded into the jitted step.
* **The weights' working copy.** As the reference's ``BF16_Optimizer`` keeps bf16
  model weights beside the fp32 masters and refreshes them at the end of ``step()``,
  the plain fused step program (``ds_train_step``) takes and returns, as donated
  state beside ``engine.params``, the copy the model names (``model.working_copy``:
  every leaf its forward would cast to the compute dtype, cast): the forward reads
  it and casts nothing, and the optimizer writes the next one as one more output of
  each leaf's update, so a step reads the masters once. The copy is derived state:
  made with the weights in the engine's init program, never in a checkpoint,
  dropped by whatever else writes ``engine.params`` (the property's setter: the
  imperative ``step()``, the offload path, a checkpoint load, a compression pass)
  and made again at the next fused step. The imperative path and the offload, 1-bit
  and ZeRO++ step programs cast in the step; with fp32 compute there is no copy.
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.config import DeepSpeedTpuConfig
from deepspeed_tpu.models.spec import num_params
from deepspeed_tpu.observability import steplog
from deepspeed_tpu.ops import lowerings
from deepspeed_tpu.parallel import Topology, build_mesh
from deepspeed_tpu.parallel import sharding as shd
from deepspeed_tpu.runtime.dataloader import DeepSpeedTpuDataLoader
from deepspeed_tpu.runtime.lr_schedules import LRSchedulerShim, build_schedule
from deepspeed_tpu.runtime.optimizers import build_optimizer
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import ThroughputTimer


def _leaf(tree, path):
    for name in path:
        tree = tree[name]
    return tree


def _with_leaf(tree, path, value):
    """``tree`` (nested dicts) with the leaf at ``path`` replaced."""
    if not path:
        return value
    return {**tree, path[0]: _with_leaf(tree[path[0]], path[1:], value)}


def _overlaid(tree, over):
    """``tree`` (nested dicts) with the leaves ``over`` holds in their
    places."""
    if not isinstance(over, dict):
        return over
    return {**tree, **{k: _overlaid(tree[k], v) for k, v in over.items()}}


def _under(tree, over):
    """The part of ``tree`` (nested dicts) at the places ``over`` has
    leaves."""
    if not isinstance(over, dict):
        return tree
    return {k: _under(tree[k], v) for k, v in over.items()}


class DeepSpeedTpuEngine:
    """See module docstring. Public surface mirrors ``DeepSpeedEngine``."""

    def __init__(self, model, config: DeepSpeedTpuConfig, optimizer=None,
                 training_data=None, lr_scheduler=None, topology: Optional[Topology] = None,
                 collate_fn: Optional[Callable] = None, init_rng: Optional[jax.Array] = None):
        # the build in three set-up spans (``steplog.setup()``), so that a
        # slow start says which part of it was slow
        from deepspeed_tpu.observability.events import get_bus

        bus = get_bus()
        with steplog.span(bus, "setup", "engine.plan"):
            init_rng, schedule_fn = self._init_plan(
                model, config, optimizer, lr_scheduler, topology, init_rng)
        with steplog.span(bus, "setup", "engine.state"):
            self._init_state(config, init_rng, schedule_fn)
        with steplog.span(bus, "setup", "engine.rest"):
            self._init_rest(config, training_data, collate_fn)

    @property
    def params(self):
        """The fp32 master weights: what a checkpoint holds, what serving and
        every step path read."""
        return self._params

    @params.setter
    def params(self, value) -> None:
        # whoever writes the masters from outside the plain fused step (the
        # imperative ``step()``, the offload path, a checkpoint load, a
        # compression pass) leaves the working copy stale: it is dropped
        # here and made again at the next fused step
        self._params = value
        self._work = None

    def _init_plan(self, model, config, optimizer, lr_scheduler, topology,
                   init_rng):
        """``ds.setup.engine.plan``: mesh, schedules, optimizer, sharding
        layouts and the jitted functions; nothing is on the device yet."""
        self.config = config
        if topology is None and config.mesh.auto:
            # mesh: "auto" — adopt the measured-best (or cost-model-ranked)
            # shape for this model / world size / device kind
            from deepspeed_tpu.parallel.cost_model import ModelProfile

            mb = config.train_micro_batch_size_per_gpu
            topology = build_mesh(
                config.mesh, model_profile=ModelProfile.from_model(model),
                winner_cache=config.autotuning.winner_cache or None,
                zero_stage=int(config.zero_optimization.stage),
                micro_batch=mb if isinstance(mb, int) else 1)
        self.topology = topology or build_mesh(config.mesh)
        self.mesh = self.topology.mesh
        if config.elasticity.enabled:
            self._apply_elastic_batch(config)
        config.resolve_batch_sizes(self.topology.dp_world_size)

        from deepspeed_tpu.runtime.pipe import maybe_wrap_pipeline

        model = maybe_wrap_pipeline(model, config, self.topology)
        self.module = model

        self.zero_stage = int(config.zero_optimization.stage)
        self.fp16_enabled = bool(config.fp16.enabled)
        self.bf16_enabled = bool(config.bf16.enabled) and not self.fp16_enabled

        # MiCS (mics_config.py parity): params sharded over a SUB-group with
        # replication across groups. On a named mesh that IS the layout
        # {"fsdp": mics_shard_size, "dp": world/mics_shard_size} — validate
        # the mesh agrees rather than silently ignoring the key.
        mics = int(config.zero_optimization.mics_shard_size)
        if mics > 0:
            fsdp = self.topology.axis_sizes.get("fsdp", 1)
            if fsdp != mics:
                raise ValueError(
                    f"mics_shard_size={mics} but the mesh fsdp axis is {fsdp}"
                    " — MiCS on a named mesh IS {'fsdp': mics_shard_size, "
                    "'dp': world // mics_shard_size}; set the mesh to match")
            if (config.zero_optimization.mics_hierarchical_params_gather
                    and not config.zero_optimization.zero_pp.hpz):
                raise ValueError(
                    "mics_hierarchical_params_gather needs "
                    "zero_hpz_partition_size > 1 (the hierarchical gather is "
                    "the hpZ secondary partition)")

        # ---- schedules & optimizer ------------------------------------
        self.lr_scheduler = lr_scheduler
        schedule_fn = None
        if lr_scheduler is None and config.scheduler is not None:
            schedule_fn = build_schedule(config.scheduler.type, config.scheduler.params)
            self.lr_scheduler = LRSchedulerShim(schedule_fn, engine=self)
        elif callable(lr_scheduler):
            schedule_fn = lr_scheduler
            self.lr_scheduler = LRSchedulerShim(schedule_fn, engine=self)

        from deepspeed_tpu.runtime import onebit

        self.client_optimizer = optimizer
        opt_cfg = config.optimizer
        self._onebit_name = None
        if (optimizer is None and opt_cfg is not None
                and onebit.is_onebit(opt_cfg.type)):
            # 1-bit optimizers bypass optax: compression + error feedback live
            # in an explicit-collective region (runtime/onebit.py)
            self._onebit_name = opt_cfg.type
            self._schedule_fn = schedule_fn
            tx = None
        elif optimizer is not None and isinstance(optimizer, optax.GradientTransformation):
            if opt_cfg is not None and onebit.is_onebit(opt_cfg.type):
                raise ValueError(
                    f"config requests the 1-bit optimizer '{opt_cfg.type}' but "
                    "a client optax optimizer was passed — dropping to a dense "
                    "optimizer would silently lose compression; remove one")
            tx = optimizer
            if config.gradient_clipping > 0:
                tx = optax.chain(optax.clip_by_global_norm(config.gradient_clipping), tx)
        else:
            name = opt_cfg.type if opt_cfg else "adamw"
            params_cfg = dict(opt_cfg.params) if opt_cfg else {}
            tx = build_optimizer(name, params_cfg, lr_schedule=schedule_fn,
                                 gradient_clipping=config.gradient_clipping)
        self.tx = tx
        self.optimizer = self  # reference returns engine.optimizer; state lives here

        # ---- sharding layouts -----------------------------------------
        if init_rng is None:
            init_rng = jax.random.key(config.seed)
        if hasattr(model, "check_topology"):
            model.check_topology(self.topology.axis_sizes)
        model_specs = model.param_specs() if hasattr(model, "param_specs") else None
        param_shapes = jax.eval_shape(model.init, init_rng)
        self._param_shapes = param_shapes
        if model_specs is None:
            model_specs = jax.tree_util.tree_map(lambda _: None, param_shapes)
        zcfg = config.zero_optimization
        self.param_spec_tree = shd.zero_param_specs(
            param_shapes, model_specs, self.topology, self.zero_stage,
            persistence_threshold=zcfg.param_persistence_threshold)
        self.grad_spec_tree = shd.grad_specs(self.param_spec_tree, param_shapes,
                                             self.topology, self.zero_stage)
        self.param_sharding = shd.named(self.topology, self.param_spec_tree)
        self.grad_sharding = shd.named(self.topology, self.grad_spec_tree)

        if self.tx is not None:
            opt_shapes = jax.eval_shape(self.tx.init, param_shapes)
            opt_param_specs = shd.opt_state_specs(param_shapes, self.param_spec_tree,
                                                  self.topology, self.zero_stage)
            opt_spec_tree = optax.tree_map_params(
                self.tx, lambda _leaf, spec: spec, opt_shapes, opt_param_specs,
                transform_non_params=lambda _leaf: P())
            self.opt_sharding = shd.named(self.topology, opt_spec_tree)
        self._replicated = NamedSharding(self.mesh, P())

        # ---- compiled functions ---------------------------------------
        self._build_jit_fns()
        return init_rng, schedule_fn

    def _init_state(self, config, init_rng, schedule_fn):
        """``ds.setup.engine.state``: the weights, the optimizer's state and
        the scaler appear on the device. The span ends when they are
        dispatched; nothing here waits for the device."""
        # ---- materialize state ----------------------------------------
        zcfg = config.zero_optimization
        self._offload = None
        off = zcfg.offload_optimizer
        if zcfg.zenflow is not None and (off is None
                                         or off.device not in ("cpu", "nvme")):
            raise ValueError(
                "zero_optimization.zenflow requires offload_optimizer "
                "(device cpu|nvme) — there is no host step to overlap")
        with jax.sharding.set_mesh(self.mesh):
            self._params, self._work = self._init_fn(init_rng)
            if off is not None and off.device in ("cpu", "nvme"):
                self.opt_state = {}
                self._configure_offload_optimizer(off, schedule_fn)
            else:
                self.opt_state = self._opt_init_fn(self.params)
        self._refresh_hpz()
        self.scaler_state = self._init_scaler_state()
        self._grad_acc = None
        self._pending = None  # (loss, grads) from the last forward
        self._grad_acc_count = 0

    def _init_rest(self, config, training_data, collate_fn):
        """``ds.setup.engine.rest``: counters, monitors, observability, data
        efficiency, resilience, the data loader and the start-up checks."""
        # ---- bookkeeping ----------------------------------------------
        self.global_steps = 0
        self.global_samples = 0
        self.micro_steps = 0
        self.skipped_steps = 0
        self._last_loss = None
        self._last_gnorm = None
        self._world_params = num_params(self._param_shapes)
        self.tput_timer = ThroughputTimer(
            batch_size=int(self.config.train_batch_size),
            steps_per_output=config.steps_per_print,
            monitor_memory=config.observability.monitor_memory)
        self.monitor = None
        if any(m.enabled for m in (config.monitor_config.tensorboard,
                                   config.monitor_config.wandb,
                                   config.monitor_config.csv_monitor)):
            from deepspeed_tpu.monitor import MonitorMaster

            self.monitor = MonitorMaster(config.monitor_config)
        self._configure_observability(config)

        # ---- data efficiency (curriculum sampling/truncation + random-LTD) --
        de = config.data_efficiency
        self._curriculum = None
        self._ltd_cfg = None
        if de.enabled and de.data_sampling.enabled \
                and de.data_sampling.curriculum_learning.enabled:
            from deepspeed_tpu.runtime.data_pipeline import CurriculumScheduler

            self._curriculum = CurriculumScheduler(
                de.data_sampling.curriculum_learning.model_dump())
            # every distinct difficulty value is a distinct jit shape: a
            # fine-grained schedule would silently thrash the compile cache
            n_buckets = (self._curriculum.max_difficulty
                         - self._curriculum.min_difficulty) \
                // max(self._curriculum.difficulty_step, 1) + 1
            if n_buckets > 64:
                raise ValueError(
                    f"curriculum_learning would create {n_buckets} distinct "
                    "sequence-length buckets (each one a fresh XLA compile); "
                    "raise schedule_config.difficulty_step so "
                    "(max_difficulty - min_difficulty) / difficulty_step "
                    "<= 64")
        if de.enabled and de.data_routing.enabled \
                and de.data_routing.random_ltd.enabled:
            self._ltd_cfg = de.data_routing.random_ltd
            if not hasattr(self.module, "set_random_ltd"):
                raise ValueError("random_ltd requires a model with "
                                 "set_random_ltd (TransformerLM family)")
            self._update_random_ltd()
        self._pld = None
        self._pld_tiers = 0
        if config.progressive_layer_drop.enabled:
            if self._ltd_cfg is not None:
                raise ValueError("progressive_layer_drop and random_ltd both "
                                 "rewrite the layer loop; enable one")
            from deepspeed_tpu.runtime.progressive_layer_drop import \
                ProgressiveLayerDrop

            self._pld = ProgressiveLayerDrop(
                theta=config.progressive_layer_drop.theta,
                gamma=config.progressive_layer_drop.gamma)
            self._pld_tiers = int(config.progressive_layer_drop
                                  .compiled_tiers)
            if self._pld_tiers > 0:
                if hasattr(self.module, "_one_pass_only"):
                    self.module._one_pass_only("progressive layer drop")
                if getattr(getattr(self.module, "cfg", None),
                           "patterned", False):
                    # the static-depth slice would silently no-op under the
                    # scan over periods while still paying a jit rebuild per
                    # tier change
                    raise NotImplementedError(
                        "progressive_layer_drop.compiled_tiers does not "
                        "support models whose layers are of more than one "
                        "attention kind (attn_pattern)")
                wd = float((config.optimizer.params or {}).get(
                    "weight_decay", 0.0)) if config.optimizer else 0.0
                if wd > 0.0:
                    # decoupled decay updates EVERY param each step; layers
                    # sliced out of the compiled program stop getting grads
                    # but would keep decaying toward zero — silent damage
                    # to the full-depth model
                    raise ValueError(
                        "progressive_layer_drop.compiled_tiers requires "
                        "weight_decay=0: the statically-dropped tail "
                        "layers receive no gradients but decoupled decay "
                        "would keep shrinking them every step")

        # ---- resilience (guard, retries, coordination, heartbeat) -------
        rcfg = config.resilience
        self._guard = None
        self._coordinator = None
        self._heartbeat = None
        self._watchdog = None
        self._ckpt_managers: Dict[str, Any] = {}
        self._primary_mgr = None
        self._resilience_report_dir = os.environ.get("DSTPU_CHECKPOINT_DIR")
        if rcfg.enabled:
            from deepspeed_tpu import comm as comm_mod
            from deepspeed_tpu.resilience import (FaultInjector, RetryPolicy,
                                                  StepGuard, set_injector)

            if rcfg.faults:
                set_injector(FaultInjector(rcfg.faults))
            self._guard = StepGuard(
                self, max_consecutive_bad_steps=rcfg.max_consecutive_bad_steps)
            comm_mod.set_retry_policy(RetryPolicy(**rcfg.retry.model_dump()))
            if rcfg.coordination.enabled:
                from deepspeed_tpu.resilience.coordinator import \
                    ResilienceCoordinator

                self._coordinator = ResilienceCoordinator(
                    interval_steps=rcfg.coordination.interval_steps)
            if rcfg.heartbeat.enabled:
                from deepspeed_tpu.resilience.heartbeat import (HangWatchdog,
                                                                Heartbeat)

                if rcfg.heartbeat.on_hang == "abort" \
                        and self._coordinator is None:
                    # the default escalation routes through the coordinated
                    # decide; without it the watchdog would detect and then
                    # do nothing — the exact wedge it exists to prevent
                    raise ValueError(
                        "resilience.heartbeat.on_hang='abort' requires "
                        "resilience.coordination.enabled; use on_hang="
                        "'exit' (hard wedges) or 'report' instead")
                hb_dir = rcfg.heartbeat.dir
                if hb_dir is None and self._resilience_report_dir:
                    hb_dir = os.path.join(self._resilience_report_dir,
                                          "heartbeats")
                if hb_dir is None:
                    # liveness still works per-process, but peers can only
                    # be classified off a SHARED directory — say so loudly
                    # instead of silently littering the cwd
                    import tempfile

                    hb_dir = os.path.join(
                        tempfile.gettempdir(),
                        f"dstpu_heartbeats_{os.getpid()}")
                    logger.warning(
                        "resilience.heartbeat.dir is unset and no checkpoint "
                        f"dir is known; writing heartbeats to {hb_dir} — "
                        "peer straggler classification needs a shared "
                        "directory (set heartbeat.dir or "
                        "DSTPU_CHECKPOINT_DIR)")
                self._heartbeat = Heartbeat(
                    hb_dir, interval_s=rcfg.heartbeat.interval_s).start()
                self._watchdog = HangWatchdog(
                    self._heartbeat, deadline_s=rcfg.heartbeat.deadline_s,
                    collective_deadline_s=rcfg.heartbeat.collective_deadline_s,
                    poll_s=rcfg.heartbeat.poll_s,
                    coordinator=self._coordinator,
                    on_hang=rcfg.heartbeat.on_hang,
                    exit_code=rcfg.heartbeat.exit_code).start()
            if self._resilience_report_dir:
                # launched under the elastic agent: arm the preemption
                # handler against the agent's checkpoint dir right away
                self._resilience_manager(self._resilience_report_dir)

        self.training_dataloader = None
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(training_data,
                                                         collate_fn=collate_fn)
        self._first_batch_checked = not (config.sanity_checks
                                         and config.sanity_check_batches)
        if config.sanity_checks:
            from deepspeed_tpu.runtime.sanity import run_startup_checks

            run_startup_checks(self)
        log_dist(f"engine ready: {self._world_params/1e6:.1f}M params, "
                 f"zero_stage={self.zero_stage}, mesh={self.topology}, "
                 f"batch={config.train_batch_size} (micro={config.train_micro_batch_size_per_gpu}"
                 f" x ga={config.gradient_accumulation_steps} x dp={self.topology.dp_world_size})")

    def _apply_elastic_batch(self, config) -> None:
        """Elasticity: derive (batch, micro, ga) from the elastic config for
        THIS world size — the global batch stays constant across every
        admissible chip count (reference elasticity/config.py contract)."""
        from deepspeed_tpu.elasticity import compute_elastic_config

        ecfg = config.elasticity
        explicit = [k for k, v in (
            ("train_batch_size", config.train_batch_size),
            ("train_micro_batch_size_per_gpu",
             config.train_micro_batch_size_per_gpu),
            ("gradient_accumulation_steps", config.gradient_accumulation_steps),
        ) if v not in (None, "auto")]
        if explicit and not ecfg.ignore_non_elastic_batch_info:
            raise ValueError(
                f"elasticity.enabled with explicit {explicit}: set "
                "ignore_non_elastic_batch_info=true to let the elastic config "
                "own the batch triple (reference raises the same)")
        dp = self.topology.dp_world_size
        batch, _valid, micro_map = compute_elastic_config(
            ecfg.model_dump(), target_chips=dp)
        micro = micro_map[dp]
        # the elastic agent ships its decision via env; a drift between the
        # agent's elastic config and the trainer's would silently void the
        # constant-global-batch guarantee — verify instead of trusting
        agent_micro = os.environ.get("DSTPU_ELASTIC_MICRO")
        if agent_micro is not None and int(agent_micro) != micro:
            raise ValueError(
                f"elastic agent chose micro_batch={agent_micro} but this "
                f"trainer's elasticity config derives {micro} at dp={dp} — "
                "agent and trainer elastic configs have drifted")
        config.train_batch_size = batch
        config.train_micro_batch_size_per_gpu = micro
        config.gradient_accumulation_steps = batch // (micro * dp)
        log_dist(f"elastic batch: global={batch} micro={micro} "
                 f"ga={config.gradient_accumulation_steps} at dp={dp}")

    # ------------------------------------------------------------------
    # compiled-function construction
    # ------------------------------------------------------------------
    def _build_jit_fns(self) -> None:
        model, tx = self.module, self.tx
        fp16 = self.fp16_enabled

        from deepspeed_tpu.parallel import zeropp
        from deepspeed_tpu.runtime import onebit

        self._onebit = None
        if self._onebit_name is not None:
            off = self.config.zero_optimization.offload_optimizer
            if hasattr(model, "num_stages"):
                raise ValueError("1-bit optimizers do not compose with "
                                 "pipeline parallelism")
            if off is not None and off.device in ("cpu", "nvme"):
                raise ValueError("1-bit optimizers do not compose with "
                                 "offload_optimizer")
            if zeropp.enabled(self.config.zero_optimization):
                raise ValueError("1-bit optimizers and ZeRO++ both own the "
                                 "gradient-reduce region; enable one of them")
            if self.fp16_enabled:
                raise NotImplementedError(
                    "1-bit optimizers run bf16/fp32 here; fp16 loss scaling "
                    "is not folded into the compressed step")
            if self.config.gradient_clipping > 0:
                logger.warning(
                    "gradient_clipping is not applied in the 1-bit compressed "
                    "phase (error feedback makes clipped-and-compressed "
                    "gradients biased); clipping is skipped")
            self._onebit = onebit.build_plan(
                model, self.topology, self.param_spec_tree, self._param_shapes,
                self._onebit_name, dict(self.config.optimizer.params),
                self.zero_stage, schedule_fn=getattr(self, "_schedule_fn", None))
            # grads carry a leading device axis in the 1-bit layout
            self.grad_sharding = self._onebit.grad_sharding
            self.opt_sharding = self._onebit.state_sharding

        self._zpp = None
        if zeropp.enabled(self.config.zero_optimization):
            if hasattr(model, "num_stages"):  # pipeline-wrapped
                raise ValueError("ZeRO++ (qwZ/qgZ/hpZ) does not compose with "
                                 "pipeline parallelism yet")
            off = self.config.zero_optimization.offload_optimizer
            if off is not None and off.device in ("cpu", "nvme"):
                raise ValueError(
                    "ZeRO++ (qwZ/qgZ/hpZ) does not compose with "
                    "offload_optimizer: the fused offload step bypasses the "
                    "explicit-collective region")
            self._zpp = zeropp.build_plan(
                model, self.topology, self.param_spec_tree,
                self.grad_spec_tree, self.config.zero_optimization)
        self._hpz_secondary = None

        def loss_of(params, batch, scale):
            loss = model.loss_fn(params, batch)
            return loss * scale, loss

        def fwd_bwd(params, batch, scale):
            if hasattr(model, "loss_and_grad"):
                # hand-scheduled backward (1F1B pipeline): the model computes
                # grads itself — autodiff of its loss_fn would reimpose the
                # GPipe all-forwards-then-all-backwards order
                return model.loss_and_grad(params, batch, scale)
            (_, loss), grads = jax.value_and_grad(loss_of, has_aux=True)(
                params, batch, scale)
            return loss, grads

        if self._onebit is not None:
            ob = self._onebit

            def fwd_bwd_ob(params, batch, scale):
                grads, loss = ob.grads_fn(params, batch, scale, 1)
                return loss, grads

            self._fwd_bwd = jax.jit(
                fwd_bwd_ob,
                out_shardings=(self._replicated, self.grad_sharding))
            self._onebit_apply = jax.jit(
                ob.apply_fn, donate_argnums=(0, 1, 2),
                out_shardings=(self.param_sharding, self.opt_sharding, None))
        elif self._zpp is not None:
            zpp = self._zpp

            def fwd_bwd_zpp(params_in, batch, scale):
                grads, loss = zpp.grads_fn(params_in, batch, scale, 1)
                return loss, grads

            self._fwd_bwd = jax.jit(
                fwd_bwd_zpp,
                out_shardings=(self._replicated, self.grad_sharding))
        else:
            self._fwd_bwd = jax.jit(
                fwd_bwd,
                in_shardings=(self.param_sharding, None, self._replicated),
                out_shardings=(self._replicated, self.grad_sharding))

        def accum(acc, grads):
            return jax.tree_util.tree_map(jnp.add, acc, grads)

        self._accum = jax.jit(accum, donate_argnums=(0,),
                              out_shardings=self.grad_sharding)

        ga_build = float(self.config.gradient_accumulation_steps)

        def apply_step(params, opt_state, grads, scaler, *, ga=ga_build):
            """Unscale → clip/step → (fp16) overflow-skip + scaler update.

            Shared verbatim between the imperative ``step()`` jit and the fused
            single-jit train step so the perf path and the parity path keep
            identical semantics (loss scaling, skip, scaler window). ``ga`` is
            keyword-only so fused callers pass their own accumulation factor
            rather than silently inheriting the build-time value."""
            with jax.named_scope("optimizer"):
                scale = scaler["scale"]
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32) / (scale * ga), grads)
                gnorm = optax.global_norm(grads)
                if fp16:
                    finite = jnp.isfinite(gnorm)
                    safe = jax.tree_util.tree_map(
                        lambda g: jnp.where(finite, g, jnp.zeros_like(g)), grads)
                    updates, new_opt = tx.update(safe, opt_state, params)
                    new_params = optax.apply_updates(params, updates)
                    new_params = jax.tree_util.tree_map(
                        lambda n, o: jnp.where(finite, n, o), new_params, params)
                    new_opt = jax.tree_util.tree_map(
                        lambda n, o: jnp.where(finite, n, o), new_opt, opt_state)
                    new_scaler = self._scaler_update(scaler, finite)
                    return new_params, new_opt, new_scaler, gnorm, ~finite
                updates, new_opt = tx.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
                return new_params, new_opt, scaler, gnorm, jnp.zeros((), bool)

        # leaves the model moves by a rule of its own after each step
        # (``model.rule_leaves`` / ``rule_updates``: a sigmoid router's
        # selection bias): no gradient, no optimizer update, no weight decay
        self._rule_leaves = tuple(getattr(model, "rule_leaves", tuple)())
        # the weights' working copy (``model.working_copy``: the leaves the
        # forward would cast to the compute dtype, cast) that the plain
        # fused step program carries beside the masters; ``{}`` where the
        # model names none, the compute dtype is the masters', or the engine
        # steps through a program that casts in the step (offload, 1-bit,
        # ZeRO++)
        off = self.config.zero_optimization.offload_optimizer
        carried = (tx is not None and self._zpp is None
                   and not (off is not None and off.device in ("cpu", "nvme")))
        self._copy_of = (getattr(model, "working_copy", None) if carried
                         else None) or (lambda params: {})
        work_shapes = jax.eval_shape(self._copy_of, self._param_shapes)
        self.work_sharding = _under(self.param_sharding, work_shapes)
        self._work_bytes = sum(
            x.size * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(work_shapes))

        def init_state(rng):
            params = model.init(rng)
            return params, self._copy_of(params)

        self._init_fn = jax.jit(
            init_state, out_shardings=(self.param_sharding,
                                       self.work_sharding))
        # the copy again, after something else wrote the masters
        self._work_fn = jax.jit(self._copy_of,
                                out_shardings=self.work_sharding)
        if tx is not None:
            self._apply_body = apply_step
            self._apply = jax.jit(
                apply_step, donate_argnums=(0, 1, 2),
                out_shardings=(self.param_sharding, self.opt_sharding, None, None, None))
            self._opt_init_fn = jax.jit(tx.init, out_shardings=self.opt_sharding)
        else:
            self._opt_init_fn = jax.jit(self._onebit.init_state,
                                        out_shardings=self.opt_sharding)
        self._fused_step_cache: Dict[Any, Callable] = {}

    # ---- fp16 dynamic loss scaler (loss_scaler.py:187 parity) ----------
    def _init_scaler_state(self) -> Dict[str, jax.Array]:
        c = self.config.fp16
        init_scale = 1.0
        if self.fp16_enabled:
            init_scale = (c.loss_scale if c.loss_scale > 0
                          else 2.0 ** c.initial_scale_power)
        # placed on the mesh like the step jits' outputs: an unplaced scalar
        # has a different input type than the mesh-typed one the first step
        # returns, which recompiled every step program on its second call
        return jax.device_put(
            {"scale": np.float32(init_scale), "good_steps": np.int32(0)},
            NamedSharding(self.mesh, P()))

    def _scaler_update(self, scaler, finite):
        c = self.config.fp16
        static = c.loss_scale > 0
        if static:
            return scaler
        good = jnp.where(finite, scaler["good_steps"] + 1, 0)
        grow = good >= c.loss_scale_window
        scale = scaler["scale"]
        scale = jnp.where(finite,
                          jnp.where(grow, scale * 2.0, scale),
                          jnp.maximum(scale / 2.0, c.min_loss_scale))
        good = jnp.where(grow, 0, good)
        return {"scale": scale, "good_steps": good}

    # ------------------------------------------------------------------
    # data
    # ------------------------------------------------------------------
    def deepspeed_io(self, dataset, batch_size: Optional[int] = None,
                     collate_fn: Optional[Callable] = None, **kw) -> DeepSpeedTpuDataLoader:
        """Build the engine data loader (reference ``deepspeed_io`` engine.py:2486).

        Yields *global* micro-batches (micro_batch_size × dp_world_size examples)."""
        gbs = batch_size or (int(self.config.train_micro_batch_size_per_gpu)
                             * self.topology.dp_world_size)
        return DeepSpeedTpuDataLoader(dataset, gbs, collate_fn=collate_fn,
                                      seed=self.config.seed, **kw)

    def _update_random_ltd(self) -> None:
        """Advance the random-LTD kept-token schedule (data_routing parity):
        keep grows from min_value by step_size every interval steps, clamped at
        max_value — once at the ceiling the bucket never changes again. A
        bucket change rebuilds the jitted programs (one recompile per
        bucket)."""
        c = self._ltd_cfg
        ceil = c.max_value or getattr(self.module.cfg, "max_seq_len", 1 << 30)
        keep = min(ceil, c.min_value
                   + c.step_size * (self.global_steps // max(c.interval, 1)))
        if keep != self.module._ltd_keep:
            self.module.set_random_ltd(
                keep, (c.random_ltd_layer_start, c.random_ltd_layer_end))
            if hasattr(self, "_fused_step_cache"):
                self._fused_step_cache.clear()
                self._build_jit_fns()
                self._refresh_hpz()  # _build_jit_fns resets the hpZ secondary

    def curriculum_difficulty(self) -> Optional[int]:
        if self._curriculum is None:
            return None
        return self._curriculum.update_difficulty(self.global_steps)

    def _apply_curriculum(self, batch):
        """Truncate sequence keys to the curriculum difficulty (the engine-side
        half of DeepSpeedDataSampler: shapes bucket by difficulty_step, so
        recompiles are bounded by the schedule's granularity)."""
        if self._curriculum is None or not isinstance(batch, dict):
            return batch
        diff = self.curriculum_difficulty()
        out = {}
        for k, v in batch.items():
            arr = np.asarray(v)
            if k == "position_ids" and arr.ndim == 3:
                arr = arr[..., :diff]       # [axes, B, T]
            elif arr.ndim >= 2 and arr.shape[1] > diff and k in (
                    "input_ids", "labels", "attention_mask", "position_ids",
                    "noised_ids", "loss_weights"):
                arr = arr[:, :diff]
            out[k] = arr
        return out

    def _inject_ltd_seed(self, batch):
        """Per-step routing inputs riding the batch (broadcast per example so
        the fused GA reshape works): the random-LTD/PLD step seed, and the
        progressive-layer-drop theta (a traced scalar — no recompiles as it
        decays). In PLD's compiled-tiers mode the theta maps to a STATIC
        depth instead (``_update_pld_depth``) and nothing rides the batch."""
        if (self._ltd_cfg is None and self._pld is None) \
                or not isinstance(batch, dict):
            return batch
        if self._pld is not None and self._pld_tiers > 0:
            self._update_pld_depth()
            return batch
        b = np.asarray(batch["input_ids"]).shape[0]
        out = {**batch, "ltd_seed": np.full((b,), self.global_steps
                                            + self.micro_steps, np.int32)}
        if self._pld is not None:
            self._pld.update_state(self.global_steps)
            out["pld_theta"] = np.full((b,), self._pld.get_theta(), np.float32)
        return out

    def _update_pld_depth(self) -> None:
        """Advance the static-depth PLD tier (compiled_tiers mode): theta's
        expected kept-layer count quantized onto the tier grid; a tier
        change rebuilds the jitted programs — one recompile per tier over
        the run, and each step then RUNS only k layers (the reference's
        wall-clock saving, expressed as compiled depth instead of
        per-step stochastic skips)."""
        from deepspeed_tpu.runtime.progressive_layer_drop import \
            active_layers

        if not hasattr(self.module, "set_pld_depth"):
            raise NotImplementedError(
                "progressive_layer_drop.compiled_tiers requires a "
                "TransformerLM module (not supported under pipeline "
                "wrapping)")
        self._pld.update_state(self.global_steps)
        k = active_layers(self._pld.get_theta(),
                          self.module.cfg.num_layers, self._pld_tiers,
                          theta_min=self._pld.theta)
        if k != self.module._pld_depth:
            self.module.set_pld_depth(k)
            if hasattr(self, "_fused_step_cache"):
                self._fused_step_cache.clear()
                self._build_jit_fns()
                self._refresh_hpz()

    def _put_batch(self, batch):
        """Host batch → device arrays laid out over (dp, fsdp) × sp."""
        bspec = shd.batch_spec(self.topology)

        def put(x, lead=()):
            x = np.asarray(x)
            dims = x.ndim - len(lead)
            spec = P(*lead, *list(bspec)[:max(dims, 0)]) if x.ndim else P()
            return jax.device_put(x, NamedSharding(self.mesh, spec))

        with self._ebus.span("train", "put_batch"):
            axes = (batch.get("position_ids")
                    if isinstance(batch, dict) else None)
            if axes is not None and np.ndim(axes) == 3:
                # positions over the rope's axes [axes, B, T]: the batch
                # is the second dimension
                batch = {**jax.tree_util.tree_map(
                    put, {k: v for k, v in batch.items()
                          if k != "position_ids"}),
                    "position_ids": put(axes, (None,))}
            else:
                batch = jax.tree_util.tree_map(put, batch)
        self._t_put = time.perf_counter()
        return batch

    # ------------------------------------------------------------------
    # train loop UX
    # ------------------------------------------------------------------
    def forward(self, batch, *args, **kwargs):
        """Compute micro-batch loss (and, functionally, its grads) — engine.py:2675."""
        self.tput_timer.start()
        if self._breakdown:
            self.wall_timers("fwd").start(synchronize=False)
        if self._ltd_cfg is not None and self._grad_acc_count == 0:
            self._update_random_ltd()  # only at accumulation boundaries
        batch = self._apply_curriculum(batch)
        batch = self._inject_ltd_seed(batch)
        if not self._first_batch_checked:
            from deepspeed_tpu.runtime.sanity import check_batch_consistency

            check_batch_consistency(batch)  # engine.py:641 broadcast check
            self._first_batch_checked = True
        batch = self._put_batch(batch)
        p_in = (self._hpz_secondary
                if self._zpp is not None and self._zpp.uses_secondary
                else self.params)
        with jax.sharding.set_mesh(self.mesh):
            loss, grads = self._fwd_bwd(p_in, batch, self.scaler_state["scale"])
        self._pending = grads
        self._last_loss = loss
        if self._breakdown:
            # record=False: the per-micro-step records list is unbounded;
            # the gauge only needs elapsed(reset=True) at the boundary
            self.wall_timers("fwd").stop(record=False, synchronize=False)
        return loss

    __call__ = forward

    def backward(self, loss=None, *args, **kwargs):
        """Fold the pending micro-batch grads into the accumulator — engine.py:3066."""
        if self._pending is None:
            raise RuntimeError("backward() called before forward()")
        if self._breakdown:
            self.wall_timers("bwd").start(synchronize=False)
        with jax.sharding.set_mesh(self.mesh):
            if self._grad_acc is None or self._grad_acc_count == 0:
                self._grad_acc = self._pending
            else:
                self._grad_acc = self._accum(self._grad_acc, self._pending)
        self._pending = None
        self._grad_acc_count += 1
        self.micro_steps += 1
        if self._breakdown:
            self.wall_timers("bwd").stop(record=False, synchronize=False)
        return loss

    def is_gradient_accumulation_boundary(self) -> bool:
        return self._grad_acc_count >= int(self.config.gradient_accumulation_steps)

    def _configure_offload_optimizer(self, off, schedule_fn) -> None:
        """ZeRO-Offload/Infinity path (engine.py:1960 CPUAdam selection parity);
        ``zero_optimization.zenflow`` turns on the asynchronous overlap step."""
        from deepspeed_tpu.offload import (HostOffloadOptimizer,
                                           ZenFlowSelectiveOptimizer)

        zf = self.config.zero_optimization.zenflow
        overlap = bool(zf is not None and zf.overlap_step)
        selective = bool(zf is not None and zf.topk_ratio > 0)
        if (overlap or selective) and self.fp16_enabled:
            raise NotImplementedError(
                "zenflow needs the overflow-skip decision at step "
                "time; it does not compose with fp16 dynamic loss scaling "
                "(use bf16)")
        p = dict(self.config.optimizer.params) if self.config.optimizer else {}
        aio = self.config.offload.aio
        common = dict(
            lr=p.get("lr", 1e-3), betas=tuple(p.get("betas", (0.9, 0.999))),
            eps=p.get("eps", 1e-8), weight_decay=p.get("weight_decay", 0.0),
            gradient_clipping=self.config.gradient_clipping,
            schedule_fn=schedule_fn,
            nvme_path=off.nvme_path if off.device == "nvme" else None,
            # offload.aio owns HOW bytes move; 0-threads falls back to the
            # autotuner (when on) or the legacy buffer_count knob
            aio_threads=(aio.threads if aio.threads > 0
                         else (0 if aio.autotune else off.buffer_count)),
            aio_chunk_mb=aio.chunk_mb,
            prefetch_depth=aio.prefetch_depth,
            aio_autotune=aio.autotune,
            aio_autotune_cache=aio.autotune_cache,
            aio_o_direct=aio.o_direct,
            upload_overlap=aio.upload_overlap)
        self._offload_unscale = jax.jit(
            lambda t, d: jax.tree_util.tree_map(lambda g: g / d, t),
            out_shardings=self.grad_sharding)
        if selective:
            self._offload = ZenFlowSelectiveOptimizer(
                self.params, topk_ratio=zf.topk_ratio,
                select_interval=zf.resolved_select_interval(),
                update_interval=zf.resolved_update_interval(),
                full_warm_up_rounds=zf.full_warm_up_rounds, **common)
        else:
            self._offload = HostOffloadOptimizer(
                self.params, overlap_step=overlap,
                state_shardings=self.grad_sharding, **common)

    def step(self, *args, **kwargs):
        """Optimizer step at the GA boundary — engine.py:3241."""
        if not self.is_gradient_accumulation_boundary():
            return
        self._rule_moves_only_here("forward / backward / step")
        # self-healing guard: fires configured faults, then skips (instead of
        # applying) a step whose loss/grads are non-finite
        if self._guard is not None and self._guard.intercept():
            return
        self._opt_t0 = time.perf_counter()
        if self._offload is not None:
            ga = float(self.config.gradient_accumulation_steps)
            denom = ga * float(self.scaler_state["scale"])  # unscale fp16 loss scale
            with jax.sharding.set_mesh(self.mesh):
                # keep the grad sharding through the unscale so the offload
                # tier's per-shard D2H fast path matches its layout
                grads = (self._grad_acc if denom == 1.0
                         else self._offload_unscale(self._grad_acc,
                                                    jnp.float32(denom)))
            if self._offload.overlap:
                self._collect_offload()
                # snapshot BEFORE launching: the worker overwrites _last_gnorm
                gnorm_prev = jnp.float32(self._offload._last_gnorm)
                self._offload.step_async(grads, self.params, self.global_steps)
                # gnorm/skip reporting lags one step by design (ZenFlow's
                # bounded staleness); bf16-only so skips are inf-grad rare
                self._finish_step(gnorm_prev, jnp.zeros((), bool))
                return
            new_params, skipped = self._offload.step(grads, self.params,
                                                     self.global_steps)
            if not skipped:
                self.params = new_params
            if self.fp16_enabled:
                self.scaler_state = jax.tree_util.tree_map(
                    jnp.asarray,
                    self._scaler_update(self.scaler_state,
                                        jnp.asarray(not skipped)))
            self._finish_step(jnp.float32(self._offload._last_gnorm),
                              jnp.asarray(skipped))
            return
        if self._onebit is not None:
            denom = jnp.float32(self.config.gradient_accumulation_steps)
            with jax.sharding.set_mesh(self.mesh):
                (self.params, self.opt_state, gnorm) = self._onebit_apply(
                    self.params, self.opt_state, self._grad_acc, denom)
            self._finish_step(gnorm, jnp.zeros((), bool))
            return
        if (self._obs is not None and self._zpp is not None
                and "qgz" in self._zpp.quant_error_fns
                and (self.global_steps + 1)
                % self.config.steps_per_print == 0):
            # sample the qgZ roundtrip error on the real grad accumulator
            # BEFORE _apply donates its buffers (print cadence only)
            with jax.sharding.set_mesh(self.mesh):
                self._qgz_err = float(
                    self._zpp.quant_error_fns["qgz"](self._grad_acc))
        with jax.sharding.set_mesh(self.mesh):
            (self.params, self.opt_state, self.scaler_state, gnorm,
             skipped) = self._apply(self.params, self.opt_state, self._grad_acc,
                                    self.scaler_state)
        # params are unchanged on an fp16 overflow skip — don't pay the
        # cross-group gather (only fp16 can skip; the bool() sync already
        # happens in _commit_step on this path)
        if not (self.fp16_enabled and bool(skipped)):
            self._refresh_hpz()
        self._finish_step(gnorm, skipped)

    def _collect_offload(self) -> None:
        """Apply the previous async offload step's params (ZenFlow overlap:
        the host Adam of step N-1 ran during step N's fwd/bwd)."""
        prev = self._offload.finish_pending()
        if prev is not None:
            new_params, skipped = prev
            if not skipped:
                self.params = new_params
            else:
                # the launch-time _commit_step already counted this as a
                # successful step; restate it as skipped so the counters
                # match the synchronous path (the one LR-schedule tick it
                # took is not unwound — bounded, and skips are rare in bf16)
                self.skipped_steps += 1
                self.global_steps = max(0, self.global_steps - 1)

    def _refresh_hpz(self) -> None:
        """Rebuild the hpZ secondary (slice-local) bf16 param copy from the
        primary shards — the once-per-step cross-group gather hpZ amortizes
        (quantized under qwZ). Host-side dispatch time feeds the
        ``train/quant_comm_ms`` gauge."""
        if self._zpp is not None and self._zpp.uses_secondary:
            t0 = time.perf_counter()
            with jax.sharding.set_mesh(self.mesh):
                self._hpz_secondary = self._zpp.hpz_refresh(self.params)
            self._quant_comm_ms = (time.perf_counter() - t0) * 1e3

    def _finish_step(self, gnorm, skipped):
        self._grad_acc = None
        self._grad_acc_count = 0
        self._last_gnorm = gnorm
        t0 = getattr(self, "_opt_t0", None)
        if t0 is not None:
            # imperative path only: the fused paths bury the optimizer
            # inside one jit, where only train/step_ms is meaningful
            self._opt_ms = (time.perf_counter() - t0) * 1e3
            self._opt_t0 = None
        self._commit_step(bool(skipped))
        self.tput_timer.stop(global_step=True, report_speed=True)

    def _commit_step(self, skipped: bool) -> None:
        """Shared end-of-step bookkeeping for the imperative, fused, and fused
        offload paths: skip accounting, LR schedule, progress + monitor."""
        with self._ebus.span("train", "commit"):
            if skipped:
                self.skipped_steps += 1
            else:
                self.global_steps += 1
                if self.lr_scheduler is not None:
                    self.lr_scheduler.step()
            self.global_samples += int(self.config.train_batch_size)
            if self.global_steps and self.global_steps % self.config.steps_per_print == 0:
                self._report_progress()
            if self.monitor is not None:
                self.monitor.write_events([
                    ("Train/Samples/train_loss", float(self._last_loss), self.global_samples),
                    ("Train/Samples/lr", self.get_lr()[0], self.global_samples),
                ])
                if self.global_steps and \
                        self.global_steps % self.config.steps_per_print == 0:
                    self.monitor.write_events(self._resilience_events())
            if self._obs is not None:
                self._emit_train_metrics()
            if self._heartbeat is not None:
                self._heartbeat.notify_step(self.global_steps)
            self._resilience_step_boundary()

    def train_batch(self, data_iter: Optional[Iterable] = None):
        """One full global batch = GA micro-steps + optimizer step
        (parity: ``PipelineEngine.train_batch`` pipe/engine.py:337 UX for non-pipe)."""
        if data_iter is None:
            if self.training_dataloader is None:
                raise ValueError("no data_iter and no training_data configured")
            data_iter = iter(self.training_dataloader)
        total = 0.0
        for _ in range(int(self.config.gradient_accumulation_steps)):
            batch = next(data_iter)
            loss = self.forward(batch)
            self.backward(loss)
            total += float(loss)
        self.step()
        return total / int(self.config.gradient_accumulation_steps)

    # ---- fused single-jit step (bench / graft path) -------------------
    def _fused_grads(self, params, batch, scale, ga: int):
        """GA scan producing (summed scaled-loss grads, mean loss, the loss's
        parts) — the shared forward/backward half of the fused step
        (single-sourced with the 1-bit fwd/bwd region in runtime/onebit.py)."""
        from deepspeed_tpu.runtime.onebit import ga_grads

        return ga_grads(self.module, params, batch, scale, ga)

    def fused_train_step(self, batch):
        """GA loop + apply inside ONE jit: batch leading dim = ga*micro*dp examples.

        This is the performance path — everything (grad accumulation scan,
        collectives, optimizer) compiles into a single XLA program with full
        overlap — with the SAME semantics as forward/backward/step: fp16 loss
        scaling, overflow skip and scaler update ride inside the jit, and the
        host-offload optimizer is supported via a fused grads-only program.

        The plain step program (``ds_train_step``: an optax optimizer on
        the device, no ZeRO++) also carries the weights' working copy (module
        docstring): ``engine.params`` stay the fp32 masters, the copy rides
        beside them from step to step.

        The whole call is the ``ds.train.step`` span (``put_batch``,
        ``dispatch`` and ``commit`` nest inside it), and leaves one row in the
        process's :class:`~deepspeed_tpu.observability.steplog.StepLog`: the
        four stamps, and beside them what the thread's clocks read at enter
        and at exit (on a core, runnable, the whole process on a core) and
        when ``_put_batch`` returned.
        """
        step = self.global_steps
        enter = steplog.host_state()
        self._host_enter = (step, enter)
        with self._ebus.span("train", "step", step=step):
            loss = self._fused_train_step(batch)
        exit_ = steplog.thread_state()
        self._steplog.step(step, enter[0], self._t_dispatched, exit_[0],
                           (enter[1], enter[2], enter[3], self._t_put,
                            exit_[1], exit_[2]))
        self._host_enter = None
        return loss

    def _step_program(self, key, jitted: Callable) -> None:
        """A ``_fused_step_cache`` miss: keep the jitted step program and enter
        it in the step-program table under its function's name, which is also
        what the device trace's module line says ran (``jit_ds_train_step``)."""
        self._fused_step_cache[key] = jitted
        self._uncaptured[key] = steplog.record_program(
            jitted.__name__, key, jitted, self.mesh,
            # how the state is partitioned: what the row's collectives (the
            # compiler's, read from the compiled text on request) are for
            zero_stage=self.zero_stage,
            mesh_axes={a: n for a, n in self.topology.axis_sizes.items()
                       if n > 1},
            **self._program_facts())

    def _program_facts(self, batch_shape=None) -> Dict[str, Any]:
        """What the model says of a step program of its own (over a batch of
        ``batch_shape``, once the first call has shown one); nothing where it
        does not say."""
        facts = getattr(self.module, "step_program_facts", None)
        return {**({} if facts is None else facts(batch_shape)),
                # what the plain fused step carries beside the masters
                "working_copy_bytes": self._work_bytes}

    def _dispatch_fused(self, key, *args):
        """Call the jitted step program of ``key`` (the ``ds.train.dispatch``
        span; the call returns when the program is enqueued)."""
        row = self._uncaptured.pop(key, None)
        if row is not None:     # the program's first or second call
            # called from this frame like every later call: from a method
            # of its own, one frame deeper, the program's lowering took
            # 0.2-0.3 s longer on the chip (PERF.md §6, PR 37)
            with self._watched(row, key, args):
                out = self._fused_step_cache[key](*args)
            return out
        with self._ebus.span("train", "dispatch"), \
                jax.sharding.set_mesh(self.mesh):
            out = self._fused_step_cache[key](*args)
        self._t_dispatched = time.perf_counter()
        return out

    @contextlib.contextmanager
    def _watched(self, row, key, args):
        """Around a step program's first two calls, timed into its row. The
        first traces, lowers and loads it, under a ``ds.train.build`` span
        inside its ``ds.train.dispatch`` (so the build record and a profile
        name the build), and reads what the trace counted; the second shows
        whether ``jax.jit`` built again for the first call's outputs. Then
        the engine forgets the row."""
        first = row.first_call_s is None
        if first:
            row.capture(args)
            traced = lowerings.snapshot()
        t0 = time.perf_counter()
        with steplog.span(self._ebus, "train", "dispatch"), \
                jax.sharding.set_mesh(self.mesh):
            if first:
                with steplog.span(self._ebus, "train", "build",
                                  program=row.name):
                    yield
            else:
                yield
        self._t_dispatched = time.perf_counter()
        if not first:
            row.second_call_s = self._t_dispatched - t0
            return
        row.first_call_s = self._t_dispatched - t0
        self._uncaptured[key] = row
        row.counted = lowerings.since(traced)
        batch_shape = next((a["input_ids"].shape for a in args
                            if isinstance(a, dict) and "input_ids" in a),
                           None)
        if batch_shape is not None:     # what the batch's shape adds
            row.facts.update(self._program_facts(batch_shape))

    def _rule_moves_only_here(self, what: str) -> None:
        """Raise on a step path that does not carry the model's rule-moved
        leaves (:meth:`_moved_by_rule`): it would train with them frozen."""
        if self._rule_leaves:
            raise NotImplementedError(
                f"{what} does not carry the leaves the model moves by a "
                f"rule of its own after each step "
                f"({['/'.join(p) for p in self._rule_leaves]}: "
                f"model.rule_updates); only fused_train_step's plain step "
                f"program (ds_train_step) does")

    def _moved_by_rule(self, params, new_params, parts, skipped):
        """``new_params`` with the model's rule-moved leaves set from the
        step's parts (inside the step program; kept as they were where the
        step was skipped)."""
        with jax.named_scope("optimizer"):
            for path, new in self.module.rule_updates(params, parts).items():
                new_params = _with_leaf(
                    new_params, path,
                    jnp.where(skipped, _leaf(params, path), new))
        return new_params

    def _fused_train_step(self, batch):
        ga = int(self.config.gradient_accumulation_steps)
        if self._ltd_cfg is not None:
            self._update_random_ltd()
        batch = self._apply_curriculum(batch)
        batch = self._inject_ltd_seed(batch)
        if self._guard is not None:
            self._guard.pre_step()  # crash faults fire on the fused path too
        if (self._offload is not None or self._onebit is not None
                or self._zpp is not None):
            self._rule_moves_only_here(
                "the fused offload, 1-bit and ZeRO++ step programs "
                "(ds_train_step_offload, _onebit, _zpp)")
            block = getattr(getattr(self.module, "cfg", None),
                            "diffusion_block", None)
            if block is not None:
                raise NotImplementedError(
                    f"the fused offload, 1-bit and ZeRO++ step programs "
                    f"(ds_train_step_offload, _onebit, _zpp) have not been "
                    f"run with a block-diffusion batch (diffusion_block="
                    f"{block}: noised_ids and loss_weights beside "
                    f"input_ids, the loss's parts in the step record); only "
                    f"fused_train_step's plain step program "
                    f"(ds_train_step) has")
            cfg = getattr(self.module, "cfg", None)
            if getattr(cfg, "has_delta", False) and cfg.num_experts > 1:
                raise NotImplementedError(
                    f"the fused offload, 1-bit and ZeRO++ step programs "
                    f"(ds_train_step_offload, _onebit, _zpp) have not been "
                    f"run with gated delta-rule layers beside routed experts "
                    f"(attn_pattern={cfg.attn_pattern}, num_experts="
                    f"{cfg.num_experts}: a recurrent mixer's outputs and "
                    f"the router's counts in one step record); only "
                    f"fused_train_step's plain step program (ds_train_step) "
                    f"has")
        if self._offload is not None:
            return self._guarded_loss(self._fused_offload_step(batch, ga))
        if self._onebit is not None:
            return self._guarded_loss(self._fused_onebit_step(batch, ga))
        if self._zpp is not None:
            return self._guarded_loss(self._fused_zpp_step(batch, ga))
        key = ga
        if key not in self._fused_step_cache:
            rules = self._rule_leaves

            def ds_train_step(params, work, opt_state, batch, scaler):
                # the forward reads the copy's leaf where there is one and
                # casts nothing there; its cotangent is the cast's own
                # operand, which ``ga_grads`` adds into float32 and
                # ``apply_step`` reads as float32, as the cast's transpose did
                grads, loss, parts = self._fused_grads(
                    _overlaid(params, work), batch, scaler["scale"], ga)
                for path in rules:      # out of the norm and the update
                    grads = _with_leaf(grads, path,
                                       jnp.zeros_like(_leaf(grads, path)))
                new_params, new_opt, new_scaler, gnorm, skipped = \
                    self._apply_body(params, opt_state, grads, scaler, ga=float(ga))
                if rules:
                    new_params = self._moved_by_rule(params, new_params,
                                                     parts, skipped)
                with jax.named_scope("optimizer"):
                    # beside the master it has just made: one more output
                    # of each leaf's update, not a pass of its own
                    new_work = self._copy_of(new_params)
                return (new_params, new_work, new_opt, new_scaler, loss,
                        gnorm, skipped, parts)

            self._step_program(key, jax.jit(
                ds_train_step, donate_argnums=(0, 1, 2),
                out_shardings=(self.param_sharding, self.work_sharding,
                               self.opt_sharding, None, None, None, None,
                               None)))
        if self._work is None:      # something else wrote the masters
            with jax.sharding.set_mesh(self.mesh):
                self._work = self._work_fn(self._params)
        batch = self._put_batch(batch)
        (self._params, self._work, self.opt_state, self.scaler_state, loss,
         gnorm, skipped, parts) = self._dispatch_fused(
            key, self._params, self._work, self.opt_state, batch,
            self.scaler_state)
        self._last_loss, self._last_gnorm = loss, gnorm
        if parts:
            # the loss's parts (a looped model's per-pass losses and exit
            # distribution) ride out of the step program with the loss and
            # are kept as device values: nobody waits for them here
            self._last_loss_parts = parts
            self._steplog.loss_parts(self.global_steps, loss, parts)
        # only fp16 can skip; reading `skipped` otherwise would force a host
        # sync per step and serialize the dispatch pipeline
        self._commit_step(self.fp16_enabled and bool(skipped))
        return self._guarded_loss(loss)

    def _guarded_loss(self, loss):
        """Post-hoc health check for fused paths: the update already ran in
        one jit, so a bad step is detected (and escalated past the budget)
        rather than unwound — use the imperative path or fp16's in-jit skip
        when per-step skipping matters."""
        if self._guard is not None:
            self._guard.check_loss(loss)
        return loss

    def _fused_onebit_step(self, batch, ga: int):
        """Fused 1-bit step: local-grad scan + compressed momentum allreduce +
        update in one XLA program."""
        ob = self._onebit
        key = ("onebit", ga)
        if key not in self._fused_step_cache:
            def ds_train_step_onebit(params, opt_state, batch):
                grads, loss = ob.grads_fn(params, batch, jnp.float32(1.0), ga)
                new_p, new_s, gnorm = ob.apply_fn(params, opt_state, grads,
                                                  jnp.float32(ga))
                return new_p, new_s, loss, gnorm

            self._step_program(key, jax.jit(
                ds_train_step_onebit, donate_argnums=(0, 1),
                out_shardings=(self.param_sharding, self.opt_sharding,
                               None, None)))
        batch = self._put_batch(batch)
        (self.params, self.opt_state, loss, gnorm) = self._dispatch_fused(
            key, self.params, self.opt_state, batch)
        self._last_loss, self._last_gnorm = loss, gnorm
        self._commit_step(False)
        return loss

    def _fused_zpp_step(self, batch, ga: int):
        """Fused step through the ZeRO++ explicit-collective region (qwZ/qgZ/
        hpZ): the quantized gathers/reduces, optimizer, and (for hpZ) the
        secondary refresh all compile into one XLA program."""
        zpp = self._zpp
        key = ("zpp", ga)
        if key not in self._fused_step_cache:
            uses_sec = zpp.uses_secondary

            def ds_train_step_zpp(params, opt_state, batch, scaler, *sec):
                p_in = sec[0] if uses_sec else params
                grads, loss = zpp.grads_fn(p_in, batch, scaler["scale"], ga)
                new_params, new_opt, new_scaler, gnorm, skipped = \
                    self._apply_body(params, opt_state, grads, scaler, ga=float(ga))
                out = (new_params, new_opt, new_scaler, loss, gnorm, skipped)
                if uses_sec:
                    out += (zpp.hpz_refresh(new_params),)
                return out

            self._step_program(key, jax.jit(
                ds_train_step_zpp,
                donate_argnums=(0, 1, 4) if uses_sec else (0, 1),
                out_shardings=(self.param_sharding, self.opt_sharding,
                               None, None, None, None)
                + ((zpp.hpz_sharding,) if uses_sec else ())))
        batch = self._put_batch(batch)
        sec = ((self._hpz_secondary,) if zpp.uses_secondary else ())
        out = self._dispatch_fused(
            key, self.params, self.opt_state, batch, self.scaler_state, *sec)
        (self.params, self.opt_state, self.scaler_state, loss, gnorm,
         skipped) = out[:6]
        if zpp.uses_secondary:
            self._hpz_secondary = out[6]
        self._last_loss, self._last_gnorm = loss, gnorm
        self._commit_step(self.fp16_enabled and bool(skipped))
        return loss

    def _fused_offload_step(self, batch, ga: int):
        """Fused fwd/bwd jit + host optimizer step (ZeRO-Offload/Infinity)."""
        key = ("offload", ga)
        if key not in self._fused_step_cache:
            def ds_train_step_offload(params, batch, scaler):
                scale = scaler["scale"]
                grads, loss, _ = self._fused_grads(params, batch, scale, ga)
                grads = jax.tree_util.tree_map(
                    lambda g: g / (scale * ga), grads)
                return grads, loss

            self._step_program(key, jax.jit(
                ds_train_step_offload,
                out_shardings=(self.grad_sharding, None)))
        batch = self._put_batch(batch)
        grads, loss = self._dispatch_fused(
            key, self.params, batch, self.scaler_state)
        if self._offload.overlap:
            self._collect_offload()
            gnorm_prev = jnp.float32(self._offload._last_gnorm)
            self._offload.step_async(grads, self.params, self.global_steps)
            self._last_loss = loss
            self._last_gnorm = gnorm_prev
            self._commit_step(False)
            return loss
        new_params, skipped = self._offload.step(grads, self.params,
                                                 self.global_steps)
        if not skipped:
            self.params = new_params
        if self.fp16_enabled:
            self.scaler_state = jax.tree_util.tree_map(
                jnp.asarray,
                self._scaler_update(self.scaler_state, jnp.asarray(not skipped)))
        self._last_loss = loss
        self._last_gnorm = jnp.float32(self._offload._last_gnorm)
        self._commit_step(bool(skipped))
        return loss

    # ------------------------------------------------------------------
    # introspection (reference public getters)
    # ------------------------------------------------------------------
    def get_lr(self):
        if self.lr_scheduler is not None:
            return self.lr_scheduler.get_last_lr()
        lr = (self.config.optimizer.params.get("lr", 0.0)
              if self.config.optimizer else 0.0)
        return [lr]

    def get_global_grad_norm(self) -> Optional[float]:
        return None if self._last_gnorm is None else float(self._last_gnorm)

    def gradient_accumulation_steps(self) -> int:
        return int(self.config.gradient_accumulation_steps)

    def train_micro_batch_size_per_gpu(self) -> int:
        return int(self.config.train_micro_batch_size_per_gpu)

    def train_batch_size(self) -> int:
        return int(self.config.train_batch_size)

    def get_model(self):
        return self.module

    def zero_optimization_stage(self) -> int:
        return self.zero_stage

    def _report_progress(self):
        lr = self.get_lr()[0]
        loss = None if self._last_loss is None else float(self._last_loss)
        gnorm = self.get_global_grad_norm()
        log_dist(f"step={self.global_steps} loss={loss:.4f} lr={lr:.3e} "
                 f"grad_norm={gnorm if gnorm is None else round(gnorm, 4)} "
                 f"scale={float(self.scaler_state['scale']):.0f} "
                 f"skipped={self.skipped_steps}")

    # ------------------------------------------------------------------
    # checkpointing (delegates to runtime/checkpoint.py)
    # ------------------------------------------------------------------
    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[Dict] = None, **kw) -> None:
        from deepspeed_tpu.runtime.checkpoint import save_checkpoint

        if self._offload is not None and self._offload.overlap:
            self._collect_offload()  # drain the async step before snapshotting
        t0 = time.perf_counter()
        if self._resilience_enabled():
            self._resilience_manager(save_dir).save(
                self, tag=tag, client_state=client_state or {})
        else:
            save_checkpoint(self, save_dir, tag=tag,
                            client_state=client_state or {})
        if self._obs is not None:
            # async saves report their stage time here; commit latency
            # streams separately via resilience/ckpt_save_ms
            self._obs["checkpoint_ms"].set((time.perf_counter() - t0) * 1e3)

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None,
                        load_optimizer_states: bool = True, **kw):
        from deepspeed_tpu.runtime.checkpoint import load_checkpoint

        if self._offload is not None and self._offload.overlap:
            self._collect_offload()
        if self._resilience_enabled():
            out = self._resilience_manager(load_dir).load(
                self, tag=tag, load_optimizer_states=load_optimizer_states)
        else:
            out = load_checkpoint(self, load_dir, tag=tag,
                                  load_optimizer_states=load_optimizer_states)
        self._refresh_hpz()  # secondary copy is derived state, not checkpointed
        return out

    # ------------------------------------------------------------------
    # observability surface
    # ------------------------------------------------------------------
    def _configure_observability(self, config) -> None:
        """Registry gauges for the per-step breakdown, the registry→monitor
        bridge, the optional ``/metrics`` server, and the on-demand profile
        trigger. Cheap-by-default: with ``observability.enabled`` the per
        step cost is a handful of host float ops; the breakdown timers are
        opt-in and never add a device sync (``synchronize=False`` — host
        timestamps bound dispatch, and the paths that already sync, e.g.
        ``float(loss)`` in the monitor write, stay the only syncs)."""
        from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer

        from deepspeed_tpu.observability.events import get_bus

        ocfg = config.observability
        self.wall_timers = SynchronizedWallClockTimer()
        self._ebus = get_bus()
        # the fused step's record (observability/steplog.py): a row a step,
        # a row a collector pause, a row a step program built
        steplog.install_gc_hook()
        self._steplog = steplog.get_steplog()
        self._uncaptured: Dict[Any, steplog.StepProgram] = {}
        self._t_dispatched = self._t_put = 0.0
        # the fused step's number and the sample taken as its span opened,
        # while it is open
        self._host_enter: Optional[tuple] = None
        self._last_loss_parts: Optional[Dict[str, Any]] = None
        self._obs = None
        self._obs_bridge = None
        self._obs_server = None
        self._profile_trigger = None
        self._breakdown = bool(ocfg.enabled and (
            ocfg.train_breakdown or config.wall_clock_breakdown))
        self._opt_ms: Optional[float] = None
        # _refresh_hpz may already have run during init (it stamps the
        # refresh dispatch time) — keep that first sample
        self._quant_comm_ms: Optional[float] = getattr(
            self, "_quant_comm_ms", None)
        self._qgz_err: Optional[float] = None
        self._last_commit_t: Optional[float] = None
        # baseline NOW, not 0: the comms logger is a process singleton, and
        # latency recorded before this engine existed (a previous engine,
        # init-time collectives) must not land in our first step's delta
        from deepspeed_tpu.comm.logger import comms_logger

        self._comm_lat_base = comms_logger.total_latency_s()
        if ocfg.tracing.enabled:
            # causal event tracing + crash flight recorder: applied to the
            # process bus in place, so every already-constructed seam
            # (serving, engine, swap, resilience) starts emitting
            from deepspeed_tpu.observability import configure_tracing

            configure_tracing(ocfg.tracing)
        if not ocfg.enabled:
            return
        from deepspeed_tpu.observability import (MonitorBridge,
                                                 ObservabilityServer,
                                                 ProfileTrigger, get_registry)

        reg = get_registry()
        g = reg.gauge
        self._obs = {
            "step_ms": g("train/step_ms", "wall clock between step commits"),
            "fwd_ms": g("train/fwd_ms", "forward dispatch (breakdown mode)"),
            "bwd_ms": g("train/bwd_ms", "grad fold (breakdown mode)"),
            "optimizer_ms": g("train/optimizer_ms", "optimizer apply"),
            "comm_ms": g("train/comm_ms",
                         "eager comm.* call time this step; a fused step's "
                         "exchanges are the compiler's and read 0 here "
                         "(steplog: StepProgram.collectives)"),
            "checkpoint_ms": g("train/checkpoint_ms",
                               "last checkpoint save wall clock"),
            "loss": g("train/loss", "last reported loss"),
            "lr": g("train/lr", "current learning rate"),
            "steps": g("train/steps", "global optimizer steps"),
            "samples": g("train/samples", "global samples consumed"),
            "skipped_steps": g("train/skipped_steps",
                               "overflow/guard-skipped steps"),
            "host_off_cpu_ms": g(
                "train/host_off_cpu_ms",
                "of the last fused step's period (enter to enter), the time "
                "its thread was neither on a core nor waiting for one"),
            "host_runnable_ms": g(
                "train/host_runnable_ms",
                "of the same period, the time it was runnable and waiting "
                "for a core (where the host has /proc schedstat)"),
        }
        if self._zpp is not None:
            # ZeRO++ instruments: in-jit quantized collectives are
            # compiler-scheduled (their volume lands in comm/<op>_bytes);
            # the one EAGER quantized collective is the hpZ secondary
            # refresh, timed host-side like the other breakdown gauges
            self._obs["quant_comm_ms"] = g(
                "train/quant_comm_ms",
                "eager quantized-collective dispatch (hpZ refresh)")
            for feat in self._zpp.quant_error_fns:
                self._obs[f"{feat}_quant_error"] = g(
                    f"train/{feat}_quant_error",
                    f"blockwise {feat} quantize/dequantize relative L2 "
                    "error (largest leaf, steps_per_print cadence)")
        if self.monitor is not None:
            # serving/* belongs to a co-resident batcher's bridge (its own
            # step axis); flushing it here too would interleave conflicting
            # step keys into the same CSV/TB series
            self._obs_bridge = MonitorBridge(self.monitor, reg,
                                             exclude=("serving/",))
        if ocfg.profile.enabled:
            self._profile_trigger = ProfileTrigger.from_config(ocfg.profile)
            if ocfg.profile.signal_enabled:
                self._profile_trigger.install_signal_handler()
        if ocfg.http_server and jax.process_index() == 0:
            self._obs_server = ObservabilityServer(
                reg, host=ocfg.http_host, port=ocfg.http_port).start()

    def _emit_train_metrics(self) -> None:
        """Per-commit registry update (host floats only — the one forced
        device read, ``float(loss)``, happens at ``steps_per_print`` cadence
        where ``_report_progress`` already pays it)."""
        o = self._obs
        now = time.perf_counter()
        if self._last_commit_t is not None:
            o["step_ms"].set((now - self._last_commit_t) * 1e3)
        self._last_commit_t = now
        o["steps"].set(float(self.global_steps))
        o["samples"].set(float(self.global_samples))
        o["skipped_steps"].set(float(self.skipped_steps))
        if self._host_enter is not None:    # inside a fused step's span
            last = self._steplog.last_period(*self._host_enter)
            if last is not None:
                o["host_off_cpu_ms"].set(last[0])
                if last[1] == last[1]:
                    o["host_runnable_ms"].set(last[1])
        if self._ebus.enabled:
            # one instant per committed step: the training heartbeat the
            # flight recorder shows around an abort (host clock only)
            self._ebus.instant("train", "step",
                               args={"step": int(self.global_steps)})
        if self._opt_ms is not None:
            o["optimizer_ms"].set(self._opt_ms)
            self._opt_ms = None
        if self._quant_comm_ms is not None and "quant_comm_ms" in o:
            o["quant_comm_ms"].set(self._quant_comm_ms)
            self._quant_comm_ms = None
        if self._breakdown:
            for timer, key in (("fwd", "fwd_ms"), ("bwd", "bwd_ms")):
                if self.wall_timers.has(timer):
                    o[key].set(self.wall_timers(timer).elapsed(reset=True)
                               * 1e3)
        from deepspeed_tpu.comm.logger import comms_logger

        lat = comms_logger.total_latency_s()
        # a comms_logger.reset() mid-run rewinds the total below our base;
        # rebase instead of reporting a negative step delta
        o["comm_ms"].set(max(0.0, lat - self._comm_lat_base) * 1e3)
        self._comm_lat_base = lat
        at_print = self.global_steps and \
            self.global_steps % self.config.steps_per_print == 0
        if at_print:
            if self._last_loss is not None:
                o["loss"].set(float(self._last_loss))
            if self._last_loss_parts:
                # read where float(loss) already waited for the same program
                from deepspeed_tpu.observability import get_registry

                for name, val in self._last_loss_parts.items():
                    for i, v in enumerate(np.atleast_1d(np.asarray(val))):
                        get_registry().gauge(
                            f"train/{name}", "a part of the last reported "
                            "loss (models/transformer.py:loss_and_parts)",
                            labels={"pass": str(i)}).set(float(v))
            o["lr"].set(float(self.get_lr()[0]))
            if self._zpp is not None:
                # quant-error gauges ride the print cadence where the
                # float() sync is already paid; qwZ error samples the
                # params, qgZ error the pre-apply grad accumulator
                # (stamped by step() — fused paths keep grads in-jit)
                fn = self._zpp.quant_error_fns.get("qwz")
                if fn is not None:
                    with jax.sharding.set_mesh(self.mesh):
                        o["qwz_quant_error"].set(float(fn(self.params)))
                if self._qgz_err is not None:
                    o["qgz_quant_error"].set(self._qgz_err)
                    self._qgz_err = None
        if self._profile_trigger is not None:
            self._profile_trigger.check(self.global_steps)
        if self._obs_bridge is not None:
            interval = (self.config.observability.flush_interval_steps
                        or self.config.steps_per_print)
            if self.global_steps and self.global_steps % interval == 0:
                self._obs_bridge.flush(self.global_samples)

    def observability_report(self) -> Dict[str, Any]:
        """One-call snapshot of the observability surface itself."""
        from deepspeed_tpu.observability import get_registry

        return {
            "enabled": self._obs is not None,
            "breakdown": self._breakdown,
            "metrics_url": (self._obs_server.url
                            if self._obs_server is not None else None),
            "profile": (self._profile_trigger.report()
                        if self._profile_trigger is not None else None),
            "families": sorted(f.name for f in get_registry().collect()),
        }

    # ------------------------------------------------------------------
    # resilience surface
    # ------------------------------------------------------------------
    def _resilience_enabled(self) -> bool:
        return bool(self.config.resilience.enabled)

    def _resilience_step_boundary(self) -> None:
        """Fold local signals into the fleet decision at this boundary.

        With coordination on (the default under ``resilience.enabled``) no
        process saves ``latest`` or exits unilaterally: SIGTERM/preemption,
        step-guard budget, and watchdog hangs become votes in one host
        max-reduce, and every process acts on the agreed code at the same
        step. With coordination off this degrades to PR 1's local-only
        emergency save."""
        mgr, guard = self._primary_mgr, self._guard
        if self._coordinator is None:
            if mgr is not None and mgr.preempted:
                # uncoordinated fallback: per-process emergency save
                if self._offload is not None and self._offload.overlap:
                    self._collect_offload()
                mgr.maybe_emergency_save(self)
                rc = self.config.resilience.checkpoint
                if rc.exit_on_preempt:
                    raise SystemExit(rc.preempt_exit_code)
            return
        from deepspeed_tpu.resilience.coordinator import (ABORT, CONTINUE,
                                                          SAVE)

        local, reason = CONTINUE, ""
        if mgr is not None and mgr.preempted:
            local, reason = SAVE, "preemption notice (SIGTERM)"
        if guard is not None and \
                guard.consecutive_bad >= guard.max_consecutive_bad_steps:
            local, reason = ABORT, (f"{guard.consecutive_bad} consecutive "
                                    "non-finite steps")
        decision = self._coordinator.decide(self.global_steps, local, reason)
        if decision == SAVE:
            self._coordinated_emergency_save()
        elif decision == ABORT:
            self._coordinated_abort()

    def _coordinated_emergency_save(self) -> None:
        """Every process commits the SAME emergency tag this boundary."""
        coord = self._coordinator
        mgr = self._primary_mgr
        if mgr is None and self._resilience_report_dir:
            mgr = self._resilience_manager(self._resilience_report_dir)
        if mgr is None:
            logger.error("fleet agreed SAVE but no checkpoint dir is known "
                         "(set DSTPU_CHECKPOINT_DIR or save once first); "
                         "skipping the emergency save")
            return
        # the step boundary is the consistent point: params/opt state are
        # complete trees — but an overlapped host-offload step may still
        # be in flight; drain it so the snapshot matches global_steps
        if self._offload is not None and self._offload.overlap:
            self._collect_offload()
        mgr.preempted = False  # consumed fleet-wide, signaled host or not
        tag = f"preempt_step{self.global_steps}"
        path = mgr.save(self, tag=tag, emergency=True,
                        decision=coord.decision_record())
        from deepspeed_tpu.observability import flight_dump

        flight_dump("emergency_save",
                    extra={"tag": tag, "path": path,
                           "decision": coord.decision_record()},
                    key=f"emergency-{tag}")
        logger.warning(f"coordinated emergency checkpoint saved to {path}")
        if self.monitor is not None:
            self.monitor.write_events(
                [("resilience/decision", float(SAVE), self.global_samples)])
        rc = self.config.resilience.checkpoint
        if rc.exit_on_preempt:
            raise SystemExit(rc.preempt_exit_code)

    def _coordinated_abort(self) -> None:
        """Every process exits to the elastic agent at the same step."""
        from deepspeed_tpu.resilience.coordinator import ABORT, CoordinatedAbort

        coord, guard = self._coordinator, self._guard
        reason = coord.last_reason or "peer abort"
        if self.monitor is not None:
            self.monitor.write_events(
                [("resilience/decision", float(ABORT), self.global_samples)])
        if guard is not None and \
                guard.consecutive_bad >= guard.max_consecutive_bad_steps:
            # this process's own guard budget is the cause: keep the
            # established abort path (report write + TooManyBadSteps)
            guard.abort(reason)
        if self._resilience_report_dir:
            try:
                self.write_resilience_report(self._resilience_report_dir)
            except OSError as e:
                logger.error(f"could not write resilience report: {e}")
        from deepspeed_tpu.observability import flight_dump

        # same per-step key as guard.abort: whichever layer surfaces the
        # incident first ships the one black box
        flight_dump("coordinated_abort",
                    extra={"step": int(self.global_steps),
                           "reason": reason},
                    key=f"abort-step{int(self.global_steps)}")
        logger.error(f"coordinated abort to the elastic agent: {reason}")
        raise CoordinatedAbort(reason)

    def _resilience_events(self):
        """The ``resilience/*`` monitor stream: one gauge per counter the
        report exposes, written at the ``steps_per_print`` cadence (and on
        every non-CONTINUE decision)."""
        from deepspeed_tpu import comm as comm_mod

        s = self.global_samples
        events = [("resilience/skipped_steps", float(self.skipped_steps), s),
                  ("resilience/comm_retries",
                   float(comm_mod.get_retry_stats()["retries"]), s)]
        if self._guard is not None:
            events += [
                ("resilience/guard_bad_steps_skipped",
                 float(self._guard.counters["bad_steps_skipped"]), s),
                ("resilience/guard_consecutive_bad",
                 float(self._guard.consecutive_bad), s)]
        agg: Dict[str, float] = {}
        for mgr in self._ckpt_managers.values():
            for k, v in mgr.counters.items():
                agg[k] = agg.get(k, 0) + v
            if mgr.async_stats["commits"]:
                events.append(("resilience/async_save_latency_s",
                               float(mgr.async_stats["last_latency_s"]), s))
        for k in ("emergency_saves", "verify_failures", "load_fallbacks",
                  "gc_removed", "io_retries", "async_saves",
                  "async_commit_failures"):
            if k in agg:
                events.append((f"resilience/ckpt_{k}", float(agg[k]), s))
        if self._coordinator is not None:
            c = self._coordinator.counters
            events += [("resilience/decisions_save",
                        float(c["saves_agreed"]), s),
                       ("resilience/decisions_abort",
                        float(c["aborts_agreed"]), s)]
        if self._watchdog is not None:
            w = self._watchdog.counters
            events += [("resilience/hangs_detected",
                        float(w["hangs_detected"]), s),
                       ("resilience/heartbeat_max_peer_gap_s",
                        float(w["max_peer_gap_s"]), s)]
        if self._heartbeat is not None:
            events.append(("resilience/heartbeat_step_age_s",
                           float(self._heartbeat.step_age_s()), s))
        return events

    def shutdown(self) -> None:
        """Orderly teardown: drain in-flight async work (offload step, async
        checkpoint commits) and stop the resilience threads. Idempotent."""
        if self._offload is not None:
            if self._offload.overlap:
                self._collect_offload()
            self._offload.close()  # drain AIO + release pooled buffers
        for mgr in self._ckpt_managers.values():
            mgr.drain(raise_on_error=False)
        if self._watchdog is not None:
            self._watchdog.stop()
        if self._heartbeat is not None:
            self._heartbeat.stop()
        if self._profile_trigger is not None:
            self._profile_trigger.close()
        if self._obs_server is not None:
            self._obs_server.close()
            self._obs_server = None
        if self.monitor is not None:
            # release cached CSV handles / writer threads (backends reopen
            # on the next write, so a late event after shutdown still lands)
            self.monitor.close()

    def _resilience_manager(self, ckpt_dir: str):
        """One CheckpointManager per checkpoint directory; the first becomes
        the preemption-save target."""
        from deepspeed_tpu.resilience import CheckpointManager, RetryPolicy

        key = os.path.abspath(ckpt_dir)
        mgr = self._ckpt_managers.get(key)
        if mgr is None:
            rc = self.config.resilience
            mgr = CheckpointManager(
                ckpt_dir, keep_last_k=rc.checkpoint.keep_last_k,
                verify=rc.checkpoint.verify,
                retry_policy=RetryPolicy(**rc.retry.model_dump()),
                async_save=rc.checkpoint.async_save)
            if rc.checkpoint.save_on_preempt:
                mgr.install_preemption_handler()
            self._ckpt_managers[key] = mgr
            if self._primary_mgr is None:
                self._primary_mgr = mgr
            if not self._resilience_report_dir:
                self._resilience_report_dir = key
        return mgr

    def resilience_report(self) -> Dict[str, Any]:
        """The FULL recovery picture in one call, for the elastic agent's
        respawn-vs-give-up decision and for operators: step-guard
        skips/aborts, checkpoint verification failures/fallbacks/GC,
        async-save commit stats, comm retries + the in-flight collective,
        coordination decisions, heartbeat/hang counters, faults fired."""
        from deepspeed_tpu import comm as comm_mod
        from deepspeed_tpu.resilience.faults import get_injector

        ckpt: Dict[str, int] = {}
        async_stats = {"commits": 0, "last_latency_s": 0.0,
                       "total_latency_s": 0.0}
        for mgr in self._ckpt_managers.values():
            for k, v in mgr.counters.items():
                ckpt[k] = ckpt.get(k, 0) + v
            for k, v in mgr.async_stats.items():
                async_stats[k] = (max(async_stats[k], v)
                                  if k == "last_latency_s"
                                  else async_stats[k] + v)
        guard = self._guard
        aborted = bool(guard.counters["aborts"]) if guard else False
        coord = self._coordinator
        if coord is not None:
            aborted = aborted or bool(coord.counters["aborts_agreed"])
        return {
            "schema": 2,
            "global_steps": self.global_steps,
            "skipped_steps": self.skipped_steps,
            "guard": dict(guard.counters) if guard is not None else {},
            "consecutive_bad_steps": (guard.consecutive_bad
                                      if guard is not None else 0),
            "aborted": aborted,
            "checkpoint": ckpt,
            "checkpoint_async": async_stats,
            "comm": {**comm_mod.get_retry_stats(),
                     "inflight": comm_mod.get_inflight()},
            "coordination": coord.report() if coord is not None else {},
            "heartbeat": (self._watchdog.report()
                          if self._watchdog is not None else {}),
            "faults_fired": list(get_injector().fired),
        }

    def offload_report(self) -> Dict[str, Any]:
        """The offload data path in one call (``resilience_report()``
        sibling): tier layout, pipeline depth/overlap flags, last-step Adam
        + upload stage timings, measured pipeline-stall fraction, and the
        swapper's pool/bandwidth counters."""
        if self._offload is None:
            return {"enabled": False}
        return {"enabled": True, **self._offload.report()}

    def write_resilience_report(self, out_dir: str) -> str:
        """Atomically persist ``resilience_report()`` where the elastic agent
        looks for it (the checkpoint dir)."""
        import json

        from deepspeed_tpu.utils.io import atomic_write_text

        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, "resilience_report.json")
        atomic_write_text(path, json.dumps(self.resilience_report(), indent=2))
        return path
