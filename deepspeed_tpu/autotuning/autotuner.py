"""In-process autotuner.

Parity target: ``deepspeed/autotuning/autotuner.py:42`` ``Autotuner.tune()`` — the
reference launches subprocess experiments over (zero stage, micro-batch, offload)
combos and picks the fastest that fits. On TPU a trial is: build an engine with the
candidate config, run ``fused_train_step`` a few times, record tokens/sec; OOM →
candidate rejected (the reference's "model info" prune step is replaced by actually
asking XLA, which is cheap on one chip).

v2 adds the axis the reference never had — **mesh shape**, the dominant perf
knob on TPU. ``mesh_candidates`` takes explicit axis-size dicts or
``"auto"``: enumerate every legal factorization of the device count (pruned
by model divisibility — heads % tp, layers % pp, experts % ep; see
``parallel/cost_model.py``), rank by the cost model (its default rates, or
the ``cost_model`` given, say one fitted from a sweep just run), and
measure only the ``mesh_top_k`` survivors. The winning shape is persisted to
the :class:`~deepspeed_tpu.autotuning.mesh_store.WinnerStore` keyed
(model signature, world size, device kind) so ``mesh: "auto"`` engine
configs adopt it without re-tuning.
"""

from __future__ import annotations

import copy
import dataclasses
import itertools
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import numpy as np

from deepspeed_tpu.utils.logging import log_dist


@dataclasses.dataclass
class TrialResult:
    config: Dict[str, Any]
    ok: bool
    samples_per_sec: float = 0.0
    error: str = ""


class Autotuner:
    """Grid search over mesh-shape × micro-batch × zero-stage × remat ×
    offload. Offload combos run only at stage >= 1; remat candidates apply
    when ``model_factory`` accepts ``remat_policy``; mesh candidates apply
    to the whole visible device set (a factory accepting ``mesh_shape``
    gets the candidate, e.g. to switch on Ulysses attention for sp > 1)."""

    def __init__(self, model_factory: Callable[..., Any], base_config: Dict[str, Any],
                 micro_batch_candidates: Sequence[int] = (1, 2, 4, 8),
                 zero_stage_candidates: Sequence[int] = (0, 1, 2, 3),
                 remat_candidates: Sequence[str] = ("none",),
                 offload_candidates: Sequence[Optional[str]] = (None,),
                 mesh_candidates: Union[None, str,
                                        Sequence[Dict[str, int]]] = None,
                 mesh_top_k: Optional[int] = None, cost_model=None,
                 winner_store=None, steps: Optional[int] = None,
                 make_batch: Optional[Callable[[int], Any]] = None):
        self.model_factory = model_factory
        self.base_config = base_config
        self.micro_batch_candidates = list(micro_batch_candidates)
        self.zero_stage_candidates = list(zero_stage_candidates)
        self.remat_candidates = list(remat_candidates)
        self.offload_candidates = list(offload_candidates)
        self.mesh_candidates = mesh_candidates
        # search-shape defaults come from the base config's `autotuning`
        # block (the same knobs a mesh:"auto" engine config carries);
        # explicit constructor args win
        at = dict(base_config.get("autotuning") or {}) \
            if isinstance(base_config, dict) else {}
        self.mesh_top_k = int(mesh_top_k if mesh_top_k is not None
                              else at.get("top_k", 2))
        self.mesh_axes = tuple(at.get("mesh_axes")
                               or ("pp", "dp", "fsdp", "ep", "sp", "tp"))
        self.cost_model = cost_model
        self.winner_store = winner_store
        self._winner_cache = at.get("winner_cache") or None
        self.steps = int(steps if steps is not None
                         else at.get("measure_steps", 3))
        self.make_batch = make_batch
        self.results: List[TrialResult] = []
        self._profile_cache = None
        # model_factory(remat_policy=..., mesh_shape=...) only when accepted
        import inspect

        try:
            sig = inspect.signature(model_factory)
            var_kw = any(p.kind == p.VAR_KEYWORD
                         for p in sig.parameters.values())
            self._factory_takes_remat = ("remat_policy" in sig.parameters
                                         or var_kw)
            self._factory_takes_mesh = ("mesh_shape" in sig.parameters
                                        or var_kw)
        except (TypeError, ValueError):
            self._factory_takes_remat = False
            self._factory_takes_mesh = False

    def _make_model(self, remat: str, mesh: Optional[Dict[str, int]]):
        kw: Dict[str, Any] = {}
        if self._factory_takes_remat:
            kw["remat_policy"] = remat
        if self._factory_takes_mesh and mesh is not None:
            kw["mesh_shape"] = mesh
        return self.model_factory(**kw)

    def _profile(self):
        """The model's cost-model profile, computed once — the layout facts
        are identical for every factory call, and a user factory may be
        expensive (e.g. an HF weight import)."""
        if self._profile_cache is None:
            from deepspeed_tpu.parallel.cost_model import ModelProfile

            self._profile_cache = ModelProfile.from_model(
                self._make_model("none", None))
        return self._profile_cache

    def _run_trial(self, mb: int, stage: int, remat: str,
                   offload: Optional[str],
                   mesh: Optional[Dict[str, int]] = None) -> TrialResult:
        import deepspeed_tpu as ds

        key = {"micro_batch": mb, "stage": stage, "remat": remat,
               "offload": offload}
        if mesh is not None:
            key["mesh"] = dict(mesh)
        cfg = copy.deepcopy(self.base_config)
        cfg["train_micro_batch_size_per_gpu"] = mb
        cfg.pop("train_batch_size", None)
        zo = cfg.setdefault("zero_optimization", {})
        zo["stage"] = stage
        if offload:
            zo["offload_optimizer"] = {"device": offload}
        if mesh is not None:
            cfg["mesh"] = {k: int(v) for k, v in mesh.items()}
        engine = None
        try:
            model = self._make_model(remat, mesh)
            engine, *_ = ds.initialize(model=model, config=cfg)
            batch = self.make_batch(mb * engine.topology.dp_world_size)
            engine.fused_train_step(batch)  # compile + warm
            t0 = time.perf_counter()
            for _ in range(self.steps):
                loss = engine.fused_train_step(batch)
            loss.block_until_ready()
            dt = time.perf_counter() - t0
            sps = self.steps * engine.train_batch_size() / dt
            return TrialResult(key, True, sps)
        except Exception as e:  # OOM / invalid combo → rejected candidate
            return TrialResult(key, False, error=str(e)[:200])
        finally:
            # grid trials share one process: without a teardown every
            # trial's monitor/checkpoint/offload worker threads and HBM
            # buffers leak into (and skew) every later trial's timing
            if engine is not None:
                try:
                    engine.shutdown()
                except Exception as e:
                    log_dist(f"autotune: trial engine shutdown failed: {e}")

    def _resolved_mesh_candidates(self) -> List[Optional[Dict[str, int]]]:
        """None (keep the base config's mesh), an explicit list, or
        ``"auto"``: enumerate legal factorizations of the visible device
        count, rank by the cost model, keep the top-K."""
        if self.mesh_candidates is None:
            return [None]
        if self.mesh_candidates != "auto":
            return [dict(m) for m in self.mesh_candidates]
        import jax

        from deepspeed_tpu.parallel.cost_model import (CostModel,
                                                       enumerate_meshes)

        world = len(jax.devices())
        profile = self._profile()
        if profile is None:
            log_dist("autotune: model not introspectable; mesh axis skipped")
            return [None]
        if self._factory_takes_mesh and not profile.sp_capable:
            # a mesh-aware factory can switch on ulysses/ring for sp > 1
            profile = dataclasses.replace(profile, sp_capable=True)
        cands = enumerate_meshes(world, profile, axes=self.mesh_axes)
        cm = self.cost_model or CostModel()
        stage = max(self.zero_stage_candidates or [0])
        ranked = cm.rank_by_throughput(
            profile, cands, zero_stage=stage,
            micro_batch=max(self.micro_batch_candidates))
        keep = [m for m, _ in ranked[:self.mesh_top_k]]
        log_dist(f"autotune: mesh=auto kept {keep} of {len(cands)} legal "
                 f"factorizations of {world} devices "
                 f"(calibrated_from={cm.bw.calibrated_from})")
        return keep

    def _persist_winner(self, best: TrialResult) -> None:
        """Record the winning mesh keyed (model signature, world, device
        kind) so ``mesh: "auto"`` configs adopt it without re-tuning."""
        if best.config.get("mesh") is None:
            return
        import jax

        from deepspeed_tpu.autotuning.mesh_store import (WinnerStore,
                                                         device_kind)
        from deepspeed_tpu.parallel.cost_model import model_signature

        profile = self._profile()
        if profile is None:
            return
        store = self.winner_store or WinnerStore(self._winner_cache)
        store.put(model_signature(profile), len(jax.devices()),
                  device_kind(), best.config["mesh"], best.samples_per_sec,
                  zero_stage=int(best.config["stage"]))
        log_dist(f"autotune: persisted mesh winner {best.config['mesh']} "
                 f"({best.samples_per_sec:.1f} samples/s) → {store.path}")

    def tune(self) -> Optional[TrialResult]:
        """Return the fastest working (mesh, micro_batch, stage, remat,
        offload) combo — the reference tuner's axis set (autotuner.py:42)
        plus the mesh-shape axis."""
        assert self.make_batch is not None, "make_batch factory is required"
        remats = (self.remat_candidates
                  if self._factory_takes_remat else ["none"])
        if not self._factory_takes_remat and self.remat_candidates != ["none"]:
            log_dist("autotune: model_factory does not accept remat_policy; "
                     "remat candidates skipped")
        for mesh, mb, stage, remat, off in itertools.product(
                self._resolved_mesh_candidates(),
                self.micro_batch_candidates, self.zero_stage_candidates,
                remats, self.offload_candidates):
            if off and stage < 1:
                continue  # offload_optimizer needs a zero shard layout
            r = self._run_trial(mb, stage, remat, off, mesh=mesh)
            self.results.append(r)
            log_dist(f"autotune trial {r.config}: "
                     f"{'%.1f samples/s' % r.samples_per_sec if r.ok else 'FAIL ' + r.error}")
        ok = [r for r in self.results if r.ok]
        if not ok:
            return None
        best = max(ok, key=lambda r: r.samples_per_sec)
        try:
            self._persist_winner(best)
        except Exception as e:  # the cache is an optimization, never a sink
            log_dist(f"autotune: winner persistence failed: {e}")
        return best
