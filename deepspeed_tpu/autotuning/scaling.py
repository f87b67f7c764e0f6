"""Measured multi-chip scaling harness — the curves behind the cost model.

ROADMAP "measured multi-chip scaling as a first-class artifact": the
MULTICHIP dryruns prove the pp×fsdp×tp / dp×sp / dp×ep×sp meshes *compile*;
this module measures them. For each (world size, mesh shape) point it builds
a real engine on a device subset, times ``fused_train_step``, and records

* ``tokens_per_sec_per_chip`` and ``parallel_efficiency`` (vs the measured
  1-chip baseline of the same model kind),
* per-step comm bytes from the logged comm layer (the ZeRO++ explicit-
  collective region logs dense and quantized wire payloads; XLA-inserted
  collectives are invisible to the logger and show up as ``{}``),
* the analytic volume breakdown (``parallel/cost_model.py``) the bandwidth
  calibration regresses against.

``tools/scaling_drill.py`` runs :func:`run_sweep` on the eight virtual CPU
devices and fits the rates from the sweep it has just run, in memory; nothing
records or gates the curves. On real hardware the same sweep measures actual
ICI/DCN rates — the harness is device-agnostic, only the numbers change.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from deepspeed_tpu.parallel.cost_model import (ModelProfile,
                                               collective_volumes,
                                               fit_bandwidths)
from deepspeed_tpu.utils.logging import log_dist

#: sweep defaults — small enough that the full grid runs in minutes on the
#: 8-virtual-device CPU mesh, structured enough that every axis is exercised
DEFAULT_WORLDS: Tuple[int, ...] = (1, 2, 4, 8)
DEFAULT_SEQ = 64
DEFAULT_MICRO_BATCH = 2

#: ZeRO++ wire config measured by the ``fsdp_qz`` shape
ZPP_QUANT: Dict[str, Any] = {"enabled": True, "qwz": True, "qgz": True,
                             "weight_bits": 4, "grad_bits": 8}


def harness_model_config(kind: str):
    """The sweep's model zoo. 8 heads so tp divides up to 8; 2 layers so
    pp=2 divides; seq 64 so sp divides; the moe variant carries 4 experts
    for the ep axis (ring attention over sp, per the MULTICHIP dryruns)."""
    from deepspeed_tpu.models import TransformerConfig

    if kind == "dense":
        return TransformerConfig(vocab_size=256, hidden_size=64,
                                 num_layers=2, num_heads=8, num_kv_heads=8,
                                 max_seq_len=DEFAULT_SEQ, arch="llama")
    if kind == "dense_sp":
        return TransformerConfig(vocab_size=256, hidden_size=64,
                                 num_layers=2, num_heads=8, num_kv_heads=8,
                                 max_seq_len=DEFAULT_SEQ, arch="llama",
                                 attention_impl="ulysses")
    if kind == "moe":
        return TransformerConfig(vocab_size=256, hidden_size=64,
                                 num_layers=2, num_heads=4, num_kv_heads=4,
                                 max_seq_len=DEFAULT_SEQ, arch="llama",
                                 num_experts=4, top_k=2,
                                 attention_impl="ring")
    raise ValueError(f"unknown harness model kind {kind!r}")


def build_harness_model(kind: str):
    from deepspeed_tpu.models import TransformerLM

    return TransformerLM(harness_model_config(kind))


@dataclasses.dataclass(frozen=True)
class ShapeCandidate:
    name: str
    axis_sizes: Dict[str, int]
    model_kind: str = "dense"
    zero_stage: int = 0
    zero_pp: Optional[Dict[str, Any]] = None
    micro_batches: int = 1                      # pipeline chunks
    extra_config: Optional[Dict[str, Any]] = None


def shape_candidates(world: int,
                     shapes: Optional[Sequence[str]] = None
                     ) -> List[ShapeCandidate]:
    """The mesh shapes the sweep measures at one world size (the ISSUE /
    ROADMAP set: dp, fsdp, tp, pp×fsdp×tp, dp×sp, dp×ep×sp, plus the
    quantized-wire fsdp variant). Shapes whose axes don't divide ``world``
    (or the harness models) are simply absent at that world size."""
    w = int(world)
    out: List[ShapeCandidate] = [ShapeCandidate("dp", {"dp": w})]
    if w >= 2:
        base_zpp = {"enabled": True}            # logged dense collectives
        out.append(ShapeCandidate("fsdp", {"fsdp": w}, zero_stage=3,
                                  zero_pp=base_zpp))
        out.append(ShapeCandidate("fsdp_qz", {"fsdp": w}, zero_stage=3,
                                  zero_pp=dict(ZPP_QUANT)))
        if harness_model_config("dense").num_heads % w == 0:
            out.append(ShapeCandidate("tp", {"tp": w}))
        out.append(ShapeCandidate("dp_sp", {"dp": w // 2, "sp": 2},
                                  model_kind="dense_sp"))
    if w >= 4 and w % 4 == 0:
        out.append(ShapeCandidate("dp_ep_sp",
                                  {"dp": w // 4, "ep": 2, "sp": 2},
                                  model_kind="moe"))
    if w == 8:
        out.append(ShapeCandidate(
            "pp_fsdp_tp", {"pp": 2, "fsdp": 2, "tp": 2}, zero_stage=3,
            micro_batches=2,
            extra_config={"pipeline": {"micro_batches": 2}}))
    if shapes is not None:
        out = [c for c in out if c.name in set(shapes)]
    return out


class _comm_logging:
    """Enable per-collective byte logging for one measurement, restoring
    the prior state on exit — this is library code; leaking prof_all into
    the caller's process would spam logs and tax every later engine."""

    def __enter__(self):
        from deepspeed_tpu.comm.logger import comms_logger

        self.lg = comms_logger
        self._prior = (comms_logger.enabled, comms_logger.prof_all)
        comms_logger.enabled = True
        comms_logger.prof_all = True
        return comms_logger

    def __exit__(self, *exc):
        self.lg.enabled, self.lg.prof_all = self._prior
        return False


def _bytes_delta(before: Dict[str, float], after: Dict[str, float]
                 ) -> Dict[str, int]:
    ops = set(before) | set(after)
    return {op: int(after.get(op, 0.0) - before.get(op, 0.0)) for op in ops
            if after.get(op, 0.0) != before.get(op, 0.0)}


def measure_point(cand: ShapeCandidate, world: int, *,
                  steps: int = 4, micro_batch: int = DEFAULT_MICRO_BATCH,
                  seq: int = DEFAULT_SEQ, devices=None,
                  seed: int = 0) -> Dict[str, Any]:
    """One measured curve point: build an engine for ``cand`` on a
    ``world``-device subset, time ``steps`` fused train steps (after a
    compile/warm step), and return throughput + logged comm bytes + the
    analytic volume breakdown. The engine is always shut down — grid
    measurement shares one process and must not accumulate workers."""
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.parallel import build_mesh

    devs = list(devices if devices is not None else jax.devices())[:world]
    if len(devs) < world:
        raise ValueError(f"need {world} devices, have {len(devs)}")
    topo = build_mesh(devices=devs, axis_sizes=dict(cand.axis_sizes))

    config: Dict[str, Any] = {
        "train_micro_batch_size_per_gpu": int(micro_batch),
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": int(cand.zero_stage),
                              "param_persistence_threshold": 0},
        "steps_per_print": 10 ** 9,
    }
    if cand.zero_pp is not None:
        config["zero_optimization"]["zero_pp"] = dict(cand.zero_pp)
    if cand.extra_config:
        config.update(cand.extra_config)

    model = build_harness_model(cand.model_kind)
    profile = ModelProfile.from_transformer_config(model.cfg, seq=seq)

    rng = np.random.default_rng(seed)
    engine = None
    try:
        with _comm_logging() as lg:
            engine, *_ = ds.initialize(model=model, config=config,
                                       mesh=topo)
            n = int(micro_batch) * engine.topology.dp_world_size
            batch = {"input_ids": rng.integers(
                0, model.cfg.vocab_size, (n, seq)).astype(np.int32)}
            tokens_per_step = n * seq

            before = dict(lg.bytes)
            loss = engine.fused_train_step(batch)     # compile + warm
            last_loss = float(loss)
            # trace-time logging: the delta over the compile step IS the
            # per-step wire payload of the explicit-collective region
            comm_bytes = _bytes_delta(before, dict(lg.bytes))

            t0 = time.perf_counter()
            for _ in range(steps):
                loss = engine.fused_train_step(batch)
            last_loss = float(loss)                   # drain device work
            dt = time.perf_counter() - t0
    finally:
        if engine is not None:
            try:
                engine.shutdown()
            except Exception as e:
                log_dist(f"scaling: engine shutdown failed: {e}")

    tps = tokens_per_step * steps / dt
    predicted = collective_volumes(
        profile, cand.axis_sizes, zero_stage=cand.zero_stage,
        zero_pp=cand.zero_pp, tokens=tokens_per_step,
        micro_batches=cand.micro_batches, ici_sizes=topo.ici_sizes)
    predicted.pop("per_axis", None)
    return {
        "world": world, "mesh": dict(cand.axis_sizes),
        "model": cand.model_kind, "zero_stage": cand.zero_stage,
        "zero_pp": cand.zero_pp, "tokens_per_step": tokens_per_step,
        "step_ms": round(dt / steps * 1e3, 2),
        "tokens_per_sec": round(tps, 1),
        "tokens_per_sec_per_chip": round(tps / world, 1),
        "comm_bytes_per_step": comm_bytes,
        "predicted": predicted, "loss": round(last_loss, 4),
    }


def run_sweep(worlds: Sequence[int] = DEFAULT_WORLDS,
              shapes: Optional[Sequence[str]] = None, *,
              steps: int = 4, micro_batch: int = DEFAULT_MICRO_BATCH,
              seq: int = DEFAULT_SEQ, devices=None) -> Dict[str, Any]:
    """The full scaling sweep: world sizes × mesh shapes, normalized to the
    measured 1-chip baseline of each model kind. Returns the
    sweep's result (curves keyed ``shape → wN → point``)."""
    import jax

    from deepspeed_tpu.autotuning.mesh_store import device_kind

    devs = list(devices if devices is not None else jax.devices())
    worlds = sorted({int(w) for w in worlds if int(w) <= len(devs)})
    kind = device_kind(devs)

    # 1-chip baselines per model kind (the denominator of every
    # parallel-efficiency number; a kind whose baseline fails to run
    # yields points WITHOUT an efficiency value — no-data, never a
    # cross-model ratio)
    baselines: Dict[str, Dict[str, Any]] = {}
    kinds = sorted({c.model_kind
                    for w in worlds if w > 1
                    for c in shape_candidates(w, shapes)} | {"dense"})
    for mk in kinds:
        try:
            baselines[mk] = measure_point(
                ShapeCandidate(f"baseline_{mk}", {"dp": 1}, model_kind=mk),
                1, steps=steps, micro_batch=micro_batch, seq=seq,
                devices=devs)
            log_dist(f"scaling baseline[{mk}]: "
                     f"{baselines[mk]['tokens_per_sec_per_chip']} tok/s/chip")
        except Exception as e:
            log_dist(f"scaling baseline[{mk}] failed: {e}")

    curves: Dict[str, Dict[str, Any]] = {}
    failures: List[Dict[str, Any]] = []
    for w in worlds:
        if w <= 1:
            continue
        for cand in shape_candidates(w, shapes):
            try:
                pt = measure_point(cand, w, steps=steps,
                                   micro_batch=micro_batch, seq=seq,
                                   devices=devs)
            except Exception as e:
                failures.append({"shape": cand.name, "world": w,
                                 "error": str(e)[:200]})
                log_dist(f"scaling point {cand.name}@w{w} failed: "
                         f"{str(e)[:200]}")
                continue
            # efficiency ONLY against the shape's own model-kind baseline:
            # silently switching denominators (e.g. moe point over the
            # dense baseline) would make the trend series compare
            # incommensurable numbers across runs — a missing baseline
            # means "no efficiency datum", which the gate treats as
            # no-data, never as a regression
            base = baselines.get(cand.model_kind)
            if base:
                pt["baseline_model"] = base["model"]
                pt["parallel_efficiency"] = round(
                    pt["tokens_per_sec_per_chip"]
                    / base["tokens_per_sec_per_chip"], 4)
            curves.setdefault(cand.name, {})[f"w{w}"] = pt
            log_dist(f"scaling {cand.name}@w{w}: "
                     f"{pt['tokens_per_sec_per_chip']} tok/s/chip "
                     f"(eff={pt.get('parallel_efficiency')})")

    # calibrate link bandwidths from THIS sweep's measured points
    samples = [{"step_s": pt["step_ms"] / 1e3, **pt["predicted"]}
               for pts in curves.values() for pt in pts.values()]
    samples += [{"step_s": b["step_ms"] / 1e3, **b["predicted"]}
                for b in baselines.values()]
    bw = fit_bandwidths(samples)

    top_world = max((int(k[1:]) for pts in curves.values() for k in pts),
                    default=1)
    best_at_top = max((pts[f"w{top_world}"]["tokens_per_sec_per_chip"]
                       for pts in curves.values() if f"w{top_world}" in pts),
                      default=None)
    return {
        "metric": "scaling_tokens_per_sec_per_chip",
        "value": best_at_top, "unit": "tokens/s/chip",
        "device": kind, "worlds": worlds, "steps": steps,
        "micro_batch": micro_batch, "seq": seq,
        "baselines": baselines,
        # curves are scoped under the device kind: a TPU sweep's points
        # and a CPU harness's are orders of magnitude apart
        "curves": {kind: curves},
        "failures": failures, "calibration": bw.as_dict(),
    }
