#!/usr/bin/env python
"""Mesh-autotuner drill CLI: prove on the 8-device mesh that

* the winner store round-trips and ``mesh: "auto"`` config parses into the
  resolution path (``store`` scenario — fast),
* the full data-driven loop closes (``mesh-auto`` scenario): measure every
  drill candidate shape exhaustively through the Autotuner's mesh axis,
  calibrate the cost model's link bandwidths from those measurements, and
  check that (a) the cost model's top-2 ranked shapes contain the
  measured-fastest shape, (b) the production flow — rank, measure only the
  top-2 survivors, persist the winner — adopts a shape within 10 % of the
  best exhaustively measured tokens/s, and (c) an engine built with
  ``mesh: "auto"`` actually adopts the persisted winner.

    python tools/scaling_drill.py --list
    python tools/scaling_drill.py --scenario store
    python tools/scaling_drill.py --scenario mesh-auto
    python tools/scaling_drill.py --all

Exit code 0 = invariants held; 1 = violated (details on stdout as JSON).
Slow pytest wrappers live in ``tests/unit/test_scaling.py`` under the
``scaling`` + ``slow`` markers. The drill asserts the decision loop; the
scaling curves themselves (tokens/s/chip vs world size) are measured nowhere
yet (``autotuning/scaling.py:run_sweep`` would, on a pod).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOL_ADOPT = 0.10         # winner must be within 10% of the exhaustive best
TOP_K = 2                # survivors the production flow measures

#: the drill's candidate space — the MULTICHIP shape set at world 8
CANDIDATES = [
    {"dp": 8},
    {"fsdp": 8},
    {"tp": 8},
    {"dp": 4, "sp": 2},
    {"dp": 2, "fsdp": 2, "tp": 2},
    {"pp": 2, "fsdp": 2, "tp": 2},
]


class DrillFailure(AssertionError):
    pass


def check(ok, msg, details):
    if not ok:
        raise DrillFailure(f"{msg}: {json.dumps(details, default=str)}")


def _model_factory(mesh_shape=None):
    """Dense harness model; switches on Ulysses attention when the
    candidate shape has an sp axis (the Autotuner's mesh-aware factory
    contract)."""
    from deepspeed_tpu.autotuning.scaling import build_harness_model

    kind = "dense_sp" if (mesh_shape or {}).get("sp", 1) > 1 else "dense"
    return build_harness_model(kind)


def _base_config():
    return {
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"param_persistence_threshold": 0},
        "pipeline": {"micro_batches": 2},   # only consulted when pp > 1
        "steps_per_print": 10 ** 9,
    }


def _make_batch(n):
    import numpy as np

    return {"input_ids": np.random.default_rng(0).integers(
        0, 256, (n, 64)).astype(np.int32)}


def _tune(mesh_candidates, store=None, steps=3):
    from deepspeed_tpu.autotuning import Autotuner

    tuner = Autotuner(
        _model_factory, _base_config(), micro_batch_candidates=(2,),
        zero_stage_candidates=(3,), mesh_candidates=mesh_candidates,
        winner_store=store, steps=steps, make_batch=_make_batch)
    best = tuner.tune()
    return tuner, best


def _mesh_key(m):
    return json.dumps({k: m[k] for k in sorted(m)}) if m else "{}"


# ---------------------------------------------------------------------------
# scenario: store — winner persistence + mesh:"auto" resolution plumbing
# ---------------------------------------------------------------------------

def scenario_store(workdir=None):
    import tempfile

    import deepspeed_tpu as ds
    from deepspeed_tpu.autotuning.mesh_store import (WinnerStore, device_kind,
                                                     resolve_auto_axis_sizes)
    from deepspeed_tpu.parallel.cost_model import (ModelProfile,
                                                   model_signature)

    path = os.path.join(workdir or tempfile.mkdtemp(prefix="dstpu_drill_"),
                        "winners.json")
    store = WinnerStore(path)
    model = _model_factory()
    profile = ModelProfile.from_model(model)
    sig = model_signature(profile)
    kind = device_kind()

    # miss → cost-model fallback (never an error, never an implicit tune)
    fallback = resolve_auto_axis_sizes(8, profile, winner_cache=path,
                                       zero_stage=3)
    check(isinstance(fallback, dict) and fallback,
          "auto resolution returned no mesh on a cache miss", fallback)

    mesh = {"fsdp": 4, "dp": 2}
    store.put(sig, 8, kind, mesh, 123.4, zero_stage=3)
    hit = resolve_auto_axis_sizes(8, profile, winner_cache=path,
                                  zero_stage=3)
    check(hit == mesh, "winner store round-trip lost the mesh",
          {"put": mesh, "got": hit})
    # winners are keyed per zero stage: a stage-3 shape must not be
    # visible to a stage-0 lookup (that run falls through to the cost
    # model, which ranks without the fsdp gather term)
    check(store.get(sig, 8, kind, zero_stage=0) is None,
          "stage-0 lookup returned a stage-3 winner", {"winner": mesh})

    # the engine-level path: mesh:"auto" config adopts the stored winner
    eng = None
    try:
        eng, *_ = ds.initialize(model=model, config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 3,
                                  "param_persistence_threshold": 0},
            "mesh": "auto",
            "autotuning": {"winner_cache": path},
            "steps_per_print": 10 ** 9})
        adopted = {k: v for k, v in eng.topology.axis_sizes.items()
                   if v > 1}
        check(adopted == mesh, "mesh:'auto' engine ignored the winner",
              {"winner": mesh, "adopted": adopted})
    finally:
        if eng is not None:
            eng.shutdown()
    return {"store": path, "winner": mesh, "fallback": fallback}


# ---------------------------------------------------------------------------
# scenario: mesh-auto — the full measured decision loop
# ---------------------------------------------------------------------------

def scenario_mesh_auto(workdir=None):
    import tempfile

    import deepspeed_tpu as ds
    from deepspeed_tpu.autotuning.mesh_store import WinnerStore, device_kind
    from deepspeed_tpu.parallel.cost_model import (CostModel, ModelProfile,
                                                   collective_volumes,
                                                   fit_bandwidths)

    workdir = workdir or tempfile.mkdtemp(prefix="dstpu_drill_")

    # 1) exhaustive measurement over the candidate space (one protocol:
    #    the Autotuner's own trial loop)
    tuner_full, best_full = _tune(CANDIDATES,
                                  store=WinnerStore(
                                      os.path.join(workdir, "full.json")))
    ok_trials = [r for r in tuner_full.results if r.ok]
    check(best_full is not None and len(ok_trials) >= 4,
          "exhaustive measurement lost too many candidates",
          {"ok": len(ok_trials),
           "errors": [r.error for r in tuner_full.results if not r.ok]})

    profile = ModelProfile.from_model(_model_factory())
    measured = {}           # mesh key -> (mesh, samples/s, volumes)
    for r in ok_trials:
        mesh = r.config["mesh"]
        dpw = mesh.get("dp", 1) * mesh.get("fsdp", 1)
        tokens = 2 * dpw * 64
        vol = collective_volumes(
            profile, mesh, zero_stage=3, tokens=tokens,
            micro_batches=2 if mesh.get("pp", 1) > 1 else 1)
        measured[_mesh_key(mesh)] = (mesh, r.samples_per_sec, tokens, vol)

    # 2) calibrate link bandwidths from the measured trials themselves
    samples = [{"step_s": tokens / 64.0 / sps, **vol}
               for (_, sps, tokens, vol) in measured.values()]
    bw = fit_bandwidths(samples)
    cm = CostModel(bw)

    # 3) rank: predicted tokens/s per candidate; the measured-fastest
    #    shape must sit in the top-2 (the acceptance gate)
    ranked = cm.rank_by_throughput(
        profile, [m for (m, _, _, _) in measured.values()],
        zero_stage=3, micro_batch=2)
    best_measured = max(measured.values(), key=lambda t: t[1])
    top2 = [_mesh_key(m) for m, _ in ranked[:TOP_K]]
    check(_mesh_key(best_measured[0]) in top2,
          "cost-model top-2 does not contain the measured-fastest shape",
          {"ranked": [(m, round(t, 1)) for m, t in ranked],
           "measured": {k: round(v[1], 2) for k, v in measured.items()},
           "calibration": bw.as_dict()})

    # 4) the production flow: measure ONLY the top-2 survivors, persist
    topk_store = WinnerStore(os.path.join(workdir, "winners.json"))
    topk_meshes = [m for m, _ in ranked[:TOP_K]]
    tuner_topk, winner = _tune(topk_meshes, store=topk_store)
    check(winner is not None, "top-K measurement produced no winner",
          {"errors": [r.error for r in tuner_topk.results if not r.ok]})

    # 5) winner within 10% of the exhaustive best (tokens/s == samples/s
    #    here: same seq everywhere); compare on the EXHAUSTIVE table so
    #    run-to-run noise between the two tuner passes doesn't leak in
    win_key = _mesh_key(winner.config["mesh"])
    win_sps = measured[win_key][1] if win_key in measured \
        else winner.samples_per_sec
    ratio = win_sps / best_measured[1]
    check(ratio >= 1.0 - TOL_ADOPT,
          f"adopted mesh more than {TOL_ADOPT:.0%} off the exhaustive best",
          {"winner": winner.config["mesh"], "winner_sps": round(win_sps, 2),
           "best": best_measured[0], "best_sps": round(best_measured[1], 2),
           "ratio": round(ratio, 3)})

    # 6) mesh:"auto" adopts the persisted winner
    eng = None
    try:
        eng, *_ = ds.initialize(model=_model_factory(
            mesh_shape=winner.config["mesh"]), config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 3,
                                  "param_persistence_threshold": 0},
            "pipeline": {"micro_batches": 2},
            "mesh": "auto",
            "autotuning": {"winner_cache": topk_store.path},
            "steps_per_print": 10 ** 9})
        adopted = {k: v for k, v in eng.topology.axis_sizes.items()
                   if v > 1}
        check(adopted == winner.config["mesh"],
              "mesh:'auto' engine did not adopt the tuned winner",
              {"winner": winner.config["mesh"], "adopted": adopted})
    finally:
        if eng is not None:
            eng.shutdown()

    return {
        "measured": {k: round(v[1], 2) for k, v in measured.items()},
        "ranked": [( {a: b for a, b in m.items()}, round(t, 1))
                   for m, t in ranked],
        "calibration": bw.as_dict(),
        "winner": winner.config["mesh"],
        "winner_vs_best": round(ratio, 3),
        "store": topk_store.path,
    }


SCENARIOS = {
    "store": scenario_store,
    "mesh-auto": scenario_mesh_auto,
}


def run_scenario(name: str) -> dict:
    fn = SCENARIOS.get(name)
    if fn is None:
        raise SystemExit(f"unknown scenario {name!r} "
                         f"(have: {', '.join(SCENARIOS)})")
    t0 = time.perf_counter()
    try:
        detail = fn()
        ok, err = True, None
    except DrillFailure as e:
        detail, ok, err = None, False, str(e)
    return {"scenario": name, "ok": ok, "error": err, "detail": detail,
            "elapsed_s": round(time.perf_counter() - t0, 1)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scenario", help="which drill to run")
    ap.add_argument("--all", action="store_true", help="run every scenario")
    ap.add_argument("--list", action="store_true", help="list scenarios")
    args = ap.parse_args(argv)
    if args.list:
        print("\n".join(SCENARIOS))
        return 0
    names = list(SCENARIOS) if args.all else (
        [args.scenario] if args.scenario else None)
    if not names:
        ap.error("pass --scenario NAME, --all, or --list")
    rc = 0
    for name in names:
        verdict = run_scenario(name)
        print(json.dumps(verdict))
        if not verdict["ok"]:
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
