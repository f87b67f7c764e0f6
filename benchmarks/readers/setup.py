"""Readers of what the program says of its own set-up: the set-up spans,
the build record and the step-program rows of
``deepspeed_tpu.observability.steplog``, and the first step rows of its
record. ``SETUP.md`` beside this file says which metric reads which of them.

``setup_s`` runs from ``harness.T_PROCESS_START`` to the window's start, on
``time.perf_counter``, the clock of every span and row the program keeps. So
the program's part of it can be taken out by name: the package's import,
``deepspeed_tpu.initialize``, the warm-up steps' spans. What is left is the
interpreter's and the TPU runtime's start, the benchmark's own reference
check, and the device's run of the warm-up steps.

Everything is computed once a run (:func:`analysis`, kept in the context) and
printed on an earlier line; each metric is one key of it (:func:`value`). A
program without ``steplog.setup`` gives an empty analysis: every metric is
then left out and nothing is raised. :func:`reduce` works on plain data, so
``benchmarks/tests/test_setup_readers.py`` runs it without the program.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

from benchmarks import harness

IMPORT_SPAN = "ds.setup.import"
INITIALIZE_SPAN = "ds.setup.initialize"
SETUP_PREFIX = "ds.setup."
STEP_PROGRAM = "ds_train_step"
#: a build row is printed where its phases took more than this together
PRINT_OVER_S = 0.010


def _build_s(row: Dict) -> float:
    return row["trace_s"] + row["lower_s"] + row["compile_s"]


def reduce(spans: Sequence[Dict], builds: Sequence[Dict],
           program: Optional[Dict], steps: Sequence[Sequence[float]],
           t_start: float, setup_s: float) -> Dict:
    """The eight metrics and what is printed beside them.

    ``spans``: ``steplog.setup()``; ``builds``: ``steplog.builds()``;
    ``program``: the newest step program's ``name``, ``first_call_s``,
    ``second_call_s``; ``steps``: rows ``[step, enter, dispatched, exit]``
    of the step record, oldest first. Set-up is ``[t_start, t_start +
    setup_s]``: a span counts with the part of it inside, a step row if its
    span closed inside, a build row if its first event closed inside (a row
    holds every build of one name under one span, so a row whose events lie
    on both sides of the window's start counts whole, and is named under
    ``straddling_rows``).
    """
    t_window = t_start + setup_s

    def inside(start: float, end: Optional[float]) -> float:
        if end is None:
            return 0.0
        return max(0.0, min(end, t_window) - max(start, t_start))

    def length_of(name: str) -> float:
        return sum(inside(s["start"], s["end"]) for s in spans
                   if s["name"] == name)

    by_id = {s["id"]: s["name"] for s in spans}
    said_spans = [{"name": s["name"], "parent": by_id.get(s["parent"]),
                   "start_s": s["start"] - t_start,
                   "length_s": None if s["end"] is None
                   else s["end"] - s["start"],
                   "self_s": s["self_s"],
                   **{k: v for k, v in s.items()
                      if k not in ("id", "name", "parent", "start", "end",
                                   "self_s")}}
                  for s in spans if s["start"] < t_window]
    warmup = [r for r in steps if t_start <= r[1] and r[3] <= t_window]
    before = [b for b in builds if b["first"] < t_window]
    step_rows = [b for b in before if b["name"].startswith(STEP_PROGRAM)]
    by_span: Dict[str, Dict[str, float]] = {}
    for b in before:
        slot = by_span.setdefault(b["span"], {"lowers": 0, "compiles": 0,
                                              "cache_hits": 0, "build_s": 0.0})
        slot["lowers"] += b["lowers"]
        slot["compiles"] += b["compiles"]
        slot["cache_hits"] += b["cache_hits"]
        slot["build_s"] += _build_s(b)
    import_s = length_of(IMPORT_SPAN)
    engine_s = length_of(INITIALIZE_SPAN)
    steps_s = float(sum(r[3] - r[1] for r in warmup))
    program = program or {}
    return {
        "setup_import_s": import_s,
        "setup_engine_build_s": engine_s,
        "setup_engine_programs": float(sum(
            v["lowers"] for s, v in by_span.items()
            if s.startswith(SETUP_PREFIX))),
        "setup_step_program_build_s": float(sum(map(_build_s, step_rows))),
        "setup_step_program_builds": float(sum(b["lowers"]
                                               for b in step_rows)),
        "setup_step_first_call_s": float(program.get("first_call_s") or 0.0),
        "setup_step_second_call_s": float(program.get("second_call_s")
                                          or 0.0),
        "setup_outside_program_s": setup_s - import_s - engine_s - steps_s,
        "said": {
            "setup_s": setup_s,
            "warmup_steps_s": steps_s,
            "spans": said_spans,
            "warmup_steps": [{"step": int(r[0]), "start_s": r[1] - t_start,
                              "span_s": r[3] - r[1],
                              "put_and_dispatch_s": r[2] - r[1]}
                             for r in warmup],
            "step_program": program,
            "programs_by_span": by_span,
            "builds_over_10ms": [
                {**{k: v for k, v in b.items() if k not in ("first", "last")},
                 "first_s": b["first"] - t_start,
                 "last_s": b["last"] - t_start}
                for b in before if _build_s(b) > PRINT_OVER_S],
            "straddling_rows": [[b["name"], b["span"]] for b in before
                                if b["last"] >= t_window],
        }}


def collect(ctx: Dict) -> Optional[Dict]:
    """The program's record as :func:`reduce` takes it; None where the
    program keeps none."""
    try:
        from deepspeed_tpu.observability import steplog
    except ImportError:
        return None
    if not hasattr(steplog, "setup"):
        return None
    setup_s = ctx["values"].get("setup_s")
    if setup_s is None:
        return None
    table = [p for p in steplog.programs()
             if p.name.startswith(STEP_PROGRAM)]
    program = None
    if table:
        row = table[-1]
        program = {"name": row.name, "first_call_s": row.first_call_s,
                   "second_call_s": row.second_call_s, "build": row.build()}
    return {"spans": steplog.setup(), "builds": steplog.builds(),
            "program": program,
            "steps": steplog.get_steplog().steps().tolist(),
            "t_start": harness.T_PROCESS_START, "setup_s": float(setup_s),
            "listener_calls": steplog.build_events()}


def analysis(ctx: Dict) -> Dict:
    if "setup_program" in ctx:
        return ctx["setup_program"]
    data = collect(ctx)
    a: Dict = {}
    if data is not None:
        calls = data.pop("listener_calls")
        a = reduce(**data)
        harness.say(setup_program={
            **{k: v for k, v in a.items() if k != "said"}, **a["said"],
            "listener_calls": calls})
    ctx["setup_program"] = a
    return a


def value(ctx: Dict, key: str) -> Optional[float]:
    v = analysis(ctx).get(key)
    return None if v is None else float(v)


#: the keys of :func:`reduce` that are metrics
METRIC_KEYS = ("setup_import_s", "setup_engine_build_s",
               "setup_engine_programs", "setup_step_program_build_s",
               "setup_step_program_builds", "setup_step_first_call_s",
               "setup_step_second_call_s", "setup_outside_program_s")
