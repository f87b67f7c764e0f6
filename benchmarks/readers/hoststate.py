"""Readers of what the program says of the state of its host thread: the
``host`` ring beside the step record and the thread's clocks on the set-up
spans (``deepspeed_tpu.observability.steplog``). ``HOSTSTATE.md`` beside this
file says which metric reads which field.

Thin: the window's rows are taken as ``readers/program.py:_steplog_part``
takes them (the record's last ``values["steps"]`` rows, the period in which
the runner stopped the profiler left out), the arithmetic is the program's
own (``steplog.host_states``), and set-up is sums over the samples the spans
and the step rows carry. Everything is computed once a run
(:func:`analysis`, kept in the context) and printed on one ``host_state``
earlier line; each metric is one key of it (:func:`value`). A program
without ``steplog.host_states`` gives an empty analysis: every metric is
then left out and nothing is raised.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, List, Optional, Sequence

from benchmarks import harness
from benchmarks.readers import program as program_reader
from benchmarks.readers.setup import IMPORT_SPAN, INITIALIZE_SPAN

#: the keys of an analysis that are metrics (``benchmarks/metrics/*.json``
#: name one each); the two ``*_runnable_*`` pairs are computed where the
#: host can tell and listed in no manifest while the chip's host cannot
METRIC_KEYS = ("train_put_ms", "train_dispatch_ms",
               "train_span_off_cpu_share", "slow_step_off_cpu_share",
               "host_other_threads_cpu_share", "setup_outside_off_cpu_s")
RUNNABLE_KEYS = ("train_span_runnable_share", "slow_step_runnable_share",
                 "setup_outside_runnable_s")


def _split(start: Sequence, end: Sequence) -> Dict[str, float]:
    """Two samples ``[t, cpu_ns, runnable_ns, process_cpu_ns]`` of one
    thread (a clock the host lacks None or NaN; the last may be missing) as
    seconds of wall, on a core, runnable, off the core and of the other
    threads' CPU."""
    def d(i):
        a, b = start[i], end[i]
        return 0.0 if a is None or b is None or a != a or b != b else b - a
    wall, cpu, runnable = d(0), d(1) / 1e9, d(2) / 1e9
    out = {"wall_s": wall, "cpu_s": cpu, "runnable_s": runnable,
           "off_cpu_s": wall - cpu - runnable}
    if len(start) > 3 and len(end) > 3:
        out["other_cpu_s"] = d(3) / 1e9 - cpu
    return out


def setup_part(spans: Sequence[Dict], steps: Sequence[Sequence[float]],
               host: Sequence[Sequence[float]], n_window: int,
               t_start: float) -> Dict:
    """How the main thread spent set-up outside the program's own spans.

    ``spans``: ``steplog.setup()``; ``steps`` / ``host``: every row of the
    step record and of the ring beside it, oldest first, the last
    ``n_window`` of them the window's. Set-up runs from ``t_start``
    (``harness.T_PROCESS_START``) to the first window step's enter; the
    thread's counters are cumulative since it began, so that sample alone
    says how all of it was spent (what they had counted by the first sample,
    the interpreter's start before the harness's clock, is printed as
    ``before_the_clock`` and counted nowhere). Taken out: the ``ds.setup.import`` and
    ``ds.setup.initialize`` spans and the warm-up steps' spans, by their own
    samples. What is left is what ``setup_outside_program_s`` holds, and is
    printed piece by piece (``between``): process start to the import, the
    import's end to ``initialize`` (the TPU runtime's start, the model's
    construction), its end to the first warm-up step (the reference check),
    and from each warm-up step's exit on.
    """
    first = len(steps) - n_window
    if n_window <= 0 or first < 0 or host[first][0] != host[first][0]:
        return {}
    at_window = [steps[first][1], host[first][0], host[first][1],
                 host[first][2]]
    marks: List = []            # (name, sample at start, sample at end)
    for s in spans:
        if s["name"] in (IMPORT_SPAN, INITIALIZE_SPAN) \
                and s.get("host_end") and s["end"] <= at_window[0]:
            marks.append((s["name"], s["host_start"], s["host_end"]))
    # the counters began with the thread, before the harness's clock did
    # (the interpreter's start): they count from the first sample there is
    before = marks[0][1] if marks else [t_start, 0.0, 0.0, 0.0]
    zero = [t_start] + list(before[1:])
    whole = _split(zero, at_window)
    for r, h in zip(steps[:first], host[:first]):
        if r[1] >= t_start:
            marks.append((f"warmup_step_{int(r[0])}", [r[1], h[0], h[1]],
                          [r[3], h[4], h[5]]))
    marks.sort(key=lambda m: m[1][0])
    inside = [{"name": name, **_split(a, b)} for name, a, b in marks]
    between, at = [], ("process_start", zero)
    for name, a, b in marks + [("window", at_window, None)]:
        between.append({"from": at[0], "to": name, **_split(at[1], a)})
        at = (name, b)
    out = {"setup_outside_off_cpu_s":
           whole["off_cpu_s"] - sum(m["off_cpu_s"] for m in inside),
           "setup_outside_runnable_s":
           whole["runnable_s"] - sum(m["runnable_s"] for m in inside),
           "setup": {"before_the_clock": {
                         "cpu_s": (before[1] or 0.0) / 1e9,
                         "process_cpu_s": (before[3] or 0.0) / 1e9},
                     "whole": whole, "inside": inside, "between": between,
                     "spans": [{"name": s["name"],
                                **_split(s["host_start"], s["host_end"])}
                               for s in spans
                               if s.get("host_start") and s.get("host_end")]}}
    return out


def clock_offset(step_spans: Sequence, steps: Sequence[Sequence[float]]
                 ) -> Optional[Dict[str, float]]:
    """The record's place on the profiler's clock: each ``ds.train.step``
    annotation's start (nanoseconds, the trace's host clock) less its row's
    enter (``perf_counter``), by the annotation's ``step`` argument, in
    microseconds: the median, and the distance between the extremes. Add
    the median to a row's stamp to lay it on the kept trace."""
    enter = {int(r[0]): r[1] for r in steps}
    gaps = [s.start - round(enter[int(s.label)] * 1e9) for s in step_spans
            if s.label.isdigit() and int(s.label) in enter]
    if not gaps:
        return None
    return {"median_us": statistics.median(gaps) / 1e3,
            "spread_us": (max(gaps) - min(gaps)) / 1e3, "steps": len(gaps)}


def _sayable(x):
    """Non-finite floats as None: the line is JSON."""
    if isinstance(x, dict):
        return {k: _sayable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_sayable(v) for v in x]
    return None if isinstance(x, float) and not math.isfinite(x) else x


def analysis(ctx: Dict) -> Dict:
    if "host_state" in ctx:
        return ctx["host_state"]
    a: Dict = {}
    try:
        from deepspeed_tpu.observability import steplog
    except ImportError:
        steplog = None
    n = int(ctx["values"].get("steps") or 0)
    if steplog is not None and hasattr(steplog, "host_states") and n:
        path = program_reader.xplane_path(ctx["cell"]["name"])
        spans = program_reader.load_program_events(path)["spans"] \
            if path else []
        log = steplog.get_steplog()
        steps, host = log.steps(), log.host()
        said: Dict = {"unavailable": steplog.unavailable()}
        window = steplog.host_states(
            steps[-n:], host[-n:],
            exclude=program_reader.last_traced_step(spans))
        if window:
            a.update({
                "train_put_ms": window["median_ms"]["put"],
                "train_dispatch_ms": window["median_ms"]["dispatch"],
                "train_span_off_cpu_share": window["span_off_cpu_share"],
                "slow_step_off_cpu_share": window["slow_off_cpu_share"],
                "host_other_threads_cpu_share":
                    window["other_threads_cpu_share"]})
            if window["runnable_read"]:
                a.update({
                    "train_span_runnable_share":
                        window["span_runnable_share"],
                    "slow_step_runnable_share":
                        window["slow_runnable_share"]})
            said["window"] = window
        part = setup_part(steplog.setup(), steps.tolist(), host.tolist(), n,
                          harness.T_PROCESS_START)
        if part:
            said["setup"] = part.pop("setup")
            if not (window and window["runnable_read"]):
                part.pop("setup_outside_runnable_s")
            a.update(part)
        said["clock_offset"] = clock_offset(
            [s for s in spans if s.name == program_reader.STEP_SPAN], steps)
        harness.say(host_state=_sayable({**a, **said}))
    ctx["host_state"] = a
    return a


def value(ctx: Dict, key: str) -> Optional[float]:
    v = analysis(ctx).get(key)
    return None if v is None else float(v)
