"""Readers of what the program itself says about its train step: the ``ds.``
spans it writes into the profiler's trace, the name of its jitted step
program on the device plane's module line, the scope of every device
operation, and the per-step record, pause ring and step-program table of
``deepspeed_tpu.observability.steplog``. ``PROGRAM.md`` beside this file says
which metric reads which of them.

Everything is computed once a run (:func:`analysis`, kept in the context)
and printed on an earlier line; each metric is one key of it (:func:`value`).
A program that has none of this (no ``ds.`` span, no ``steplog``) gives an
empty analysis: every metric is then left out and nothing is raised.

The arithmetic works on plain data (``Op`` tuples, arrays, HLO text) so that
``benchmarks/tests/test_program_readers.py`` runs it on the CPU against a
trace recorded on the chip.
"""

from __future__ import annotations

import glob
import os
import re
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks import harness, trace_reduce
from benchmarks.trace_reduce import Op, Trace

SPAN_PREFIX = "ds."
STEP_SPAN = "ds.train.step"
DISPATCH_SPAN = "ds.train.dispatch"
MODULES_LINE = "XLA Modules"
STEP_MODULE = re.compile(r"^jit_(ds_train_step\w*)\(")
#: scopes of the step program (``models/transformer.py:STEP_SCOPES``) and
#: the metric each is summed into
GROUPS = {"attn": "attn", "mlp": "mlp", "moe": "mlp", "layers": "mlp",
          "lm_head": "head_loss", "loss": "head_loss",
          "final_norm": "head_loss", "embed": "head_loss",
          "optimizer": "optimizer", "grad_accum": "optimizer"}
_CONTAINER = re.compile(r"^(while|conditional|call)([.\d]*)$")


# ---- the trace file -------------------------------------------------------

def xplane_path(cell_name: str) -> Optional[str]:
    paths = glob.glob(os.path.join(harness.ROOT, ".bench_trace", cell_name,
                                   "plugins", "profile", "*", "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def load_program_events(path: str) -> Dict:
    """``spans``: the host plane's ``ds.`` events as ``Op`` (label = the
    annotation's ``step`` argument, where it has one); ``modules``: the first
    device plane's module line."""
    from jax.profiler import ProfileData

    spans: List[Op] = []
    modules: List[Op] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        step = [str(v) for k, v in ev.stats if k == "step"]
                        s = int(ev.start_ns)
                        spans.append(Op(ev.name, s, s + int(ev.duration_ns),
                                        step[0] if step else ""))
        elif trace_reduce.DEVICE_PLANE.match(plane.name) and not modules:
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for ev in line.events:
                        s = int(ev.start_ns)
                        modules.append(Op(ev.name, s,
                                          s + int(ev.duration_ns)))
    spans.sort(key=lambda o: (o.start, -o.end))
    modules.sort(key=lambda o: o.start)
    return {"spans": spans, "modules": modules}


# ---- scopes from the compiled program's text ------------------------------

_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OPERAND = re.compile(r"(?<![=\w])%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")


def scope_of(op_name: str) -> Tuple[Optional[str], str]:
    """(innermost step scope of a JAX ``op_name`` path, direction). Scopes
    nest (``layers/while/body/attn/dot_general``): the innermost counts."""
    scope = None
    for part in re.split(r"[/()]", op_name):
        if part in GROUPS:
            scope = part
    return scope, ("backward" if "transpose(" in op_name else "forward")


def instruction_scopes(hlo_text: str) -> Dict[str, Tuple[Optional[str], str]]:
    """instruction name -> (scope, direction) for every instruction of a
    compiled module's text. In this order: the instruction's own ``op_name``;
    for a fusion whose own path names no block (nothing, or only the layer
    loop), the scope most of its fused instructions carry; for what the
    compiler made itself and gave no path (copies, async halves, zero
    fills), the scope of the nearest operand that has one, else of the
    nearest user."""
    own: Dict[str, Tuple[Optional[str], str]] = {}
    calls: Dict[str, str] = {}
    members: Dict[str, List[str]] = {}
    operands: Dict[str, List[str]] = {}
    comp = ""
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        members.setdefault(comp, []).append(name)
        meta = _OP_NAME.search(line)
        own[name] = scope_of(meta.group(1)) if meta else (None, "forward")
        operands[name] = _OPERAND.findall(line[m.end():])
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)
    out = dict(own)
    for name, (scope, _) in own.items():
        if scope not in (None, "layers") or name not in calls:
            continue
        votes: Dict[Tuple[str, str], int] = {}
        for inner in members.get(calls[name], []):
            s = own[inner]
            if s[0] not in (None, "layers"):
                votes[s] = votes.get(s, 0) + 1
        if votes:
            out[name] = max(votes.items(), key=lambda kv: kv[1])[0]
    users: Dict[str, List[str]] = {}
    for name, ops in operands.items():
        for o in ops:
            users.setdefault(o, []).append(name)
    settled = dict(out)
    for name, (scope, _) in settled.items():
        if scope is None:
            out[name] = (_nearest(name, operands, settled)
                         or _nearest(name, users, settled)
                         or (None, "forward"))
    return out


def _nearest(name: str, edges: Dict[str, List[str]],
             scopes: Dict[str, Tuple[Optional[str], str]]
             ) -> Optional[Tuple[str, str]]:
    """Breadth-first along ``edges`` to the first instruction with a scope."""
    seen, frontier = {name}, [name]
    while frontier:
        nxt = []
        for n in frontier:
            for o in edges.get(n, []):
                if o in seen:
                    continue
                seen.add(o)
                if scopes.get(o, (None,))[0] is not None:
                    return scopes[o]
                nxt.append(o)
        frontier = nxt
    return None


# ---- reductions on plain data ---------------------------------------------

def step_modules(modules: Sequence[Op], win: Tuple[int, int]
                 ) -> Tuple[Optional[str], List[Op]]:
    """The step program's name and its module events wholly inside the
    window."""
    hits = [(STEP_MODULE.match(m.name), m) for m in modules
            if m.start >= win[0] and m.end <= win[1]]
    hits = [(h.group(1), m) for h, m in hits if h]
    return (hits[0][0], [m for _, m in hits]) if hits else (None, [])


def device_ms_by_scope(ops: Sequence[Op], runs: Sequence[Op],
                       scopes: Dict[str, Tuple[Optional[str], str]]
                       ) -> Dict[str, Dict[str, float]]:
    """Self time of the device operations inside the step program's runs,
    by scope and direction, in milliseconds a step. ``unscoped`` holds what
    no scope claims, with the heaviest such instructions."""
    if not runs:
        return {}
    acc: Dict[str, Dict[str, float]] = {}
    loose: Dict[str, float] = {}
    i = 0
    for op, t in trace_reduce.self_times(ops):
        while i < len(runs) and runs[i].end <= op.start:
            i += 1
        if i == len(runs) or op.start < runs[i].start \
                or op.end > runs[i].end or _CONTAINER.match(op.name):
            continue
        scope, direction = scopes.get(op.name, (None, "forward"))
        if scope is None:
            loose[op.name] = loose.get(op.name, 0.0) + t / 1e6 / len(runs)
        slot = acc.setdefault(scope or "unscoped", {})
        slot[direction] = slot.get(direction, 0.0) + t / 1e6 / len(runs)
    acc["unscoped_ops"] = dict(sorted(loose.items(),
                                      key=lambda kv: -kv[1])[:10])
    return acc


def host_phases(spans: Sequence[Op], win: Tuple[int, int]
                ) -> Dict[str, float]:
    """Median milliseconds of each ``ds.`` span inside the window."""
    by_name: Dict[str, List[float]] = {}
    for s in spans:
        if s.start >= win[0] and s.end <= win[1]:
            by_name.setdefault(s.name, []).append((s.end - s.start) / 1e6)
    return {name: statistics.median(xs) for name, xs in by_name.items()}


def device_start_after_dispatch_ms(spans: Sequence[Op], runs: Sequence[Op]
                                   ) -> Optional[Dict[str, float]]:
    """Each run's start on the device's clock minus the opening of its
    ``ds.train.dispatch`` on the host's. No run starts before its dispatch
    opened, so a negative minimum is how far the device's clock runs ahead."""
    opens = [s.start for s in spans if s.name == DISPATCH_SPAN]
    gaps = []
    for i, t in enumerate(opens):
        nxt = opens[i + 1] if i + 1 < len(opens) else None
        first = next((r for r in runs if r.end > t
                      and (nxt is None or r.start < nxt)), None)
        if first is not None:
            gaps.append((first.start - t) / 1e6)
    if not gaps:
        return None
    return {"min": min(gaps), "median": statistics.median(gaps),
            "max": max(gaps), "runs": len(gaps)}


def last_traced_step(spans: Sequence[Op]) -> List[int]:
    """The number of the last ``ds.train.step`` the trace holds: the runner
    stopped the profiler before the next one began."""
    steps = [int(s.label) for s in spans
             if s.name == STEP_SPAN and s.label.isdigit()]
    return [max(steps)] if steps else []


# ---- one analysis a run ---------------------------------------------------

def _steplog_part(ctx: Dict, profiler_stopped_in: Sequence[int]) -> Dict:
    """From the program's record and tables; {} where it has none. The
    window's steps are the record's last ``values["steps"]`` rows; the period
    of the step after which the runner stopped the profiler is left out."""
    try:
        from deepspeed_tpu.observability import steplog
    except ImportError:
        return {}
    n = int(ctx["values"].get("steps") or 0)
    log = steplog.get_steplog()
    rows = log.steps()[-n:] if n else log.steps()[:0]
    out: Dict = {}
    slow = steplog.slow_steps(rows, log.pauses(),
                              exclude=profiler_stopped_in)
    if slow:
        # with no slow step there is no excess to share out: the host's
        # share reads 0 beside an excess share of 0, and is never left out
        # (a cell's result line carries every metric the manifest lists)
        out.update({"slow_step_excess_share": slow["excess_share"],
                    "slow_step_host_share": slow["host_share"] or 0.0,
                    "gc_pause_ms": slow["pause_ms_per_step"],
                    "steplog": slow})
    table = [p for p in steplog.programs() if p.name.startswith("ds_train_step")]
    if table and len(rows):
        out["step_program_builds_in_window"] = float(
            sum(p.built_at >= rows[0, 1] for p in table))
        row = table[-1]
        mem = row.memory_analysis()
        if mem:
            out["step_program_temp_bytes"] = float(mem["temp"])
        out["programs"] = [{"name": p.name, "key": p.key,
                            "built_before_window_s": rows[0, 1] - p.built_at}
                           for p in table]
        out["memory"] = mem
        out["hlo_text"] = row.hlo_text()
    return out


def analysis(ctx: Dict) -> Dict:
    if "program" in ctx:
        return ctx["program"]
    trace: Optional[Trace] = ctx.get("trace")
    path = xplane_path(ctx["cell"]["name"])
    reduced = ctx.get("reduced") or {}
    events = load_program_events(path) if path else {"spans": []}
    a: Dict = _steplog_part(ctx, last_traced_step(events["spans"]))
    if trace is not None and path and reduced.get("window_ns"):
        a.update(trace_part(trace, events, tuple(reduced["window_ns"]),
                            a.get("hlo_text")))
    said = {k: v for k, v in a.items() if k != "hlo_text"}
    if said:
        harness.say(program=said)
    ctx["program"] = a
    return a


def trace_part(trace: Trace, events: Dict, win: Tuple[int, int],
               hlo_text: Optional[str]) -> Dict:
    """What the profiler window says: module runs, device time by scope,
    host phases, idle gaps by ``ds.`` span, the clocks' offset."""
    a: Dict = {}
    spans, ops = events["spans"], next(iter(trace.devices.values()), [])
    name, runs = step_modules(events["modules"], win)
    if runs:
        a["module"] = name
        a["train_step_device_ms"] = statistics.median(
            (r.end - r.start) / 1e6 for r in runs)
    if runs and hlo_text:
        by = device_ms_by_scope(ops, runs, instruction_scopes(hlo_text))
        a["device_ms_by_scope"] = by
        for group in ("attn", "mlp", "head_loss", "optimizer"):
            a[f"{group}_device_ms"] = sum(
                sum(v.values()) for s, v in by.items()
                if GROUPS.get(s) == group)
        a["unscoped_device_ms"] = sum(by.get("unscoped", {}).values())
    phases = host_phases(spans, win)
    if phases:
        a["host_phases_ms"] = phases
        if STEP_SPAN in phases:
            a["train_host_ms"] = phases[STEP_SPAN]
        a["idle_gaps_by_span"] = trace_reduce.idle_gaps(
            Trace(devices=trace.devices, host=list(spans)), win)
        a["device_start_after_dispatch_ms"] = \
            device_start_after_dispatch_ms(spans, runs)
    return a


def value(ctx: Dict, key: str) -> Optional[float]:
    v = analysis(ctx).get(key)
    return None if v is None else float(v)
