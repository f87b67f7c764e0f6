"""Readers of what a sharded train step exchanges between chips: the
step-program row's record of the collectives in its compiled text
(``deepspeed_tpu.observability.steplog``: ``StepProgram.collectives()`` and
the sums it answers as attributes), and from the device trace the time each
core spent in or waiting on an exchange, and how far apart the chips finish
a step. ``COLLECTIVES.md`` beside this file says which metric reads which
field, and what a second sharded cell needs.

Which device operations are an exchange is the yardstick's to say, and is
said here (:func:`is_collective`: the one list; a program PR cannot move
``collective_exposed_ms`` by editing it). The trace's readings need nothing
of the program; the counters need its record, and a program without one
(the parent of the PR that brought it) leaves them out and raises nothing.
A one-chip cell's record is empty and its sums read 0.

The reductions work on plain data (``Op`` tuples by device plane) so that
``benchmarks/tests/test_collectives_readers.py`` runs them on the CPU
against ``testdata/zero3_4chip_planes.json.gz``, two runs of the step on the
four planes of a v5e host.
"""

from __future__ import annotations

import re
import statistics
import time
from typing import Dict, List, Optional, Sequence

from benchmarks import harness, trace_reduce
from benchmarks.readers import program
from benchmarks.trace_reduce import Op


# The names a device trace's op line gives an exchange: the opcode with its
# instruction number, one blocking operation (``all-reduce.9``,
# ``all-to-all``) or the halves of an asynchronous pair
# (``collective-permute-start.22``, ``collective-permute-done.22``), and the
# two fusions the v5e compiler wraps a gather's first and last step in
# (``async-collective-start``, ``async-collective-done.1``; the steps between
# them ride in compute fusions named ``fusion.n`` and are compute). Read off
# the first four-chip trace (PERF.md §6, PR 69);
# ``tests/test_collectives_readers.py`` holds it to every name of
# ``testdata/zero3_4chip_planes.json.gz``.
_EXCHANGE = re.compile(
    r"^(?:all-gather|reduce-scatter|all-reduce|all-to-all|collective-permute"
    r"|async-collective)(?:-start|-done)?(?:\.\d+)*$")


def is_collective(instruction_name: str) -> bool:
    """Whether a device operation of this name (an HLO instruction's, as the
    op line of a device trace has it, ``%`` or not) is an exchange between
    chips: while it holds the line the core computes nothing."""
    return bool(_EXCHANGE.match(instruction_name.lstrip("%")))


def _steplog():
    """The program's record module where it has the collective record."""
    try:
        from deepspeed_tpu.observability import steplog
    except ImportError:
        return None
    return steplog if hasattr(steplog, "collectives") else None


# ---- the trace file -------------------------------------------------------

def load_module_runs(path: str, win) -> Dict[str, List[Op]]:
    """device plane -> the step program's module events wholly inside the
    window, in order (``readers/program.py`` keeps the first plane's alone)."""
    from jax.profiler import ProfileData

    out: Dict[str, List[Op]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != program.MODULES_LINE:
                continue
            events = sorted((Op(ev.name, int(ev.start_ns),
                                int(ev.start_ns) + int(ev.duration_ns))
                             for ev in line.events), key=lambda o: o.start)
            out[plane.name] = program.step_modules(events, win)[1]
    return out


# ---- reductions on plain data ---------------------------------------------

def exposed_ms(ops: Sequence[Op], runs: Sequence[Op],
               scope_of: Optional[Dict[str, str]] = None) -> Dict:
    """One plane: self time on the ``XLA Ops`` line (``ops``, sorted by
    start) of the operations :func:`is_collective` names (blocking forms,
    ``-start`` and ``-done`` halves alike) inside the step program's
    ``runs``, in milliseconds a step: the time the core itself spent in or
    waiting on an exchange. In all, by kind (the name without its half and
    number) and by the scope ``scope_of`` gives the instruction that opened
    the exchange."""
    n = len(runs)
    by_kind: Dict[str, float] = {}
    by_scope: Dict[str, float] = {}
    i = 0
    for op, t in trace_reduce.self_times(ops):
        while i < n and runs[i].end <= op.start:
            i += 1
        if i == n or op.start < runs[i].start or op.end > runs[i].end \
                or not is_collective(op.name):
            continue
        base = op.name.split(".")[0]
        kind = base.removesuffix("-start").removesuffix("-done")
        by_kind[kind] = by_kind.get(kind, 0.0) + t / 1e6 / n
        if scope_of is not None:
            opened = op.name.replace("-done", "-start", 1)
            scope = scope_of.get(opened, scope_of.get(op.name)) or "(none)"
            by_scope[scope] = by_scope.get(scope, 0.0) + t / 1e6 / n
    return {"exposed_ms": sum(by_kind.values()), "by_kind": by_kind,
            "by_scope": by_scope}


def step_skew_ms(runs_by_plane: Dict[str, Sequence[Op]]) -> Optional[float]:
    """Median over the window's steps of the latest less the earliest end of
    the step program across the planes: how long the first chip to finish
    waits for the last. None with fewer than two planes, or where the planes
    hold different numbers of runs (a window that cut one short)."""
    runs = list(runs_by_plane.values())
    if len(runs) < 2 or not runs[0] or len({len(r) for r in runs}) != 1:
        return None
    ends = zip(*[[r.end for r in plane] for plane in runs])
    return statistics.median((max(e) - min(e)) / 1e6 for e in ends)


def record_part(rows: Sequence[Dict], sums: Dict) -> Dict:
    """What the counters, and the earlier line, say of the row's record."""
    by: Dict[str, int] = {}
    for r in rows:
        key = (f"{r['kind']} {r['scope']} "
               f"{'backward' if r['backward'] else 'forward'} "
               f"{'in' if r['in_layer_loop'] else 'outside'}")
        by[key] = by.get(key, 0) + r["bytes"] * r["trips"]
    return {**sums,
            "bytes_by_kind_scope_direction_loop": dict(
                sorted(by.items(), key=lambda kv: -kv[1])),
            "blocking_calls_per_step": sum(
                r["trips"] for r in rows if not r["async"])}


# ---- one analysis a run ---------------------------------------------------

def analysis(ctx: Dict) -> Dict:
    if "collectives" in ctx:
        return ctx["collectives"]
    a: Dict = {}
    ctx["collectives"] = a
    if int(ctx["cell"].get("chips", 1)) < 2:
        return a
    steplog = _steplog()
    table = [] if steplog is None else [
        p for p in steplog.programs() if p.name.startswith("ds_train_step")]
    rows = None
    if table:
        row = table[-1]
        t0 = time.perf_counter()
        rows = row.collectives()
        # reading the text; the compile behind it is the row's, made once
        # for whoever asks first (``readers/program.py``, by the manifest's
        # order)
        a["record_s"] = time.perf_counter() - t0
        if rows is not None:
            a.update(record_part(rows, steplog.collective_sums(rows)))
            a["zero_stage"], a["mesh_axes"] = row.zero_stage, row.mesh_axes
    trace, reduced = ctx.get("trace"), ctx.get("reduced") or {}
    path = program.xplane_path(ctx["cell"]["name"])
    if trace is not None and path and reduced.get("window_ns"):
        runs = load_module_runs(path, tuple(reduced["window_ns"]))
        scope_of = {r["name"]: r["scope"] for r in rows or []}
        planes = {name: exposed_ms(ops, runs.get(name, []), scope_of)
                  for name, ops in trace.devices.items() if runs.get(name)}
        if planes:
            a["planes"] = planes
            a["collective_exposed_ms"] = statistics.mean(
                p["exposed_ms"] for p in planes.values())
            step_ms = statistics.median(
                (r.end - r.start) / 1e6
                for name in planes for r in runs[name])
            a["step_device_ms_all_planes"] = step_ms
            a["collective_exposed_share"] = \
                100.0 * a["collective_exposed_ms"] / step_ms
        skew = step_skew_ms(runs)
        if skew is not None:
            a["device_step_skew_ms"] = skew
    if a:
        harness.say(collectives=a)
    return a


def value(ctx: Dict, key: str) -> Optional[float]:
    v = analysis(ctx).get(key)
    return None if v is None else float(v)
