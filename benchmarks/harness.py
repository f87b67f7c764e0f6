"""What every runner shares: finding a cell's files, refusing to measure
without the chip, the compile cache, compile counting, the profiler window,
per-layer metric readers and the one result line.

Everything that belongs to one cell, configuration, traffic mix or per-layer
metric is a file of its own, found by name:

* ``workloads/<cell>.json``  — configuration, traffic, runner, chips, why;
* ``configs/<config>.json``  — the published sizes, what was cut, the
  deployment (engine settings) it stands for;
* ``traffic/<traffic>.json`` — parameters of the generator;
* ``metrics/<metric>.json``  — the reader (``module:function`` under
  ``benchmarks``) with its arguments; which cells report the metric, its
  unit, layer and what it moves are ``BENCHMARK.json``'s to say.
"""

from __future__ import annotations

import glob
import importlib
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
T_PROCESS_START = time.perf_counter()       # run.py imports this first


def _load(kind: str, name: str) -> Dict:
    path = os.path.join(HERE, kind, f"{name}.json")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmarks: no {kind}/{name}.json")
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> Dict:
    """The cell with its configuration and traffic merged in; a cell's
    ``traffic_params`` override the traffic file (what belongs to the pair
    of configuration and mix, not to the mix)."""
    cell = _load("workloads", name)
    cell["name"] = name
    cell["config_name"] = cell["config"]
    cell["config"] = _load("configs", cell["config"])
    cell["traffic_name"] = cell["traffic"]
    cell["traffic"] = {**_load("traffic", cell["traffic"]),
                       **cell.get("traffic_params", {})}
    return cell


def load_peaks(device_kind: str) -> Dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise SystemExit(f"benchmarks: no peaks for device kind "
                         f"{device_kind!r} in peaks.json (have "
                         f"{sorted(peaks)}); add a row with its source")
    return peaks[device_kind]


def apply_rehearsal(cell: Dict) -> Dict:
    """Toy widths and short lengths for a CPU rehearsal of the control flow.
    A rehearsal prints no result line."""
    with open(os.path.join(HERE, "rehearsal.json")) as f:
        toy = json.load(f)
    def merge(into: Dict, frm: Dict, only_known: bool) -> None:
        for key, val in frm.items():
            if only_known and key not in into:
                continue
            if isinstance(val, dict) and isinstance(into.get(key), dict):
                into[key] = {**into[key], **val}
            else:
                into[key] = val

    merge(cell["config"], toy["config"], only_known=False)
    merge(cell["config"]["deployment"],
          toy["deployment"].get(cell["runner"], {}), only_known=False)
    merge(cell["traffic"], toy["traffic"], only_known=True)
    return cell


# ---- the device -----------------------------------------------------------

def setup_jax(chips: int, rehearse: bool):
    """Import JAX, refuse anything but the chips the cell asks for, and put
    the persistent compile cache at its fixed place. Returns (jax, devices
    to use, device facts)."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flag = f"--xla_force_host_platform_device_count={max(chips, 1)}"
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                       + " " + flag).strip()
    import jax

    from deepspeed_tpu.utils.compile_cache import place_compile_cache

    if not rehearse:
        # where JAX_COMPILATION_CACHE_DIR says, else <checkout>/.jax_cache;
        # every program is kept, however quickly it compiled, so that a
        # second run finds all of them
        place_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    else:
        jax.config.update("jax_enable_compilation_cache", False)
    devs = jax.devices()
    facts = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if not rehearse:
        if facts["platform"] != "tpu":
            raise SystemExit(f"benchmarks: JAX found no TPU (platform "
                             f"{facts['platform']!r}); a measuring run never "
                             f"falls back")
        if len(devs) < chips:
            raise SystemExit(f"benchmarks: the cell needs {chips} chips, "
                             f"JAX found {len(devs)}")
    if len(devs) < chips:
        raise SystemExit(f"benchmarks: rehearsal needs {chips} devices")
    return jax, devs[:chips], facts


def memory_peak_bytes(devices) -> int:
    peak = 0
    for d in devices:
        s = d.memory_stats() or {}
        peak = max(peak, int(s.get("peak_bytes_in_use",
                                   s.get("bytes_in_use", 0)) or 0))
    return peak


class CompileCount:
    """Backend compiles seen by this process (``jax.monitoring``), and the
    persistent cache's hits and misses."""

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# ---- host spans and the profiler window -----------------------------------

class Spans:
    """Host spans written into the profiler's trace as ``TraceAnnotation``s
    (``bench.<name>``), so that device gaps can be named by what the host
    was doing. Cheap when no trace is being taken."""

    def __init__(self):
        import jax

        self._annotate = jax.profiler.TraceAnnotation

    def span(self, name: str):
        return self._annotate("bench." + name)

    def wrap(self, name: str, fn):
        def wrapped(*a, **kw):
            with self.span(name):
                return fn(*a, **kw)
        return wrapped


class TraceWindow:
    """With ``--trace 1``: the profiler runs for ``seconds`` of the measured
    window, written under ``<checkout>/.bench_trace/<cell>`` (a fixed,
    ignored path, emptied first) and reduced when the window is over."""

    def __init__(self, enabled: bool, cell: str, seconds: float):
        self.enabled = enabled
        self.seconds = float(seconds)
        self.dir = os.path.join(ROOT, ".bench_trace", cell)
        self._t0: Optional[float] = None
        self._ann = None
        self.active = False
        self.done = False
        self.trace = None           # the plain trace, once reduced

    def start(self) -> None:
        if not self.enabled:
            return
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        self.active = True

    def maybe_stop(self) -> None:
        """Call between steps; stops the profiler once its time is up."""
        if self.active and time.perf_counter() - self._t0 >= self.seconds:
            self.stop()

    def stop(self) -> None:
        if not self.active:
            return
        import jax

        self._ann.__exit__(None, None, None)
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        self.active = False
        self.done = True

    def covers(self, t: float) -> bool:
        """Whether host time ``t`` (perf_counter) fell inside the traced
        window."""
        return self.done and self._t0 <= t <= self.t_stop

    def reduce(self) -> Dict:
        if not self.done:
            return {}
        from benchmarks import trace_reduce

        paths = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not paths:
            return {}
        self.trace = trace_reduce.load_xplane(max(paths, key=os.path.getmtime))
        reduced = trace_reduce.reduce(self.trace)
        if reduced:
            say(trace_ops=trace_reduce.named_ops(
                self.trace, tuple(reduced["window_ns"]), 40))
        return reduced


# ---- metrics --------------------------------------------------------------

def _manifest() -> Dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _listed(entries: List[Dict], cell_name: str) -> List[Dict]:
    """The metrics of ``BENCHMARK.json`` this cell reports: those that list
    it under ``workloads``, and those that list no cells."""
    return [m for m in entries
            if "workloads" not in m or cell_name in m["workloads"]]


def read_per_layer(cell_name: str, ctx: Dict[str, Any]) -> Dict[str, Dict]:
    """Run the reader that ``metrics/<name>.json`` names, for every
    per-layer metric ``BENCHMARK.json`` lists for this cell, on what the run
    collected. A reader that finds nothing to read returns None and the
    metric is left out."""
    out = {}
    for m in _listed(_manifest()["per_layer"], cell_name):
        spec = _load("metrics", m["name"])
        mod, fn = spec["reader"].split(":")
        reader = getattr(importlib.import_module(f"benchmarks.{mod}"), fn)
        value = reader(ctx, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def end_to_end_for(cell_name: str) -> Dict[str, str]:
    """name -> unit of the end-to-end metrics this cell reports."""
    return {m["name"]: m["unit"]
            for m in _listed(_manifest()["end_to_end"], cell_name)}


def end_to_end_metrics(cell_name: str, values: Dict) -> Dict[str, Dict]:
    return {name: {"value": float(values[name]), "unit": unit}
            for name, unit in end_to_end_for(cell_name).items()
            if values.get(name) is not None}


def fill_metrics(result: Dict, cell: Dict, traced: bool, trace: TraceWindow,
                 values: Dict, peak: Optional[Dict]) -> Dict:
    """Complete a result: without a trace the cell's end-to-end metrics;
    with one its per-layer metrics, the device's busy time and the
    breakdown."""
    if not traced:
        result["metrics"] = end_to_end_metrics(cell["name"], values)
        return result
    reduced = trace.reduce()
    ctx = {"cell": cell, "cfg": cell["config"], "peak": peak,
           "trace": trace.trace, "reduced": reduced, "values": values}
    result["metrics"] = read_per_layer(cell["name"], ctx)
    if ctx.get("roofline_notes"):
        say(roofline=ctx["roofline_notes"])
    if reduced:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    return result


def say(**facts) -> None:
    """An earlier line of the output: facts for a reader, not the result."""
    print(json.dumps(facts), flush=True)


def emit_result(result: Dict) -> None:
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
