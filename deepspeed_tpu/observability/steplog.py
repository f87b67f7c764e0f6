"""What the train step leaves behind for a reader, at no cost to the step:
a per-step record, the collector's pauses, and a table of the step programs
the engine built.

Process-wide like the metrics registry, so a reader (the benchmark's
``readers/program.py``, a debugger) reaches it without a handle on the
engine.

* :class:`StepLog` — three preallocated float64 rings. ``steps``: one row per
  ``fused_train_step`` call (step number, span enter, dispatch return, span
  exit, on ``time.perf_counter``), written once as the ``ds.train.step`` span
  closes. ``host``: beside each step row, what the calling thread's clocks
  read at enter and at exit (:func:`host_state`: on a core, runnable, the
  whole process on a core) and when ``_put_batch`` returned. ``pauses``: one
  row per cyclic collection of generation >= 1 (start, length, generation),
  written from ``gc.callbacks`` inside a ``ds.gc`` profiler annotation. A
  write stores floats into the ring: nothing outlives the step and nothing is
  collector-tracked.
* ``loss_parts`` — for a model whose loss has parts (a looped model's
  per-pass losses and exit distribution, a held share of experts' router
  counts, a sigmoid router's counts over every expert and the biases its
  rule moved, by layer the mean square of the mixer's output of a model with
  state-space layers or latent attention): the last ``PARTS_KEPT`` steps'
  loss and parts as the device values the step program returned. Writing a
  row waits for nothing; :meth:`StepLog.parts` reads them to the host when a
  reader asks, after the step.
* the step-program table — one :class:`StepProgram` per jitted step program
  an engine built, appended on a ``_fused_step_cache`` miss only. The row
  keeps the program's abstract arguments, so ``memory_analysis()`` and the
  compiled HLO text are computed when a reader asks (lowering and compiling
  again: a hit in the persistent compile cache), never at engine build or on
  a step. Two readings of that text are pure functions of it, so that they
  run on the CPU against a kept text: :func:`recomputed_kernels` (where the
  recomputation policy still pays for a kernel twice) and :func:`collectives`
  (what a sharded step exchanges between chips: ZeRO's gathers and
  reductions are the compiler's, pass no ``comm.*`` call, and stand nowhere
  but in that text). The row answers their sums as attributes
  (``row.collective_bytes_per_step``).
* :func:`slow_steps` — the arithmetic that says which steps of a record were
  slow and how much of their excess the host or the collector took;
  :func:`host_states` — on the same terms, every period's four phases (put,
  dispatch, commit, outside) and what state the thread was in inside the
  span and outside it. ``benchmarks/readers/HOSTSTATE.md`` says which metric
  reads which field.
* set-up — :func:`span` opens a span through ``EventBus.span`` and keeps its
  name on the calling thread's stack while it is open; a ``ds.setup.*`` span
  also leaves a row (name, start, end, parent, the thread's clocks at both
  ends) that :func:`setup` returns: ``ds.setup.import``,
  ``ds.setup.initialize`` and its children.
* the build record — :func:`install_build_hook` registers two
  ``jax.monitoring`` listeners that fold every trace, lowering, backend
  compile and compile-cache event of the process into :func:`builds`: one row
  per (program name, innermost recorded span open on the thread). The
  listeners fire only when JAX builds something; a step whose program is
  built costs nothing. ``benchmarks/readers/SETUP.md`` says which metric
  reads which span and field.
"""

from __future__ import annotations

import gc
import itertools
import re
import threading
import time
import weakref
from collections import Counter, deque
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence)

import numpy as np
from jax.profiler import TraceAnnotation

from deepspeed_tpu._hoststate import (host_state, thread_state,  # noqa: F401
                                      unavailable)

__all__ = ["StepLog", "StepProgram", "get_steplog", "install_gc_hook",
           "install_build_hook", "span", "setup", "builds", "build_events",
           "record_program", "programs", "slow_steps", "host_states",
           "host_state", "thread_state", "unavailable", "SLOW_FACTOR",
           "collectives", "collective_sums", "COLLECTIVE_KINDS"]

#: a step is slow when its period exceeds this many medians
SLOW_FACTOR = 1.25
#: steps whose loss parts are kept (device values: a few floats each)
PARTS_KEPT = 256
#: a row of the ``host`` ring: the thread's clocks at the span's enter
#: (:func:`host_state` less its ``t``, which the step row holds), the moment
#: ``_put_batch`` returned, and the thread's clocks at the span's exit
HOST_COLUMNS = ("enter_cpu_ns", "enter_runnable_ns", "enter_process_cpu_ns",
                "t_put", "exit_cpu_ns", "exit_runnable_ns")
_NO_HOST = (float("nan"),) * len(HOST_COLUMNS)


class StepLog:
    """Rings of the last ``size`` steps and collector pauses (see module
    docstring). ``n_steps``/``n_pauses`` count every write, so a reader can
    tell a wrapped ring."""

    def __init__(self, size: int = 4096):
        self.size = int(size)
        self._steps = np.zeros((self.size, 4))
        self._host = np.zeros((self.size, len(HOST_COLUMNS)))
        self._pauses = np.zeros((self.size, 3))
        self._parts: List[Any] = [None] * PARTS_KEPT
        self.n_steps = 0
        self.n_pauses = 0
        self.n_parts = 0

    def loss_parts(self, step: int, loss: Any, parts: Dict[str, Any]) -> None:
        """Keep one step's loss and its parts as they came out of the step
        program (device values; nothing is read here)."""
        self._parts[self.n_parts % PARTS_KEPT] = (step, loss, parts)
        self.n_parts += 1

    def parts(self, last: Optional[int] = None) -> List[Dict[str, Any]]:
        """The kept rows (the ``last`` newest of them), oldest first, read to
        the host: ``{"step", "loss", <part>: array ...}``."""
        n = self.n_parts
        rows = (self._parts[:n] if n <= PARTS_KEPT else
                self._parts[n % PARTS_KEPT:] + self._parts[:n % PARTS_KEPT])
        if last is not None:
            rows = rows[max(0, len(rows) - last):]
        return [{"step": int(step), "loss": float(loss),
                 **{k: np.asarray(v) for k, v in parts.items()}}
                for step, loss, parts in rows]

    def step(self, step: int, t_enter: float, t_dispatched: float,
             t_exit: float, host: Sequence[float] = _NO_HOST) -> None:
        """One row into ``steps`` and, at the same index, ``host``
        (:data:`HOST_COLUMNS`; NaN where the caller took no sample)."""
        i = self.n_steps % self.size
        self._steps[i] = (step, t_enter, t_dispatched, t_exit)
        self._host[i] = host
        self.n_steps += 1

    def pause(self, t_start: float, seconds: float, generation: int) -> None:
        self._pauses[self.n_pauses % self.size] = (t_start, seconds,
                                                   generation)
        self.n_pauses += 1

    @staticmethod
    def _ordered(ring: np.ndarray, n: int) -> np.ndarray:
        size = len(ring)
        if n <= size:
            return ring[:n].copy()
        return np.roll(ring, -(n % size), axis=0)

    def steps(self) -> np.ndarray:
        """Rows ``[step, enter, dispatched, exit]``, oldest first."""
        return self._ordered(self._steps, self.n_steps)

    def host(self) -> np.ndarray:
        """Rows of :data:`HOST_COLUMNS`, oldest first, a row beside each row
        of :meth:`steps`."""
        return self._ordered(self._host, self.n_steps)

    def last_period(self, step: int, enter: Sequence[float]
                    ) -> Optional[tuple]:
        """``(off_cpu_ms, runnable_ms)`` of the period that the sample
        ``enter`` (:func:`host_state`, taken as step ``step``'s span opens)
        closes: from the newest row's enter to it. None unless that row is
        step ``step - 1``'s and holds a sample."""
        i = (self.n_steps - 1) % self.size
        if not self.n_steps or self._steps[i, 0] != step - 1:
            return None
        cpu_ms = (enter[1] - self._host[i, 0]) / 1e6
        if cpu_ms != cpu_ms:
            return None
        runnable_ms = (enter[2] - self._host[i, 1]) / 1e6
        wall_ms = (enter[0] - self._steps[i, 1]) * 1e3
        return (wall_ms - cpu_ms - (runnable_ms if runnable_ms == runnable_ms
                                    else 0.0), runnable_ms)

    def pauses(self) -> np.ndarray:
        """Rows ``[start, seconds, generation]``, oldest first."""
        return self._ordered(self._pauses, self.n_pauses)


_LOG = StepLog()


def get_steplog() -> StepLog:
    return _LOG


# ---- collector pauses -----------------------------------------------------

_gc_open: Optional[TraceAnnotation] = None
_gc_t0 = 0.0


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    """``gc.callbacks`` hook: one ``ds.gc`` annotation and one pause row per
    collection of generation >= 1 (generation 0 runs many times a step and
    takes microseconds)."""
    global _gc_open, _gc_t0
    if info["generation"] < 1:
        return
    if phase == "start":
        _gc_open = TraceAnnotation("ds.gc", generation=info["generation"])
        _gc_open.__enter__()
        _gc_t0 = time.perf_counter()
    elif _gc_open is not None:
        _LOG.pause(_gc_t0, time.perf_counter() - _gc_t0, info["generation"])
        _gc_open.__exit__(None, None, None)
        _gc_open = None


def install_gc_hook() -> None:
    """Idempotent; the train engine calls it when it is built."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


# ---- recorded spans and the set-up record ----------------------------------

_tls = threading.local()    # .stack: names of the recorded spans open here
_SETUP: deque = deque(maxlen=256)
_setup_ids = itertools.count(1)


class _Recorded:
    """A span opened through :func:`span`: the bus's own span inside, its
    name on the thread's stack while it is open, and for a set-up span the
    row that :func:`setup` returns."""

    __slots__ = ("inner", "name", "row")

    def __init__(self, inner, name: str, row: Optional[Dict]):
        self.inner = inner
        self.name = name
        self.row = row

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        if self.row is not None:
            self.row["parent"] = next(
                (s.row["id"] for s in reversed(stack) if s.row is not None),
                None)
            if self.row["host_start"] is None:
                self.row["host_start"] = host_state()
            self.row["start"] = self.row["host_start"][0]
            _SETUP.append(self.row)
        stack.append(self)
        self.inner.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.inner.__exit__(exc_type, exc, tb)
        if self.row is not None:
            self.row["host_end"] = host_state()
            self.row["end"] = self.row["host_end"][0]
        _tls.stack.pop()
        return False


def span(bus, cat: str, name: str, *,
         host_start: Optional[Sequence[float]] = None,
         program: Optional[str] = None, **facts) -> _Recorded:
    """``with steplog.span(bus, "setup", "initialize"):`` — ``bus.span(cat,
    name)`` (the profiler's annotation ``ds.<cat>.<name>`` and, with tracing
    on, the ring's B/E pair), remembered as the innermost span of the calling
    thread while it is open, so that the build record can say under which
    span a program was built. A span of category ``setup`` also leaves a row
    for :func:`setup`, with ``facts`` beside its times and the thread's
    clocks at both ends (:func:`host_state`); ``host_start``, a sample
    taken earlier, backdates that row (the import span begins before
    anything could open it). For spans that open a few times a process: a
    step's own spans stay on ``bus.span``."""
    row = None
    if cat == "setup":
        row = {"id": next(_setup_ids), "name": f"ds.{cat}.{name}",
               "start": None, "end": None, "parent": None,
               "host_start": host_start, "host_end": None, **facts}
    return _Recorded(bus.span(cat, name, program=program, args=facts or None),
                     f"ds.{cat}.{name}", row)


def setup() -> List[Dict]:
    """The process's set-up spans in the order they opened: ``{"id", "name",
    "start", "end" (None while open), "parent" (an id or None), "self_s"`` (the
    span's length less its children's)``, "host_start", "host_end"`` (the
    thread's clocks at both ends, :func:`host_state`'s fields as a list; a
    clock the host lacks reads None, as ``host_end`` does while the span is
    open) and the facts the span was opened with``}``, on
    ``time.perf_counter``. The last 256."""
    rows = [dict(r) for r in _SETUP]
    for r in rows:
        for key in ("host_start", "host_end"):
            if r[key] is not None:
                r[key] = [None if v != v else v for v in r[key]]
        r["self_s"] = None if r["end"] is None else (r["end"] - r["start"]) \
            - sum(c["end"] - c["start"] for c in rows
                  if c["parent"] == r["id"] and c["end"] is not None)
    return rows


# ---- the build record ------------------------------------------------------

#: most program names the build record keeps; the rest fold into ``_other_``
BUILD_NAMES = 512
#: the span under which a reader's request compiles a step program again
INSPECT_SPAN = "ds.train.inspect"
_TRACES, _LOWERS, _COMPILES, _HITS, _READ_S, _MISSES, _FIRST, _LAST = \
    0, 2, 4, 6, 7, 8, 9, 10
_DURATIONS = {"/jax/core/compile/jaxpr_trace_duration": _TRACES,
              "/jax/core/compile/jaxpr_to_mlir_module_duration": _LOWERS,
              "/jax/core/compile/backend_compile_duration": _COMPILES}
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": 0,
                 "/jax/compilation_cache/cache_misses": 2}
_BUILD_FIELDS = ("traces", "trace_s", "lowers", "lower_s", "compiles",
                 "compile_s", "cache_hits", "cache_read_s", "cache_misses",
                 "first", "last")
_BUILDS: Dict[Any, List[float]] = {}    # (name, span) -> the row's numbers
_build_names: set = set()
_build_events = [0, 0]                  # duration events, plain events seen
_hooked = False


def _on_duration(event: str, duration: float, fun_name: str = "?", **_):
    """One ``jax.monitoring`` duration event into its row. A trace event
    names the function (``f``), a lowering or a backend compile the module
    (``jit(f)``): one name. The compile-cache's events carry no name and fire
    inside the backend compile that asked, so they wait on the thread for the
    backend-compile event that closes next there."""
    _build_events[0] += 1
    col = _DURATIONS.get(event)
    if col is None:
        if event == _CACHE_READ:
            _pending()[1] += duration
        return
    now = time.perf_counter()
    name = fun_name[4:-1] if fun_name.startswith("jit(") else fun_name
    if name not in _build_names:
        if len(_build_names) < BUILD_NAMES:
            _build_names.add(name)
        else:
            name = "_other_"
    stack = getattr(_tls, "stack", None)
    key = (name, stack[-1].name if stack else "outside")
    row = _BUILDS.get(key)
    if row is None:
        row = _BUILDS.setdefault(key, [0, 0.0, 0, 0.0, 0, 0.0, 0, 0.0, 0,
                                       now, now])
    row[col] += 1
    row[col + 1] += duration
    row[_LAST] = now
    if col == _COMPILES:
        cache = getattr(_tls, "cache", None)
        if cache is not None and (cache[0] or cache[2]):
            row[_HITS] += cache[0]
            row[_READ_S] += cache[1]
            row[_MISSES] += cache[2]
            cache[0], cache[1], cache[2] = 0, 0.0, 0


def _on_event(event: str, **_):
    _build_events[1] += 1
    col = _CACHE_EVENTS.get(event)
    if col is not None:
        _pending()[col] += 1


def _pending() -> List[float]:
    """This thread's compile-cache events that no backend compile has
    closed over yet: hits, seconds reading, misses."""
    cache = getattr(_tls, "cache", None)
    if cache is None:
        cache = _tls.cache = [0, 0.0, 0]
    return cache


def install_build_hook() -> None:
    """Idempotent; the package calls it when it is imported, so that programs
    built before any engine are in the record."""
    global _hooked
    if not _hooked:
        import jax.monitoring as monitoring

        _hooked = True
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)


def builds(name: Optional[str] = None) -> List[Dict]:
    """The build record, oldest row first: ``{"name", "span", "traces",
    "trace_s", "lowers", "lower_s", "compiles", "compile_s"`` (the backend
    compile, which holds the read from the compile cache)``, "cache_hits",
    "cache_read_s", "cache_misses", "first", "last"`` (``perf_counter`` of
    the row's first and last event)``}``; of one program where ``name`` is
    given."""
    return [{"name": key[0], "span": key[1], **dict(zip(_BUILD_FIELDS, row))}
            for key, row in sorted(_BUILDS.items(),
                                   key=lambda kv: kv[1][_FIRST])
            if name is None or key[0] == name]


def build_events() -> Dict[str, int]:
    """How often each listener has been called (what the record costs is
    this many dict updates)."""
    return {"duration": _build_events[0], "plain": _build_events[1]}


def _build_sums(name: str) -> List[float]:
    """The rows of ``name`` summed, but for what a reader's own look at the
    compiled program built (:meth:`StepProgram.compiled`)."""
    sums = [0, 0.0, 0, 0.0, 0, 0.0, 0, 0.0, 0]
    for key, row in list(_BUILDS.items()):
        if key[0] == name and key[1] != INSPECT_SPAN:
            for i in range(_FIRST):
                sums[i] += row[i]
    return sums


# ---- the step programs ----------------------------------------------------

class StepProgram:
    """One jitted step program: its name (the device trace's module line says
    ``jit_<name>``), the engine's cache key, when it was built
    (``perf_counter``), how long its first two calls took, and what a reader
    needs to compile it again. The jitted function is held weakly: when its
    engine is gone, so is the program, and the row answers None."""

    def __init__(self, name: str, key: Any, fn: Callable, mesh, **facts):
        self.name = name
        self.key = str(key)
        #: what the model said of the program (its ``step_program_facts``,
        #: where each fact is described) and, from the program's first call
        #: on, what the pickers counted while it was traced
        #: (``ops/lowerings.py``: ``{site: {answer: n}}``). Either reads as
        #: an attribute, a site also as ``<site>_lowerings``; a name nobody
        #: said anything under answers None
        self.facts: Dict[str, Any] = {
            k: tuple(v) if isinstance(v, list) else v
            for k, v in facts.items()}
        self.counted: Dict[str, Any] = {}
        self.built_at = time.perf_counter()
        #: the length of the ``ds.train.dispatch`` span of the program's
        #: first call (trace, lowering, the compile or its read from the
        #: cache, the load) and of its second (milliseconds, unless
        #: ``jax.jit`` built again for what the first call returned); None
        #: until that call
        self.first_call_s: Optional[float] = None
        self.second_call_s: Optional[float] = None
        self._build_base = _build_sums(name)
        self._fn = weakref.ref(fn)
        self._mesh = mesh
        self._args = None
        self._compiled = None
        self._collectives = None

    def __getattr__(self, name: str):
        if name.startswith("_") or name in ("facts", "counted"):
            raise AttributeError(name)
        if name in self.facts:
            return self.facts[name]
        if name.startswith("collective_"):
            # a sum over :meth:`collectives` (:func:`collective_sums` names
            # them); 0 where the program exchanges nothing, None only where
            # the program is gone
            rows = self.collectives()
            return None if rows is None else collective_sums(rows).get(name)
        return self.counted.get(name.removesuffix("_lowerings"))

    def capture(self, args) -> None:
        """Keep the abstract arguments (shape, dtype, sharding) of the
        program's first call; the arrays themselves are not held."""
        import jax

        self._args = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding), args)

    def build(self) -> Dict[str, float]:
        """The build record's rows of this program's name, summed (the
        fields of :func:`builds` up to ``cache_misses``): what was built
        under the name since this row was entered and before the next row
        of the name was. ``lowers`` above 1 is a second lowering inside
        ``jax.jit``'s own cache, which enters no row here."""
        later = next((p for p in list(_PROGRAMS)
                      if p.name == self.name and p.built_at > self.built_at),
                     None)
        upto = _build_sums(self.name) if later is None else later._build_base
        return {f: u - b for f, u, b in
                zip(_BUILD_FIELDS, upto, self._build_base)}

    def compiled(self):
        """Lower and compile again from the kept arguments (memoised), under
        a ``ds.train.inspect`` span: the build record keeps what this built
        apart from what the program's calls built."""
        if self._compiled is None:
            import jax

            fn = self._fn()
            if fn is None or self._args is None:
                return None
            from deepspeed_tpu.observability.events import get_bus

            with span(get_bus(), "train", "inspect"), \
                    jax.sharding.set_mesh(self._mesh):
                self._compiled = fn.lower(*self._args).compile()
        return self._compiled

    def memory_analysis(self) -> Optional[Dict[str, int]]:
        c = self.compiled()
        m = None if c is None else c.memory_analysis()
        if m is None:
            return None
        return {"temp": int(m.temp_size_in_bytes),
                "argument": int(m.argument_size_in_bytes),
                "output": int(m.output_size_in_bytes),
                "generated_code": int(m.generated_code_size_in_bytes)}

    def hlo_text(self) -> Optional[str]:
        c = self.compiled()
        return None if c is None else c.as_text()

    def recomputed_kernels(self) -> Optional[Dict[str, int]]:
        """:func:`recomputed_kernels` of the compiled program's text: where
        the recomputation policy still pays for a kernel twice."""
        text = self.hlo_text()
        return None if text is None else recomputed_kernels(text)

    def collectives(self) -> Optional[List[Dict[str, Any]]]:
        """:func:`collectives` of the compiled program's text (memoised):
        one row for each exchange between chips the compiled step holds,
        ``[]`` on one device. For a reader after the window: it compiles
        again (:meth:`compiled`), so nothing on a step's path calls it."""
        if self._collectives is None:
            text = self.hlo_text()
            if text is None:
                return None
            self._collectives = collectives(text)
        return self._collectives


_MOSAIC_CALL = re.compile(
    r' custom-call\(.*tpu_custom_call.*op_name="([^"]*)"')
_LOOP = re.compile(r' while\(.*op_name="([^"]*)"')


def kernel_calls(hlo_text: str, scope: Optional[str] = None) -> List[str]:
    """``op_name`` of a compiled program's kernel calls, those under
    ``/<scope>/`` where one is given: its Mosaic calls, or, in a program
    without any (off the chip a kernel runs interpreted, as a loop over its
    grid), its loops, a ``lax.scan`` inside a layer among them."""
    names = _MOSAIC_CALL.findall(hlo_text) or _LOOP.findall(hlo_text)
    return [n for n in names if scope is None or f"/{scope}/" in n]


def recomputed_kernels(hlo_text: str) -> Dict[str, int]:
    """How many of a compiled program's :func:`kernel_calls` stand in a
    ``rematted_computation``, the region ``jax.checkpoint`` makes again for
    the backward, by innermost scope (the name in front of the call's own
    ``jit(...)``): ``{"sconv_conv": 2}`` says two calls under that scope run a
    second time, which a policy that kept what their kernel's forward rule
    names would spare (``runtime/activation_checkpointing.py``)."""
    return dict(Counter(
        [p for p in name.split("/")[:-1] if "(" not in p][-1]
        for name in kernel_calls(hlo_text) if "rematted_computation" in name))


# ---- what a sharded step exchanges ------------------------------------------

#: the exchanges between chips an HLO program can hold, by opcode
COLLECTIVE_KINDS = ("all-gather", "reduce-scatter", "all-reduce",
                    "all-to-all", "collective-permute")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OPERAND = re.compile(r"(?<![=\w])%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"\b(calls|to_apply|body|condition|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_ARRAY = re.compile(r"\b(pred|[suf]\d+|bf16|f8\w+|c64|c128)\[([\d,]*)\]")
_TRIPS = re.compile(r'"known_trip_count":\{"n":"(\d+)"')
_GROUPS_IOTA = re.compile(r"replica_groups=\[\d+,(\d+)\]<=")
_GROUPS_LIST = re.compile(r"replica_groups=\{\{([\d,]*)\}")
_PAIRS = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")
_CHANNEL = re.compile(r"channel_id=(\d+)")
_INDEX = re.compile(r"index=(\d+)")
_CONSTANT = re.compile(r" constant\((-?\d+)\)")
_BITS = {"pred": 8, "c64": 64, "c128": 128, "bf16": 16}


def _type_and_opcode(rest: str):
    """``"(f32[4]{0}, u32[]) all-gather-start(%x), ..."`` -> (the result's
    type, the opcode, what follows the opcode's bracket)."""
    end = 0
    if rest.startswith("("):
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if not depth:
                break
    space = rest.find(" ", end)
    bracket = rest.find("(", space)
    return rest[:space], rest[space + 1:bracket], rest[bracket:]


def _bytes(type_text: str) -> int:
    """Bytes of every array a type names; a tuple's are summed."""
    total = 0
    for dtype, dims in _ARRAY.findall(type_text):
        bits = _BITS.get(dtype) or (8 if dtype.startswith("f8")
                                    else int(dtype[1:]))
        n = 1
        for d in dims.split(","):
            n *= int(d) if d else 1
        total += -(-n * bits // 8)
    return total


def _tuple_parts(type_text: str) -> List[str]:
    """The elements of a tuple type, one level down."""
    parts, depth, start = [], 0, 1
    for i, ch in enumerate(type_text):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if (ch == "," and depth == 1) or (ch == ")" and depth == 0):
            parts.append(type_text[start:i].strip())
            start = i + 1
    return parts


class _Instruction(NamedTuple):
    comp: str           # the computation it stands in
    type: str           # its result's type, as written
    opcode: str
    line: str


_NO_INSTRUCTION = _Instruction("", "", "", "")


def _path_parts(op_name: str) -> List[str]:
    """The scopes and transforms a JAX ``op_name`` path names, outermost
    first (``jit(f)/transpose(jvp(layers))/while/body/attn/dot``)."""
    return re.split(r"[/()]", op_name)


class _Module:
    """A compiled module's text, parsed once: every instruction with its
    computation, type, opcode and operands, and who calls which computation."""

    def __init__(self, hlo_text: str):
        self.at: Dict[str, _Instruction] = {}
        self._runs: Dict[str, tuple] = {}
        self.members: Dict[str, List[str]] = {}
        self.operands: Dict[str, List[str]] = {}
        self.op_name: Dict[str, str] = {}
        self.entry = comp = ""
        for line in hlo_text.splitlines():
            m = _COMPUTATION.match(line)
            if m:
                comp = m.group(1)
                if line.startswith("ENTRY"):
                    self.entry = comp
                continue
            m = _INSTRUCTION.match(line)
            if not m:
                continue
            name = m.group(1)
            type_text, opcode, tail = _type_and_opcode(line[m.end():])
            self.at[name] = _Instruction(comp, type_text, opcode, line)
            self.members.setdefault(comp, []).append(name)
            self.operands[name] = _OPERAND.findall(tail)
            meta = _OP_NAME.search(tail)
            if meta:
                self.op_name[name] = meta.group(1)
        #: computation -> [(calling instruction, how it is called)]
        self.callers: Dict[str, List[tuple]] = {}
        self.users: Dict[str, List[str]] = {}
        for name, at in self.at.items():
            for o in self.operands[name]:
                self.users.setdefault(o, []).append(name)
            for how, called in _CALLED.findall(at.line):
                self.callers.setdefault(called, []).append((name, how))
            for group in _BRANCHES.findall(at.line):
                for called in _OPERAND.findall(group):
                    self.callers.setdefault(called, []).append(
                        (name, "branch"))

    def trips(self, loop: str) -> Optional[int]:
        """How often a ``while`` runs its body: what the compiler wrote
        (``known_trip_count``), else what a counted loop's own text says (a
        condition ``i < N`` on a tuple element that starts at a constant 0
        and that the body adds a constant 1 to: every ``lax.scan`` and
        ``fori_loop``; the v5e compiler writes no trip count), else None."""
        line = self.at[loop].line
        known = _TRIPS.search(line)
        if known:
            return int(known.group(1))
        called = dict(_CALLED.findall(line))
        cond, body = called.get("condition"), called.get("body")
        root = next((self.at[n] for n in self.members.get(cond, [])
                     if self.at[n].line.lstrip().startswith("ROOT")), None)
        if root is None or root.opcode != "compare" \
                or "direction=LT" not in root.line:
            return None
        index = bound = None
        for o in _OPERAND.findall(root.line.partition(" compare(")[2]):
            if self._tuple_index(o) is not None:
                index = self._tuple_index(o)
            elif self._constant(o) is not None:
                bound = self._constant(o)
        if index is None or bound is None:
            return None
        # the counter starts at 0 ...
        start = self.operands[loop][:1]
        if not start or self.at.get(start[0], _NO_INSTRUCTION).opcode \
                != "tuple" or len(self.operands[start[0]]) <= index or \
                self._constant(self.operands[start[0]][index]) != 0:
            return None
        # ... and the body adds 1 to it
        for n in self.members.get(body, []):
            if self.at[n].opcode != "add" or len(self.operands[n]) != 2:
                continue
            a, b = self.operands[n]
            for counter, step in ((a, b), (b, a)):
                if self._tuple_index(counter) == index \
                        and self._constant(step) == 1:
                    return bound
        return None

    def _tuple_index(self, name: str) -> Optional[int]:
        """Which element of a tuple a ``get-tuple-element`` takes."""
        at = self.at.get(name, _NO_INSTRUCTION)
        return int(_INDEX.search(at.line).group(1)) \
            if at.opcode == "get-tuple-element" else None

    def _constant(self, name: str) -> Optional[int]:
        """The integer an instruction is a constant of, through copies."""
        at = self.at.get(name, _NO_INSTRUCTION)
        while at.opcode in ("copy", "bitcast") and self.operands[name]:
            name = self.operands[name][0]
            at = self.at.get(name, _NO_INSTRUCTION)
        hit = _CONSTANT.search(at.line) if at.opcode == "constant" else None
        return int(hit.group(1)) if hit else None

    def runs(self, comp: str, seen=()) -> tuple:
        """(how often a step runs the computation: the trip counts of every
        ``while`` whose body holds it, multiplied; how many of those loops
        there are; how many of them gave no trip count and count once;
        whether one of them is the layer loop)."""
        if comp == self.entry or comp in seen:
            return 1, 0, 0, False
        if comp in self._runs:
            return self._runs[comp]
        total = loops = unknown = 0
        in_layers = False
        for caller, how in self.callers.get(comp, []):
            if how in ("condition", "to_apply"):
                continue
            n, depth, u, inside = self.runs(self.at[caller].comp,
                                            seen + (comp,))
            if how == "body":
                trips = self.trips(caller)
                n, depth, u = n * (trips or 1), depth + 1, u + (trips is None)
                inside = inside or "layers" in _path_parts(
                    self.op_name.get(caller, ""))
            total, loops = total + n, max(loops, depth)
            unknown, in_layers = unknown + u, in_layers or inside
        out = (total or 1, loops, unknown, in_layers)
        if not seen:
            self._runs[comp] = out
        return out

    def scope(self, name: str, scopes: Sequence[str]) -> Optional[str]:
        """The innermost of ``scopes`` that the instruction's ``op_name``
        names; for one the compiler made and gave no path (the gather of a
        sharded argument, an asynchronous half), the scope of the nearest
        user that has one, else of the nearest operand, the fusion that
        holds it standing in for it where its own computation has none."""
        def own(n):
            named = [p for p in _path_parts(self.op_name.get(n, ""))
                     if p in scopes]
            return named[-1] if named else None

        while name:
            for edges in (self.users, self.operands):
                seen, frontier = {name}, [name]
                while frontier:
                    found = next(filter(None, map(own, frontier)), None)
                    if found:
                        return found
                    frontier = [o for n in frontier
                                for o in edges.get(n, [])
                                if o not in seen and not seen.add(o)]
            holders = [c for c, how in self.callers.get(
                self.at[name].comp, []) if how == "calls"]
            name = holders[0] if holders else ""
        return None


def collectives(hlo_text: str) -> List[Dict[str, Any]]:
    """What one run of a compiled program exchanges between chips, from its
    text alone: a row for every ``all-gather``, ``reduce-scatter``,
    ``all-reduce``, ``all-to-all`` and ``collective-permute`` of it, those
    inside a fusion or a called computation too, in the text's order. An
    asynchronous pair is one row, at its ``-start``; so is a gather the v5e
    compiler spreads over a chain of fusions (``async-collective-start``,
    compute fusions that each hold one more ``all-gather`` of the same
    ``channel_id``, ``async-collective-done``), at the member a step runs
    least often. A program on one device: ``[]``.

    A row: ``name``; ``kind``; ``async`` (a ``-start`` / ``-done`` pair or
    such a chain, which compute can hide, and not one blocking operation);
    ``bytes`` a device receives in one run of it (of a group of ``g``: a
    gather's result less its own shard, ``(g - 1) / g`` of it; a
    reduce-scatter's operand likewise, ``g - 1`` results; an all-reduce's
    result ``2 (g - 1) / g`` times, as a ring moves it; an all-to-all's
    ``(g - 1) / g``; a permute's operand whole; a tuple's arrays summed);
    ``group`` (the replica group's size; a permute's pairs); ``scope`` (the
    innermost of the step's ``models/transformer.py:STEP_SCOPES`` in its
    ``op_name``, or lent: :meth:`_Module.scope`; None where nothing names
    one); ``backward``
    (the path holds ``transpose(``); ``loops``, how many ``while`` bodies it
    stands inside, and ``trips``, how often a step runs it: their trip
    counts multiplied (:meth:`_Module.trips`), 1 outside every loop;
    ``unknown_trips``, how many of those loops gave no count and were taken
    as one; ``in_layer_loop`` (one of them names the ``layers`` scope: a
    gather there runs once a layer, one outside it once a step)."""
    from deepspeed_tpu.models.transformer import STEP_SCOPES
    m = _Module(hlo_text)
    rows: Dict[Any, Dict[str, Any]] = {}
    for name, at in m.at.items():
        kind = at.opcode.removesuffix("-start")
        if kind not in COLLECTIVE_KINDS:
            continue
        trips, loops, unknown, in_layers = m.runs(at.comp)
        group = _group_size(at.line)
        channel = _CHANNEL.search(at.line)
        chained = "async_collective_fusion_config" in at.line
        row = {"name": name, "kind": kind,
               "async": at.opcode.endswith("-start") or chained,
               "bytes": _received_bytes(m, name, kind, group),
               "group": group, "scope": m.scope(name, STEP_SCOPES),
               "backward": "transpose(" in m.op_name.get(name, ""),
               "loops": loops, "trips": trips, "unknown_trips": unknown,
               "in_layer_loop": in_layers}
        key = (kind, channel.group(1)) if channel and chained else name
        if key not in rows or trips < rows[key]["trips"]:
            rows[key] = row
    return list(rows.values())


def _received_bytes(m: _Module, name: str, kind: str, group: int) -> int:
    """The bytes a device receives in one run of a collective instruction."""
    at = m.at[name]
    result = at.type
    if at.opcode.endswith("-start") and at.type.startswith("("):
        # (operands, results[, contexts]); a reduction's start has its
        # result's type
        parts = _tuple_parts(at.type)
        result = at.type if kind == "all-reduce" else parts[1]
    size, g = _bytes(result), max(group, 1)
    if kind == "collective-permute":
        return size
    if kind == "reduce-scatter":
        return size * (g - 1)
    if kind == "all-reduce":
        return 2 * size * (g - 1) // g
    return size * (g - 1) // g


def _group_size(line: str) -> int:
    """A replica group's size (either spelling), a permute's pairs, 0 where
    the instruction names neither."""
    pairs = _PAIRS.search(line)
    if pairs:
        return pairs.group(1).count("{")
    iota = _GROUPS_IOTA.search(line)
    if iota:
        return int(iota.group(1))
    listed = _GROUPS_LIST.search(line)
    return listed.group(1).count(",") + 1 if listed else 0


def collective_sums(rows: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """:func:`collectives`' rows summed a step (``trips`` applied), under the
    names a :class:`StepProgram` answers as attributes: calls and bytes in
    all, by kind, and inside or outside the layer loop. No row: zeros."""
    out: Dict[str, Any] = {
        "collective_calls_per_step": 0, "collective_bytes_per_step": 0,
        "collective_calls_by_kind": {}, "collective_bytes_by_kind": {},
        "collective_calls_in_layer_loop": 0,
        "collective_bytes_in_layer_loop": 0,
        "collective_calls_outside_layer_loop": 0,
        "collective_bytes_outside_layer_loop": 0,
        "collective_unknown_trips": 0}
    for r in rows:
        where = "in" if r["in_layer_loop"] else "outside"
        for what, n in (("calls", r["trips"]),
                        ("bytes", r["trips"] * r["bytes"])):
            out[f"collective_{what}_per_step"] += n
            out[f"collective_{what}_{where}_layer_loop"] += n
            by = out[f"collective_{what}_by_kind"]
            by[r["kind"]] = by.get(r["kind"], 0) + n
        out["collective_unknown_trips"] += bool(r["unknown_trips"])
    return out


_PROGRAMS: deque = deque(maxlen=64)


def record_program(name: str, key: Any, fn: Callable, mesh,
                   **facts) -> StepProgram:
    """Enter a step program in the table; ``facts`` are what its model says
    of itself (:attr:`StepProgram.facts`)."""
    row = StepProgram(name, key, fn, mesh, **facts)
    _PROGRAMS.append(row)
    return row


def programs() -> List[StepProgram]:
    """Rows oldest first (the last 64 builds of the process)."""
    return list(_PROGRAMS)


# ---- reading a record -----------------------------------------------------

def _periods(steps: np.ndarray, factor: float, exclude: Sequence[int]):
    """What :func:`slow_steps` and :func:`host_states` agree on: the periods
    of consecutive step rows in milliseconds (enter to the next enter), which
    of them count (``exclude`` left out), their median, and the indices of
    the slow ones (over ``factor`` medians). None when fewer than two
    count."""
    if len(steps) < 2:
        return None
    keep = ~np.isin(steps[:-1, 0], list(exclude))
    if keep.sum() < 2:
        return None
    period = np.diff(steps[:, 1]) * 1e3
    mid = float(np.median(period[keep]))
    return period, keep, mid, np.nonzero(keep & (period > factor * mid))[0]


def slow_steps(steps: np.ndarray, pauses: np.ndarray,
               factor: float = SLOW_FACTOR,
               exclude: Sequence[int] = ()) -> Optional[Dict]:
    """Which steps of a record were slow, and what took their excess.

    ``steps`` are consecutive rows of :meth:`StepLog.steps`. A step's period
    runs from its span's enter to the next step's enter (the last row only
    closes the one before it), so it holds the span (put, dispatch, commit)
    and everything up to the next call: the caller's wait for the device and
    its next batch. A step is slow when its period exceeds ``factor`` medians;
    its excess is the period minus the median. Of that excess the host's part
    is what the span took beyond the median span, plus collector pauses that
    began in the period outside the span; the rest is the wait for the
    device (or the caller). The periods of the step numbers in ``exclude``
    are left out of every sum (the caller did something else there: stopped
    a profiler, saved a checkpoint). Times in milliseconds; None when fewer
    than two periods are left.
    """
    picked = _periods(steps, factor, exclude)
    if picked is None:
        return None
    period, keep, mid, slow_at = picked
    enter, dispatched, exit_ = steps[:, 1], steps[:, 2], steps[:, 3]
    inside = (exit_ - enter)[:-1] * 1e3
    mid_inside = float(np.median(inside[keep]))
    total = float(period[keep].sum())
    slow = []
    for i in slow_at:
        excess = float(period[i] - mid)
        inside_p = [[float((s - enter[i]) * 1e3), float(sec * 1e3), int(g)]
                    for s, sec, g in pauses if enter[i] <= s < enter[i + 1]]
        outside_ms = sum(p[1] for p in inside_p
                         if p[0] >= (exit_[i] - enter[i]) * 1e3)
        host = min(excess, max(0.0, float(inside[i]) - mid_inside)
                   + outside_ms)
        slow.append({"index": int(i), "step": int(steps[i, 0]),
                     "period_ms": float(period[i]), "excess_ms": excess,
                     "put_dispatch_ms": float((dispatched[i] - enter[i])
                                              * 1e3),
                     "commit_ms": float((exit_[i] - dispatched[i]) * 1e3),
                     "outside_ms": float(period[i] - inside[i]),
                     "host_excess_ms": host, "pauses": inside_p})
    excess = sum(s["excess_ms"] for s in slow)
    in_window = pauses[(pauses[:, 0] >= enter[0])
                       & (pauses[:, 0] < enter[-1])] if len(pauses) else pauses
    return {"steps": int(keep.sum()), "median_ms": mid,
            "median_inside_ms": mid_inside, "window_ms": total,
            "excess_ms": excess,
            "excess_share": 100.0 * excess / total,
            "host_share": (100.0 * sum(s["host_excess_ms"] for s in slow)
                           / excess) if slow else None,
            "pause_ms_per_step": (float(in_window[:, 1].sum()) * 1e3
                                  / len(period)) if len(pauses) else 0.0,
            "pauses": int(len(in_window)), "slow": slow}


#: the phases of a period by the wall clock, and the two of them whose ends
#: carry the thread's clocks: the span (put, dispatch and commit together)
#: and what lies outside it
PHASES = ("put", "dispatch", "commit", "outside")
STATE_PHASES = ("span", "outside")
STATES = ("cpu", "runnable", "off_cpu")
#: the longest periods :func:`host_states` lists phase by phase
WORST_KEPT = 5


def host_states(steps: np.ndarray, host: np.ndarray,
                factor: float = SLOW_FACTOR,
                exclude: Sequence[int] = ()) -> Optional[Dict]:
    """Where each period of a record went, by phase and by what state the
    host thread was in, on :func:`slow_steps`' terms (same periods, same
    ``factor``, same ``exclude``).

    ``steps`` are consecutive rows of :meth:`StepLog.steps`, ``host`` the
    rows of :meth:`StepLog.host` beside them. A period has four phases by the
    wall clock: ``put`` (enter to ``_put_batch``'s return), ``dispatch`` (to
    the jitted call's return), ``commit`` (to exit) and ``outside`` (exit to
    the next enter: the caller's wait for the device and its next batch).
    The thread's clocks are read at enter and at exit, so the state is told
    for the ``span`` (the first three phases) and for ``outside``: on a core
    (``cpu``), ``runnable`` and waiting for one, and ``off_cpu``, which is
    the rest of the phase's wall time. Where the host has no ``schedstat``
    (``runnable_read`` False) runnable reads 0 and its time lies in
    ``off_cpu``, or in ``cpu`` where the kernel under the thread is a
    sandbox's. ``other_cpu`` is what the process's other threads burned in
    the period: Δ(``process_cpu_ns`` − ``cpu_ns``) from enter to enter.

    For the window: medians by phase and state, the sums, and the shares the
    metrics read. For the steps :func:`slow_steps` calls slow: the split of
    their excess by phase (a phase's excess is its length less that phase's
    median) and, for the span and outside, by state (a state's excess is its
    time less that state's median there; ``off_cpu`` takes the rest, so the
    states sum to the phase). With no slow step every excess and share
    reads 0. Times in milliseconds; None when fewer than two periods are
    left or the rows hold no sample.
    """
    picked = _periods(steps, factor, exclude)
    if picked is None:
        return None
    period, keep, _, slow_at = picked
    enter, dispatched, exit_ = steps[:, 1], steps[:, 2], steps[:, 3]
    t_put = host[:, 3]
    wall = {"put": (t_put - enter)[:-1] * 1e3,
            "dispatch": (dispatched - t_put)[:-1] * 1e3,
            "commit": (exit_ - dispatched)[:-1] * 1e3,
            "outside": (enter[1:] - exit_[:-1]) * 1e3,
            "span": (exit_ - enter)[:-1] * 1e3}
    runnable_read = bool(np.isfinite(host[:, 1]).any())
    run_enter, run_exit = (np.nan_to_num(host[:, c]) for c in (1, 5))
    state = {"span": {"cpu": (host[:, 4] - host[:, 0])[:-1] / 1e6,
                      "runnable": (run_exit - run_enter)[:-1] / 1e6},
             "outside": {"cpu": (host[1:, 0] - host[:-1, 4]) / 1e6,
                         "runnable": (run_enter[1:] - run_exit[:-1]) / 1e6}}
    for p in STATE_PHASES:
        state[p]["off_cpu"] = wall[p] - state[p]["cpu"] - state[p]["runnable"]
    other = np.diff(host[:, 2] - host[:, 0]) / 1e6
    # a period whose rows hold no sample (another caller wrote them) counts
    # in no sum
    keep = keep & np.isfinite(state["span"]["cpu"] + state["outside"]["cpu"])
    if keep.sum() < 2:
        return None

    def med(x):
        return float(np.median(x[keep]))

    def total(x):
        return float(x[keep].sum())

    medians = {p: med(wall[p]) for p in PHASES + ("span",)}
    state_medians = {p: {s: med(state[p][s]) for s in STATES}
                     for p in STATE_PHASES}
    sums = {p: {"wall": total(wall[p]),
                **{s: total(state[p][s]) for s in STATES}}
            for p in STATE_PHASES}
    slow_at = slow_at[keep[slow_at]]
    by_phase = {p: float((wall[p][slow_at] - medians[p]).sum())
                for p in PHASES}
    by_state = {}
    for p in STATE_PHASES:
        part = {s: float((state[p][s][slow_at] - state_medians[p][s]).sum())
                for s in ("cpu", "runnable")}
        part["off_cpu"] = float((wall[p][slow_at] - medians[p]).sum()) \
            - part["cpu"] - part["runnable"]
        by_state[p] = part
    excess = sum(sum(v.values()) for v in by_state.values())

    def row(i):
        return {"index": int(i), "step": int(steps[i, 0]),
                "period_ms": float(period[i]),
                **{f"{p}_ms": float(wall[p][i]) for p in PHASES},
                **{p: {s: float(state[p][s][i]) for s in STATES}
                   for p in STATE_PHASES},
                "other_cpu_ms": float(other[i])}

    def share(part, whole):
        return 100.0 * part / whole if whole else 0.0

    window = float(period[keep].sum())
    return {
        "steps": int(keep.sum()), "runnable_read": runnable_read,
        "window_ms": window, "median_ms": medians,
        "state_median_ms": state_medians, "sum_ms": sums,
        "other_cpu_ms": total(other),
        "span_off_cpu_share": share(sums["span"]["off_cpu"],
                                    sums["span"]["wall"]),
        "span_runnable_share": share(sums["span"]["runnable"],
                                     sums["span"]["wall"]),
        "other_threads_cpu_share": share(total(other), window),
        "slow": len(slow_at), "excess_ms": excess,
        "excess_by_phase_ms": by_phase, "excess_by_state_ms": by_state,
        "slow_off_cpu_share": share(
            sum(by_state[p]["off_cpu"] for p in STATE_PHASES), excess),
        "slow_runnable_share": share(
            sum(by_state[p]["runnable"] for p in STATE_PHASES), excess),
        "worst": [row(i) for i in sorted(np.nonzero(keep)[0],
                                         key=lambda i: -period[i])[:WORST_KEPT]],
    }
