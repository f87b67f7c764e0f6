"""What the train step leaves behind for a reader, at no cost to the step:
a per-step record, the collector's pauses, and a table of the step programs
the engine built.

Process-wide like the metrics registry, so a reader (the benchmark's
``readers/program.py``, a debugger) reaches it without a handle on the
engine.

* :class:`StepLog` — two preallocated float64 rings. ``steps``: one row per
  ``fused_train_step`` call (step number, span enter, dispatch return, span
  exit, on ``time.perf_counter``), written once as the ``ds.train.step`` span
  closes. ``pauses``: one row per cyclic collection of generation >= 1
  (start, length, generation), written from ``gc.callbacks`` inside a
  ``ds.gc`` profiler annotation. A write stores floats into the ring: nothing
  outlives the step and nothing is collector-tracked.
* ``loss_parts`` — for a model whose loss has parts (a looped model's
  per-pass losses and exit distribution, a held share of experts' router
  counts, by layer the mean square of the mixer's output of a model with
  state-space layers): the last ``PARTS_KEPT`` steps'
  loss and parts as the device values the step program returned. Writing a
  row waits for nothing; :meth:`StepLog.parts` reads them to the host when a
  reader asks, after the step.
* the step-program table — one :class:`StepProgram` per jitted step program
  an engine built, appended on a ``_fused_step_cache`` miss only. The row
  keeps the program's abstract arguments, so ``memory_analysis()`` and the
  compiled HLO text are computed when a reader asks (lowering and compiling
  again: a hit in the persistent compile cache), never at engine build or on
  a step.
* :func:`slow_steps` — the arithmetic that says which steps of a record were
  slow and how much of their excess the host or the collector took.
"""

from __future__ import annotations

import gc
import time
import weakref
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np
from jax.profiler import TraceAnnotation

__all__ = ["StepLog", "StepProgram", "get_steplog", "install_gc_hook",
           "record_program", "programs", "slow_steps", "SLOW_FACTOR"]

#: a step is slow when its period exceeds this many medians
SLOW_FACTOR = 1.25
#: steps whose loss parts are kept (device values: a few floats each)
PARTS_KEPT = 256


class StepLog:
    """Rings of the last ``size`` steps and collector pauses (see module
    docstring). ``n_steps``/``n_pauses`` count every write, so a reader can
    tell a wrapped ring."""

    def __init__(self, size: int = 4096):
        self.size = int(size)
        self._steps = np.zeros((self.size, 4))
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
             t_exit: float) -> None:
        self._steps[self.n_steps % self.size] = (step, t_enter, t_dispatched,
                                                 t_exit)
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


# ---- the step programs ----------------------------------------------------

class StepProgram:
    """One jitted step program: its name (the device trace's module line says
    ``jit_<name>``), the engine's cache key, when it was built
    (``perf_counter``), and what a reader needs to compile it again. The
    jitted function is held weakly: when its engine is gone, so is the
    program, and the row answers None."""

    def __init__(self, name: str, key: Any, fn: Callable, mesh,
                 layer_applications: Optional[int] = None,
                 layer_pattern: Optional[Sequence[str]] = None,
                 moe_kernel_resolved: Optional[str] = None,
                 experts_held: Optional[Sequence[int]] = None,
                 ssm_chunk: Optional[int] = None):
        self.name = name
        self.key = str(key)
        #: block applications one micro-batch's forward holds (layers run x
        #: passes over them); None where the model does not say
        self.layer_applications = layer_applications
        #: the period of layer kinds the layer loop scans ("window" / "full"
        #: attention, "ssm" a state-space layer); None where the model does
        #: not say
        self.layer_pattern = None if layer_pattern is None \
            else tuple(layer_pattern)
        #: the grouped expert product the program was traced with: "ragged"
        #: (every pair over the sorted rows: the Pallas kernels or
        #: ``lax.ragged_dot``, see ``moe_grouped_lowerings``) or "padded" (its
        #: einsum twin, also what ``resolve_moe_kernel`` falls to where
        #: ragged_dot does not lower);
        #: None for a model without grouped experts
        self.moe_kernel_resolved = moe_kernel_resolved
        #: (first, count, routed) of the experts a layer holds; None for a
        #: model without experts
        self.experts_held = None if experts_held is None \
            else tuple(experts_held)
        #: flash-backward lowerings of the program's trace by the kernel they
        #: took, ``{"fused": n, "split": m}`` (``ops/flash_attention.py``);
        #: None until the program's first call has traced it
        self.flash_bwd_lowerings: Optional[Dict[str, int]] = None
        #: the tiles one head of the flash forward takes by arm and whether
        #: its log-sum-exp leaves as rows, ``{"masked", "unmasked", "dead",
        #: "rows"}``, of the newest forward the program's trace lowered; None
        #: where it lowered none, and until the first call
        self.flash_fwd_tiles: Optional[Dict[str, Any]] = None
        #: the experts' grouped products the program's trace lowered, by the
        #: lowering each took, ``{"pallas": n, "xla": m}``: a product counts
        #: once and its backward's two transposes once each
        #: (``ops/grouped_matmul.py``); None where the trace held none
        self.moe_grouped_lowerings: Optional[Dict[str, int]] = None
        #: the expert layers' dispatches and combines the program's trace
        #: lowered, by the lowering each took, ``{"pallas": n, "xla": m}``: a
        #: move counts once and its backward once more
        #: (``moe/sharded_moe.py``, ``ops/moe_rows.py``); None where the
        #: trace held none
        self.moe_dispatch_lowerings: Optional[Dict[str, int]] = None
        #: the chunk length of the state-space layers' scan, and the chunks
        #: one step's forward scans (state-space layers x rows x ceil(T /
        #: chunk), from the batch of the program's first call); None for a
        #: model without such a layer
        self.ssm_chunk = ssm_chunk
        self.ssm_chunks_per_step: Optional[int] = None
        self.built_at = time.perf_counter()
        self._fn = weakref.ref(fn)
        self._mesh = mesh
        self._args = None
        self._compiled = None

    def capture(self, args) -> None:
        """Keep the abstract arguments (shape, dtype, sharding) of the
        program's first call; the arrays themselves are not held."""
        import jax

        self._args = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding), args)

    def compiled(self):
        """Lower and compile again from the kept arguments (memoised)."""
        if self._compiled is None:
            import jax

            fn = self._fn()
            if fn is None or self._args is None:
                return None
            with jax.sharding.set_mesh(self._mesh):
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


_PROGRAMS: deque = deque(maxlen=64)


def record_program(name: str, key: Any, fn: Callable, mesh,
                   **facts) -> StepProgram:
    """Enter a step program in the table; ``facts`` are what its model says
    of itself (:class:`StepProgram`'s keyword arguments)."""
    row = StepProgram(name, key, fn, mesh, **facts)
    _PROGRAMS.append(row)
    return row


def programs() -> List[StepProgram]:
    """Rows oldest first (the last 64 builds of the process)."""
    return list(_PROGRAMS)


# ---- reading a record -----------------------------------------------------

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
    if len(steps) < 2:
        return None
    enter, dispatched, exit_ = steps[:, 1], steps[:, 2], steps[:, 3]
    keep = ~np.isin(steps[:-1, 0], list(exclude))
    period = np.diff(enter) * 1e3
    inside = (exit_ - enter)[:-1] * 1e3
    if keep.sum() < 2:
        return None
    mid = float(np.median(period[keep]))
    mid_inside = float(np.median(inside[keep]))
    total = float(period[keep].sum())
    slow = []
    for i in np.nonzero(keep & (period > factor * mid))[0]:
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
