"""What state the calling thread was in: on a core, runnable and waiting for
one, or off it. The one place in the package that reads these sources; the
standard library alone, so that the package's first line can take a sample
before anything else is imported. ``observability/steplog.py`` keeps the
samples and does the arithmetic; ``benchmarks/readers/HOSTSTATE.md`` says
which metric reads which field, and what was left out and why.

| field | from | says |
|---|---|---|
| ``t`` | ``time.perf_counter()`` | the step record's clock (seconds) |
| ``cpu_ns`` | ``time.thread_time_ns()`` | on a core |
| ``runnable_ns`` | 2nd field of ``/proc/thread-self/schedstat`` | runnable, waiting for a core |
| ``process_cpu_ns`` | ``time.process_time_ns()`` | every thread of the process on a core: less ``cpu_ns``, the other threads |

The counters are cumulative since the thread (the process) began: two
samples of one thread bracket an interval, and off the core = Δ``t`` −
Δ``cpu_ns`` − Δ``runnable_ns``. A host without ``schedstat`` (a sandboxed
kernel) reads NaN there and is named by :func:`unavailable`; nothing
raises, and what its kernel cannot tell apart from running (waiting for a
core of the machine under it) then counts as on a core. The descriptor is
opened once a thread, kept, and closed with the thread (one opened through
``thread-self`` stays the opening thread's whoever reads it).
"""

import os
import threading
import time

NAN = float("nan")
SCHEDSTAT = "/proc/thread-self/schedstat"

_missing = set()            # the sources some thread found absent


class _Kept:
    """A thread's descriptor on its own ``schedstat`` (-1 where the host has
    none), opened at the thread's first sample and closed when the thread's
    locals go."""

    __slots__ = ("fd",)

    def __init__(self):
        try:
            self.fd = os.open(SCHEDSTAT, os.O_RDONLY)
        except OSError:
            _missing.add(SCHEDSTAT)
            self.fd = -1

    def __del__(self):
        if self.fd >= 0:
            os.close(self.fd)


_tls = threading.local()    # .kept: this thread's _Kept


def _runnable_ns() -> float:
    kept = getattr(_tls, "kept", None)
    if kept is None:
        kept = _tls.kept = _Kept()
    if kept.fd < 0:
        return NAN
    try:
        return float(os.pread(kept.fd, 96, 0).split()[1])
    except (OSError, IndexError, ValueError):
        _missing.add(SCHEDSTAT)
        return NAN


def thread_state():
    """``(t, cpu_ns, runnable_ns)`` of the calling thread."""
    return time.perf_counter(), time.thread_time_ns(), _runnable_ns()


def host_state():
    """The whole sample of the calling thread: ``(t, cpu_ns, runnable_ns,
    process_cpu_ns)``."""
    return (time.perf_counter(), time.thread_time_ns(), _runnable_ns(),
            time.process_time_ns())


def unavailable():
    """The sources a sample found absent on this host, sorted."""
    return sorted(_missing)
