"""From a profiler trace to numbers: busy and idle share of the device,
time per kernel, and the idle gaps named by what the host was doing.

Two halves. :func:`load_xplane` turns an ``.xplane.pb`` (read with
``jax.profiler.ProfileData``, nothing else) into plain data; every function
after it works on that plain data, so the arithmetic is tested on the CPU
against a small recorded trace and a synthetic one
(``benchmarks/tests/test_trace_reduce.py``).

Plain data: ``Trace.devices`` maps a device plane's name to its operations,
``Op(name, start, end, label)`` in nanoseconds, as the plane's "XLA Ops" line
has them. On a TPU that line names an event by the whole HLO instruction
(``%fusion.12 = bf16[16,4096]{...} fusion(...)``): ``name`` is the part before
``=`` without the ``%`` and ``label`` the whole text, which is the only thing
that tells one Pallas kernel from another (all are ``custom-call`` to
``tpu_custom_call``, told apart by their result shapes). Operations nest (a ``while`` holds its body), so sums use *self*
time: an operation's span minus what its children cover. ``Trace.host`` holds
the host spans whose names start with the benchmark's prefix (written with
``jax.profiler.TraceAnnotation`` by the runners).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
_CONTAINERS = re.compile(r"^(while|conditional|call)([.\d]*)$")


@dataclass(frozen=True)
class Op:
    name: str
    start: int
    end: int
    label: str = ""             # the program's scope path, where the trace has it


@dataclass
class Trace:
    devices: Dict[str, List[Op]] = field(default_factory=dict)
    host: List[Op] = field(default_factory=list)


def load_xplane(path: str) -> Trace:
    """Read device operations and the benchmark's host spans."""
    from jax.profiler import ProfileData

    trace = Trace()
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops = []
                for ev in line.events:
                    label = ""
                    for key, val in ev.stats:
                        if key in ("tf_op", "name_scope", "long_name") \
                                and isinstance(val, str) and not label:
                            label = val
                    s = int(ev.start_ns)
                    name, label = split_hlo_name(ev.name, label)
                    ops.append(Op(name, s, s + int(ev.duration_ns), label))
                trace.devices[plane.name] = sorted(
                    ops, key=lambda o: (o.start, -o.end))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        s = int(ev.start_ns)
                        trace.host.append(
                            Op(ev.name, s, s + int(ev.duration_ns)))
    trace.host.sort(key=lambda o: (o.start, -o.end))
    return trace


def split_hlo_name(text: str, label: str = "") -> Tuple[str, str]:
    """``"%attn.23 = (bf16[..]) custom-call(..)"`` -> ``("attn.23", text)``;
    a plain name is kept, with whatever label the trace gave it."""
    head, sep, _ = text.partition(" = ")
    if sep and head.startswith("%"):
        return head[1:], text
    return text, label


# ---- intervals ------------------------------------------------------------

def _union(spans: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(spans):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(spans, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in spans
            if min(e, hi) > max(s, lo)]


def _length(spans) -> int:
    return sum(e - s for s, e in spans)


def _subtract(a: List[Tuple[int, int]], b: List[Tuple[int, int]]):
    """Parts of the (disjoint, sorted) spans ``a`` that no span of ``b``
    (disjoint, sorted) covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def window(trace: Trace) -> Optional[Tuple[int, int]]:
    """The traced window: the runner's ``bench.window`` span if there is
    one, else from the first to the last event of any kind."""
    for op in trace.host:
        if op.name == WINDOW_SPAN:
            return op.start, op.end
    every = [o for ops in trace.devices.values() for o in ops] + trace.host
    if not every:
        return None
    return min(o.start for o in every), max(o.end for o in every)


def self_times(ops: Sequence[Op]) -> List[Tuple[Op, int]]:
    """(op, self nanoseconds): an op's span minus its direct children's. Ops
    must be sorted by (start, -end), as :func:`load_xplane` leaves them."""
    out: List[List] = []
    stack: List[int] = []           # indices into out
    for op in ops:
        while stack and out[stack[-1]][0].end <= op.start:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[1] -= min(op.end, parent[0].end) - op.start
        out.append([op, op.end - op.start])
        stack.append(len(out) - 1)
    return [(o, max(0, t)) for o, t in out]


def _is_container(op: Op) -> bool:
    return bool(_CONTAINERS.match(op.name))


# ---- the reductions -------------------------------------------------------

def busy(trace: Trace, win: Tuple[int, int]) -> Dict[str, float]:
    """Seconds in which an operation ran, averaged over the devices, and the
    window's length. Idle share = 1 - busy_s / window_s."""
    lo, hi = win
    per_dev = [_length(_clip(_union((o.start, o.end) for o in ops), lo, hi))
               for ops in trace.devices.values()]
    if not per_dev:
        return {}
    return {"busy_s": sum(per_dev) / len(per_dev) / 1e9,
            "window_s": (hi - lo) / 1e9,
            "devices": len(per_dev)}


def kernel_seconds(trace: Trace, win: Tuple[int, int], pattern: str,
                   field_name: str = "name") -> Dict[str, float]:
    """Self time of the operations whose name (or label) matches the regular
    expression, per device on average, and how many calls that was."""
    rx = re.compile(pattern)
    lo, hi = win
    total = calls = 0
    for ops in trace.devices.values():
        for op, t in self_times(ops):
            if op.start < lo or op.end > hi:
                continue
            if rx.search(getattr(op, field_name)):
                total += t
                calls += 1
    n = max(1, len(trace.devices))
    return {"seconds": total / n / 1e9, "calls": calls / n}


_SUFFIX = re.compile(r"[.\d]+$")
_SHAPE = re.compile(r"= \(?([a-z]+\d*\[[\d,]*\])")


def _kind(op: Op) -> str:
    """A readable name for a group of operations: the instruction's name
    without its number, then its first result shape if the label is HLO
    text, else the last parts of the program's scope path."""
    base = _SUFFIX.sub("", op.name) or op.name
    shape = _SHAPE.search(op.label)
    if shape:
        return f"{base} {shape.group(1)}"
    scope = "/".join(p for p in op.label.split("/")[-3:-1]
                     if p and not p.startswith("jit("))
    return f"{base} {scope}" if scope else base


def top_device_ops(trace: Trace, win: Tuple[int, int], limit: int = 10
                   ) -> List[List]:
    """[name, seconds] of the operations with most self time, numbered
    instances of one operation summed (see :func:`_kind`)."""
    lo, hi = win
    acc: Dict[str, int] = {}
    for ops in trace.devices.values():
        for op, t in self_times(ops):
            if op.end <= lo or op.start >= hi or _is_container(op):
                continue
            key = _kind(op)
            acc[key] = acc.get(key, 0) + t
    n = max(1, len(trace.devices))
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:limit]
    return [[k, v / n / 1e9] for k, v in ranked]


def named_ops(trace: Trace, win: Tuple[int, int], limit: int = 40
              ) -> List[List]:
    """[name, label, seconds, calls] by self time, one row per instruction:
    the list a person reads to find what a kernel is called in this trace
    (labels cut to 400 characters)."""
    lo, hi = win
    acc: Dict[Tuple[str, str], List] = {}
    for ops in trace.devices.values():
        for op, t in self_times(ops):
            if op.end <= lo or op.start >= hi:
                continue
            key = (op.name, op.label[:400])
            slot = acc.setdefault(key, [0, 0])
            slot[0] += t
            slot[1] += 1
    n = max(1, len(trace.devices))
    ranked = sorted(acc.items(), key=lambda kv: -kv[1][0])[:limit]
    return [[k[0], k[1], v[0] / n / 1e9, v[1] / n] for k, v in ranked]


def idle_gaps(trace: Trace, win: Tuple[int, int], limit: int = 10
              ) -> List[List]:
    """[host span name, seconds]: idle time of the first device, shared out
    to the benchmark's host spans by overlap (the innermost span wins), and
    ``(no span)`` for the rest. Largest first."""
    if not trace.devices:
        return []
    lo, hi = win
    ops = next(iter(trace.devices.values()))
    busy_spans = _clip(_union((o.start, o.end) for o in ops), lo, hi)
    gaps = _subtract([(lo, hi)], busy_spans)
    spans = [h for h in trace.host if h.name != WINDOW_SPAN]
    acc: Dict[str, int] = {}
    for h, t in self_times(spans):
        # self time of a host span that falls into device gaps
        inner = [(c.start, c.end) for c in spans
                 if c is not h and c.start >= h.start and c.end <= h.end]
        own = _subtract([(h.start, h.end)], _union(inner))
        got = sum(_length(_clip(gaps, s, e)) for s, e in own)
        if got:
            acc[h.name] = acc.get(h.name, 0) + got
    named = sum(acc.values())
    rest = _length(gaps) - named
    if rest > 0:
        acc["(no span)"] = rest
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:limit]
    return [[k, v / 1e9] for k, v in ranked]


def reduce(trace: Trace) -> Dict:
    """Everything the runners report from one trace."""
    win = window(trace)
    if win is None or not trace.devices:
        return {}
    return {**busy(trace, win),
            "device_ops": top_device_ops(trace, win),
            "idle_gaps": idle_gaps(trace, win),
            "window_ns": list(win)}
