"""Roofline share of a kernel: the least time the chip could take for the
work the kernel was given (the larger of operations over peak FLOP/s and
bytes over peak bytes/s, from ``opcount`` and ``peaks.json``) over the time
the trace shows for it. The kernel's events are found by a regular
expression kept in the metric's file, because the program gives its kernels
no stable names yet."""

from __future__ import annotations

from typing import Dict, Optional

from benchmarks import opcount, trace_reduce


def _kernel(ctx: Dict, pattern: str, field: str):
    trace, r = ctx.get("trace"), ctx.get("reduced")
    if trace is None or not r or ctx.get("peak") is None:
        return None
    k = trace_reduce.kernel_seconds(trace, tuple(r["window_ns"]), pattern,
                                    field)
    return k if k["seconds"] > 0 else None


def _share(ops: Dict[str, float], seconds: float, ctx: Dict) -> float:
    roof = opcount.roofline_seconds(ops, ctx["peak"])
    ctx.setdefault("roofline_notes", []).append(
        {"bound": roof["bound"], "roof_s": roof["seconds"],
         "kernel_s": seconds})
    return 100.0 * roof["seconds"] / seconds


def flash_train(ctx: Dict, pattern: str, field: str = "name",
                which: str = "forward") -> Optional[float]:
    """The flash kernel in training: every call is one layer over the rows
    of one chip at the cell's sequence length."""
    k = _kernel(ctx, pattern, field)
    if k is None:
        return None
    v = ctx["values"]
    fn = opcount.flash_forward if which == "forward" \
        else opcount.flash_backward
    per_call = fn(ctx["cfg"], int(v["seq"]),
                  batch=int(v["rows"]) // int(v["chips"]))
    ops = {n: x * k["calls"] for n, x in per_call.items()}
    return _share(ops, k["seconds"], ctx)

