"""Readers of per-layer metrics. A reader takes the run's context and the
``args`` of its metric file, and returns a number, or None when what it
reads is not there (the metric is then left out of the line).

The context: ``values`` (what the runner measured or counted), ``reduced``
(``trace_reduce.reduce`` of the profiler window), ``trace`` (the plain
trace), ``cfg`` (the configuration file), ``peak`` (the device's row of
``peaks.json``), ``cell``.
"""

from __future__ import annotations

import statistics
from typing import Dict, Optional

from benchmarks import opcount


def value(ctx: Dict, key: str) -> Optional[float]:
    """A number the runner already holds: ``values[key]``."""
    v = ctx["values"].get(key)
    return None if v is None else float(v)


def list_median(ctx: Dict, key: str) -> Optional[float]:
    xs = ctx["values"].get(key)
    return float(statistics.median(xs)) if xs else None


def train_mfu(ctx: Dict) -> Optional[float]:
    """End-to-end utilisation: operations a token needs (forward and
    backward, no recomputation) x tokens/s/chip over the chip's bf16 peak.
    Not a kernel's roofline share."""
    v, peak = ctx["values"], ctx["peak"]
    if peak is None or not v.get("train_tok_s_chip"):
        return None
    flops = opcount.train_flops_per_token(ctx["cfg"], int(v["seq"]))
    return 100.0 * flops * v["train_tok_s_chip"] / peak["bf16_flops_per_s"]


def device_idle_share(ctx: Dict) -> Optional[float]:
    r = ctx["reduced"]
    if not r or not r.get("window_s"):
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])

