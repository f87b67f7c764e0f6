"""Readers of what block-diffusion training adds to the train step (SDAR, a
``[noised ; clean]`` row of two positions a token under the block-diffusion
mask): the positions the layers run a step, the host span of the noising,
the flash kernels' roofline share under the rounded diagonal with their work
taken from the pairs the **mask** keeps over the clean keys (whatever the
kernels chose to compute, so that dead work cannot raise the share), the
grouped expert products' share for the pairs the router's counter says were
computed, and the end-to-end utilisation a **token** (``opcount_sdar``).

As everywhere under ``readers/``: a reader that finds nothing to read (a
program without the fact, the span, the kernels or the scope, another
configuration) returns None and the metric is left out of the line; nothing
raises.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmarks import opcount, opcount_sdar
from benchmarks.readers import moe_share, program, roofline


def _is_bd(ctx: Dict) -> bool:
    return "block_length" in ctx["cfg"]


def _row(name: str):
    """``name`` of the newest ``ds_train_step*`` row of the program's
    step-program table; None without the table, a row or the fact."""
    try:
        from deepspeed_tpu.observability import steplog
    except ImportError:
        return None
    rows = [p for p in steplog.programs()
            if p.name.startswith("ds_train_step")]
    return getattr(rows[-1], name, None) if rows else None


def positions_per_step(ctx: Dict) -> Optional[float]:
    """The positions the layers of one step run: the step-program row's
    ``positions_per_token`` times the tokens of a step."""
    per = _row("positions_per_token")
    v = ctx["values"]
    if per is None or not v.get("seq") or not v.get("rows"):
        return None
    return float(per) * int(v["seq"]) * int(v["rows"])


def host_span_ms(ctx: Dict, span: str) -> Optional[float]:
    """Median milliseconds of the host span ``span`` (``ds.<cat>.<name>``)
    inside the profiler window."""
    ms = (program.analysis(ctx).get("host_phases_ms") or {}).get(span)
    return None if ms is None else float(ms)


def flash_bd(ctx: Dict, pattern: str, field: str = "name",
             which: str = "forward") -> Optional[float]:
    """The flash kernels under the rounded diagonal: a layer's two calls
    (the clean half's, the noised half's over the clean keys) are asked for
    the ``seq^2`` pairs the mask keeps over the clean keys between them, so
    the calls found carry that work times calls over two."""
    if not _is_bd(ctx):
        return None
    k = roofline._kernel(ctx, pattern, field)
    if k is None:
        return None
    v, cfg = ctx["values"], ctx["cfg"]
    seq = int(v["seq"])
    fwd = which == "forward"
    per_layer = opcount_sdar.attend(
        cfg, seq, batch=int(v["rows"]) // int(v["chips"]),
        forwards=int(fwd), backwards=int(not fwd),
        pairs=opcount_sdar.cross_pairs(cfg, seq))
    times = k["calls"] / 2.0
    return roofline._share({n: x * times for n, x in per_layer.items()},
                           k["seconds"], ctx)


def experts_roofline(ctx: Dict, scope: str = "moe_experts"
                     ) -> Optional[float]:
    """The grouped products' least time for the (position, expert) pairs
    that were computed (``values["moe_pairs_per_step"]``: the router's
    counter, summed over the layers), each product counted as often as the
    step runs it, over the device time under ``scope`` a step."""
    v, cfg, peak = ctx["values"], ctx["cfg"], ctx.get("peak")
    if not _is_bd(ctx) or peak is None or not v.get("moe_pairs_per_step"):
        return None
    ms = moe_share.scope_device_ms(ctx, scope)
    if not ms:
        return None
    layers = int(cfg["num_hidden_layers"])
    ops = opcount_sdar.grouped_products(
        cfg, v["moe_pairs_per_step"] / layers,
        forwards=moe_share._forwards(cfg), backwards=1)
    roof = opcount.roofline_seconds(
        {n: x * layers for n, x in ops.items()}, peak)
    ctx.setdefault("roofline_notes", []).append(
        {"bound": roof["bound"], "roof_s": roof["seconds"],
         "kernel_s": ms / 1e3, "what": scope + " a step"})
    return 100.0 * roof["seconds"] / (ms / 1e3)


def train_mfu(ctx: Dict) -> Optional[float]:
    """End-to-end utilisation: operations a **token** needs (two positions
    in every layer, one at the head; forward and backward, the held experts'
    share at its expectation, no recomputation) x tokens/s/chip over the
    chip's bf16 peak. Not a roofline share."""
    v, peak = ctx["values"], ctx["peak"]
    if not _is_bd(ctx) or peak is None or not v.get("train_tok_s_chip"):
        return None
    flops = opcount_sdar.train_flops_per_token(ctx["cfg"], int(v["seq"]))
    return 100.0 * flops * v["train_tok_s_chip"] / peak["bf16_flops_per_s"]
