"""Readers of what a model whose window and full attention layers differ in
their query heads adds to the train step: the heads the step-program table
says each step's layers ran, the flash kernels' roofline share with each
call's work taken from its own layer's heads, window and length, the grouped
expert products' roofline share for the pairs the router's counter says were
computed, and the end-to-end utilisation (``opcount_laguna``).

As everywhere under ``readers/``: a reader that finds nothing to read (a
program without the fact, the kernels or the scope, another configuration)
returns None and the metric is left out of the line; nothing raises.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmarks import opcount, opcount_laguna
from benchmarks.readers import moe_share, roofline


def _by_layer(ctx: Dict) -> bool:
    return "num_attention_heads_per_layer" in ctx["cfg"]


def attn_heads(ctx: Dict) -> Optional[float]:
    """The query heads the window and full layers of one micro-batch's
    forward ran, summed over those layers, as the newest ``ds_train_step*``
    row of the program's step-program table says."""
    try:
        from deepspeed_tpu.observability import steplog
    except ImportError:
        return None
    rows = [p for p in steplog.programs()
            if p.name.startswith("ds_train_step")]
    n = getattr(rows[-1], "attn_heads_per_step", None) if rows else None
    return None if n is None else float(n)


def flash_heads(ctx: Dict, pattern: str, field: str = "name",
                which: str = "forward") -> Optional[float]:
    """The flash kernel in training over layers that differ in kind and in
    heads: the calls found are the kept layers' in turn (every step runs
    each as often: the count is the compiled step's, recomputed forwards
    among them), so their work is the kept layers' summed, each from its own
    heads and kept pairs, times calls over layers."""
    if not _by_layer(ctx):
        return None
    k = roofline._kernel(ctx, pattern, field)
    if k is None:
        return None
    v, cfg = ctx["values"], ctx["cfg"]
    fn = opcount_laguna.flash_forward if which == "forward" \
        else opcount_laguna.flash_backward
    per_stack = fn(cfg, int(v["seq"]),
                   batch=int(v["rows"]) // int(v["chips"]))
    times = k["calls"] / len(opcount_laguna.kinds(cfg))
    return roofline._share({n: x * times for n, x in per_stack.items()},
                           k["seconds"], ctx)


def experts_roofline(ctx: Dict, scope: str = "moe_experts"
                     ) -> Optional[float]:
    """The grouped products' least time for the (token, expert) pairs that
    were computed (``values["moe_pairs_per_step"]``: the router's counter,
    summed over the routed layers), each product counted as often as the
    step runs it, over the device time under ``scope`` a step."""
    v, cfg, peak = ctx["values"], ctx["cfg"], ctx.get("peak")
    if not _by_layer(ctx) or peak is None \
            or not v.get("moe_pairs_per_step"):
        return None
    ms = moe_share.scope_device_ms(ctx, scope)
    if not ms:
        return None
    layers = sum(ffn == "moe" for _, ffn in opcount_laguna.kinds(cfg))
    ops = opcount_laguna.grouped_products(
        cfg, v["moe_pairs_per_step"] / layers,
        forwards=moe_share._forwards(cfg), backwards=1)
    roof = opcount.roofline_seconds(
        {n: x * layers for n, x in ops.items()}, peak)
    ctx.setdefault("roofline_notes", []).append(
        {"bound": roof["bound"], "roof_s": roof["seconds"],
         "kernel_s": ms / 1e3, "what": scope + " a step"})
    return 100.0 * roof["seconds"] / (ms / 1e3)


def train_mfu(ctx: Dict) -> Optional[float]:
    """End-to-end utilisation: operations a token needs (forward and
    backward, the held experts' share at its expectation, no recomputation)
    x tokens/s/chip over the chip's bf16 peak. Not a roofline share."""
    v, peak = ctx["values"], ctx["peak"]
    if not _by_layer(ctx) or peak is None or not v.get("train_tok_s_chip"):
        return None
    flops = opcount_laguna.train_flops_per_token(ctx["cfg"], int(v["seq"]))
    return 100.0 * flops * v["train_tok_s_chip"] / peak["bf16_flops_per_s"]
