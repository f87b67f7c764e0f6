"""Readers of what a model with latent attention and a held share of
sigmoid-routed experts adds to the train step: the flash kernels' roofline
share at keys wider than values (``opcount_kanana2.flash_forward`` /
``flash_backward``: the work of 32 heads x (192 + 128) over the causal pairs,
from the shapes alone, whatever implements it: padding shows as a low share),
the grouped expert products' roofline share for the pairs the router's
counter says were computed, and the end-to-end utilisation with this
configuration's operation counts.

As everywhere under ``readers/``: a reader that finds nothing to read (a
program without the kernels or the scope, another configuration) returns
None and the metric is left out of the line; nothing raises.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmarks import opcount, opcount_kanana2
from benchmarks.readers import moe_share, roofline


def _is_mla(ctx: Dict) -> bool:
    return "kv_lora_rank" in ctx["cfg"]


def flash_mla(ctx: Dict, pattern: str, field: str = "name",
              which: str = "forward") -> Optional[float]:
    """The flash kernel in training at key width dn + dr over value width dv:
    every call is one layer over the rows of one chip at the cell's sequence
    length (the recomputed forward is a call like the first)."""
    if not _is_mla(ctx):
        return None
    k = roofline._kernel(ctx, pattern, field)
    if k is None:
        return None
    v = ctx["values"]
    fn = opcount_kanana2.flash_forward if which == "forward" \
        else opcount_kanana2.flash_backward
    per_call = fn(ctx["cfg"], int(v["seq"]),
                  batch=int(v["rows"]) // int(v["chips"]))
    return roofline._share({n: x * k["calls"] for n, x in per_call.items()},
                           k["seconds"], ctx)


def experts_roofline(ctx: Dict, scope: str = "moe_experts"
                     ) -> Optional[float]:
    """The grouped products' least time for the (token, expert) pairs that
    were computed (``values["moe_pairs_per_step"]``: the router's counter,
    summed over the routed layers), each product counted as often as the
    step runs it, over the device time under ``scope`` a step."""
    v, cfg, peak = ctx["values"], ctx["cfg"], ctx.get("peak")
    if not _is_mla(ctx) or peak is None or not v.get("moe_pairs_per_step"):
        return None
    ms = moe_share.scope_device_ms(ctx, scope)
    if not ms:
        return None
    layers = int(cfg["num_hidden_layers"]) - int(cfg["first_k_dense_replace"])
    ops = opcount_kanana2.grouped_products(
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
    if not _is_mla(ctx) or peak is None or not v.get("train_tok_s_chip"):
        return None
    flops = opcount_kanana2.train_flops_per_token(ctx["cfg"], int(v["seq"]))
    return 100.0 * flops * v["train_tok_s_chip"] / peak["bf16_flops_per_s"]
