"""Readers of what a model with gated short-convolution layers beside routed
experts (LFM2-MoE) adds to the train step: the roofline share of the gates
and the convolution, with their work reckoned from the shapes alone
(``opcount_lfm2.short_conv``), the grouped expert products' roofline share
for the pairs the router's counter says were computed, and the end-to-end
utilisation with this configuration's operation counts.

As everywhere under ``readers/``: a reader that finds nothing to read (a
program without the scope, another configuration) returns None and the metric
is left out of the line; nothing raises.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmarks import opcount_lfm2
from benchmarks.readers import delta, moe_share, program
from benchmarks.readers.latent_moe import _share_of_scope


def _is_lfm2(ctx: Dict) -> bool:
    return "conv_L_cache" in ctx["cfg"]


def conv_roofline(ctx: Dict, scope: str = "sconv_conv") -> Optional[float]:
    """The gates' and the convolutions' least time a step (every kept conv
    layer's: the forward as often as the compiled step runs it,
    ``readers.delta:rule_forwards``, and the backward) over the device time
    under ``scope`` a step: the same work whatever implements it."""
    v, cfg = ctx["values"], ctx["cfg"]
    if not _is_lfm2(ctx) or ctx.get("peak") is None:
        return None
    text = program.analysis(ctx).get("hlo_text")
    if not text:
        return None
    forwards = delta.rule_forwards(text, scope)
    share = _share_of_scope(ctx, scope, opcount_lfm2.short_conv(
        cfg, int(v["seq"]), batch=int(v["rows"]) // int(v["chips"]),
        forwards=forwards, backwards=1),
        sum(mixer == "conv" for mixer, _ in opcount_lfm2.kinds(cfg)))
    if share is not None:
        ctx["roofline_notes"][-1]["forwards"] = forwards
    return share


def experts_roofline(ctx: Dict, scope: str = "moe_experts"
                     ) -> Optional[float]:
    """The grouped products' least time for the (token, expert) pairs that
    were computed (``values["moe_pairs_per_step"]``: the router's counter,
    summed over the routed layers), each product counted as often as the
    step runs it, over the device time under ``scope`` a step."""
    v, cfg = ctx["values"], ctx["cfg"]
    if (not _is_lfm2(ctx) or ctx.get("peak") is None
            or not v.get("moe_pairs_per_step")):
        return None
    layers = sum(ffn == "moe" for _, ffn in opcount_lfm2.kinds(cfg))
    return _share_of_scope(ctx, scope, opcount_lfm2.grouped_products(
        cfg, v["moe_pairs_per_step"] / layers,
        forwards=moe_share._forwards(cfg), backwards=1), layers)


def train_mfu(ctx: Dict) -> Optional[float]:
    """End-to-end utilisation: operations a token needs (forward and
    backward, the held experts' share at its expectation, the attention
    layer's scores and values included, no recomputation) x tokens/s/chip
    over the chip's bf16 peak. A share of the whole step's peak, not a
    kernel's roofline share."""
    v, peak = ctx["values"], ctx["peak"]
    if not _is_lfm2(ctx) or peak is None or not v.get("train_tok_s_chip"):
        return None
    flops = opcount_lfm2.train_flops_per_token(ctx["cfg"], int(v["seq"]))
    return 100.0 * flops * v["train_tok_s_chip"] / peak["bf16_flops_per_s"]
