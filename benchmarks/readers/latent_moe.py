"""Readers of what a model of one-branch layers (Mamba-2, attention,
LatentMoE experts: Nemotron-H) adds to the train step: the grouped expert
products' roofline share at two products an expert in the latent, for the
pairs the router's counter says were computed
(``opcount_nemotron_h.grouped_products``), the scan's roofline share with its
work reckoned from the shapes alone (``opcount_nemotron_h.ssd_scan``: chunk
128, the group and heads held), and the end-to-end utilisation with this
configuration's operation counts.

As everywhere under ``readers/``: a reader that finds nothing to read (a
program without the scope, another configuration) returns None and the metric
is left out of the line; nothing raises.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmarks import opcount, opcount_nemotron_h
from benchmarks.readers import moe_share


def _is_latent(ctx: Dict) -> bool:
    return "moe_latent_size" in ctx["cfg"]


def _share_of_scope(ctx: Dict, scope: str, ops: Dict[str, float],
                    layers: int) -> Optional[float]:
    """``layers`` x ``ops``' least time over the device time under ``scope``
    a step."""
    ms = moe_share.scope_device_ms(ctx, scope)
    if not ms:
        return None
    roof = opcount.roofline_seconds(
        {n: x * layers for n, x in ops.items()}, ctx["peak"])
    ctx.setdefault("roofline_notes", []).append(
        {"bound": roof["bound"], "roof_s": roof["seconds"],
         "kernel_s": ms / 1e3, "what": scope + " a step"})
    return 100.0 * roof["seconds"] / (ms / 1e3)


def experts_roofline(ctx: Dict, scope: str = "moe_experts"
                     ) -> Optional[float]:
    """The grouped products' least time for the (token, expert) pairs that
    were computed (``values["moe_pairs_per_step"]``: the router's counter,
    summed over the expert layers), each product counted as often as the
    step runs it, over the device time under ``scope`` a step."""
    v, cfg = ctx["values"], ctx["cfg"]
    if (not _is_latent(ctx) or ctx.get("peak") is None
            or not v.get("moe_pairs_per_step")):
        return None
    layers = opcount_nemotron_h.kinds(cfg).count("E")
    return _share_of_scope(ctx, scope, opcount_nemotron_h.grouped_products(
        cfg, v["moe_pairs_per_step"] / layers,
        forwards=moe_share._forwards(cfg), backwards=1), layers)


def scan_roofline(ctx: Dict, scope: str = "ssm_scan") -> Optional[float]:
    """The scans' least time a step (every kept Mamba layer's: the forward
    as often as the step runs it, once more under any recomputation policy,
    and the backward) over the device time under ``scope`` a step."""
    v, cfg = ctx["values"], ctx["cfg"]
    if not _is_latent(ctx) or ctx.get("peak") is None:
        return None
    return _share_of_scope(ctx, scope, opcount_nemotron_h.ssd_scan(
        cfg, int(v["seq"]), batch=int(v["rows"]) // int(v["chips"]),
        forwards=moe_share._forwards(cfg), backwards=1),
        opcount_nemotron_h.kinds(cfg).count("M"))


def train_mfu(ctx: Dict) -> Optional[float]:
    """End-to-end utilisation: operations a token needs (forward and
    backward, the held experts' share at its expectation, the scan's and the
    attention layer's included, no recomputation) x tokens/s/chip over the
    chip's bf16 peak. A share of the whole step's peak, not a kernel's
    roofline share."""
    v, peak = ctx["values"], ctx["peak"]
    if not _is_latent(ctx) or peak is None or not v.get("train_tok_s_chip"):
        return None
    flops = opcount_nemotron_h.train_flops_per_token(ctx["cfg"],
                                                     int(v["seq"]))
    return 100.0 * flops * v["train_tok_s_chip"] / peak["bf16_flops_per_s"]
