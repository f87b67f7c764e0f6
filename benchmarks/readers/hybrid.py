"""Readers of what a model with state-space layers adds to the train step:
the scan's roofline share, with its work reckoned from the shapes alone
(``opcount_granite4h.ssd_scan``), the end-to-end utilisation with the scan's
operations counted in, and the chunks a step scans as the step-program table
says.

As everywhere under ``readers/``: a reader that finds nothing to read (a
program without the scope, the table or the field) returns None and the
metric is left out of the line; nothing raises.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmarks import opcount, opcount_granite4h
from benchmarks.readers import moe_share


def scan_roofline(ctx: Dict, scope: str = "ssm_scan") -> Optional[float]:
    """The scans' least time a step (every kept state-space layer's: the
    forward as often as the step runs it, once more under any recomputation
    policy, none of which names the scan's outputs, and the backward) over
    the device time under ``scope`` a step."""
    v, cfg, peak = ctx["values"], ctx["cfg"], ctx.get("peak")
    if "mamba_n_heads" not in cfg or peak is None:
        return None
    ms = moe_share.scope_device_ms(ctx, scope)
    if not ms:
        return None
    layers = opcount_granite4h.kinds(cfg).count("mamba")
    ops = opcount_granite4h.ssd_scan(
        cfg, int(v["seq"]), batch=int(v["rows"]) // int(v["chips"]),
        forwards=moe_share._forwards(cfg), backwards=1)
    roof = opcount.roofline_seconds(
        {n: x * layers for n, x in ops.items()}, peak)
    ctx.setdefault("roofline_notes", []).append(
        {"bound": roof["bound"], "roof_s": roof["seconds"],
         "kernel_s": ms / 1e3, "what": scope + " a step"})
    return 100.0 * roof["seconds"] / (ms / 1e3)


def train_mfu(ctx: Dict) -> Optional[float]:
    """End-to-end utilisation: operations a token needs (forward and
    backward, the scan's and the attention layer's included, no
    recomputation) x tokens/s/chip over the chip's bf16 peak. Not a roofline
    share."""
    v, peak = ctx["values"], ctx["peak"]
    if peak is None or not v.get("train_tok_s_chip") \
            or "mamba_n_heads" not in ctx["cfg"]:
        return None
    flops = opcount_granite4h.train_flops_per_token(ctx["cfg"],
                                                    int(v["seq"]))
    return 100.0 * flops * v["train_tok_s_chip"] / peak["bf16_flops_per_s"]


def chunks_per_step(ctx: Dict) -> Optional[float]:
    """Chunks the state-space layers of one step scan, as the newest
    ``ds_train_step*`` row of the program's step-program table says
    (``observability/steplog.py``: state-space layers x rows x chunks a
    row)."""
    try:
        from deepspeed_tpu.observability import steplog
    except ImportError:
        return None
    rows = [p for p in steplog.programs()
            if p.name.startswith("ds_train_step")]
    n = getattr(rows[-1], "ssm_chunks_per_step", None) if rows else None
    return None if n is None else float(n)
