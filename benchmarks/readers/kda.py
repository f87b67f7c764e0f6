"""Readers of what a model with KDA layers (the delta rule with a decay a key
channel) beside a latent-attention layer and a held share of group-limited
sigmoid-routed experts adds to the train step: the rule's roofline share,
with its work reckoned from the shapes alone (``opcount_ling3.kda_rule``:
the same work whatever implements the rule), the flash kernels' share at
keys wider than values for the one latent-attention layer's held heads, the
grouped expert products' share for the pairs the router's counter says were
computed, the end-to-end utilisation with this configuration's operation
counts, and the chunks a step goes through as the step-program table says.

As everywhere under ``readers/``: a reader that finds nothing to read (a
program without the scope, the table or the field, another configuration)
returns None and the metric is left out of the line; nothing raises.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmarks import opcount_ling3
from benchmarks.readers import delta, moe_share, program, roofline
from benchmarks.readers.latent_moe import _share_of_scope


def _is_ling3(ctx: Dict) -> bool:
    return "kda_lower_bound" in ctx["cfg"]


def _layers(cfg: Dict, slot: int, kind: str) -> int:
    return sum(k[slot] == kind for k in opcount_ling3.kinds(cfg))


def scan_roofline(ctx: Dict, scope: str = "kda_scan") -> Optional[float]:
    """The rules' least time a step (every kept KDA layer's: the forward as
    often as the compiled step runs it, ``readers.delta:rule_forwards``, and
    the backward) over the device time under ``scope`` a step."""
    v, cfg = ctx["values"], ctx["cfg"]
    if not _is_ling3(ctx) or ctx.get("peak") is None:
        return None
    text = program.analysis(ctx).get("hlo_text")
    if not text:
        return None
    forwards = delta.rule_forwards(text, scope)
    share = _share_of_scope(ctx, scope, opcount_ling3.kda_rule(
        cfg, int(v["seq"]), batch=int(v["rows"]) // int(v["chips"]),
        forwards=forwards, backwards=1), _layers(cfg, 0, "kda"))
    if share is not None:
        ctx["roofline_notes"][-1]["forwards"] = forwards
    return share


def flash(ctx: Dict, pattern: str, field: str = "name",
          which: str = "forward") -> Optional[float]:
    """The flash kernel in training at key width dn + dr over value width dv:
    every call is the one latent-attention layer's held heads over the rows
    of one chip at the cell's sequence length (a recomputed forward is a call
    like the first)."""
    if not _is_ling3(ctx):
        return None
    k = roofline._kernel(ctx, pattern, field)
    if k is None:
        return None
    v = ctx["values"]
    fn = opcount_ling3.flash_forward if which == "forward" \
        else opcount_ling3.flash_backward
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
    v, cfg = ctx["values"], ctx["cfg"]
    if (not _is_ling3(ctx) or ctx.get("peak") is None
            or not v.get("moe_pairs_per_step")):
        return None
    layers = _layers(cfg, 1, "moe")
    return _share_of_scope(ctx, scope, opcount_ling3.grouped_products(
        cfg, v["moe_pairs_per_step"] / layers,
        forwards=moe_share._forwards(cfg), backwards=1), layers)


def train_mfu(ctx: Dict) -> Optional[float]:
    """End-to-end utilisation: operations a token needs (forward and
    backward, the rule's and the latent-attention layer's scores and values
    included, the held experts' share at its expectation, no recomputation)
    x tokens/s/chip over the chip's bf16 peak. A share of the whole step's
    peak, not a kernel's roofline share."""
    v, peak = ctx["values"], ctx["peak"]
    if not _is_ling3(ctx) or peak is None or not v.get("train_tok_s_chip"):
        return None
    flops = opcount_ling3.train_flops_per_token(ctx["cfg"], int(v["seq"]))
    return 100.0 * flops * v["train_tok_s_chip"] / peak["bf16_flops_per_s"]


def chunks_per_step(ctx: Dict) -> Optional[float]:
    """Chunks the KDA layers of one step go through, as the newest
    ``ds_train_step*`` row of the program's step-program table says
    (``observability/steplog.py``: KDA layers x rows x chunks a row)."""
    try:
        from deepspeed_tpu.observability import steplog
    except ImportError:
        return None
    rows = [p for p in steplog.programs()
            if p.name.startswith("ds_train_step")]
    n = getattr(rows[-1], "kda_chunks_per_step", None) if rows else None
    return None if n is None else float(n)
