"""Readers of what a model whose attention reads only the keys a learned
indexer picks, over a held share of softmax-routed experts, adds to the
train step: the roofline shares of the indexer and of the attention over the
set, with their work reckoned from the shapes alone (``opcount_keye_vl2``:
the work the equations ask for, the attention over the selected pairs, the
indexer over the causal pairs, whatever implements them), the grouped expert
products' share for the pairs the router's counter says were computed, the
end-to-end utilisation with this configuration's operation counts, and the
share of the causal pairs the sets keep as the step-program table says.

As everywhere under ``readers/``: a reader that finds nothing to read (a
program without the scope, the table or the field, another configuration)
returns None and the metric is left out of the line; nothing raises.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmarks import opcount_keye_vl2
from benchmarks.readers import delta, moe_share, program
from benchmarks.readers.latent_moe import _share_of_scope


def _is_dsa(ctx: Dict) -> bool:
    return "sa_config" in ctx["cfg"]


def _forwards(ctx: Dict, scope: str) -> Optional[int]:
    """How often the compiled step runs the products under ``scope``'s
    forward (``readers.delta:rule_forwards``: once more where the backward's
    recomputed region holds them)."""
    text = program.analysis(ctx).get("hlo_text")
    return delta.rule_forwards(text, scope) if text else None


def attend_roofline(ctx: Dict, scope: str = "dsa_attend") -> Optional[float]:
    """The least time of the attention over the selected pairs a step (every
    layer's: the forward as often as the compiled step runs it, and the
    backward) over the device time under ``scope`` a step."""
    v, cfg = ctx["values"], ctx["cfg"]
    if not _is_dsa(ctx) or ctx.get("peak") is None:
        return None
    forwards = _forwards(ctx, scope)
    if forwards is None:
        return None
    share = _share_of_scope(ctx, scope, opcount_keye_vl2.attend(
        cfg, int(v["seq"]), batch=int(v["rows"]) // int(v["chips"]),
        forwards=forwards, backwards=1), int(cfg["num_hidden_layers"]))
    if share is not None:
        ctx["roofline_notes"][-1]["forwards"] = forwards
    return share


def indexer_roofline(ctx: Dict, scope: str = "dsa_indexer",
                     attend: str = "dsa_attend") -> Optional[float]:
    """The least time of what lies under ``scope`` a step (every layer's
    indexer: its projections, forward as often as the compiled step runs
    them and backward, and its scores over the causal pairs, forward as
    often as the step runs the op that holds them, which is the attention's
    under ``attend``: the two stand in one forward rule and are kept or run
    again together; the scores' backward lies under ``dsa_loss``) over the
    device time under ``scope`` a step."""
    v, cfg = ctx["values"], ctx["cfg"]
    if not _is_dsa(ctx) or ctx.get("peak") is None:
        return None
    proj, score = _forwards(ctx, scope), _forwards(ctx, attend)
    if proj is None:
        return None
    share = _share_of_scope(ctx, scope, opcount_keye_vl2.indexer(
        cfg, int(v["seq"]), batch=int(v["rows"]) // int(v["chips"]),
        proj_forwards=proj, score_forwards=score, proj_backwards=1),
        int(cfg["num_hidden_layers"]))
    if share is not None:
        ctx["roofline_notes"][-1]["forwards"] = (proj, score)
    return share


def experts_roofline(ctx: Dict, scope: str = "moe_experts"
                     ) -> Optional[float]:
    """The grouped products' least time for the (token, expert) pairs that
    were computed (``values["moe_pairs_per_step"]``: the router's counter,
    summed over the layers), each product counted as often as the step runs
    it, over the device time under ``scope`` a step."""
    v, cfg = ctx["values"], ctx["cfg"]
    if (not _is_dsa(ctx) or ctx.get("peak") is None
            or not v.get("moe_pairs_per_step")):
        return None
    layers = int(cfg["num_hidden_layers"])
    return _share_of_scope(ctx, scope, opcount_keye_vl2.grouped_products(
        cfg, v["moe_pairs_per_step"] / layers,
        forwards=moe_share._forwards(cfg), backwards=1), layers)


def train_mfu(ctx: Dict) -> Optional[float]:
    """End-to-end utilisation: operations a token needs (forward and
    backward, the attention over the selected pairs, the indexer over the
    causal pairs, the held experts' share at its expectation, no
    recomputation) x tokens/s/chip over the chip's bf16 peak. A share of the
    whole step's peak, not a kernel's roofline share."""
    v, peak = ctx["values"], ctx["peak"]
    if not _is_dsa(ctx) or peak is None or not v.get("train_tok_s_chip"):
        return None
    flops = opcount_keye_vl2.train_flops_per_token(ctx["cfg"], int(v["seq"]))
    return 100.0 * flops * v["train_tok_s_chip"] / peak["bf16_flops_per_s"]


def selected_share(ctx: Dict) -> Optional[float]:
    """Selected (query, key) pairs over causal pairs a step, in percent, as
    the newest ``ds_train_step*`` row of the program's step-program table
    says (``observability/steplog.py``: ``dsa_selected_share``)."""
    try:
        from deepspeed_tpu.observability import steplog
    except ImportError:
        return None
    rows = [p for p in steplog.programs()
            if p.name.startswith("ds_train_step")]
    share = getattr(rows[-1], "dsa_selected_share", None) if rows else None
    return None if share is None else 100.0 * float(share)
