"""Readers of what Qwen3-Next adds to the train step (gated delta-rule layers
whose key heads serve several value heads, a gated full-attention layer of
256-wide heads, thin routed experts): the rule's roofline share with q and k
read once a key head, the flash backward's with a split pair of kernels
counted as the one backward it is, the grouped products' share for the pairs
the router's counter says were computed, the rows of q and k the rules read a
step, and the end-to-end utilisation by the work the cut does
(``opcount_qwen3_next``). The flash forward's share is
``readers.roofline:flash_train``'s, under this cell's pattern.

As everywhere under ``readers/``: a reader that finds nothing to read (a
program without the scope, the table, the kernels or the counter, another
configuration) returns None and the metric is left out of the line; nothing
raises.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmarks import opcount, opcount_qwen3_next
from benchmarks.readers import bd, delta, moe_share, program, roofline


def _is_gdn(ctx: Dict) -> bool:
    return "linear_num_key_heads" in ctx["cfg"] \
        and "shared_expert_intermediate_size" in ctx["cfg"]


def _scope_share(ctx: Dict, scope: str, ops: Dict[str, float],
                 op_name: Optional[str] = None, **note) -> Optional[float]:
    """``ops`` (a step's) at the chip's roofline over the device time under
    ``scope`` a step."""
    peak = ctx.get("peak")
    if peak is None:
        return None
    ms = moe_share.scope_device_ms(ctx, scope, op_name)
    if not ms:
        return None
    roof = opcount.roofline_seconds(ops, peak)
    ctx.setdefault("roofline_notes", []).append(
        {"bound": roof["bound"], "roof_s": roof["seconds"],
         "kernel_s": ms / 1e3, "what": scope + " a step", **note})
    return 100.0 * roof["seconds"] / (ms / 1e3)


def scan_roofline(ctx: Dict, scope: str = "delta_scan") -> Optional[float]:
    """The rules' least time a step (every kept delta layer's: the forward
    as often as the compiled step runs it, ``readers.delta.rule_forwards``,
    and the backward; q and k read once a key head) over the device time
    under ``scope`` a step."""
    if not _is_gdn(ctx):
        return None
    v, cfg = ctx["values"], ctx["cfg"]
    text = program.analysis(ctx).get("hlo_text")
    if not text:
        return None
    layers = opcount_qwen3_next.kinds(cfg).count("linear_attention")
    forwards = delta.rule_forwards(text, scope)
    ops = opcount_qwen3_next.delta_rule(
        cfg, int(v["seq"]), batch=int(v["rows"]) // int(v["chips"]),
        forwards=forwards, backwards=1)
    return _scope_share(ctx, scope, {n: x * layers for n, x in ops.items()},
                        forwards=forwards)


def flash_bwd(ctx: Dict, pattern: str, field: str = "name"
              ) -> Optional[float]:
    """The flash backward at d 256: the calls found are one a backward where
    the fused kernel runs and two where the split pair does (the step
    program's row says which, ``flash_bwd_lowerings``); each backward is
    asked for the causal pairs' four products once."""
    if not _is_gdn(ctx):
        return None
    k = roofline._kernel(ctx, pattern, field)
    arms = bd._row("flash_bwd_lowerings")       # the newest step program's
    if k is None or not arms:
        return None
    v = ctx["values"]
    per = opcount_qwen3_next.flash(
        ctx["cfg"], int(v["seq"]), batch=int(v["rows"]) // int(v["chips"]),
        forwards=0, backwards=1)
    times = k["calls"] / (2.0 if "split" in arms else 1.0)
    return roofline._share({n: x * times for n, x in per.items()},
                           k["seconds"], ctx)


def experts_roofline(ctx: Dict, scope: str = "moe_experts"
                     ) -> Optional[float]:
    """The grouped products' least time for the (token, expert) pairs that
    were computed (``values["moe_pairs_per_step"]``: the router's counter,
    summed over the layers), each product counted as often as the step runs
    it, over the device time under ``scope`` a step. At 320 pairs an expert
    the weights' read bounds it."""
    v, cfg = ctx["values"], ctx["cfg"]
    if not _is_gdn(ctx) or not v.get("moe_pairs_per_step"):
        return None
    layers = int(cfg["num_hidden_layers"])
    ops = opcount_qwen3_next.grouped_products(
        cfg, v["moe_pairs_per_step"] / layers,
        forwards=moe_share._forwards(cfg), backwards=1)
    return _scope_share(ctx, scope, {n: x * layers for n, x in ops.items()},
                        moe_share.RAGGED_DOT)


def qk_rows_per_step(ctx: Dict) -> Optional[float]:
    """The rows of q and k the delta layers' rules read a step
    (``values["delta_qk_rows_per_step"]``, which the runner takes from the
    step-program row's ``delta_qk_rows`` counter): ``rows x seq x key heads
    x delta layers x 2`` where a key head's q and k are read once, twice
    that where they are repeated to the value heads."""
    n = ctx["values"].get("delta_qk_rows_per_step")
    return None if n is None else float(n)


def train_mfu(ctx: Dict) -> Optional[float]:
    """End-to-end utilisation: operations a token needs (forward and
    backward, the rule's and the full layer's attention included, the held
    experts' share at its expectation, no recomputation) x tokens/s/chip
    over the chip's bf16 peak. A share of the whole step's peak, not a
    kernel's roofline share."""
    v, peak = ctx["values"], ctx["peak"]
    if not _is_gdn(ctx) or peak is None or not v.get("train_tok_s_chip"):
        return None
    flops = opcount_qwen3_next.train_flops_per_token(ctx["cfg"],
                                                     int(v["seq"]))
    return 100.0 * flops * v["train_tok_s_chip"] / peak["bf16_flops_per_s"]
