"""Readers of what a model with gated delta-rule layers adds to the train
step: the rule's roofline share, with its work reckoned from the shapes alone
(``opcount_olmo_hybrid.delta_rule``), the end-to-end utilisation with the
rule's operations counted in, and the chunks a step goes through as the
step-program table says.

As everywhere under ``readers/``: a reader that finds nothing to read (a
program without the scope, the table or the field) returns None and the
metric is left out of the line; nothing raises.
"""

from __future__ import annotations

import re
from typing import Dict, Optional

from benchmarks import opcount, opcount_olmo_hybrid
from benchmarks.readers import moe_share, program


def rule_forwards(hlo_text: str, scope: str) -> int:
    """How often the compiled step runs the rule's forward: once, and once
    more where the backward's recomputed region (``checkpoint/
    rematted_computation`` in an instruction's ``op_name``) holds a product
    or a kernel under ``scope``. Read from the program, not from the policy's
    name: ``dots_saveable`` keeps the einsum form's products and reruns only
    what is elementwise around them (one forward), ``full`` reruns them, and
    a kernel behind a ``custom_vjp`` is no dot and is rerun whole under
    either."""
    rerun = re.compile(r"rematted_computation/.*\b%s/.*(dot_general|"
                       r"pallas_call)" % re.escape(scope))
    return 2 if any(rerun.search(m.group(1)) for m in
                    program._OP_NAME.finditer(hlo_text)) else 1


def scan_roofline(ctx: Dict, scope: str = "delta_scan") -> Optional[float]:
    """The rules' least time a step (every kept delta layer's: the forward
    as often as the compiled step runs it, :func:`rule_forwards`, and the
    backward) over the device time under ``scope`` a step."""
    v, cfg, peak = ctx["values"], ctx["cfg"], ctx.get("peak")
    if "linear_num_value_heads" not in cfg or peak is None:
        return None
    ms = moe_share.scope_device_ms(ctx, scope)
    if not ms:
        return None
    layers = opcount_olmo_hybrid.kinds(cfg).count("linear_attention")
    forwards = rule_forwards(program.analysis(ctx)["hlo_text"], scope)
    ops = opcount_olmo_hybrid.delta_rule(
        cfg, int(v["seq"]), batch=int(v["rows"]) // int(v["chips"]),
        forwards=forwards, backwards=1)
    roof = opcount.roofline_seconds(
        {n: x * layers for n, x in ops.items()}, peak)
    ctx.setdefault("roofline_notes", []).append(
        {"bound": roof["bound"], "roof_s": roof["seconds"],
         "kernel_s": ms / 1e3, "what": scope + " a step",
         "forwards": forwards})
    return 100.0 * roof["seconds"] / (ms / 1e3)


def train_mfu(ctx: Dict) -> Optional[float]:
    """End-to-end utilisation: operations a token needs (forward and
    backward, the rule's and the full layer's attention included, no
    recomputation) x tokens/s/chip over the chip's bf16 peak. A share of the
    whole step's peak, not a kernel's roofline share."""
    v, peak = ctx["values"], ctx["peak"]
    if peak is None or not v.get("train_tok_s_chip") \
            or "linear_num_value_heads" not in ctx["cfg"]:
        return None
    flops = opcount_olmo_hybrid.train_flops_per_token(ctx["cfg"],
                                                      int(v["seq"]))
    return 100.0 * flops * v["train_tok_s_chip"] / peak["bf16_flops_per_s"]


def chunks_per_step(ctx: Dict) -> Optional[float]:
    """Chunks the delta layers of one step go through, as the newest
    ``ds_train_step*`` row of the program's step-program table says
    (``observability/steplog.py``: delta layers x rows x chunks a row)."""
    try:
        from deepspeed_tpu.observability import steplog
    except ImportError:
        return None
    rows = [p for p in steplog.programs()
            if p.name.startswith("ds_train_step")]
    n = getattr(rows[-1], "delta_chunks_per_step", None) if rows else None
    return None if n is None else float(n)
