"""Readers of what a model with window and full layers in turn and a held
share of its experts adds to the train step: the flash kernels' roofline
share with each call's work taken from its layer's kind, the grouped expert
products' roofline share for the pairs the router's counter says were
computed, and the end-to-end utilisation with the experts' share of the
arithmetic (``opcount_mellum2``).

As everywhere under ``readers/``: a reader that finds nothing to read returns
None and the metric is left out of the line; nothing raises.
"""

from __future__ import annotations

import re
from typing import Dict, Optional

from benchmarks import opcount, opcount_mellum2
from benchmarks.readers import looped, program, roofline

#: the grouped products as the compiled program names them: the compiler's
#: rewrite of ``ragged_dot`` into its kernels gives them ``op_name=
#: "ragged-dot-..."`` and drops the scope they were traced under
RAGGED_DOT = r"^ragged-dot"


def scope_device_ms(ctx: Dict, scope: str, op_name: Optional[str] = None
                    ) -> Optional[float]:
    """``readers.looped:scope_device_ms`` (self time a step of the device
    operations under ``scope``) with the instructions whose whole
    ``op_name`` matches the pattern ``op_name`` counted in."""
    a = program.analysis(ctx)
    trace, reduced = ctx.get("trace"), ctx.get("reduced") or {}
    path = program.xplane_path(ctx["cell"]["name"])
    if not a.get("hlo_text") or trace is None or not path \
            or not reduced.get("window_ns"):
        return None
    under = looped.instructions_under(a["hlo_text"], scope)
    if op_name:
        for line in a["hlo_text"].splitlines():
            m, meta = program._INSTR.match(line), program._OP_NAME.search(line)
            if m and meta and re.search(op_name, meta.group(1)):
                under[m.group(1)] = True
    if not any(under.values()):
        return None
    events = program.load_program_events(path)
    _, runs = program.step_modules(events["modules"],
                                   tuple(reduced["window_ns"]))
    ops = next(iter(trace.devices.values()), [])
    by = program.device_ms_by_scope(
        ops, runs, {n: (scope if u else "other", "forward")
                    for n, u in under.items()})
    return sum(by[scope].values()) if scope in by else None


def flash_mixed(ctx: Dict, pattern: str, field: str = "name",
                which: str = "forward") -> Optional[float]:
    """The flash kernel in training, over layers of more than one kind: the
    calls found are the kept layers' in turn (every step runs each as
    often), so their work is the kept layers' summed (three window layers'
    pairs and a full one's) times calls over layers."""
    k = roofline._kernel(ctx, pattern, field)
    if k is None or "layer_types" not in ctx["cfg"]:
        return None
    v, cfg = ctx["values"], ctx["cfg"]
    fn = opcount_mellum2.flash_forward if which == "forward" \
        else opcount_mellum2.flash_backward
    per_period = fn(cfg, int(v["seq"]),
                    batch=int(v["rows"]) // int(v["chips"]))
    times = k["calls"] / len(opcount_mellum2.kinds(cfg))
    return roofline._share({n: x * times for n, x in per_period.items()},
                           k["seconds"], ctx)


def _forwards(cfg: Dict) -> int:
    """How often a step runs a layer's forward: once, and once more in the
    backward under any recomputation policy (every policy the program has
    recomputes the grouped products: none of them names their outputs)."""
    return 1 if cfg["deployment"].get("remat_policy", "none") == "none" else 2


def experts_roofline(ctx: Dict, scope: str = "moe_experts",
                     op_name: Optional[str] = RAGGED_DOT) -> Optional[float]:
    """The grouped products' least time for the (token, expert) pairs that
    were computed (``values["moe_pairs_per_step"]``: the router's counter,
    not the buffer's rows), each product counted as often as the step runs
    it, over the device time under ``scope`` a step."""
    v, cfg, peak = ctx["values"], ctx["cfg"], ctx.get("peak")
    ms = scope_device_ms(ctx, scope, op_name)
    if not ms or peak is None or not v.get("moe_pairs_per_step"):
        return None
    layers = int(cfg["num_hidden_layers"])
    ops = opcount_mellum2.grouped_products(
        cfg, v["moe_pairs_per_step"] / layers, forwards=_forwards(cfg),
        backwards=1)
    roof = opcount.roofline_seconds(
        {n: x * layers for n, x in ops.items()}, peak)
    ctx.setdefault("roofline_notes", []).append(
        {"bound": roof["bound"], "roof_s": roof["seconds"],
         "kernel_s": ms / 1e3, "what": scope + " a step"})
    return 100.0 * roof["seconds"] / (ms / 1e3)


def train_mfu(ctx: Dict) -> Optional[float]:
    """End-to-end utilisation: operations a token needs (forward and
    backward, the experts' share at its expectation, no recomputation) x
    tokens/s/chip over the chip's bf16 peak. Not a roofline share."""
    v, peak = ctx["values"], ctx["peak"]
    if peak is None or not v.get("train_tok_s_chip"):
        return None
    flops = opcount_mellum2.train_flops_per_token(ctx["cfg"], int(v["seq"]))
    return 100.0 * flops * v["train_tok_s_chip"] / peak["bf16_flops_per_s"]
