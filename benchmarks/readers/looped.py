"""Readers of what the looped decoder adds to the train step: the work its
passes do (``opcount_ouro``), the device time under the ``exit_gate`` scope,
and the block applications the step-program table says the program holds.

As everywhere under ``readers/``: a reader that finds nothing to read (a
program without the scope, the table or the field) returns None and the
metric is left out of the line; nothing raises.
"""

from __future__ import annotations

import re
from typing import Dict, Optional

from benchmarks import opcount_ouro
from benchmarks.readers import program


def train_mfu(ctx: Dict) -> Optional[float]:
    """End-to-end utilisation of the looped model: operations a token needs
    (R passes over L layers, R head applications, forward and backward, no
    recomputation) x tokens/s/chip over the chip's bf16 peak."""
    v, peak = ctx["values"], ctx["peak"]
    if peak is None or not v.get("train_tok_s_chip"):
        return None
    flops = opcount_ouro.train_flops_per_token(ctx["cfg"], int(v["seq"]))
    return 100.0 * flops * v["train_tok_s_chip"] / peak["bf16_flops_per_s"]


def layer_applications(ctx: Dict) -> Optional[float]:
    """Block applications one micro-batch's forward holds, as the newest
    ``ds_train_step*`` row of the program's step-program table says
    (``observability/steplog.py``: layers run x passes over them)."""
    try:
        from deepspeed_tpu.observability import steplog
    except ImportError:
        return None
    rows = [p for p in steplog.programs()
            if p.name.startswith("ds_train_step")]
    n = getattr(rows[-1], "layer_applications", None) if rows else None
    return None if n is None else float(n)


def instructions_under(hlo_text: str, scope: str) -> Dict[str, bool]:
    """instruction name -> whether it lies under ``scope``: its own
    ``op_name`` path holds the scope, or it is a fusion whose own path does
    not and most of whose fused instructions' paths do."""
    own: Dict[str, Optional[bool]] = {}
    calls: Dict[str, str] = {}
    members: Dict[str, list] = {}
    comp = ""
    for line in hlo_text.splitlines():
        m = program._COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = program._INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        members.setdefault(comp, []).append(name)
        meta = program._OP_NAME.search(line)
        own[name] = None if not meta \
            else scope in re.split(r"[/()]", meta.group(1))
        called = program._CALLS.search(line)
        if called:
            calls[name] = called.group(1)
    out = {}
    for name, mine in own.items():
        if not mine and name in calls:
            votes = [own[i] for i in members.get(calls[name], [])
                     if own[i] is not None]
            mine = bool(votes) and 2 * sum(votes) > len(votes)
        out[name] = bool(mine)
    return out


def scope_device_ms(ctx: Dict, scope: str) -> Optional[float]:
    """Self time a step of the device operations under ``scope``, inside the
    step program's runs of the traced window (the reduction of
    ``program.device_ms_by_scope``, asked about one scope)."""
    a = program.analysis(ctx)
    trace, reduced = ctx.get("trace"), ctx.get("reduced") or {}
    path = program.xplane_path(ctx["cell"]["name"])
    if not a.get("hlo_text") or trace is None or not path \
            or not reduced.get("window_ns"):
        return None
    under = instructions_under(a["hlo_text"], scope)
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
