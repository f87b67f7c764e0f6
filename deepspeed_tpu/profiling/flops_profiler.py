"""FLOPs profiler over XLA cost analysis.

Parity target: ``profiling/flops_profiler/profiler.py`` ``FlopsProfiler`` (:30):
``start_profile/stop_profile/print_model_profile`` surface, flops/MACs/params/latency
readouts. Instead of patched-function MAC formulas this reads the compiled HLO's cost
analysis — exact for the program XLA actually runs (post-fusion), including the
backward pass.
"""

from __future__ import annotations

import re
import time
from typing import Any, Callable, Dict, Optional

import jax

from deepspeed_tpu.utils.logging import log_dist


def profile_fn(fn: Callable, *args, static_argnums=(), **kwargs) -> Dict[str, float]:
    """Compile ``fn(*args)`` and return {'flops', 'bytes_accessed', 'peak_bytes'...}."""
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn, static_argnums=static_argnums)
    lowered = jitted.lower(*args, **kwargs)
    compiled = lowered.compile()
    cost = compiled.cost_analysis() or {}
    out = {"flops": float(cost.get("flops", 0.0)),
           "bytes_accessed": float(cost.get("bytes accessed", 0.0))}
    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            out["peak_bytes"] = float(getattr(mem, "temp_size_in_bytes", 0)
                                      + getattr(mem, "output_size_in_bytes", 0))
            out["argument_bytes"] = float(getattr(mem, "argument_size_in_bytes", 0))
    except Exception:
        pass
    return out


def per_module_profile(fn: Callable, *args, depth: int = 2,
                       _compiled=None, **kwargs
                       ) -> Dict[str, Dict[str, float]]:
    """Per-module GFLOPs/bytes attribution from the compiled HLO.

    The reference profiler patches ``torch.nn.functional`` to build a
    per-module MAC tree (profiler.py:523-776); here each HLO instruction
    carries the ``jax.named_scope`` path in its ``op_name`` metadata, so the
    compiled program itself is the tree: matmul (dot/conv) FLOPs and operand
    bytes are parsed per instruction and grouped by the scope prefix
    (truncated to ``depth`` segments). Bodies of ``lax.scan``/``while`` count
    ONCE per compiled region — a scanned layer stack reports per-layer cost
    (multiply by the trip count for totals).
    """
    if _compiled is not None:
        txt = _compiled.as_text()
    else:
        jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
        txt = jitted.lower(*args, **kwargs).compile().as_text()

    def shape_of(s):
        vals = [int(v) for v in s.split(",") if v]
        n = 1
        for v in vals:
            n *= v
        return n, vals

    # pass 1: every instruction's result shape, keyed by %name
    shapes: Dict[str, tuple] = {}
    for m in re.finditer(r"%?([\w.-]+) = \(?([a-z0-9]+)\[([0-9,]*)\]", txt):
        shapes[m.group(1)] = shape_of(m.group(3))
    # pass 2: dots + matmul-shaped convolutions (XLA:TPU lowers dots to
    # convolution) — operand shapes resolved through the definitions
    # operands may carry a typed prefix (`dot(f32[32,64]{1,0} %lhs, ...)`,
    # older XLA dumps) or be bare names (`dot(%lhs, ...)`, newer dumps)
    _operand = r"(?:[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})? )?%?([\w.-]+)"
    inst = re.compile(
        r"= *[a-z0-9]+\[([0-9,]*)\][^=\n]* (dot|convolution)"
        r"\(" + _operand + r", " + _operand + r"\)([^\n]*?)"
        r"metadata=\{[^}]*op_name=\"([^\"]+)\"")
    cdim_re = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
    label_re = re.compile(r"dim_labels=([a-z0-9]+)_")
    win_re = re.compile(r"window=\{size=([0-9x]+)")
    # "layers" is the model's scope around its layer loop: structure, like
    # the loop's own while/body, so that blocks stay the top-level modules
    drop = ("while", "body", "cond", "closed_call", "checkpoint", "rematted",
            "transpose", "layers")
    out: Dict[str, Dict[str, float]] = {}
    for m in inst.finditer(txt):
        res, kind, lhs_name, rhs_name, attrs, op_name = m.groups()
        n_res, _ = shape_of(res)
        n_lhs, lhs_dims = shapes.get(lhs_name, (0, []))
        n_rhs, _ = shapes.get(rhs_name, (0, []))
        k = 1
        if kind == "dot":
            cd = cdim_re.search(attrs)
            for d in (cd.group(1).split(",") if cd else []):
                if d and lhs_dims and int(d) < len(lhs_dims):
                    k *= lhs_dims[int(d)]
        else:  # convolution: contraction = lhs feature dim x window size
            lb = label_re.search(attrs)
            if lb and lhs_dims and "f" in lb.group(1):
                f_idx = lb.group(1).index("f")
                if f_idx < len(lhs_dims):
                    k *= lhs_dims[f_idx]
            wn = win_re.search(attrs)
            for w in (wn.group(1).split("x") if wn else []):
                k *= int(w)
        # scope path: drop jit()/autodiff/control-flow wrappers, keep `depth`
        # segments; transpose(...) wrappers mark the true backward pass
        bwd = "transpose(" in op_name
        parts = []
        for p in op_name.split("/"):
            # unwrap nested autodiff wrappers: transpose(jvp(attn)) -> attn
            while p.startswith(("jvp(", "transpose(", "vjp(")) \
                    and p.endswith(")"):
                p = p[p.index("(") + 1:-1]
            if not p or p.startswith("jit(") or p.startswith("<") \
                    or p.split(".")[0] in drop:
                continue
            parts.append(p)
        scope = "/".join(parts[:depth]) or "<toplevel>"
        if bwd:
            scope += " [bwd]"
        slot = out.setdefault(scope, {"gflops": 0.0, "gbytes": 0.0,
                                      "ops": 0})
        slot["gflops"] += 2.0 * n_res * k / 1e9
        slot["gbytes"] += (n_lhs + n_rhs + n_res) * 2 / 1e9  # ~bf16
        slot["ops"] += 1
    return dict(sorted(out.items(), key=lambda kv: -kv[1]["gflops"]))


class FlopsProfiler:
    """Engine-attached profiler (FlopsProfiler :30 surface)."""

    def __init__(self, engine=None):
        self.engine = engine
        self._measurements: Dict[str, Dict[str, float]] = {}
        self._t0 = 0.0
        self._wall = 0.0

    def start_profile(self) -> None:
        self._t0 = time.perf_counter()

    def stop_profile(self) -> None:
        self._wall = time.perf_counter() - self._t0

    def profile_step(self, batch) -> Dict[str, float]:
        """Cost analysis of the engine's forward+backward for one micro-batch."""
        eng = self.engine
        batch = eng._put_batch(batch)
        with jax.sharding.set_mesh(eng.mesh):
            compiled = eng._fwd_bwd.lower(
                eng.params, batch, eng.scaler_state["scale"]).compile()
        cost = compiled.cost_analysis() or {}
        stats = {"flops": float(cost.get("flops", 0.0)),
                 "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
                 "params": float(eng._world_params)}
        self._measurements["fwd_bwd"] = stats
        try:  # same compiled program feeds the per-module breakdown
            self._modules = per_module_profile(None, _compiled=compiled)
        except Exception:  # HLO text shape drift must not sink the step
            self._modules = {}
        return stats

    def print_model_profile(self, profile_step: int = 1, module_depth: int = -1,
                            top_modules: int = 3, detailed: bool = True,
                            output_file: Optional[str] = None) -> str:
        lines = ["flops profiler (XLA cost analysis):"]
        for name, st in self._measurements.items():
            gf = st.get("flops", 0) / 1e9
            gb = st.get("bytes_accessed", 0) / 1e9
            intensity = gf / gb if gb else float("inf")
            lines.append(f"  {name}: {gf:.2f} GFLOPs, {gb:.2f} GB touched, "
                         f"arithmetic intensity {intensity:.1f} flop/byte, "
                         f"params {st.get('params', 0)/1e6:.1f}M")
        mods = getattr(self, "_modules", None)
        if mods:
            lines.append("  per-module matmul cost (named_scope attribution; "
                         "scan bodies count once per compiled region):")
            shown = list(mods.items())
            if top_modules > 0:
                shown = shown[:top_modules]
            for scope, st in shown:
                lines.append(f"    {scope}: {st['gflops']:.3f} GFLOPs over "
                             f"{st['ops']} matmuls, ~{st['gbytes']:.3f} GB")
        text = "\n".join(lines)
        if output_file:
            with open(output_file, "w") as f:
                f.write(text + "\n")
        else:
            log_dist(text)
        return text


def start_trace(log_dir: str) -> None:
    """xprof trace capture (NVTX/nsys parity via jax.profiler)."""
    jax.profiler.start_trace(log_dir)


def stop_trace() -> None:
    jax.profiler.stop_trace()
