#!/usr/bin/env python
"""Quantized-collective (ZeRO++) drill CLI: prove on the 8-device mesh that

* the ``comm/<op>_bytes`` accounting matches the ANALYTIC wire payload for
  dense and quantized collectives (the acceptance instrument is itself
  pinned),
* a short fsdp training run with qwZ+hpZ+qgZ matches the bf16-collective
  baseline's final loss within tolerance, with the quantized ops' byte
  counters showing >= 3x volume reduction,
* the fp32 master path is bit-identical when quantization is off (the
  explicit-collective region with every feature disabled is
  deterministic),
* the two-hop qgZ split (intra-slice bf16, inter-slice quantized) holds
  loss parity and logs its hops under the documented op names, and hpZ
  falls back gracefully on a single-slice mesh.

* the MoE expert-dispatch all-to-alls (dense / quantized / hierarchical
  two-hop) log exactly the ``moe_a2a_wire_bytes`` analytic payload, and a
  full traced ``_grouped_moe_ep`` dispatch decomposes into those terms.

    python tools/comm_drill.py --list
    python tools/comm_drill.py --scenario bytes
    python tools/comm_drill.py --scenario parity
    python tools/comm_drill.py --scenario two-hop
    python tools/comm_drill.py --scenario moe-a2a
    python tools/comm_drill.py --all

Exit code 0 = invariants held; 1 = violated (details on stdout as JSON).
Slow pytest wrappers live in ``tests/unit/test_zeropp.py`` under the
``zpp`` + ``slow`` markers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOL_LOSS = 0.05          # quantized-vs-baseline final-loss tolerance
MIN_REDUCTION = 3.0      # required comm-volume shrink on the quantized ops


class DrillFailure(AssertionError):
    pass


def check(ok, msg, details):
    if not ok:
        raise DrillFailure(f"{msg}: {json.dumps(details)}")


def _logger():
    from deepspeed_tpu.comm.logger import comms_logger

    comms_logger.enabled = True
    comms_logger.prof_all = True
    return comms_logger


def _delta(before, after):
    ops = set(before) | set(after)
    return {op: after.get(op, 0.0) - before.get(op, 0.0) for op in ops
            if after.get(op, 0.0) != before.get(op, 0.0)}


# ---------------------------------------------------------------------------
# scenario: bytes — the counters match the analytic wire payload
# ---------------------------------------------------------------------------

def scenario_bytes(workdir=None):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu import comm
    from deepspeed_tpu.comm import quantized as cq
    from deepspeed_tpu.parallel import build_mesh

    lg = _logger()
    topo = build_mesh(axis_sizes={"dp": 8})
    n = 4096                      # per-device elements
    bs = 512

    def traced_bytes(fn, x, in_spec, out_spec):
        """Trace (never execute) one shard_map'd collective and return the
        per-op byte deltas the trace logged."""
        before = dict(lg.bytes)
        jax.make_jaxpr(jax.shard_map(fn, mesh=topo.mesh, in_specs=in_spec,
                                     out_specs=out_spec,
                                     check_vma=False))(x)
        return _delta(before, dict(lg.bytes))

    xb = jnp.zeros((8 * n,), jnp.bfloat16)
    xf = jnp.zeros((8 * n,), jnp.float32)
    cases = []

    # dense ops: size * itemsize of the traced operand
    d = traced_bytes(lambda v: comm.all_gather(v, axis="dp"), xb,
                     P("dp"), P("dp"))
    cases.append(("all_gather bf16", d.get("all_gather"), n * 2))
    d = traced_bytes(lambda v: comm.reduce_scatter(v, axis="dp"), xf,
                     P(None), P("dp"))
    cases.append(("reduce_scatter fp32", d.get("reduce_scatter"), 8 * n * 4))
    d = traced_bytes(lambda v: comm.broadcast(v, src=0, axis="dp"), xb,
                     P("dp"), P("dp"))
    cases.append(("broadcast bf16", d.get("broadcast"), n * 2))

    # quantized ops: packed payload + fp32 block scales (wire_bytes)
    for bits in (8, 4):
        d = traced_bytes(
            lambda v, b=bits: cq.all_gather_q(v, "dp", bits=b, block_size=bs),
            xb, P("dp"), P("dp"))
        cases.append((f"all_gather int{bits}", d.get("all_gather"),
                      cq.wire_bytes(n, bits, bs)))
        d = traced_bytes(
            lambda v, b=bits: cq.reduce_scatter_q(v, "dp", bits=b,
                                                  block_size=bs),
            xf, P(None), P("dp"))
        # payload = 8 per-destination chunks of n elements each
        cases.append((f"reduce_scatter int{bits}", d.get("reduce_scatter"),
                      8 * cq.wire_bytes(n, bits, bs)))
        d = traced_bytes(
            lambda v, b=bits: cq.broadcast_q(v, 0, "dp", bits=b,
                                             block_size=bs),
            xb, P("dp"), P("dp"))
        cases.append((f"broadcast int{bits}", d.get("broadcast"),
                      cq.wire_bytes(n, bits, bs)))

    # two-hop reduce-scatter: full-payload bf16 intra hop + quantized
    # 1/slice piece on the cross hop, under the documented op names
    d = traced_bytes(
        lambda v: cq.two_hop_reduce_scatter(v, "dp", 2, bits=8,
                                            block_size=bs),
        xb, P(None), P("dp"))
    cases.append(("two-hop intra bf16", d.get("reduce_scatter_intra"),
                  8 * n * 2))
    # after the 2-wide intra hop each device holds 4n elements; the cross
    # a2a quantizes them as 4 per-destination chunks of n
    cases.append(("two-hop cross int8", d.get("reduce_scatter"),
                  4 * cq.wire_bytes(n, 8, bs)))
    for name, got, want in cases:
        check(got == want, f"byte accounting mismatch: {name}",
              {"got": got, "want": want})
    return {"cases": [{"op": c[0], "bytes": c[1]} for c in cases]}


# ---------------------------------------------------------------------------
# the fsdp-training comparison the parity scenario makes
# ---------------------------------------------------------------------------

def _train(zero_pp, steps=5, seed=0, mesh=None, timing=False):
    """One short fsdp run under the given zero_pp block; returns losses,
    per-op comm byte deltas (trace-time = per-step payload), and
    step-time stats."""
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM, get_preset

    lg = _logger()
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3, "param_persistence_threshold": 0,
                              "zero_pp": zero_pp},
        "mesh": mesh or {"fsdp": 4, "dp": 2},
        "steps_per_print": 10 ** 9,
    }
    before = dict(lg.bytes)
    eng = ds.initialize(model=TransformerLM(get_preset("tiny")),
                        config=config)[0]
    rng = np.random.default_rng(seed)
    batch = {"input_ids": rng.integers(
        0, 256, (2 * eng.topology.dp_world_size, 32))}
    losses, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = eng.forward(batch)
        eng.backward(loss)
        eng.step()
        losses.append(float(loss))
        times.append(time.perf_counter() - t0)
    comm_bytes = _delta(before, dict(lg.bytes))
    tokens = batch["input_ids"].size
    out = {
        "losses": losses, "final_loss": losses[-1],
        "comm_bytes": {k: int(v) for k, v in sorted(comm_bytes.items())},
        "zpp": (dict(eng._zpp.features) if eng._zpp is not None else None),
    }
    if timing:
        med = sorted(times[1:])[len(times[1:]) // 2]  # skip the compile step
        out["step_ms"] = round(med * 1e3, 2)
        out["tokens_per_sec"] = round(tokens / med, 1)
    return out


def measure_pair(steps=5, quant=None, mesh=None, timing=True):
    """Baseline (explicit dense bf16 collectives) vs quantized run — the
    body of the parity drill."""
    quant = quant or {"enabled": True, "qwz": True, "qgz": True,
                      "hpz": True, "hpz_partition_size": 2,
                      "weight_bits": 4, "grad_bits": 8}
    base = _train({"enabled": True}, steps=steps, mesh=mesh, timing=timing)
    q = _train(quant, steps=steps, mesh=mesh, timing=timing)

    def _ratio(op):
        num = base["comm_bytes"].get(op, 0)
        den = q["comm_bytes"].get(op, 0)
        return round(num / den, 2) if den else None

    loss_delta = abs(q["final_loss"] - base["final_loss"]) \
        / max(abs(base["final_loss"]), 1e-9)
    return {
        "baseline": base, "quantized": q,
        "all_gather_reduction": _ratio("all_gather"),
        "reduce_scatter_reduction": _ratio("reduce_scatter"),
        "loss_delta_frac": round(loss_delta, 4),
        "loss_tolerance": TOL_LOSS,
    }


def scenario_parity(workdir=None):
    # determinism first: the dense explicit region (quantization OFF) must
    # be bit-identical run-to-run — the fp32 master path has no lossy op
    a = _train({"enabled": True}, steps=4)
    b = _train({"enabled": True}, steps=4)
    check(a["losses"] == b["losses"],
          "dense explicit-collective region is not bit-identical",
          {"a": a["losses"], "b": b["losses"]})
    check(a["zpp"] is not None and not any(
        a["zpp"][f] for f in ("qwz", "qgz", "hpz")),
        "dense baseline unexpectedly quantized", a["zpp"])

    res = measure_pair(steps=5, timing=False)
    check(res["loss_delta_frac"] <= TOL_LOSS,
          "quantized run lost loss parity with the bf16 baseline",
          {"delta": res["loss_delta_frac"], "tol": TOL_LOSS})
    for op in ("all_gather_reduction", "reduce_scatter_reduction"):
        check(res[op] is not None and res[op] >= MIN_REDUCTION,
              f"comm-volume reduction below {MIN_REDUCTION}x on {op}",
              {op: res[op],
               "baseline": res["baseline"]["comm_bytes"],
               "quantized": res["quantized"]["comm_bytes"]})
    return res


def scenario_two_hop(workdir=None):
    import deepspeed_tpu as ds  # noqa: F401 — ensure package import first
    from deepspeed_tpu.comm import quantized as cq

    mesh = {"fsdp": 8}
    base = _train({"enabled": True}, steps=4, mesh=mesh)
    two = _train({"enabled": True, "qgz": True, "slice_size": 2,
                  "cross_slice_only": True}, steps=4, mesh=mesh)
    check(two["zpp"]["two_hop"], "two-hop qgZ plan not built", two["zpp"])
    delta = abs(two["final_loss"] - base["final_loss"]) \
        / max(abs(base["final_loss"]), 1e-9)
    check(delta <= TOL_LOSS, "two-hop qgZ lost loss parity",
          {"delta": delta})
    cb = two["comm_bytes"]
    check(cb.get("reduce_scatter_intra", 0) > 0
          and cb.get("reduce_scatter", 0) > 0,
          "two-hop hops not logged under the documented op names", cb)
    # the cross (DCN) hop moves 1/slice_count of the intra payload,
    # quantized — it must be far smaller than the ICI hop
    check(cb["reduce_scatter"] < cb["reduce_scatter_intra"] / 2,
          "cross-slice hop not compressed vs the intra hop", cb)

    # hpZ single-slice fallback: slice-local partition would equal the
    # primary partition — the plan must disable the secondary, not crash
    hpz = _train({"enabled": True, "hpz": True}, steps=2, mesh=mesh)
    check(hpz["zpp"] is not None and not hpz["zpp"]["hpz"],
          "hpZ did not fall back gracefully on a single-slice mesh",
          hpz["zpp"])
    # int4 wire sanity rides along: packed payload is half of int8
    check(cq.wire_bytes(4096, 4, 512) < cq.wire_bytes(4096, 8, 512),
          "int4 wire payload not smaller than int8", {})
    return {"baseline_loss": base["final_loss"],
            "two_hop_loss": two["final_loss"],
            "comm_bytes": cb, "hpz_fallback": True}


def scenario_moe_a2a(workdir=None):
    """MoE expert-dispatch a2a wire accounting: every ``moe_all_to_all``
    form (dense / int8 / int4 / hierarchical two-hop) logs exactly the
    analytic payload of ``moe_a2a_wire_bytes``, and a full traced
    ``_grouped_moe_ep`` dispatch (x out, ids out, y back) decomposes into
    those same terms."""
    import types

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.comm import quantized as cq
    from deepspeed_tpu.moe import sharded_moe as sm
    from deepspeed_tpu.parallel import build_mesh

    lg = _logger()
    topo = build_mesh(axis_sizes={"ep": 8})
    cap, D, bs = 16, 32, 256

    def traced_bytes(fn, x, in_spec, out_spec):
        before = dict(lg.bytes)
        jax.make_jaxpr(jax.shard_map(fn, mesh=topo.mesh, in_specs=in_spec,
                                     out_specs=out_spec,
                                     check_vma=False))(x)
        return _delta(before, dict(lg.bytes))

    x = jnp.zeros((8, cap, D), jnp.bfloat16)
    cases = []
    for bits, sl in [(0, 0), (8, 0), (4, 0), (8, 2), (0, 2), (4, 4)]:
        d = traced_bytes(
            lambda v, b=bits, s=sl: cq.moe_all_to_all(
                v, "ep", bits=b, block_size=bs, slice_size=s),
            x, P(None, None, None), P(None, None, None))
        want = {k: v for k, v in cq.moe_a2a_wire_bytes(
            8, cap * D, bits=bits, block_size=bs, slice_size=sl,
            itemsize=2).items() if v}
        cases.append((f"moe a2a bits={bits} slice={sl}", d, want))
    for name, got, want in cases:
        check(got == want, f"moe a2a byte mismatch: {name}",
              {"got": got, "want": want})

    # full dispatch: 2 payload a2as (x out + y back) + 1 exact id a2a,
    # every term under the documented op keys
    E, Dm, top_k, B, T = 8, 16, 2, 4, 4
    cfg = types.SimpleNamespace(top_k=top_k, moe_ep_capacity_factor=0.0,
                                moe_kernel="ragged", moe_a2a_bits=8,
                                moe_a2a_slice=2, moe_a2a_block=bs)
    w = {"router": jnp.zeros((Dm, E), jnp.float32),
         "w_gate": jnp.zeros((E, Dm, 32), jnp.float32),
         "w_up": jnp.zeros((E, Dm, 32), jnp.float32),
         "w_down": jnp.zeros((E, 32, Dm), jnp.float32)}
    h = jnp.zeros((B, T, Dm), jnp.float32)
    before = dict(lg.bytes)
    with jax.sharding.set_mesh(topo.mesh):
        jax.make_jaxpr(lambda hh: sm.grouped_moe_mlp_block(hh, w, cfg))(h)
    got = _delta(before, dict(lg.bytes))
    ep_cap = -(-B * T // 8) * top_k         # s_local * top_k, dropless
    xw = cq.moe_a2a_wire_bytes(8, ep_cap * Dm, bits=8, block_size=bs,
                               slice_size=2, itemsize=4)
    iw = cq.moe_a2a_wire_bytes(8, ep_cap, bits=0, block_size=bs,
                               slice_size=2, itemsize=4)
    want = {k: v for k, v in
            {k: 2 * xw[k] + iw[k] for k in xw}.items() if v}
    check(got == want, "full _grouped_moe_ep dispatch bytes mismatch",
          {"got": got, "want": want})
    return {"cases": [{"op": c[0], "bytes": c[1]} for c in cases],
            "full_dispatch": {k: int(v) for k, v in got.items()}}


SCENARIOS = {
    "bytes": scenario_bytes,
    "parity": scenario_parity,
    "two-hop": scenario_two_hop,
    "moe-a2a": scenario_moe_a2a,
}


def run_scenario(name: str) -> dict:
    fn = SCENARIOS.get(name)
    if fn is None:
        raise SystemExit(f"unknown scenario {name!r} "
                         f"(have: {', '.join(SCENARIOS)})")
    t0 = time.perf_counter()
    try:
        detail = fn()
        ok, err = True, None
    except DrillFailure as e:
        detail, ok, err = None, False, str(e)
    return {"scenario": name, "ok": ok, "error": err, "detail": detail,
            "elapsed_s": round(time.perf_counter() - t0, 1)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scenario", help="which drill to run")
    ap.add_argument("--all", action="store_true", help="run every scenario")
    ap.add_argument("--list", action="store_true", help="list scenarios")
    args = ap.parse_args(argv)
    if args.list:
        print("\n".join(SCENARIOS))
        return 0
    names = list(SCENARIOS) if args.all else (
        [args.scenario] if args.scenario else None)
    if not names:
        ap.error("pass --scenario NAME, --all, or --list")
    rc = 0
    for name in names:
        verdict = run_scenario(name)
        print(json.dumps(verdict))
        if not verdict["ok"]:
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
