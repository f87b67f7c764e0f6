"""Training cells: ``deepspeed_tpu.initialize`` -> ``engine.fused_train_step``.

One process drives every chip of the cell. Set-up: weights from ``--seed`` on
the device (the engine's own jitted init), the reference check on the first
batch, two warm-up steps. Window: a fresh batch drawn on the host from the
seed, one fused step, ``block_until_ready``, again, until ``--seconds`` have
passed; the rate is every token of every finished step over the wall time
from the window's start to the last step's end.
"""

from __future__ import annotations

import time
from typing import Dict

import numpy as np

from benchmarks import harness, modelcfg, opcount, reference


def _reference_loss(jax, engine, cfg: Dict, rows: np.ndarray) -> float:
    """The plain float32 loss of the engine's current weights (rounded to
    bf16, as the step computes with them) on ``rows`` [B, T], one row at a
    time on the first device."""
    import jax.numpy as jnp

    dev = jax.devices()[0]
    get = modelcfg.weights_getter(
        engine.params,
        lambda w: jax.device_put(w.astype(jnp.bfloat16), dev))

    losses = []
    for row in rows:
        logits = reference.forward(cfg, get, jax.device_put(row, dev))
        losses.append(float(reference.next_token_loss(logits, row)))
    return float(np.mean(losses))


def run(cell: Dict, args) -> Dict:
    jax, devices, dev = harness.setup_jax(cell["chips"], args.rehearse)
    import deepspeed_tpu as ds

    compiles = harness.CompileCount()
    spans = harness.Spans()
    cfg, traffic = cell["config"], cell["traffic"]
    dep = cfg["deployment"]
    seq = int(traffic["seq_len"])
    rows = int(traffic["rows_per_chip"]) * cell["chips"]
    peak = None if args.rehearse else harness.load_peaks(dev["kind"])

    tcfg = modelcfg.transformer_config(cfg, max_seq_len=seq,
                                       param_dtype="float32")
    from deepspeed_tpu.models import TransformerLM

    ds_cfg = dict(dep["ds_config"], seed=int(args.seed) % (2 ** 31))
    mesh = None
    if cell["chips"] == 1 and len(jax.devices()) > 1:
        from deepspeed_tpu.parallel import build_mesh
        mesh = build_mesh(devices=devices)
    t_imported = time.perf_counter()
    engine, *_ = ds.initialize(model=TransformerLM(tcfg), config=ds_cfg,
                               mesh=mesh)
    t_engine = time.perf_counter()
    step = spans.wrap("fused_train_step", engine.fused_train_step)
    rng = np.random.default_rng(int(args.seed))

    def make_batch():
        with spans.span("make_batch"):
            return {"input_ids": rng.integers(
                0, tcfg.vocab_size, (rows, seq), dtype=np.int32)}

    # ---- correctness, outside the window: reference loss of the initial
    # weights on the first batch, then that batch's step
    first = make_batch()
    ref_loss = _reference_loss(jax, engine, cfg, first["input_ids"])
    t_reference = time.perf_counter()
    first_loss = float(jax.block_until_ready(step(first)))
    check = cfg["check"]
    lo, hi = check["first_loss_range"]
    problems = []
    if not abs(first_loss - ref_loss) <= check["loss_abs_tol"]:
        problems.append(f"first loss {first_loss} vs reference {ref_loss}")
    if not lo <= first_loss <= hi:
        problems.append(f"first loss {first_loss} outside [{lo}, {hi}]")
    harness.say(check="train_first_loss", system=first_loss,
                reference=ref_loss, abs_diff=abs(first_loss - ref_loss),
                tol=check["loss_abs_tol"])
    # second call: same program, now with the step's own outputs as inputs
    jax.block_until_ready(step(make_batch()))
    harness.say(setup={
        "imports_and_device_s": t_imported - harness.T_PROCESS_START,
        "engine_build_s": t_engine - t_imported,
        "reference_check_s": t_reference - t_engine,
        "two_steps_s": time.perf_counter() - t_reference,
        "cache_hits": compiles.hits, "cache_misses": compiles.misses})

    trace = harness.TraceWindow(bool(args.trace), cell["name"],
                                cell.get("trace_seconds", 3.0))
    losses, step_ms = [], []
    compiles_before = compiles.compiles
    trace.start()
    t0 = time.perf_counter()
    setup_s = t0 - harness.T_PROCESS_START
    t_end = t0
    while t_end - t0 < args.seconds:
        ts = time.perf_counter()
        loss = step(make_batch())
        jax.block_until_ready(loss)
        t_end = time.perf_counter()
        step_ms.append((t_end - ts) * 1e3)
        losses.append(loss)
        trace.maybe_stop()
    trace.stop()
    wall = t_end - t0
    in_window = compiles.compiles - compiles_before
    losses = [float(x) for x in losses]
    if not all(np.isfinite(losses)):
        problems.append("non-finite loss in the window")
    steps = len(losses)
    tokens = steps * rows * seq
    tok_s_chip = tokens / wall / cell["chips"]
    flops_tok = opcount.train_flops_per_token(cfg, seq)
    # for a reader who looks for the cause of a slow run: were all steps
    # slower, or a few much slower
    mid = float(np.median(step_ms))
    slow = [(i, ms) for i, ms in enumerate(step_ms) if ms > 1.25 * mid]
    harness.say(window={"steps": steps, "wall_s": wall, "tokens": tokens,
                        "step_ms": {"p50": mid,
                                    "p95": float(np.percentile(step_ms, 95)),
                                    "max": max(step_ms)},
                        "slow_steps": {"n": len(slow),
                                       "excess_s": sum(ms - mid for _, ms
                                                       in slow) / 1e3,
                                       "worst": sorted(slow,
                                                       key=lambda x: -x[1])[:5]},
                        "compiles_in_window": in_window,
                        "loss_first": losses[0], "loss_last": losses[-1],
                        "cache_hits": compiles.hits,
                        "cache_misses": compiles.misses,
                        "flops_per_token": flops_tok})
    device = {**dev, "count": cell["chips"],
              "memory_peak_bytes": harness.memory_peak_bytes(devices)}
    result = {"correct": not problems, "attempted": steps,
              "failed": 0 if not problems else steps, "problems": problems,
              "device": device}
    values = {"train_tok_s_chip": tok_s_chip, "setup_s": setup_s,
              "compiles_in_window": in_window, "steps": steps, "seq": seq,
              "rows": rows, "chips": cell["chips"], "step_ms": step_ms}
    return harness.fill_metrics(result, cell, bool(args.trace), trace,
                                values, peak)
