"""Training cells of a model whose layers are state-space and attention
layers in turn (Granite-4.0-H): ``deepspeed_tpu.initialize`` ->
``engine.fused_train_step``.

Set-up and window are ``runners/train.py``'s, clock read for clock read (as
``runners/train_looped.py``'s and ``runners/train_moe_share.py``'s are): the
same process start, weights from ``--seed`` by the engine's own jitted init,
the reference check on the first batch, two steps before the window, a fresh
batch drawn on the host inside it, ``block_until_ready`` on every step, the
same ``values`` keys; so that this cell's rate means what the other training
cells' means. What differs is named by the configuration file (``modules``)
and what ``correct`` compares: the first step's loss and, for each layer, the
mean square of its mixer's output, as the timed step program itself returned
them (``StepLog.parts()``), against the reference (whose state-space layer
is the recurrence over positions) on the same bf16-rounded weights and the
same batch (:func:`compare`).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Tuple

import numpy as np

from benchmarks import harness
from benchmarks.runners.train_looped import _modules

#: what the program's TransformerConfig has to know for this runner's cells
NEEDS = ("ssm_heads", "ssm_head_dim", "ssm_state", "ssm_groups", "ssm_conv",
         "ssm_chunk", "attention_multiplier", "embedding_multiplier",
         "residual_multiplier", "logits_scaling")


def compare(system: Dict, want: Dict, check: Dict) -> Tuple[List[str], Dict]:
    """Problems (empty: correct) and the facts printed beside them. Each of
    ``check["compared"]`` has to have the reference's shape and lie within
    ``check["<name>_abs_tol"]`` of it or, where the check gives
    ``<name>_rel_tol`` instead, within that share of the reference's value,
    element by element; the loss also lies in ``check["first_loss_range"]``."""
    problems, facts = [], {}
    for name in check["compared"]:
        got = np.asarray(system.get(name, np.nan), np.float64)
        ref = np.asarray(want[name], np.float64)
        if got.shape != ref.shape:
            problems.append(f"{name}: shape {got.shape}, reference "
                            f"{ref.shape}")
            continue
        if f"{name}_rel_tol" in check:
            how, tol = "max_rel_diff", float(check[f"{name}_rel_tol"])
            diff = float(np.max(np.abs(got - ref) / np.abs(ref)))
        else:
            how, tol = "max_abs_diff", float(check[f"{name}_abs_tol"])
            diff = float(np.max(np.abs(got - ref)))
        facts[name] = {"system": got.tolist(), "reference": ref.tolist(),
                       how: diff, "tol": tol}
        if not diff <= tol:
            problems.append(f"first step's {name} {got.tolist()} vs "
                            f"reference {ref.tolist()} ({how} {diff}, tol "
                            f"{tol})")
    lo, hi = check["first_loss_range"]
    loss = float(np.asarray(system.get("loss", np.nan)))
    if not lo <= loss <= hi:
        problems.append(f"first loss {loss} outside [{lo}, {hi}]")
    return problems, facts


def _reference(jax, engine, cfg: Dict, rows: np.ndarray, mods: Dict) -> Dict:
    """The plain float32 loss and mixer-output mean squares of the engine's
    current weights (rounded to bf16, as the step computes with them; the
    leaves the program keeps in float32 as they are) on the micro-batch
    ``rows`` [B, T]: one row at a time, a layer at a time, on the first
    device."""
    import jax.numpy as jnp

    dev = jax.devices()[0]
    get = mods["modelcfg"].weights_getter(
        engine.params, cfg,
        lambda w: jax.device_put(w.astype(jnp.bfloat16), dev),
        lambda w: jax.device_put(w, dev))
    want = mods["reference"].batch_loss(
        cfg, get, [jax.device_put(row, dev) for row in rows])
    return {k: np.asarray(v, np.float64) for k, v in want.items()}


def run(cell: Dict, args) -> Dict:
    from deepspeed_tpu.models import TransformerConfig, TransformerLM

    missing = [f for f in NEEDS if f not in
               {x.name for x in dataclasses.fields(TransformerConfig)}]
    if missing:
        raise SystemExit(
            f"benchmarks: cell {cell['name']} needs a program whose "
            f"TransformerConfig has {', '.join(missing)} (a layer kind whose "
            f"mixer is a Mamba-2 state-space layer, the softmax scale, and "
            f"the embedding, residual and logits multipliers); this "
            f"checkout's has not")
    jax, devices, dev = harness.setup_jax(cell["chips"], args.rehearse)
    import deepspeed_tpu as ds
    from deepspeed_tpu.observability import steplog

    compiles = harness.CompileCount()
    spans = harness.Spans()
    cfg, traffic = cell["config"], cell["traffic"]
    mods = _modules(cfg)
    dep = cfg["deployment"]
    seq = int(traffic["seq_len"])
    rows = int(traffic["rows_per_chip"]) * cell["chips"]
    peak = None if args.rehearse else harness.load_peaks(dev["kind"])

    tcfg = mods["modelcfg"].transformer_config(cfg, max_seq_len=seq,
                                               param_dtype="float32")
    ds_cfg = dict(dep["ds_config"], seed=int(args.seed) % (2 ** 31),
                  train_micro_batch_size_per_gpu=rows // cell["chips"])
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

    # ---- correctness, outside the window: the reference on the initial
    # weights and the first batch, then that batch's step and what the step
    # program itself returned for it
    first = make_batch()
    want = _reference(jax, engine, cfg, first["input_ids"], mods)
    t_reference = time.perf_counter()
    first_loss = float(jax.block_until_ready(step(first)))
    record = steplog.get_steplog().parts(last=1)
    system = dict(record[-1]) if record else {}
    check = dict(cfg["check"])
    if args.rehearse:
        # rehearsal.json loosens the loss's; so the mean squares': at toy
        # widths a bf16 sum over 64 channels is a coarse thing
        check["mix_out_ms_rel_tol"] = max(check["mix_out_ms_rel_tol"], 0.05)
    problems, facts = compare(system, want, check)
    if system.get("loss") != first_loss:
        problems.append(f"the step record's loss {system.get('loss')} is not "
                        f"the step's {first_loss}")
    row = [p for p in steplog.programs()
           if p.name.startswith("ds_train_step")][-1]
    program = {"layer_pattern": row.layer_pattern,
               "ssm_chunk": row.ssm_chunk,
               "ssm_chunks_per_step": row.ssm_chunks_per_step,
               "layer_applications": row.layer_applications,
               "flash_fwd_tiles": row.flash_fwd_tiles,
               "flash_bwd_lowerings": row.flash_bwd_lowerings}
    n_ssm = mods["opcount"].kinds(cfg).count("mamba")
    scanned = n_ssm * rows * -(-seq // int(cfg["mamba_chunk_size"]))
    if row.ssm_chunks_per_step != scanned:
        problems.append(f"the step program scans {row.ssm_chunks_per_step} "
                        f"chunks a step, the cell's shapes say {scanned}")
    harness.say(check="train_first_loss_and_mixer_outputs", **facts,
                step_program=program)
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
    flops_tok = mods["opcount"].train_flops_per_token(cfg, seq)
    mid = float(np.median(step_ms))
    slow = [(i, ms) for i, ms in enumerate(step_ms) if ms > 1.25 * mid]
    last = steplog.get_steplog().parts(last=1)[-1]
    if not np.all(np.isfinite(np.asarray(last["mix_out_ms"]))):
        problems.append("non-finite mixer output in the window's last step")
    rec = steplog.get_steplog().steps()[-steps:]
    host_ms = {"put_dispatch": float(np.median(rec[:, 2] - rec[:, 1]) * 1e3),
               "commit": float(np.median(rec[:, 3] - rec[:, 2]) * 1e3),
               "wait_and_batch": float(np.median(rec[1:, 1] - rec[:-1, 3])
                                       * 1e3) if steps > 1 else None,
               "step_ms_series": np.round(step_ms, 2).tolist()}
    harness.say(window={"steps": steps, "wall_s": wall, "tokens": tokens,
                        "step_ms": {"p50": mid,
                                    "p95": float(np.percentile(step_ms, 95)),
                                    "max": max(step_ms)},
                        "slow_steps": {"n": len(slow),
                                       "excess_s": sum(ms - mid for _, ms
                                                       in slow) / 1e3,
                                       "worst": sorted(slow,
                                                       key=lambda x: -x[1])[:5]},
                        "compiles_in_window": in_window, "host_ms": host_ms,
                        "loss_first": losses[0], "loss_last": losses[-1],
                        "parts_last": {k: np.asarray(v).tolist()
                                       for k, v in last.items()},
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
              "rows": rows, "chips": cell["chips"], "step_ms": step_ms,
              "ssm_chunks_per_step": row.ssm_chunks_per_step}
    return harness.fill_metrics(result, cell, bool(args.trace), trace,
                                values, peak)
