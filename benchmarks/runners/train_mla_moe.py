"""Training cells of a model with latent attention, a leading dense layer
and a held share of sigmoid-routed experts beside shared ones (kanana-2 on
one chip of eight): ``deepspeed_tpu.initialize`` ->
``engine.fused_train_step``.

Set-up and window are ``runners/train.py``'s, clock read for clock read (as
``runners/train_moe_share.py``'s and ``runners/train_hybrid.py``'s are): the
same process start, weights from ``--seed`` by the engine's own jitted init,
the reference check on the first batch, two steps before the window, a fresh
batch drawn on the host inside it, ``block_until_ready`` on every step, the
same ``values`` keys; so that this cell's rate means what the other training
cells' means. What differs is named by the configuration file (``modules``)
and what ``correct`` compares: the first step's loss, its balance term, each
layer's mixer-output mean square and the (token, expert) pairs each held
expert of each routed layer received, as the timed step program itself
returned them (``StepLog.parts()``), and the selection biases the step left
in the engine's parameters, against the reference on the same bf16-rounded
weights and the same batch; and that no pair was left out of the buffer of
local pairs in any step of the window.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import numpy as np

from benchmarks import harness
from benchmarks.runners.train_hybrid import compare
from benchmarks.runners.train_looped import _modules

#: what the program's TransformerConfig has to know for this runner's cells
NEEDS = ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "rope_interleave", "first_k_dense", "moe_scoring",
         "moe_routed_scale", "moe_shared_experts", "moe_bias_rate",
         "moe_bias_init")


def _reference(jax, engine, cfg: Dict, rows: np.ndarray, mods: Dict,
               rounded: str = "bfloat16") -> Dict:
    """The plain float32 loss and parts of the engine's current weights
    (rounded to ``rounded``, bf16 as the step computes with them; the
    selection bias, which the program keeps in float32, as it is) on the
    micro-batch ``rows`` [B, T]: one row at a time, a layer at a time, on the
    first device."""
    import jax.numpy as jnp

    dev = jax.devices()[0]
    get = mods["modelcfg"].weights_getter(
        engine.params, cfg,
        lambda w: jax.device_put(
            w.astype(jnp.dtype(rounded)).astype(jnp.bfloat16), dev),
        lambda w: jax.device_put(w, dev))
    want = mods["reference"].batch_loss(
        cfg, get, [jax.device_put(row, dev) for row in rows],
        float(cfg["deployment"]["balance_coef"]))
    return {k: np.asarray(v, np.float64) for k, v in want.items()}


def compare_biases(before, after, want: Dict, check: Dict, gamma: float,
                   mods: Dict) -> (List[str], Dict):
    """The selection biases the step left against the reference's rule on
    the reference's counts, wherever an expert's count is further from its
    layer's mean than the counts' own tolerance (nearer than that the sign
    may turn on a pair that flipped under bf16 activations)."""
    counts = np.asarray(want["router_counts"], np.float64)
    far = np.abs(counts - counts.mean(-1, keepdims=True)) \
        > float(check["expert_pairs_abs_tol"])
    rule = np.asarray(mods["reference"].bias_after(before, counts, gamma),
                      np.float64)
    diff = np.abs(np.asarray(after, np.float64) - rule)
    worst = float(np.max(np.where(far, diff, 0.0)))
    facts = {"compared": int(far.sum()), "of": int(far.size),
             "moved": int(np.sum(np.asarray(after) != np.asarray(before))),
             "max_abs_diff": worst, "tol": float(check["bias_abs_tol"])}
    problems = [] if worst <= facts["tol"] else [
        f"the selection biases after the first step differ from the rule's "
        f"by {worst} (tol {facts['tol']}) on {int(far.sum())} experts whose "
        f"counts are clear of the mean"]
    if not facts["moved"]:
        problems.append("the step moved no selection bias")
    return problems, facts


def run(cell: Dict, args) -> Dict:
    from deepspeed_tpu.models import TransformerConfig, TransformerLM

    missing = [f for f in NEEDS if f not in
               {x.name for x in dataclasses.fields(TransformerConfig)}]
    if missing:
        raise SystemExit(
            f"benchmarks: cell {cell['name']} needs a program whose "
            f"TransformerConfig has {', '.join(missing)} (latent attention "
            f"with keys wider than values, FFN kinds by layer, a sigmoid "
            f"router with a selection bias the step moves by rule, shared "
            f"experts); this checkout's has not")
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
    # weights and the first batch, then that batch's step, what the step
    # program itself returned for it and the biases it left
    first = make_batch()
    want = _reference(jax, engine, cfg, first["input_ids"], mods)
    bias_before = np.array(mods["modelcfg"].biases(engine.params))
    t_reference = time.perf_counter()
    first_loss = float(jax.block_until_ready(step(first)))
    record = steplog.get_steplog().parts(last=1)
    system = dict(record[-1]) if record else {}
    check = dict(cfg["check"])
    if args.rehearse:
        # rehearsal.json loosens the loss's; so the parts': at toy widths a
        # bf16 sum over 64 channels is a coarse thing and one flipped pair
        # in 256 tokens moves the balance term by a hundredth of itself
        check["lb_loss_abs_tol"] = max(check["lb_loss_abs_tol"],
                                       0.05 * float(want["lb_loss"]))
        check["mix_out_ms_rel_tol"] = max(check["mix_out_ms_rel_tol"], 0.05)
    problems, facts = compare(system, want, check)
    if system.get("loss") != first_loss:
        problems.append(f"the step record's loss {system.get('loss')} is not "
                        f"the step's {first_loss}")
    bias_problems, facts["router_bias"] = compare_biases(
        bias_before, np.array(mods["modelcfg"].biases(engine.params)), want,
        check, float(dep["bias_update_rate"]), mods)
    problems += bias_problems
    row = [p for p in steplog.programs()
           if p.name.startswith("ds_train_step")][-1]
    program = {"layer_pattern": row.layer_pattern,
               "moe_kernel_resolved": row.moe_kernel_resolved,
               "experts_held": row.experts_held,
               "attn_widths": getattr(row, "attn_widths", None),
               "moe_scoring": getattr(row, "moe_scoring", None),
               "layer_applications": row.layer_applications,
               "flash_fwd_tiles": row.flash_fwd_tiles,
               "flash_bwd_lowerings": row.flash_bwd_lowerings,
               "moe_grouped_lowerings": row.moe_grouped_lowerings,
               "moe_dispatch_lowerings": row.moe_dispatch_lowerings}
    if row.moe_kernel_resolved != "ragged":
        problems.append(f"the step program's grouped product is "
                        f"{row.moe_kernel_resolved!r}, not the ragged one")
    harness.say(check="train_first_loss_mixer_outputs_counts_and_biases",
                **facts, step_program=program)
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
    # the router's counts of the window's steps (the record keeps the last
    # 256; the first step's are above)
    kept = steplog.get_steplog().parts(last=min(steps, steplog.PARTS_KEPT))
    dropped = int(sum(np.sum(r["pairs_dropped"]) for r in kept)
                  + np.sum(system.get("pairs_dropped", 0)))
    if dropped:
        problems.append(f"{dropped} (token, expert) pairs did not fit the "
                        f"buffer of local pairs: the layer was not dropless")
    pairs_step = float(np.mean([np.sum(r["pairs_here"]) for r in kept]))
    load = float(np.max([np.max(r["load_max_over_mean"]) for r in kept]))
    moved = float(np.mean([np.sum(r.get("bias_moved", 0)) for r in kept]))
    last = kept[-1]
    if not np.all(np.isfinite(np.asarray(last["mix_out_ms"]))):
        problems.append("non-finite mixer output in the window's last step")
    rec = steplog.get_steplog().steps()[-steps:]
    host_ms = {"put_dispatch": float(np.median(rec[:, 2] - rec[:, 1]) * 1e3),
               "commit": float(np.median(rec[:, 3] - rec[:, 2]) * 1e3),
               "wait_and_batch": float(np.median(rec[1:, 1] - rec[:-1, 3])
                                       * 1e3) if steps > 1 else None,
               "step_ms_series": np.round(step_ms, 2).tolist()}
    bias_now = np.asarray(mods["modelcfg"].biases(engine.params))
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
                                       for k, v in last.items()
                                       if k != "router_counts"},
                        "router_bias_abs": {
                            "start_max": float(np.abs(bias_before).max()),
                            "now_max": float(np.abs(bias_now).max()),
                            "moved_mean_abs": float(
                                np.abs(bias_now - bias_before).mean())},
                        "pairs_dropped_in_window": dropped,
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
              "moe_pairs_per_step": pairs_step, "moe_pairs_dropped": dropped,
              "moe_load_max_over_mean": load,
              "moe_bias_moved_per_step": moved}
    return harness.fill_metrics(result, cell, bool(args.trace), trace,
                                values, peak)
