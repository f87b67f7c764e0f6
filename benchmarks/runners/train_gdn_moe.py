"""Training cells of a model whose token mixers are gated delta-rule layers
whose key heads serve several value heads and, one layer in four, gated full
attention over wide heads, under zero-centred norms, over a held share of
softmax-routed experts beside a gated shared one (Qwen3-Next, a chip's share
of experts and vocabulary): ``deepspeed_tpu.initialize`` ->
``engine.fused_train_step``.

Set-up and window are ``runners/train.py``'s, clock read for clock read (as
``runners/train_delta.py``'s and ``runners/train_kda_moe.py``'s are; the
first step's comparison is ``train_delta.first_step``, imported): the same
process start, weights from ``--seed`` by the engine's own jitted init, the
reference check on the first batch, two steps before the window, a fresh
batch drawn on the host inside it, ``block_until_ready`` on every step, the
same ``values`` keys; so that this cell's rate means what the other training
cells' means. What differs is named by the configuration file (``modules``)
and what ``correct`` compares, all of it what the timed step program itself
returned or left for the first batch, against the reference (whose delta
layer is the recurrence over positions, whose attention a whole softmax a
block of queries after the other and whose experts a loop over the held
ones) on the same bf16-rounded weights and the same batch: the loss, its
balance term, each layer's mixer-output mean square, the (token, expert)
pairs each held expert of each layer received, the gradient read back from
AdamW's first moment (worst leaf) and the parameters' change; and that the
step program scanned as many chunks as the cell's shapes say, read q and k
once a key head, took the Pallas lowerings on the chip and left no pair out
of the buffer of held pairs in any step of the window.

``python3 -m benchmarks.runners.train_gdn_moe --control <fault> --seed n``
puts a fault in the program's place and prints what the same comparison says
of it (:func:`control`): the limits' second readings come from there.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict

import numpy as np

from benchmarks import harness
from benchmarks.runners import train_delta
from benchmarks.runners.train_delta import first_step
from benchmarks.runners.train_hybrid import compare
from benchmarks.runners.train_looped import _modules

#: what the program's TransformerConfig has to know for this runner's cells
NEEDS = ("delta_key_heads", "attn_channel_gate", "norm_zero_centred",
         "moe_shared_gate", "moe_experts_held")
#: toy sizes for a rehearsal, for the keys ``rehearsal.json`` does not name
#: (it substitutes a hidden size of 64, 4 heads of 16 on 2 key-value heads,
#: 256 rows and rows of 128 positions)
TOY = {"linear_num_key_heads": 2, "linear_num_value_heads": 4,
       "linear_key_head_dim": 16, "linear_value_head_dim": 16,
       "moe_intermediate_size": 48, "shared_expert_intermediate_size": 48,
       "router_width": 8, "num_experts": 4, "num_experts_per_tok": 2}
#: what :func:`control` can put in the program's place: the imported two
#: (the third of ``train_delta``'s is Olmo-Hybrid's own) and this model's
FAULTS = {
    **{k: train_delta.FAULTS[k] for k in ("fp8", "unchanged")},
    "no_channel_gate": "the reference without the full layer's gate a "
                       "channel, on the same bf16-rounded weights",
    "wrong_key_sharing": "the reference with value head i reading key head "
                         "i mod 16 (not i // 2), on the same bf16-rounded "
                         "weights"}
#: the step-program row's lowering facts that have to say ``pallas`` alone
#: on the chip
PALLAS = ("delta_scan_lowerings", "conv_lowerings", "moe_grouped_lowerings",
          "moe_dispatch_lowerings", "moe_topk_lowerings")


class _Fault:
    """``reference`` with ``fault`` (one of ``reference.FAULTS``) in its
    second ``batch_loss_and_grads``: ``first_step``'s stand-in for the
    program under its ``fp8`` control, which it hands fp8-rounded weights. A
    fault of the equations is judged on the weights the reference proper had
    (the first call's), so that the precision is not in the reading."""

    def __init__(self, reference, fault: str):
        self._reference, self._fault, self._get = reference, fault, None

    def __getattr__(self, name):
        return getattr(self._reference, name)

    def batch_loss_and_grads(self, cfg, get, rows, sink=None):
        if self._get is None:
            self._get = get
            return self._reference.batch_loss_and_grads(cfg, get, rows, sink)
        return self._reference.batch_loss_and_grads(
            {**cfg, "fault": self._fault}, self._get, rows, sink)


def at_widths(cfg: Dict) -> Dict:
    """``cfg`` as it is run: at the published widths as it is; under
    ``rehearsal.json``'s toy hidden size with :data:`TOY` for the keys that
    file does not name."""
    if int(cfg["hidden_size"]) >= int(cfg["moe_intermediate_size"]):
        return cfg
    return {**cfg, **TOY}


def _limits(cfg: Dict, rehearse: bool) -> Dict:
    check = train_delta._limits(cfg, rehearse)
    if rehearse:
        # at toy widths one flipped pair in 128 tokens moves the balance term
        # by a hundredth of itself and an expert's count by one
        check["lb_loss_abs_tol"] = max(check["lb_loss_abs_tol"], 0.5)
        check["expert_pairs_abs_tol"] = max(check["expert_pairs_abs_tol"], 8)
    return check


def qk_rows_per_step(row, delta_layers: int):
    """The rows of q and k the delta layers' rules read a step: what the
    rules traced counted (``delta_qk_rows``, by lowering) over the rules
    traced, times the delta layers a step runs."""
    counted = getattr(row, "delta_qk_rows", None)
    scans = getattr(row, "delta_scan_lowerings", None)
    if not counted or not scans:
        return None
    # a rule counts once where it is traced, the kernels' backward once more
    rules = scans.get("xla", 0) + scans.get("pallas", 0) // 2
    return sum(counted.values()) // max(rules, 1) * delta_layers


def _build(cell: Dict, args):
    """Set-up up to the engine: ``(jax, devices, dev, engine, cfg, mods,
    tcfg, t_imported, t_engine)``."""
    from deepspeed_tpu.models import TransformerConfig, TransformerLM

    missing = [f for f in NEEDS if f not in
               {x.name for x in dataclasses.fields(TransformerConfig)}]
    if missing:
        raise SystemExit(
            f"benchmarks: cell {cell['name']} needs a program whose "
            f"TransformerConfig has {', '.join(missing)} (delta layers whose "
            f"key heads serve several value heads beside routed experts, a "
            f"gate a channel on attention, zero-centred norms, a gated "
            f"shared expert); this checkout's has not")
    jax, devices, dev = harness.setup_jax(cell["chips"], args.rehearse)
    import deepspeed_tpu as ds

    mods = _modules(cell["config"])
    cfg = cell["config"] = at_widths(cell["config"])
    seq = int(cell["traffic"]["seq_len"])
    rows = int(cell["traffic"]["rows_per_chip"]) * cell["chips"]
    tcfg = mods["modelcfg"].transformer_config(cfg, max_seq_len=seq,
                                               param_dtype="float32")
    ds_cfg = dict(cfg["deployment"]["ds_config"],
                  seed=int(args.seed) % (2 ** 31),
                  train_micro_batch_size_per_gpu=rows // cell["chips"])
    mesh = None
    if cell["chips"] == 1 and len(jax.devices()) > 1:
        from deepspeed_tpu.parallel import build_mesh
        mesh = build_mesh(devices=devices)
    t_imported = time.perf_counter()
    engine, *_ = ds.initialize(model=TransformerLM(tcfg), config=ds_cfg,
                               mesh=mesh)
    return (jax, devices, dev, engine, cfg, mods, tcfg, t_imported,
            time.perf_counter())


def run(cell: Dict, args) -> Dict:
    (jax, devices, dev, engine, cfg, mods, tcfg, t_imported,
     t_engine) = _build(cell, args)
    from deepspeed_tpu.observability import steplog

    compiles = harness.CompileCount()
    spans = harness.Spans()
    seq = int(cell["traffic"]["seq_len"])
    rows = int(cell["traffic"]["rows_per_chip"]) * cell["chips"]
    peak = None if args.rehearse else harness.load_peaks(dev["kind"])
    step = spans.wrap("fused_train_step", engine.fused_train_step)
    rng = np.random.default_rng(int(args.seed))

    def make_batch():
        with spans.span("make_batch"):
            return {"input_ids": rng.integers(
                0, tcfg.vocab_size, (rows, seq), dtype=np.int32)}

    # ---- correctness, outside the window: the reference on the initial
    # weights and the first batch, then that batch's step and what the step
    # program itself returned and left for it
    system, want, said, first_loss, t_reference = first_step(
        jax, engine, step, cfg, mods, make_batch())
    t_checked = time.perf_counter()
    problems, facts = compare(system, want, _limits(cfg, args.rehearse))
    if system.get("loss") != first_loss:
        problems.append(f"the step record's loss {system.get('loss')} is not "
                        f"the step's {first_loss}")
    row = [p for p in steplog.programs()
           if p.name.startswith("ds_train_step")][-1]
    program = {name: getattr(row, name, None) for name in (
        "layer_pattern", "layer_applications", "experts_held",
        "moe_kernel_resolved", "delta_chunk", "delta_chunks_per_step",
        "delta_heads", "delta_rule_lowering", "delta_qk_rows", "attn_widths",
        "flash_fwd_tiles", "flash_bwd_lowerings", "flash_bwd_arm") + PALLAS}
    kinds = mods["opcount"].kinds(cfg)
    n_delta = kinds.count("linear_attention")
    if row.layer_applications != len(kinds):
        problems.append(f"the step program applies {row.layer_applications} "
                        f"layers a step, the configuration has {len(kinds)}")
    chunks = n_delta * rows * -(-seq // int(cfg["deployment"]["delta_chunk"]))
    if row.delta_chunks_per_step != chunks:
        problems.append(f"the step program scans {row.delta_chunks_per_step} "
                        f"chunks a step, the cell's shapes say {chunks}")
    heads = (int(cfg["linear_num_key_heads"]),
             int(cfg["linear_num_value_heads"]))
    if tuple(row.delta_heads or ()) != heads:
        problems.append(f"the step program's delta layers hold "
                        f"{row.delta_heads} (key, value) heads, the "
                        f"configuration says {heads}")
    if row.moe_kernel_resolved != "ragged":
        problems.append(f"the step program's grouped product is "
                        f"{row.moe_kernel_resolved!r}, not the ragged one")
    qk_rows = qk_rows_per_step(row, n_delta)
    if not args.rehearse:
        for name in PALLAS:
            if set(program[name] or {"none": 0}) != {"pallas"}:
                problems.append(f"the step program's {name} are "
                                f"{program[name]}, not the Pallas kernels "
                                f"alone")
        # q and k once a key head: a repeat to the value heads doubles it
        once = 2 * rows * seq * heads[0] * n_delta
        if qk_rows != once:
            problems.append(f"the rules read {qk_rows} rows of q and k a "
                            f"step, once a key head is {once}")
    harness.say(check="train_first_step_parts_counts_backward_update",
                **facts, **said, step_program=program)
    # second call: same program, now with the step's own outputs as inputs
    jax.block_until_ready(step(make_batch()))
    harness.say(setup={
        "imports_and_device_s": t_imported - harness.T_PROCESS_START,
        "engine_build_s": t_engine - t_imported,
        "reference_check_s": t_reference - t_engine,
        "state_check_s": t_checked - t_reference,
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
                        f"buffer of held pairs: the layer was not dropless")
    pairs_step = float(np.mean([np.sum(r["pairs_here"]) for r in kept]))
    load = float(np.max([np.max(r["load_max_over_mean"]) for r in kept]))
    last = kept[-1]
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
                        "pairs_here_by_step": [int(np.sum(r["pairs_here"]))
                                               for r in kept],
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
              "delta_qk_rows_per_step": qk_rows}
    return harness.fill_metrics(result, cell, bool(args.trace), trace,
                                values, peak)


def control(cell: Dict, args) -> Dict:
    """One of :data:`FAULTS` in the program's place, through the cell's own
    comparison: the line says what :func:`compare` made of it."""
    jax, _, _, engine, cfg, mods, tcfg, _, _ = _build(cell, args)
    seq = int(cell["traffic"]["seq_len"])
    rows = int(cell["traffic"]["rows_per_chip"]) * cell["chips"]
    batch = {"input_ids": np.random.default_rng(int(args.seed)).integers(
        0, tcfg.vocab_size, (rows, seq), dtype=np.int32)}
    fault = args.control
    if fault in mods["reference"].FAULTS:
        # a fault of the equations: the reference with it, where
        # ``first_step`` puts the fp8 reference
        mods = {**mods, "reference": _Fault(mods["reference"], fault)}
        fault = "fp8"
    system, want, said, _, _ = first_step(
        jax, engine, engine.fused_train_step, cfg, mods, batch, fault)
    problems, facts = compare(system, want, _limits(cfg, args.rehearse))
    said.pop("by_leaf_grad_err_change_err_sign_share")
    line = {"control": args.control, "what": FAULTS[args.control],
            "seed": int(args.seed), "correct": not problems,
            "problems": problems,
            "readings": {k: {x: f[x] for x in f if x.startswith("max_")
                             or x == "tol"} for k, f in facts.items()},
            **said}
    harness.say(**line)
    return line


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=control.__doc__)
    ap.add_argument("--workload", default="qwen3_next_80b_train_1chip")
    ap.add_argument("--control", required=True, choices=sorted(FAULTS))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if args.rehearse:
        cell = harness.apply_rehearsal(cell)
    # a fault that comes out correct is the failure here
    return 1 if control(cell, args)["correct"] else 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
