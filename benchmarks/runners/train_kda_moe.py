"""Training cells of a model whose token mixers are KDA layers (the delta
rule with a decay a key channel) and, one layer in six, latent attention,
both on a held share of the heads and under a head-wise output gate, with a
leading dense FFN and a held share of sigmoid-routed experts chosen under a
group limit (Ling-3.0-flash, a chip's share of heads, experts and
vocabulary): ``deepspeed_tpu.initialize`` -> ``engine.fused_train_step``.

Set-up and window are ``runners/train.py``'s, clock read for clock read (as
``runners/train_latent_moe.py``'s are, whose first step and judgement this
runner imports): the same process start, weights from ``--seed`` by the
engine's own jitted init, the reference check on the first batch, two steps
before the window, a fresh batch drawn on the host inside it,
``block_until_ready`` on every step, the same ``values`` keys; so that this
cell's rate means what the other training cells' means. What differs is named
by the configuration file (``modules``) and what ``correct`` compares
(``train_latent_moe.first_step`` / ``judge``), all of it what the timed step
program itself returned or left for the first batch, against the reference
(whose KDA layer is the recurrence over positions, whose convolution a sum of
shifted arrays, whose latent attention a whole softmax and whose experts a
loop over the held ones) on the same bf16-rounded weights and the same batch:
the loss, its balance term, each layer's mixer-output mean square, the
(token, expert) pairs each held expert of each routed layer received, the
selection biases the step left, the gradient read back from AdamW's first
moment and the parameters' change; and that the step program scanned as many
chunks as the cell's shapes say, took the Pallas lowerings on the chip where
the program has kernels (the rule with a decay a channel has none yet: its
``kda_scan_lowerings`` are printed) and left no pair out of the buffer of
held pairs in any step of the window.

``python3 -m benchmarks.runners.train_kda_moe --control <fault> --seed n``
puts a fault in the program's place and prints what the same comparison says
of it (:func:`control`): the limits' second readings come from there.
``decay_mean`` is the control that says the check sees what is new: the
reference with each head's decay taken as its mean over the head's key
channels (the scalar rule the repo had), on the same bf16-rounded weights.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict

import numpy as np

from benchmarks import harness
from benchmarks.runners import train_latent_moe
from benchmarks.runners.train_conv_moe import _judge
from benchmarks.runners.train_latent_moe import first_step, judge
from benchmarks.runners.train_looped import _modules

#: what the program's TransformerConfig has to know for this runner's cells
NEEDS = ("kda_lower_bound", "mla_head_gate", "moe_n_group", "moe_topk_group",
         "first_k_dense", "moe_scoring", "moe_bias_rate", "moe_experts_held",
         "heads_held")
#: toy sizes for a rehearsal, for the keys ``rehearsal.json`` does not name
#: (it substitutes a hidden size of 64, 4 heads of 16, a dense FFN of 128 and
#: 256 rows): experts and a latent wider than the hidden state are a toy
TOY = {"heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
       "qk_rope_head_dim": 8, "rotary_dim": 8, "v_head_dim": 16,
       "moe_intermediate_size": 48,
       "moe_shared_expert_intermediate_size": 48, "router_width": 32,
       "num_experts": 4, "num_experts_per_tok": 4, "n_group": 4,
       "topk_group": 2}
#: what :func:`control` can put in the program's place: the imported two,
#: and the fault that is this model's own
FAULTS = {**train_latent_moe.FAULTS,
          "decay_mean": "the reference with each head's decay taken as its "
                        "mean over the head's key channels (the rule with "
                        "one decay a head), on the same bf16-rounded "
                        "weights, and the AdamW step its gradient gives"}
#: the step-program row's lowering facts that have to say ``pallas`` alone
#: on the chip (the rule's own, ``kda_scan_lowerings``, is printed: it has
#: no kernels yet)
PALLAS = ("moe_grouped_lowerings", "moe_dispatch_lowerings",
          "conv_lowerings")


class _RuleFault:
    """``reference`` with ``fault`` (one of ``reference.FAULTS``) in its
    second ``batch_loss_and_grads``: ``first_step``'s stand-in for the
    program under its ``fp8`` control, which it hands fp8-rounded weights. A
    fault of the equations is judged on the weights the reference proper
    had (the first call's), so that the precision is not in the reading."""

    def __init__(self, reference, fault: str):
        self._reference, self._fault, self._get = reference, fault, None

    def __getattr__(self, name):
        return getattr(self._reference, name)

    def batch_loss_and_grads(self, cfg, get, rows, alpha, sink=None):
        if self._get is None:
            self._get = get
            return self._reference.batch_loss_and_grads(cfg, get, rows,
                                                        alpha, sink)
        return self._reference.batch_loss_and_grads(
            {**cfg, "fault": self._fault}, self._get, rows, alpha, sink)


def at_widths(cfg: Dict) -> Dict:
    """``cfg`` as it is run: at the published widths as it is; under
    ``rehearsal.json``'s toy hidden size with :data:`TOY` for the keys that
    file does not name."""
    if int(cfg["hidden_size"]) >= int(cfg["moe_intermediate_size"]):
        return cfg
    return {**cfg, **TOY}


def _build(cell: Dict, args):
    """Set-up up to the engine: ``(jax, devices, dev, engine, cfg, mods,
    tcfg, t_imported, t_engine)``."""
    from deepspeed_tpu.models import TransformerConfig, TransformerLM

    missing = [f for f in NEEDS if f not in
               {x.name for x in dataclasses.fields(TransformerConfig)}]
    if missing:
        raise SystemExit(
            f"benchmarks: cell {cell['name']} needs a program whose "
            f"TransformerConfig has {', '.join(missing)} (KDA layers "
            f"beside latent attention in one pattern, both on a held share "
            f"of the heads, over group-limited sigmoid routing); this "
            f"checkout's has not")
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
    bias_before = said["biases"][0]
    problems, facts = _judge(system, want, said, cfg, mods, args.rehearse)
    if system.get("loss") != first_loss:
        problems.append(f"the step record's loss {system.get('loss')} is not "
                        f"the step's {first_loss}")
    row = [p for p in steplog.programs()
           if p.name.startswith("ds_train_step")][-1]
    program = {name: getattr(row, name, None) for name in (
        "layer_pattern", "layer_applications", "heads_held", "experts_held",
        "moe_scoring", "moe_groups", "moe_kernel_resolved", "kda_chunk",
        "kda_chunks_per_step", "kda_scan_lowerings", "flash_fwd_tiles",
        "flash_bwd_lowerings") + PALLAS}
    kinds = mods["opcount"].kinds(cfg)
    if row.layer_applications != len(kinds):
        problems.append(f"the step program applies {row.layer_applications} "
                        f"layers a step, the configuration has {len(kinds)}")
    chunks = sum(mixer == "kda" for mixer, _ in kinds) * rows \
        * -(-seq // int(cfg["deployment"]["kda_chunk"]))
    if row.kda_chunks_per_step != chunks:
        problems.append(f"the step program scans {row.kda_chunks_per_step} "
                        f"chunks a step, the cell's shapes say {chunks}")
    groups = (int(cfg["n_group"]), int(cfg["topk_group"]))
    if tuple(row.moe_groups or ()) != groups:
        problems.append(f"the step program's router keeps {row.moe_groups} "
                        f"(groups, kept), the configuration says {groups}")
    if row.moe_kernel_resolved != "ragged":
        problems.append(f"the step program's grouped product is "
                        f"{row.moe_kernel_resolved!r}, not the ragged one")
    if not args.rehearse:
        for name in PALLAS:
            if set(program[name] or {"none": 0}) != {"pallas"}:
                problems.append(f"the step program's {name} are "
                                f"{program[name]}, not the Pallas kernels "
                                f"alone")
    harness.say(check="train_first_step_parts_counts_biases_backward_update",
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
                        "pairs_here_by_step": [int(np.sum(r["pairs_here"]))
                                               for r in kept],
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


def control(cell: Dict, args) -> Dict:
    """One of :data:`FAULTS` in the program's place, through the cell's own
    comparison: the line says what :func:`judge` made of it."""
    jax, _, _, engine, cfg, mods, tcfg, _, _ = _build(cell, args)
    seq = int(cell["traffic"]["seq_len"])
    rows = int(cell["traffic"]["rows_per_chip"]) * cell["chips"]
    batch = {"input_ids": np.random.default_rng(int(args.seed)).integers(
        0, tcfg.vocab_size, (rows, seq), dtype=np.int32)}
    fault = args.control
    if fault in mods["reference"].FAULTS:
        # a fault of the equations: the reference with it, where
        # ``first_step`` puts the fp8 reference
        mods = {**mods, "reference": _RuleFault(mods["reference"], fault)}
        fault = "fp8"
    system, want, said, _, _ = first_step(
        jax, engine, engine.fused_train_step, cfg, mods, batch, fault)
    problems, facts = _judge(system, want, said, cfg, mods, args.rehearse)
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
    ap.add_argument("--workload", default="ling3_flash_train_1chip")
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
