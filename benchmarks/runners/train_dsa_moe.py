"""Training cells of a model whose attention reads only the keys a learned
indexer picks for each query, the indexer trained beside the model by a loss
of its own, under a rope over three position axes, over a held share of
softmax-routed experts (Keye-VL-2.0's language model, a chip's share of
experts and vocabulary): ``deepspeed_tpu.initialize`` ->
``engine.fused_train_step``.

Set-up and window are ``runners/train.py``'s, clock read for clock read (as
``runners/train_latent_moe.py``'s are, whose gradient and update sums this
runner's :func:`first_step` repeats): the same process start, weights from
``--seed`` by the engine's own jitted init, the reference check on the first
batch, two steps before the window, a fresh batch drawn on the host inside
it, ``block_until_ready`` on every step, the same ``values`` keys; so that
this cell's rate means what the other training cells' means. What differs is
the batch (:func:`make_rows`: ids and, from the traffic file's image spans,
``position_ids`` [3, B, T], all from ``--seed``) and what ``correct``
compares (:func:`first_step` / :func:`judge`), all of it what the timed step
program itself returned or left for the first batch, against the reference
(``lax.top_k`` for each query's set, a whole softmax over it, a loop over the
held experts) on the same bf16-rounded weights and the same batch:

* the step's loss, its balance term, the indexer's loss, each layer's
  mixer-output mean square and the (token, expert) pairs each held expert
  received (``StepLog.parts()``);
* the share of (query, key) selections that differ between the sets the step
  left for its probe queries (``dsa_probe_sets``: eight queries a layer) and
  the reference's own sets for the same queries: neither side is given the
  other's set;
* the gradient, read back from the first moment the step left, against the
  reference's, by the worst leaf's ``|g - g_ref| / |g_ref|`` (the indexer's
  leaves get theirs from the indexer's loss alone), and the parameters'
  change over the step against the reference's AdamW on the reference's
  gradient (a state left unchanged reads 1);
* that the step program kept ``topk`` keys a query and the share of the
  causal pairs the cell's shapes say, followed three position axes, and left
  no pair out of the buffer of held pairs in any step of the window.

``python3 -m benchmarks.runners.train_dsa_moe --control <fault> --seed n``
puts a fault in the program's place and prints what the same comparison says
of it (:func:`control`): the limits' second readings come from there.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional

import numpy as np

from benchmarks import harness
from benchmarks.runners.train_delta import _adam_mu
from benchmarks.runners.train_hybrid import compare
from benchmarks.runners.train_looped import _modules

#: what the program's TransformerConfig has to know for this runner's cells
NEEDS = ("dsa_index_heads", "dsa_topk", "indexer_loss_coef", "mrope_section",
         "moe_experts_held", "qk_norm")
#: toy sizes for a rehearsal, for the keys ``rehearsal.json`` does not name
#: (it substitutes a hidden size of 64, 4 heads of 16 on 2 key-value heads,
#: 256 rows and rows of 128 positions)
TOY = {"moe_intermediate_size": 48, "router_width": 8, "num_experts": 4,
       "num_experts_per_tok": 2,
       "rope_scaling": {"mrope_section": [2, 2, 4], "rope_type": "default",
                        "type": "default"},
       "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                     "indexer_num_kv_heads": 1, "kv_chunk_size": 32,
                     "q_chunk_size": 32, "topk": 32}}
#: what :func:`control` can put in the program's place
FAULTS = {
    "fp8": "the reference on weights rounded to fp8 (e4m3), the nearest "
           "precision below the bf16 the configuration states, and the "
           "AdamW step its gradient gives",
    "unchanged": "the program's step, with the state read as it was before "
                 "it (no moment written, no parameter moved)",
    "window": "the reference with each query's set the most recent topk "
              "keys, not the indexer's, on the same bf16-rounded weights",
    "rope_one_axis": "the reference with the rope's three axes collapsed to "
                     "the first, on the same bf16-rounded weights",
    "no_indexer_loss": "the reference with the indexer's loss dropped from "
                       "the step's loss, on the same bf16-rounded weights"}


def at_widths(cfg: Dict) -> Dict:
    """``cfg`` as it is run: at the published widths as it is; under
    ``rehearsal.json``'s toy hidden size with :data:`TOY` for the keys that
    file does not name."""
    if int(cfg["hidden_size"]) >= int(cfg["moe_intermediate_size"]):
        return cfg
    return {**cfg, **TOY}


def make_rows(rng, traffic: Dict, vocab: int, rows: int, seq: int) -> Dict:
    """One batch from the generator's stream: ``input_ids`` [rows, seq]
    uniform over the vocabulary held, and ``position_ids`` [3, rows, seq]
    (time, height, width) of a row that holds ``image_spans`` spans of an
    ``image_grid`` (rows x columns of positions) at non-overlapping offsets
    drawn from the stream: a text token has its three positions equal and
    one more than the largest before it; a span's tokens have the time fixed
    at the span's first position, height and width the grid's row and column
    added to it; the text after a span resumes from the span's largest
    position plus one. (A row too short for the spans, a rehearsal's, takes
    a grid of 4 x 4.)"""
    ids = rng.integers(0, vocab, (rows, seq), dtype=np.int32)
    n, (gh, gw) = int(traffic["image_spans"]), traffic["image_grid"]
    if 2 * n * gh * gw > seq:
        gh = gw = 4
    area = gh * gw
    pos = np.empty((3, rows, seq), np.int32)
    grid_h = np.repeat(np.arange(gh, dtype=np.int32), gw)
    grid_w = np.tile(np.arange(gw, dtype=np.int32), gh)
    for r in range(rows):
        # the text before each span: sorted cuts of the text's length
        cuts = np.sort(rng.integers(0, seq - n * area + 1, n))
        at, p = 0, 0                # index in the row, next text position
        for i in range(n + 1):
            upto = (cuts[i] if i < n else seq - n * area) + i * area
            pos[:, r, at:upto] = p + np.arange(upto - at, dtype=np.int32)
            p += upto - at
            at = upto
            if i < n:
                pos[0, r, at:at + area] = p
                pos[1, r, at:at + area] = p + grid_h
                pos[2, r, at:at + area] = p + grid_w
                p += max(gh, gw)
                at += area
    return {"input_ids": ids, "position_ids": pos}


def first_step(jax, engine, step, cfg: Dict, mods: Dict, batch: Dict,
               fault: Optional[str] = None):
    """The reference on the engine's initial weights and ``batch``, then that
    batch's step, then what the step returned and left against the
    reference: ``(system, want, said, first_loss, t_reference)``, ``system``
    and ``want`` as :func:`compare` takes them, ``said`` the facts by leaf.
    ``fault`` as :data:`FAULTS` names them."""
    import jax.numpy as jnp
    from deepspeed_tpu.observability import steplog

    modelcfg, reference = mods["modelcfg"], mods["reference"]
    dev = jax.devices()[0]
    dep = cfg["deployment"]
    opt = dep["ds_config"]["optimizer"]["params"]
    b1, b2 = opt.get("betas", (0.9, 0.999))
    adamw = dict(lr=float(opt["lr"]), b1=float(b1), b2=float(b2),
                 eps=float(opt.get("eps", 1e-8)),
                 weight_decay=float(opt.get("weight_decay", 0.0)))
    alpha = float(dep["load_balance_coef"])
    rows = [jax.device_put(row, dev) for row in batch["input_ids"]]
    positions = [jax.device_put(batch["position_ids"][:, r], dev)
                 for r in range(len(rows))]
    put = lambda w: jax.device_put(w, dev)  # noqa: E731

    def rounded(dtype):
        return modelcfg.weights_getter(
            engine.params, cfg,
            lambda w: put(w.astype(dtype).astype(jnp.bfloat16)))

    def run_reference(dtype, into: Dict, faulty: Optional[str] = None) -> Dict:
        out, _ = reference.batch_loss_and_grads(
            {**cfg, "fault": faulty} if faulty else cfg, rounded(dtype),
            rows, alpha,
            lambda name, layer, g: into.__setitem__((name, layer),
                                                    np.asarray(g)),
            positions=positions)
        return {k: np.asarray(v, np.float64) for k, v in out.items()}

    # the state before the step, on the host: the step donates its buffers
    for leaf in jax.tree_util.tree_leaves(engine.params):
        leaf.copy_to_host_async()
    ref_grads: Dict = {}
    want = run_reference(jnp.bfloat16, ref_grads)
    theta0 = jax.device_get(engine.params)
    stand_in: Dict = {}
    in_place = fault not in (None, "unchanged")
    if fault == "fp8":
        system = run_reference(jnp.float8_e4m3fn, stand_in)
    elif in_place:
        system = run_reference(jnp.bfloat16, stand_in, fault)
    t_reference = time.perf_counter()
    first_loss = float(jax.block_until_ready(step(batch)))
    if not in_place:
        record = steplog.get_steplog().parts(last=1)
        system = dict(record[-1]) if record else {}
        if "dsa_probe_sets" in system:
            system["probe_sets"] = np.unpackbits(
                np.asarray(system.pop("dsa_probe_sets")).astype(np.uint8),
                axis=-1, bitorder="little").astype(bool)
    # selections that differ, over selections made (each side's own sets)
    mine = np.asarray(system.pop("probe_sets", np.zeros(0)), bool)
    theirs = np.asarray(want.pop("probe_sets"), bool)
    system["set_differs_share"] = (
        float(np.sum(mine != theirs)) / (2.0 * float(np.sum(theirs)))
        if mine.shape == theirs.shape else np.nan)
    want["set_differs_share"] = np.float64(0.0)

    @jax.jit
    def sums(g, t1, t0, g_ref):
        d_ref = reference.adamw_first_step(g_ref, t0, **adamw)
        d_own = reference.adamw_first_step(g, t0, **adamw)
        sq = lambda x: jnp.sum(jnp.square(x.astype(jnp.float32)))  # noqa
        return jnp.stack([sq(g - g_ref), sq(g_ref), sq(t1 - t0 - d_ref),
                          sq(d_ref), sq(t1 - t0 - d_own),
                          jnp.sum(jnp.sign(g) != jnp.sign(g_ref))])

    ident = lambda w: w  # noqa: E731
    before = modelcfg.weights_getter(theta0, cfg, ident)
    after = modelcfg.weights_getter(engine.params, cfg, ident)
    moment = modelcfg.weights_getter(_adam_mu(engine.opt_state), cfg, ident)
    by_leaf, total = {}, np.zeros(6)
    for (name, layer), g_ref in ref_grads.items():
        t0 = put(before(name, layer))
        if in_place:
            g = put(stand_in[(name, layer)])
            t1 = t0 + reference.adamw_first_step(g, t0, **adamw)
        elif fault == "unchanged":
            g, t1 = jnp.zeros_like(t0), t0
        else:
            g, t1 = moment(name, layer) / (1.0 - adamw["b1"]), \
                after(name, layer)
        s = np.asarray(sums(g, t1, t0, put(g_ref)), np.float64)
        total += s
        by_leaf[name if layer is None else f"{name}.{layer}"] = [
            float(np.sqrt(s[0] / max(s[1], 1e-300))),
            float(np.sqrt(s[2] / max(s[3], 1e-300))),
            float(s[5] / g_ref.size)]
    worst = max(by_leaf, key=lambda n: by_leaf[n][0])
    system["grad_err"] = by_leaf[worst][0]
    system["param_change_err"] = float(np.sqrt(total[2] / total[3]))
    want["grad_err"] = want["param_change_err"] = np.float64(0.0)
    indexer = [n for n in by_leaf if n.startswith("idx_")]
    said = {"grad_err_worst_leaf": worst,
            "grad_err_all": float(np.sqrt(total[0] / total[1])),
            "grad_err_worst_indexer_leaf": max(by_leaf[n][0]
                                               for n in indexer),
            "sign_differs_share": float(total[5] / sum(
                g.size for g in ref_grads.values())),
            "param_change_err_given_own_gradient":
                float(np.sqrt(total[4] / total[3])),
            "by_leaf_grad_err_change_err_sign_share": by_leaf}
    return system, want, said, first_loss, t_reference


def judge(system: Dict, want: Dict, cfg: Dict, rehearse: bool):
    """``(problems, facts)`` of a first step: :func:`compare` on the parts,
    the sets, the gradient and the update."""
    return compare(system, want, _limits(cfg, rehearse))


def _limits(cfg: Dict, rehearse: bool) -> Dict:
    check = dict(cfg["check"])
    if rehearse:
        # rehearsal.json loosens the loss's; so the others': at toy widths a
        # bf16 sum over 64 channels is a coarse thing, a set of 32 keys of
        # near-equal scores flips freely and a leaf's gradient reads 0.4-0.7
        # off (a state left as it was still reads 1)
        for name in ("lb_loss_abs_tol", "indexer_loss_abs_tol"):
            check[name] = max(check[name], 0.5)
        check["mix_out_ms_rel_tol"] = max(check["mix_out_ms_rel_tol"], 0.05)
        check["expert_pairs_abs_tol"] = max(check["expert_pairs_abs_tol"], 64)
        check["set_differs_share_abs_tol"] = 0.5
        for name in ("grad_err_abs_tol", "param_change_err_abs_tol"):
            check[name] = max(check[name], 0.9)
    return check


def _build(cell: Dict, args):
    """Set-up up to the engine: ``(jax, devices, dev, engine, cfg, mods,
    tcfg, t_imported, t_engine)``."""
    from deepspeed_tpu.models import TransformerConfig, TransformerLM

    missing = [f for f in NEEDS if f not in
               {x.name for x in dataclasses.fields(TransformerConfig)}]
    if missing:
        raise SystemExit(
            f"benchmarks: cell {cell['name']} needs a program whose "
            f"TransformerConfig has {', '.join(missing)} (attention over the "
            f"keys a learned indexer picks, the indexer's own loss, a rope "
            f"over three position axes); this checkout's has not")
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
    traffic = cell["traffic"]
    seq = int(traffic["seq_len"])
    rows = int(traffic["rows_per_chip"]) * cell["chips"]
    peak = None if args.rehearse else harness.load_peaks(dev["kind"])
    step = spans.wrap("fused_train_step", engine.fused_train_step)
    rng = np.random.default_rng(int(args.seed))

    def make_batch():
        with spans.span("make_batch"):
            return make_rows(rng, traffic, tcfg.vocab_size, rows, seq)

    # ---- correctness, outside the window: the reference on the initial
    # weights and the first batch, then that batch's step and what the step
    # program itself returned and left for it
    system, want, said, first_loss, t_reference = first_step(
        jax, engine, step, cfg, mods, make_batch())
    t_checked = time.perf_counter()
    problems, facts = judge(system, want, cfg, args.rehearse)
    if system.get("loss") != first_loss:
        problems.append(f"the step record's loss {system.get('loss')} is not "
                        f"the step's {first_loss}")
    row = [p for p in steplog.programs()
           if p.name.startswith("ds_train_step")][-1]
    program = {name: getattr(row, name, None) for name in (
        "layer_pattern", "layer_applications", "experts_held",
        "moe_kernel_resolved", "dsa_topk", "dsa_selected_share",
        "dsa_lowerings", "mrope_axes", "moe_grouped_lowerings",
        "moe_dispatch_lowerings")}
    oc = mods["opcount"]
    if row.layer_applications != int(cfg["num_hidden_layers"]):
        problems.append(f"the step program applies {row.layer_applications} "
                        f"layers a step, the configuration has "
                        f"{cfg['num_hidden_layers']}")
    if row.dsa_topk != int(cfg["sa_config"]["topk"]):
        problems.append(f"the step program keeps {row.dsa_topk} keys a "
                        f"query, the configuration says "
                        f"{cfg['sa_config']['topk']}")
    share = oc.selected_share(cfg, seq)
    if not abs((row.dsa_selected_share or 0.0) - share) < 1e-9:
        problems.append(f"the step program keeps {row.dsa_selected_share} "
                        f"of the causal pairs, the cell's shapes say {share}")
    if row.mrope_axes != 3:
        problems.append(f"the step program's rope follows {row.mrope_axes} "
                        f"position axes, not three")
    if row.moe_kernel_resolved != "ragged":
        problems.append(f"the step program's grouped product is "
                        f"{row.moe_kernel_resolved!r}, not the ragged one")
    harness.say(check="train_first_step_parts_sets_backward_update",
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
    flops_tok = oc.train_flops_per_token(cfg, seq)
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
    indexer_loss = float(np.mean([r["indexer_loss"] for r in kept]))
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
                                       for k, v in last.items()
                                       if k != "dsa_probe_sets"},
                        "pairs_here_by_step": [int(np.sum(r["pairs_here"]))
                                               for r in kept],
                        "indexer_loss_by_step": [float(r["indexer_loss"])
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
              "dsa_selected_share": row.dsa_selected_share,
              "indexer_loss": indexer_loss}
    return harness.fill_metrics(result, cell, bool(args.trace), trace,
                                values, peak)


def control(cell: Dict, args) -> Dict:
    """One of :data:`FAULTS` in the program's place, through the cell's own
    comparison: the line says what :func:`judge` made of it."""
    jax, _, _, engine, cfg, mods, tcfg, _, _ = _build(cell, args)
    seq = int(cell["traffic"]["seq_len"])
    rows = int(cell["traffic"]["rows_per_chip"]) * cell["chips"]
    batch = make_rows(np.random.default_rng(int(args.seed)), cell["traffic"],
                      tcfg.vocab_size, rows, seq)
    system, want, said, _, _ = first_step(
        jax, engine, engine.fused_train_step, cfg, mods, batch, args.control)
    problems, facts = judge(system, want, cfg, args.rehearse)
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
    ap.add_argument("--workload", default="keye_vl2_30b_train_1chip")
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
