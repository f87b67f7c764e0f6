"""Training cells of a model trained by block diffusion over a held share of
softmax-routed experts (SDAR-30B-A3B-Chat, a chip's share of experts and
vocabulary): ``deepspeed_tpu.initialize`` -> ``engine.fused_train_step`` on
batches of ``input_ids``, ``noised_ids`` and ``loss_weights``.

Set-up and window are ``runners/train.py``'s, clock read for clock read (as
``runners/train_dsa_moe.py``'s are, whose gradient and update sums this
runner's :func:`first_step` repeats): the same process start, weights from
``--seed`` by the engine's own jitted init, the reference check on the first
batch, two steps before the window, a fresh batch drawn and noised on the host
inside it, ``block_until_ready`` on every step, the same ``values`` keys; so
that this cell's rate means what the other training cells' means. **Tokens are
counted as the rows' ``seq_len`` a row a step**, never the ``2 x seq_len``
positions the layers run. What differs is the batch (:func:`make_rows`: ids
over ``[0, mask id)`` and the program's own host noising,
``runtime/data_pipeline/block_noise.py``, all from ``--seed``) and what
``correct`` compares (:func:`first_step` / :func:`judge`), all of it what the
timed step program itself returned or left for the first batch, against the
reference (the mask as booleans a block of queries after the other, a whole
softmax, a loop over the held experts) on the same bf16-rounded weights and
the same noised batch:

* the step's loss, its balance term, each layer's mixer-output mean square
  and the same over the first 64 positions of each half (``early_ms``: with
  random weights the scores are nearly flat, so a mask that is off by a block
  moves a late query's output by 4 keys in thousands and an early one's by
  4 in a handful: this is where the mask shows), the (position, expert)
  pairs each held expert received, the masked targets and their weights' sum
  (``StepLog.parts()``);
* the gradient, read back from the first moment the step left, against the
  reference's: ``grad_err``, the worst ``|g - g_ref| / |g_ref|`` of the
  leaves **outside the routed FFN** (a rope that does not repeat its
  positions, a loss that reads the wrong half or weighs wrongly shows
  here), and ``grad_err_all``, the same over the whole gradient. A routed
  layer's own leaves (:data:`ROUTED`) are in the second and not in the
  first: under the 1 / t weights one position of a row can hold half of the
  sum of squared weights, and where the bf16 program and the float32
  reference break a near-tie between that position's 8th and 9th expert
  differently, a whole layer's expert leaves differ by half their norm with
  nothing wrong (0.07-0.63 read over 16 seeds, always a routed leaf, beside
  0.017-0.035 for the worst other leaf; ``PERF.md`` section 6, PR 63); the
  parameters' change over the step against the reference's AdamW on the
  reference's gradient (a state left unchanged reads 1);
* that the step program ran blocks of the file's length, two positions a
  token, the head over ``seq_len`` rows, the flash kernels over at most 1.15
  x the mask's pairs, and left no pair out of the buffer of held pairs in
  any step of the window.

``python3 -m benchmarks.runners.train_bd_moe --control <fault> --seed n`` puts
a fault in the program's place and prints what the same comparison says of it
(:func:`control`): the limits' second readings come from there.
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
NEEDS = ("diffusion_block", "mask_token_id", "moe_experts_held", "qk_norm")
#: toy sizes for a rehearsal, for the keys ``rehearsal.json`` does not name
#: (it substitutes a hidden size of 64, 4 heads of 16 on 2 key-value heads,
#: 256 rows and rows of 128 tokens)
TOY = {"moe_intermediate_size": 48, "router_width": 8, "num_experts": 4,
       "num_experts_per_tok": 2, "mask_token_id": 255}
#: the kernels may work this many times the pairs the mask keeps
PAIRS_WORKED_LIMIT = 1.15
#: the leaves of a routed layer's FFN and the norm in front of it: what one
#: position's flipped expert moves (the module docstring's third point)
ROUTED = ("router", "w_gate", "w_up", "w_down", "ln2")
#: what :func:`control` can put in the program's place
FAULTS = {
    "fp8": "the reference on weights rounded to fp8 (e4m3), the nearest "
           "precision below the bf16 the configuration states, and the "
           "AdamW step its gradient gives",
    "unchanged": "the program's step, with the state read as it was before "
                 "it (no moment written, no parameter moved)",
    "mask_token_causal": "the reference with the clean half causal by "
                         "token, on the same bf16-rounded weights",
    "mask_leak": "the reference with the noised queries also reading their "
                 "own clean block, on the same bf16-rounded weights",
    "no_own_block": "the reference with the noised queries not reading "
                    "their own noised block, on the same bf16-rounded "
                    "weights",
    "positions_unrepeated": "the reference with the clean half at positions "
                            "L..2L-1, on the same bf16-rounded weights",
    "loss_unweighted": "the reference with every masked position weighing "
                       "1, on the same bf16-rounded weights",
    "loss_on_clean_half": "the reference with the loss over the clean "
                          "half's logits, on the same bf16-rounded weights"}


def at_widths(cfg: Dict) -> Dict:
    """``cfg`` as it is run: at the published widths as it is; under
    ``rehearsal.json``'s toy hidden size with :data:`TOY` for the keys that
    file does not name."""
    if int(cfg["hidden_size"]) >= int(cfg["moe_intermediate_size"]):
        return cfg
    return {**cfg, **TOY}


def make_rows(rng, traffic: Dict, cfg: Dict, rows: int, seq: int) -> Dict:
    """One batch from the generator's stream: ``input_ids`` [rows, seq]
    uniform over ``[0, mask id)``, then the program's own host noising with
    the traffic file's parameters, drawing on from the same stream."""
    from deepspeed_tpu.runtime.data_pipeline.block_noise import noise_batch

    mask_id = int(cfg["mask_token_id"])
    ids = rng.integers(0, mask_id, (rows, seq), dtype=np.int32)
    return noise_batch({"input_ids": ids}, block=int(cfg["block_length"]),
                       mask_token_id=mask_id, seed=rng,
                       t_min=float(traffic["t_min"]),
                       t_draw=str(traffic["t_draw"]))


def place_experts(jax, engine, cfg: Dict, mods: Dict, batch: Dict):
    """The engine's routers with each layer's experts placed by load
    (``deployment.expert_placement`` "by_load": ``reference.place_experts``
    on the initial weights and ``batch``, the columns of each layer's router
    put in that order; the held experts' own weights stay where the seed
    drew them, exchangeable as they are). Returns the pairs the share held
    would have received unplaced and placed, by the counts the placement was
    made from."""
    import jax.numpy as jnp

    dev = jax.devices()[0]
    shares = int(cfg["router_width"]) // int(cfg["num_experts"])
    get = mods["modelcfg"].weights_getter(
        engine.params, cfg,
        lambda w: jax.device_put(w.astype(jnp.bfloat16), dev))
    placed = mods["reference"].place_experts(
        cfg, get, {k: jax.device_put(np.asarray(v), dev)
                   for k, v in batch.items()}, shares)
    params = engine.params
    mlp = params["layers"]["mlp"]
    router = jnp.stack([mlp["router"][i][:, jnp.asarray(src)]
                        for i, src in enumerate(placed)])
    engine.params = {**params, "layers": {
        **params["layers"], "mlp": {**mlp, "router": jax.device_put(
            router, mlp["router"].sharding)}}}
    return placed


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
    if dep.get("expert_placement") == "by_load":
        place_experts(jax, engine, cfg, mods, batch)
    opt = dep["ds_config"]["optimizer"]["params"]
    b1, b2 = opt.get("betas", (0.9, 0.999))
    adamw = dict(lr=float(opt["lr"]), b1=float(b1), b2=float(b2),
                 eps=float(opt.get("eps", 1e-8)),
                 weight_decay=float(opt.get("weight_decay", 0.0)))
    alpha = float(dep["load_balance_coef"])
    put = lambda w: jax.device_put(w, dev)  # noqa: E731
    on_dev = {k: put(np.asarray(v)) for k, v in batch.items()}

    def rounded(dtype):
        return modelcfg.weights_getter(
            engine.params, cfg,
            lambda w: put(w.astype(dtype).astype(jnp.bfloat16)))

    def run_reference(dtype, into: Dict, faulty: Optional[str] = None) -> Dict:
        out, _ = reference.batch_loss_and_grads(
            {**cfg, "fault": faulty} if faulty else cfg, rounded(dtype),
            on_dev, alpha,
            lambda name, layer, g: into.__setitem__((name, layer),
                                                    np.asarray(g)))
        return {k: np.asarray(v, np.float64) for k, v in out.items()}

    # the state before the step, on the host: the step donates its buffers
    for leaf in jax.tree_util.tree_leaves(engine.params):
        leaf.copy_to_host_async()
    ref_grads: Dict = {}
    want = run_reference(jnp.bfloat16, ref_grads)
    weights = np.asarray(batch["loss_weights"], np.float64)
    want["masked_targets"] = np.float64(np.sum(weights > 0))
    want["weight_sum"] = np.float64(np.sum(weights))
    theta0 = jax.device_get(engine.params)
    stand_in: Dict = {}
    in_place = fault not in (None, "unchanged")
    if fault == "fp8":
        system = run_reference(jnp.float8_e4m3fn, stand_in)
    elif in_place:
        system = run_reference(jnp.bfloat16, stand_in, fault)
    if in_place:
        system.update(masked_targets=want["masked_targets"],
                      weight_sum=want["weight_sum"])
    t_reference = time.perf_counter()
    first_loss = float(jax.block_until_ready(step(batch)))
    if not in_place:
        record = steplog.get_steplog().parts(last=1)
        system = dict(record[-1]) if record else {}
        for ours, theirs in (("bd_early_ms", "early_ms"),
                             ("bd_masked_targets", "masked_targets"),
                             ("bd_weight_sum", "weight_sum")):
            if ours in system:
                system[theirs] = system.pop(ours)

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
    routed = {n for n in by_leaf if n.split(".")[0] in ROUTED}
    worst = max(set(by_leaf) - routed, key=lambda n: by_leaf[n][0])
    worst_routed = max(routed, key=lambda n: by_leaf[n][0])
    system["grad_err"] = by_leaf[worst][0]
    system["grad_err_all"] = float(np.sqrt(total[0] / total[1]))
    system["param_change_err"] = float(np.sqrt(total[2] / total[3]))
    want["grad_err"] = want["grad_err_all"] = want["param_change_err"] = \
        np.float64(0.0)
    said = {"grad_err_worst_leaf": worst,
            "grad_err_worst_routed_leaf": [worst_routed,
                                           by_leaf[worst_routed][0]],
            "sign_differs_share": float(total[5] / sum(
                g.size for g in ref_grads.values())),
            "param_change_err_given_own_gradient":
                float(np.sqrt(total[4] / total[3])),
            "by_leaf_grad_err_change_err_sign_share": by_leaf}
    return system, want, said, first_loss, t_reference


def judge(system: Dict, want: Dict, cfg: Dict, rehearse: bool):
    """``(problems, facts)`` of a first step: :func:`compare` on the parts,
    the gradient and the update."""
    return compare(system, want, _limits(cfg, rehearse))


def _limits(cfg: Dict, rehearse: bool) -> Dict:
    check = dict(cfg["check"])
    if rehearse:
        # rehearsal.json loosens the loss's; so the others': at toy widths a
        # bf16 sum over 64 channels is a coarse thing and a leaf's gradient
        # reads 0.4-0.7 off (a state left as it was still reads 1)
        check["lb_loss_abs_tol"] = max(check["lb_loss_abs_tol"], 0.5)
        check["loss_abs_tol"] = max(check["loss_abs_tol"], 0.5)
        for name in ("mix_out_ms_rel_tol", "early_ms_rel_tol"):
            check[name] = max(check[name], 0.05)
        check["expert_pairs_abs_tol"] = max(check["expert_pairs_abs_tol"], 64)
        for name in ("grad_err_abs_tol", "grad_err_all_abs_tol",
                     "param_change_err_abs_tol"):
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
            f"TransformerConfig has {', '.join(missing)} (block-diffusion "
            f"training: a [noised ; clean] row under the block-diffusion "
            f"mask, a weighted loss over the noised half); this checkout's "
            f"has not")
    jax, devices, dev = harness.setup_jax(cell["chips"], args.rehearse)
    import deepspeed_tpu as ds

    mods = _modules(cell["config"])
    cfg = cell["config"] = at_widths(
        {**cell["config"],
         "block_length": int(cell["traffic"]["block_length"])})
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
            return make_rows(rng, traffic, cfg, rows, seq)

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
        "moe_kernel_resolved", "diffusion_block", "positions_per_token",
        "head_rows", "bd_mask_tiles", "flash_fwd_tiles",
        "flash_diag_fwd_tiles", "flash_bwd_tiles", "flash_bwd_lowerings",
        "moe_grouped_lowerings", "moe_dispatch_lowerings")}
    oc = mods["opcount"]
    if row.layer_applications != int(cfg["num_hidden_layers"]):
        problems.append(f"the step program applies {row.layer_applications} "
                        f"layers a step, the configuration has "
                        f"{cfg['num_hidden_layers']}")
    if row.diffusion_block != int(cfg["block_length"]):
        problems.append(f"the step program's blocks are "
                        f"{row.diffusion_block} long, the configuration says "
                        f"{cfg['block_length']}")
    if row.positions_per_token != 2 or row.head_rows != seq:
        problems.append(f"the step program runs {row.positions_per_token} "
                        f"positions a token and the head over "
                        f"{row.head_rows} rows: 2 and {seq}")
    tiles = row.bd_mask_tiles or {}
    kept = oc.mask_pairs(cfg, seq)
    worked = tiles.get("pairs_worked")
    # (a rehearsal's one tile a row is worked whole: nothing to hold it to)
    if not args.rehearse and (
            tiles.get("pairs_kept") != kept or worked is None
            or not worked <= PAIRS_WORKED_LIMIT * kept):
        problems.append(f"the flash kernels work {worked} pairs a head a "
                        f"row by the step program's tiles, the mask keeps "
                        f"{kept} (limit {PAIRS_WORKED_LIMIT} x)")
    if row.moe_kernel_resolved != "ragged":
        problems.append(f"the step program's grouped product is "
                        f"{row.moe_kernel_resolved!r}, not the ragged one")
    harness.say(check="train_first_step_parts_backward_update",
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
    kept_parts = steplog.get_steplog().parts(
        last=min(steps, steplog.PARTS_KEPT))
    dropped = int(sum(np.sum(r["pairs_dropped"]) for r in kept_parts)
                  + np.sum(system.get("pairs_dropped", 0)))
    if dropped:
        problems.append(f"{dropped} (position, expert) pairs did not fit "
                        f"the buffer of held pairs: the layer was not "
                        f"dropless")
    pairs_step = float(np.mean([np.sum(r["pairs_here"])
                                for r in kept_parts]))
    load = float(np.max([np.max(r["load_max_over_mean"])
                         for r in kept_parts]))
    masked = float(np.mean([r["bd_masked_targets"] for r in kept_parts]))
    last = kept_parts[-1]
    if not np.all(np.isfinite(np.asarray(last["mix_out_ms"]))):
        problems.append("non-finite mixer output in the window's last step")
    rec = steplog.get_steplog().steps()[-steps:]
    host_ms = {"put_dispatch": float(np.median(rec[:, 2] - rec[:, 1]) * 1e3),
               "commit": float(np.median(rec[:, 3] - rec[:, 2]) * 1e3),
               "wait_and_batch": float(np.median(rec[1:, 1] - rec[:-1, 3])
                                       * 1e3) if steps > 1 else None,
               "step_ms_series": np.round(step_ms, 2).tolist()}
    harness.say(window={"steps": steps, "wall_s": wall, "tokens": tokens,
                        "tokens_per_step": rows * seq,
                        "positions_per_step": 2 * rows * seq,
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
                                               for r in kept_parts],
                        "masked_targets_by_step": [
                            int(r["bd_masked_targets"]) for r in kept_parts],
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
              "bd_masked_targets_per_step": masked}
    return harness.fill_metrics(result, cell, bool(args.trace), trace,
                                values, peak)


def control(cell: Dict, args) -> Dict:
    """One of :data:`FAULTS` in the program's place, through the cell's own
    comparison: the line says what :func:`judge` made of it."""
    jax, _, _, engine, cfg, mods, tcfg, _, _ = _build(cell, args)
    seq = int(cell["traffic"]["seq_len"])
    rows = int(cell["traffic"]["rows_per_chip"]) * cell["chips"]
    batch = make_rows(np.random.default_rng(int(args.seed)), cell["traffic"],
                      cfg, rows, seq)
    system, want, said, _, _ = first_step(
        jax, engine, engine.fused_train_step, cfg, mods, batch, args.control)
    problems, facts = judge(system, want, cfg, args.rehearse)
    said.pop("by_leaf_grad_err_change_err_sign_share")
    line = {"control": args.control, "what": FAULTS[args.control],
            "seed": int(args.seed), "correct": not problems,
            "problems": [p[:300] for p in problems],
            "readings": {k: {x: f[x] for x in f if x.startswith("max_")
                             or x == "tol"} for k, f in facts.items()},
            **said}
    harness.say(**line)
    return line


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=control.__doc__)
    ap.add_argument("--workload", default="sdar_30b_train_1chip")
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
