"""Training cells whose state is sharded over the cell's chips (ZeRO over an
``fsdp`` mesh): ``deepspeed_tpu.initialize`` -> ``engine.fused_train_step``.

The window is ``runners/train.py``'s, clock read for clock read: a fresh
batch drawn on the host inside it, one fused step, ``block_until_ready``,
the same ``values`` keys, so that this cell's rate means what the other
training cells' means. What differs:

* what ``correct`` compares (:func:`compare_first_step`), all of it what the
  timed step program returned or left for the first batch, against
  ``reference_dense_step`` (``reference.py``'s blocks, one row at a time on
  one device) on the same bf16-rounded weights and **all the rows of the
  batch**: the loss (the forward); the gradient, read back from the first
  moment the step left (``mu / (1 - b1)``: AdamW's moments start at zero),
  by the worst leaf's ``|g - g_ref| / |g_ref|`` (the backward **and the
  reduction of the chips' gradients**: a shard that holds its own row's
  alone reads about 1.7); the parameters' change over the step against the
  reference's AdamW on the reference's gradient, ``|d - d_ref| / |d_ref|``
  over all of them (the sharded optimizer: a state left as it was reads 1);
* when: the state before and after the first step is kept on the host (the
  step donates its buffers), and the reference runs **after the window**.
  ``setup_s`` is then what a user of the program waits for (imports, the
  engine, the step's compile, two steps, and the copies to the host) and
  holds none of the yardstick's own arithmetic, and ``memory_peak_bytes``,
  read before the reference, is the program's own;
* the line: an untraced run's ``breakdown`` holds ``host_ms``, the medians
  of the program's own host ring over the window (put, dispatch, the span's
  share off its core), so that a slow run's cause is on the line itself.

``python3 -m benchmarks.runners.train_sharded --control <fault,...> --seed n``
puts each fault in the program's place and prints what the same comparison
says of it (:func:`control`): the limits' second readings come from there.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import numpy as np

from benchmarks import harness, modelcfg, opcount
from benchmarks import reference_dense_step as reference
from benchmarks.runners.train_delta import _adam_mu
from benchmarks.runners.train_hybrid import compare

#: what :func:`control` can put in the program's place
FAULTS = {
    "fp8": "the reference on weights rounded to fp8 (e4m3), the nearest "
           "precision below the bf16 the configuration states, and the "
           "AdamW step its gradient gives",
    "no_reduce": "the reference on the first row alone (what a shard holds "
                 "whose gradient was never reduced over the chips), and the "
                 "AdamW step that gradient gives",
    "half_batch": "the reference on the first half of the rows, and the "
                  "AdamW step its gradient gives",
    "unchanged": "the program's step, with the state read as it was before "
                 "it (no moment written, no parameter moved)"}


def _shards_to_host(jax, tree):
    """Every leaf's shards on the host, each chip's copies under way at
    once (``jax.device_get`` of a sharded array fetches shard after shard:
    0.7 GB/s over four chips where one chip alone gives 3.4; my chip runs,
    PR 69): ``(treedef, [(shape, dtype, [(index, part), ...]), ...])``,
    to be joined after the window (:func:`_joined`)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shards = [[s for s in leaf.addressable_shards if s.replica_id == 0]
              for leaf in leaves]
    for of_leaf in shards:
        for s in of_leaf:
            s.data.copy_to_host_async()
    return treedef, [(leaf.shape, leaf.dtype,
                      [(s.index, np.asarray(s.data)) for s in of_leaf])
                     for leaf, of_leaf in zip(leaves, shards)]


def _joined(jax, held):
    """:func:`_shards_to_host`'s parts as a tree of whole host arrays."""
    treedef, leaves = held
    whole = []
    for shape, dtype, parts in leaves:
        out = np.empty(shape, dtype)
        for index, part in parts:
            out[index] = part
        whole.append(out)
    return jax.tree_util.tree_unflatten(treedef, whole)


def keep_first_step(jax, engine, step, batch: Dict) -> Dict:
    """Run ``batch``'s step and keep on the host what the comparison needs:
    the parameters before it (the step donates them), and after it the
    parameters and AdamW's first moments; each as its shards
    (:func:`_shards_to_host`). ``copies_s``: what the copies took."""
    t0 = time.perf_counter()
    theta0 = _shards_to_host(jax, engine.params)
    t1 = time.perf_counter()
    loss = float(jax.block_until_ready(step(batch)))
    t2 = time.perf_counter()
    theta1 = _shards_to_host(jax, engine.params)
    mu = _shards_to_host(jax, _adam_mu(engine.opt_state))
    return {"batch": batch, "loss": loss, "theta0": theta0, "theta1": theta1,
            "mu": mu, "copies_s": t1 - t0 + time.perf_counter() - t2}


def whole(jax, kept: Dict) -> Dict:
    """:func:`keep_first_step`'s record with its trees joined: after the
    window, once."""
    return {**kept, **{k: _joined(jax, kept[k])
                       for k in ("theta0", "theta1", "mu")}}


def reference_of(jax, cfg: Dict, kept: Dict, dtype=None, rows=None):
    """``reference_dense_step`` on the kept initial weights rounded to
    ``dtype`` and on to bf16 (as the step computes with them; by default
    bf16 alone) and on ``rows`` of the kept batch (by default all):
    ``({"loss": ...}, {(name, layer): gradient})``, float64 and float32 on
    the host."""
    import jax.numpy as jnp

    dev = jax.devices()[0]
    dtype = jnp.bfloat16 if dtype is None else dtype
    ids = kept["batch"]["input_ids"]
    out: Dict = {}
    want = reference.batch_loss_and_grads(
        cfg, modelcfg.weights_getter(
            kept["theta0"], lambda w: jax.device_put(w, dev).astype(
                dtype).astype(jnp.bfloat16)),
        [jax.device_put(row, dev) for row in (ids if rows is None else rows)],
        lambda name, layer, g: out.__setitem__((name, layer), np.asarray(g)))
    return {k: np.asarray(v, np.float64) for k, v in want.items()}, out


def compare_first_step(jax, cfg: Dict, kept: Dict, ref,
                       fault: Optional[str] = None):
    """The reference ``ref`` (:func:`reference_of` the kept weights and
    batch) against what the step returned and left (``kept``, whole):
    ``(system, want, said)``, ``system`` and ``want`` as :func:`compare`
    takes them, ``said`` the facts by leaf. ``fault`` as :data:`FAULTS`
    names them."""
    import jax.numpy as jnp

    dev = jax.devices()[0]
    opt = cfg["deployment"]["ds_config"]["optimizer"]["params"]
    b1, b2 = opt.get("betas", (0.9, 0.999))
    adamw = dict(lr=float(opt["lr"]), b1=float(b1), b2=float(b2),
                 eps=float(opt.get("eps", 1e-8)),
                 weight_decay=float(opt.get("weight_decay", 0.0)))
    ids = kept["batch"]["input_ids"]
    put = lambda w: jax.device_put(w, dev)  # noqa: E731
    want, ref_grads = dict(ref[0]), ref[1]
    system = {"loss": np.float64(kept["loss"])}
    stand_in: Dict = {}
    if fault == "fp8":
        system, stand_in = reference_of(jax, cfg, kept, jnp.float8_e4m3fn)
    elif fault in ("no_reduce", "half_batch"):
        system, stand_in = reference_of(
            jax, cfg, kept,
            rows=ids[:1 if fault == "no_reduce" else len(ids) // 2])

    @jax.jit
    def sums(g, t1, t0, g_ref):
        d_ref = reference.adamw_first_step(g_ref, t0, **adamw)
        d_own = reference.adamw_first_step(g, t0, **adamw)
        sq = lambda x: jnp.sum(jnp.square(x.astype(jnp.float32)))  # noqa
        return jnp.stack([sq(g - g_ref), sq(g_ref), sq(t1 - t0 - d_ref),
                          sq(d_ref), sq(t1 - t0 - d_own),
                          jnp.sum(jnp.sign(g) != jnp.sign(g_ref))])

    ident = lambda w: w  # noqa: E731
    before = modelcfg.weights_getter(kept["theta0"], ident)
    after = modelcfg.weights_getter(kept["theta1"], ident)
    moment = modelcfg.weights_getter(kept["mu"], ident)
    by_leaf, total = {}, np.zeros(6)
    for (name, layer), g_ref in ref_grads.items():
        t0 = put(before(name, layer))
        if stand_in:
            g = put(stand_in[(name, layer)])
            t1 = t0 + reference.adamw_first_step(g, t0, **adamw)
        elif fault == "unchanged":
            g, t1 = jnp.zeros_like(t0), t0
        else:
            g = put(moment(name, layer)) / (1.0 - adamw["b1"])
            t1 = put(after(name, layer))
        s = np.asarray(sums(g, t1, t0, put(g_ref)), np.float64)
        total += s
        by_leaf[name if layer is None else f"{name}.{layer}"] = [
            float(np.sqrt(s[0] / s[1])), float(np.sqrt(s[2] / s[3])),
            float(s[5] / g_ref.size)]
    worst = max(by_leaf, key=lambda n: by_leaf[n][0])
    system["grad_err"] = by_leaf[worst][0]
    system["param_change_err"] = float(np.sqrt(total[2] / total[3]))
    want["grad_err"] = want["param_change_err"] = np.float64(0.0)
    said = {"grad_err_worst_leaf": worst,
            "grad_err_all": float(np.sqrt(total[0] / total[1])),
            # AdamW's first step is -lr g / (|g| + eps), each element's
            # sign: a share f of signs that differ reads 2 sqrt(f) above
            "sign_differs_share": float(total[5] / sum(
                g.size for g in ref_grads.values())),
            "param_change_err_given_own_gradient":
                float(np.sqrt(total[4] / total[3])),
            "by_leaf_grad_err_change_err_sign_share": by_leaf}
    return system, want, said


def _build(cell: Dict, args):
    """Set-up up to the engine: ``(jax, devices, dev, engine, tcfg,
    t_imported, t_engine)``."""
    jax, devices, dev = harness.setup_jax(cell["chips"], args.rehearse)
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM

    cfg = cell["config"]
    tcfg = modelcfg.transformer_config(
        cfg, max_seq_len=int(cell["traffic"]["seq_len"]),
        param_dtype="float32")
    ds_cfg = dict(cfg["deployment"]["ds_config"],
                  seed=int(args.seed) % (2 ** 31))
    t_imported = time.perf_counter()
    engine, *_ = ds.initialize(model=TransformerLM(tcfg), config=ds_cfg)
    return jax, devices, dev, engine, tcfg, t_imported, time.perf_counter()


def _host_ms(n: int) -> Dict:
    """The medians of the program's host ring over the window's ``n`` steps;
    empty where the program keeps none."""
    from deepspeed_tpu.observability import steplog

    if not n or not hasattr(steplog, "host_states"):
        return {}
    log = steplog.get_steplog()
    window = steplog.host_states(log.steps()[-n:], log.host()[-n:])
    if not window:
        return {}
    return {"train_put_ms": window["median_ms"]["put"],
            "train_dispatch_ms": window["median_ms"]["dispatch"],
            "train_span_off_cpu_share": window["span_off_cpu_share"]}


def run(cell: Dict, args) -> Dict:
    jax, devices, dev, engine, tcfg, t_imported, t_engine = _build(cell, args)
    compiles = harness.CompileCount()
    spans = harness.Spans()
    cfg = cell["config"]
    seq = int(cell["traffic"]["seq_len"])
    rows = int(cell["traffic"]["rows_per_chip"]) * cell["chips"]
    peak = None if args.rehearse else harness.load_peaks(dev["kind"])
    step = spans.wrap("fused_train_step", engine.fused_train_step)
    rng = np.random.default_rng(int(args.seed))

    def make_batch():
        with spans.span("make_batch"):
            return {"input_ids": rng.integers(
                0, tcfg.vocab_size, (rows, seq), dtype=np.int32)}

    # ---- the first batch's step, with the state around it kept on the host
    # for the comparison after the window
    kept = keep_first_step(jax, engine, step, make_batch())
    t_kept = time.perf_counter()
    # second call: same program, now with the step's own outputs as inputs
    jax.block_until_ready(step(make_batch()))
    harness.say(setup={
        "imports_and_device_s": t_imported - harness.T_PROCESS_START,
        "engine_build_s": t_engine - t_imported,
        "first_step_s": t_kept - t_engine - kept["copies_s"],
        "copies_to_host_s": kept["copies_s"],
        "second_step_s": time.perf_counter() - t_kept,
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
    memory_peak = harness.memory_peak_bytes(devices)
    losses = [float(x) for x in losses]
    steps = len(losses)
    tokens = steps * rows * seq
    tok_s_chip = tokens / wall / cell["chips"]
    mid = float(np.median(step_ms))
    slow = [(i, ms) for i, ms in enumerate(step_ms) if ms > 1.25 * mid]
    host_ms = _host_ms(steps)
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
                        "cache_hits": compiles.hits,
                        "cache_misses": compiles.misses,
                        "flops_per_token": opcount.train_flops_per_token(
                            cfg, seq)})

    # ---- correctness, outside set-up and window: the reference on the kept
    # weights and first batch against what that batch's step returned and left
    t_check = time.perf_counter()
    kept = whole(jax, kept)
    system, want, said = compare_first_step(
        jax, cfg, kept, reference_of(jax, cfg, kept))
    problems, facts = compare(system, want, cfg["check"])
    if not all(np.isfinite(losses)):
        problems.append("non-finite loss in the window")
    harness.say(check="train_first_step_loss_gradient_and_update",
                **facts, **said,
                reference_check_s=time.perf_counter() - t_check)
    device = {**dev, "count": cell["chips"], "memory_peak_bytes": memory_peak}
    result = {"correct": not problems, "attempted": steps,
              "failed": 0 if not problems else steps, "problems": problems,
              "device": device}
    values = {"train_tok_s_chip": tok_s_chip, "setup_s": setup_s,
              "compiles_in_window": in_window, "steps": steps, "seq": seq,
              "rows": rows, "chips": cell["chips"], "step_ms": step_ms}
    result = harness.fill_metrics(result, cell, bool(args.trace), trace,
                                  values, peak)
    if host_ms:
        result.setdefault("breakdown", {})["host_ms"] = host_ms
    return result


def control(cell: Dict, args) -> int:
    """Each of ``args.control`` (:data:`FAULTS`, comma-separated) in the
    program's place, through the cell's own comparison, after one step of
    one engine: a line for each says what :func:`compare` made of it.
    Returns how many came out correct (each is a failure here)."""
    jax, _, _, engine, tcfg, _, _ = _build(cell, args)
    cfg = cell["config"]
    seq = int(cell["traffic"]["seq_len"])
    rows = int(cell["traffic"]["rows_per_chip"]) * cell["chips"]
    batch = {"input_ids": np.random.default_rng(int(args.seed)).integers(
        0, tcfg.vocab_size, (rows, seq), dtype=np.int32)}
    kept = whole(jax, keep_first_step(jax, engine, engine.fused_train_step,
                                      batch))
    ref = reference_of(jax, cfg, kept)
    passed = 0
    for fault in args.control.split(","):
        system, want, said = compare_first_step(jax, cfg, kept, ref, fault)
        problems, facts = compare(system, want, cfg["check"])
        said.pop("by_leaf_grad_err_change_err_sign_share")
        harness.say(control=fault, what=FAULTS[fault], seed=int(args.seed),
                    correct=not problems, problems=problems,
                    readings={k: {x: f[x] for x in f if x.startswith("max_")
                                  or x == "tol"} for k, f in facts.items()},
                    **said)
        passed += not problems
    return passed


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=control.__doc__)
    ap.add_argument("--workload", default="mistral7b_train_zero3_4chip")
    ap.add_argument("--control", required=True,
                    help="of " + ", ".join(sorted(FAULTS)))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    unknown = set(args.control.split(",")) - set(FAULTS)
    if unknown:
        ap.error(f"no such fault: {', '.join(sorted(unknown))}")
    cell = harness.load_cell(args.workload)
    if args.rehearse:
        cell = harness.apply_rehearsal(cell)
    # a fault that comes out correct is the failure here
    return 1 if control(cell, args) else 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
