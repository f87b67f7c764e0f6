"""Quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one chip: train phase, then serve phase
    python chip_smoke.py --chips 4  # four chips: the ZeRO-3 sharded path only

The parent is plain stdlib and never imports JAX or ``deepspeed_tpu``: it runs
each phase as a child process (``--phase NAME``), one at a time, so exactly one
process holds the chip at any moment and every phase starts on an empty HBM.
A phase that fails, times out or did not run on a TPU makes the script exit
non-zero. Each child prints its findings as it goes; the parent's last line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

* ``train``  — ``deepspeed_tpu.initialize`` + ``engine.fused_train_step`` on
  llama3-1b widths (depth cut to fit 16 GB with fp32 master + Adam state),
  sequence 2048, bf16 compute, one fixed batch: loss finite and falling, the
  flash ``tpu_custom_call`` in the step's HLO, no recompile after step one.
* ``serve``  — ``InferenceEngineV2`` → ``ContinuousBatcher`` → ``Replica`` →
  ``ServingFrontend`` on a localhost port, driven by ``GenerateClient`` with
  concurrent unary + SSE requests on llama3-8b widths (head dim 128, depth
  cut): every request answered in full, attention on the native Pallas
  kernels, first-token logits equal to the ``decode_kernel="xla"`` twin.
* ``sharded`` (``--chips 4``) — the train config on a one-device mesh vs
  ``{"fsdp": 4}`` ZeRO-3 (losses agree), then the full 16-layer llama3-1b on
  ``{"fsdp": 4}``: finite falling loss, the four devices' bytes within 25 %,
  all-gather and reduce-scatter in the step's HLO.

``--toy`` shrinks every size so the control flow runs on the CPU in tests; a
run that is not on a TPU never passes, toy or not.
"""

import argparse
import json
import os
import re
import subprocess
import sys
import time

PHASE_TIMEOUT_S = {"train": 540, "serve": 540, "sharded": 1100}
TRAIN_LAYERS = 4          # of llama3-1b's 16: what 16 GB holds at 16 B/param
SERVE_LAYERS = 8          # of llama3-8b's 32: leaves a multi-GB KV pool
SERVE_POOL_BYTES = 4 << 30


def _say(phase, **facts):
    print(json.dumps({"phase": phase, **facts}), flush=True)


def _device_facts():
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _memory(devices):
    out = []
    for d in devices:
        s = d.memory_stats() or {}
        out.append({"bytes_in_use": s.get("bytes_in_use"),
                    "peak_bytes_in_use": s.get("peak_bytes_in_use")})
    return out


class _CompileCount:
    """Backend compiles seen by this process (jax.monitoring)."""

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def _train_config(micro, mesh=None, stage=0):
    cfg = {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": stage},
        "steps_per_print": 10 ** 9,
    }
    if mesh:
        cfg["mesh"] = mesh
    return cfg


def _train_model(toy, layers):
    from deepspeed_tpu.models import TransformerLM, get_preset

    if toy:
        cfg = get_preset("tiny", num_layers=2, max_seq_len=128,
                         attention_impl="auto")
        return TransformerLM(cfg), 128
    cfg = get_preset("llama3-1b", num_layers=layers, max_seq_len=2048,
                     attention_impl="auto")
    return TransformerLM(cfg), 2048


def _train_steps(engine, batch, steps, compiles):
    """Run ``steps`` fused steps on one batch. Returns (losses, step seconds,
    compiles after step one, HLO text of the compiled step)."""
    import jax

    losses, times = [], []
    after_first = None
    for i in range(steps):
        t0 = time.perf_counter()
        loss = float(jax.block_until_ready(engine.fused_train_step(batch)))
        times.append(time.perf_counter() - t0)
        losses.append(loss)
        if i == 0:
            after_first = compiles.n
    recompiles = compiles.n - after_first
    from deepspeed_tpu.observability import steplog

    # the executable the steps ran, compiled again from the abstract
    # arguments its row kept: same avals, so a cache answers
    return losses, times, recompiles, steplog.programs()[-1].hlo_text()


def run_train(seed=0, toy=False, layers=TRAIN_LAYERS, steps=5):
    import jax
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.utils.compile_cache import cache_counters

    counters = cache_counters()
    compiles = _CompileCount()
    dev = _device_facts()
    model, seq = _train_model(toy, layers)
    cfg = model.cfg
    engine, *_ = ds.initialize(model=model, config=_train_config(micro=1))
    rng = np.random.default_rng(seed)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (1, seq))
             .astype(np.int32)}
    losses, times, recompiles, hlo = _train_steps(engine, batch, steps,
                                                  compiles)
    flash_native = "tpu_custom_call" in hlo
    facts = {
        "device": dev, "model": "llama3-1b widths" if not toy else "tiny",
        "hidden": cfg.hidden_size, "heads": cfg.num_heads,
        "kv_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "ffn": cfg.intermediate_size, "vocab": cfg.vocab_size,
        "layers_used": cfg.num_layers, "seq": seq,
        "params_m": round(cfg.num_params_estimate() / 1e6, 1),
        "losses": losses, "step_s": [round(t, 4) for t in times],
        "recompiles_after_first_step": recompiles,
        "flash_tpu_custom_call_in_hlo": flash_native,
        "memory": _memory(jax.local_devices()[:1]),
        "compile_cache": dict(counters),
    }
    problems = []
    if not all(np.isfinite(losses)):
        problems.append("non-finite loss")
    if not losses[-1] < losses[0]:
        problems.append("loss did not fall on the repeated batch")
    if recompiles:
        problems.append(f"{recompiles} compiles after the first step")
    if dev["platform"] == "tpu" and not flash_native:
        problems.append("no flash tpu_custom_call in the step's HLO")
    facts["ok"] = not problems
    facts["problems"] = problems
    return facts


def _serve_model(toy, layers, seed):
    import jax

    from deepspeed_tpu.models import TransformerLM, get_preset

    if toy:
        cfg = get_preset("tiny", param_dtype="bfloat16")
    else:
        cfg = get_preset("llama3-8b", num_layers=layers,
                         param_dtype="bfloat16")
    model = TransformerLM(cfg)
    params = jax.jit(model.init)(jax.random.key(seed))
    return model, params


def run_serve(seed=0, toy=False, layers=SERVE_LAYERS):
    import threading

    import jax
    import numpy as np

    from deepspeed_tpu.config.config import FrontendConfig, ServingConfig
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.serving import (ContinuousBatcher, GenerateClient,
                                       Replica, ServingFrontend)
    from deepspeed_tpu.utils.compile_cache import (cache_counters,
                                                   place_compile_cache)

    place_compile_cache()
    counters = cache_counters()
    dev = _device_facts()
    model, params = _serve_model(toy, layers, seed)
    cfg = model.cfg
    bs = 16 if toy else 128
    max_len = 256 if toy else 2048
    block_bytes = cfg.num_layers * 2 * bs * cfg.num_kv_heads * cfg.head_dim * 2
    num_blocks = 64 if toy else SERVE_POOL_BYTES // block_bytes
    geom = dict(max_sequences=8, max_seq_len=max_len, block_size=bs)
    eng = InferenceEngineV2(model, params=params, num_blocks=num_blocks,
                            **geom)
    del params                       # the engine holds its own bf16 copy
    rng = np.random.default_rng(seed)
    lens = [40, 56, 72, 33] if toy else [200, 300, 384, 260]
    new = [6, 8, 5, 7] if toy else [32, 48, 64, 40]
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist() for n in lens]
    results = [None] * len(prompts)
    spans = [None] * len(prompts)

    batcher = ContinuousBatcher(eng, ServingConfig(
        prefill_chunk=32 if toy else 256, default_max_new_tokens=8))
    rep = Replica("smoke", batcher).start()
    try:
        with ServingFrontend(rep, FrontendConfig(request_timeout_s=400)) as fe:
            cli = GenerateClient(fe.url, timeout_s=400)

            def unary(i):
                t0 = time.perf_counter()
                out = cli.generate(prompts[i], max_new_tokens=new[i])
                spans[i] = (t0, time.perf_counter())
                results[i] = {"state": out["state"], "tokens": out["tokens"],
                              "ttft_ms": out["span"]["ttft_ms"]}

            def stream(i):
                t0 = time.perf_counter()
                toks, end = [], None
                for ev in cli.stream(prompts[i], max_new_tokens=new[i]):
                    if ev["event"] == "token":
                        toks.append(ev["data"]["token"])
                    elif ev["event"] == "end":
                        end = ev["data"]
                spans[i] = (t0, time.perf_counter())
                results[i] = {"state": end["state"], "tokens": end["tokens"],
                              "streamed": len(toks)}

            threads = [threading.Thread(target=stream if i == 0 else unary,
                                        args=(i,))
                       for i in range(len(prompts))]
            t_all = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t_all
    finally:
        rep.close()

    problems = []
    for i, r in enumerate(results):
        if r is None:
            problems.append(f"request {i} raised")
        elif r["state"] != "completed" or len(r["tokens"]) != new[i]:
            problems.append(f"request {i}: {r['state']}, "
                            f"{len(r['tokens'])}/{new[i]} tokens")
    if results[0] and results[0].get("streamed") != new[0]:
        problems.append("SSE stream delivered "
                        f"{results[0].get('streamed')}/{new[0]} token events")
    done = [s for s in spans if s]
    overlap = max((sum(1 for a, b in done if a < t1 and t0 < b)
                   for t0, t1 in done), default=0)
    if overlap < 2:
        problems.append("no two requests were in flight together")

    # first-token logits: the native-kernel engine vs its XLA twin, on the
    # same weights (token equality is no check on random weights)
    twin = InferenceEngineV2(model, params=eng.params, decode_kernel="xla",
                             num_blocks=max(8, 2 * max_len // bs), **geom)
    # in three puts so every kernel is compared: a fresh chunk (self flash),
    # a continuation chunk (past work-list + self) and one decode token
    probe = np.asarray(prompts[1], np.int32)
    cut = (len(probe) // 2 // bs + 1) * bs
    err, scale = {}, {}
    chunks = {"prefill": probe[:cut], "continuation": probe[cut:],
              "decode": None}
    for name, chunk in chunks.items():
        if chunk is None:
            chunk = np.asarray([int(np.argmax(b))], np.int32)
        a = np.asarray(eng.put([10_001], [chunk])[10_001], np.float32)
        b = np.asarray(twin.put([10_001], [chunk])[10_001], np.float32)
        err[name] = float(np.max(np.abs(a - b)))
        scale[name] = float(np.max(np.abs(b)))
        if not (np.all(np.isfinite(a)) and a.shape == (cfg.vocab_size,)):
            problems.append(f"{name} logits not finite [{cfg.vocab_size}]")
        if not err[name] <= 0.03 * max(scale[name], 1.0):
            problems.append(f"{name} logits differ from the XLA twin: "
                            f"max|d|={err[name]} at scale {scale[name]}")
    paths = {step: {"attention": v["attention"][0],
                    "dequant_matmul": {n: c[0] for n, c in
                                       v["dequant_matmul"].items()}}
             for step, v in eng.kernel_paths.items()}
    if dev["platform"] == "tpu":
        if eng.decode_kernel_mode != "native":
            problems.append(f"decode_kernel_mode={eng.decode_kernel_mode}: "
                            f"{eng.decode_kernel_reason}")
        for step, v in paths.items():
            if v["attention"] != "pallas-native":
                problems.append(f"{step} attention ran on {v['attention']}")
    return {
        "device": dev, "model": "llama3-8b widths" if not toy else "tiny",
        "hidden": cfg.hidden_size, "heads": cfg.num_heads,
        "kv_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "ffn": cfg.intermediate_size, "vocab": cfg.vocab_size,
        "layers_used": cfg.num_layers, "kv_pool_blocks": int(num_blocks),
        "kv_pool_gb": round(num_blocks * block_bytes / 2 ** 30, 2),
        "prompt_tokens": lens, "new_tokens": new,
        "requests": [{k: v for k, v in (r or {}).items() if k != "tokens"}
                     for r in results],
        "max_in_flight": overlap, "wall_s": round(wall, 3),
        "decode_kernel_mode": eng.decode_kernel_mode,
        "kernel_paths": paths,
        "logits_max_abs_diff_vs_xla_twin": err, "logits_scale": scale,
        "memory": _memory(jax.local_devices()[:1]),
        "compile_cache": dict(counters),
        "ok": not problems, "problems": problems,
    }


def run_sharded(seed=0, toy=False, layers=TRAIN_LAYERS, steps=4):
    """Four devices, one process: (a) one-device mesh vs {"fsdp": 4} ZeRO-3 at
    the one-chip depth, (b) the full-depth model on {"fsdp": 4} ZeRO-3."""
    import gc

    import jax
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.parallel import build_mesh

    compiles = _CompileCount()
    dev = _device_facts()
    problems = []
    if dev["count"] != 4:
        return {"device": dev, "ok": False,
                "problems": [f"needs 4 devices, found {dev['count']}"]}
    rng = np.random.default_rng(seed)

    def run(layers, mesh, stage, rows, n_steps, one_device=False):
        model, seq = _train_model(toy, layers)
        topo = build_mesh(devices=jax.devices()[:1]) if one_device else None
        engine, *_ = ds.initialize(
            model=model, config=_train_config(1, mesh, stage), mesh=topo)
        batch = {"input_ids": rows}
        losses, times, _, hlo = _train_steps(engine, batch, n_steps, compiles)
        mem = _memory(jax.local_devices())
        del engine
        gc.collect()
        return losses, times, hlo, mem

    probe_model, seq = _train_model(toy, 1)
    tokens = rng.integers(0, probe_model.cfg.vocab_size,
                          (4, seq)).astype(np.int32)
    # (a) the same row everywhere: once on one device (4 rows, at once or
    # accumulated, do not fit 16 GB at this depth) and on each of fsdp=4, so
    # loss and mean gradient are the same function of the same weights
    same = np.repeat(tokens[:1], 4, axis=0)
    one, t_one, _, _ = run(layers, None, 0, tokens[:1], steps,
                           one_device=True)
    four, t_four, _, _ = run(layers, {"fsdp": 4}, 3, same, steps)
    diffs = [abs(a - b) for a, b in zip(one, four)]
    if not all(d <= 0.02 * max(abs(a), 1.0) for d, a in zip(diffs, one)):
        problems.append(f"1-device vs fsdp=4 losses differ: {one} vs {four}")
    part_a = {
        "device": dev, "layers_compared": layers, "seq": seq,
        "losses_one_device": one, "losses_fsdp4": four,
        "loss_abs_diffs": diffs,
        "step_s_one_device": [round(t, 4) for t in t_one],
        "step_s_fsdp4": [round(t, 4) for t in t_four]}
    _say("sharded", part="a: 1 device vs fsdp=4", **part_a,
         problems=list(problems))
    # (b) full depth, does not fit one chip
    full_layers = 4 if toy else 16
    full, t_full, hlo, mem = run(full_layers, {"fsdp": 4}, 3, tokens, steps)
    if not all(np.isfinite(full)) or not full[-1] < full[0]:
        problems.append(f"full-depth losses not finite and falling: {full}")
    used = [m["bytes_in_use"] for m in mem]
    if dev["platform"] == "tpu" and min(used) < 0.75 * max(used):
        problems.append(f"devices' bytes_in_use not within 25%: {used}")
    # the v5e compiler lowers the gradient reduce-scatter of a 2x2 to
    # collective-permute rings / all-to-all: count every kind, and ask for
    # the gather plus at least one reducing collective
    kinds = ("all-gather", "reduce-scatter", "all-reduce", "all-to-all",
             "collective-permute")
    collectives = {c: len(re.findall(rf" {c}(?:-start)?\(", hlo))
                   for c in kinds}
    if dev["platform"] == "tpu" and not (
            collectives["all-gather"] and sum(collectives.values())
            > collectives["all-gather"]):
        problems.append(f"collectives missing from the step's HLO: "
                        f"{collectives}")
    return {
        **part_a, "full_depth_layers": full_layers,
        "losses_full_depth": full,
        "step_s_full_depth": [round(t, 4) for t in t_full],
        "memory_full_depth": mem, "collectives_in_hlo": collectives,
        "ok": not problems, "problems": problems,
    }


PHASES = {"train": run_train, "serve": run_serve, "sharded": run_sharded}


def child_main(args):
    """One phase in this process. The last stdout line is the phase's JSON
    record; exit 0 only when it ran on a TPU and every check held."""
    dev = _device_facts()
    if dev["platform"] != "tpu":
        # a CPU run is never a pass, so do not spend minutes proving it
        _say(args.phase, ok=False, device=dev,
             problems=[f"JAX found no TPU (platform {dev['platform']!r})"])
        return 3
    facts = PHASES[args.phase](seed=args.seed, toy=args.toy)
    _say(args.phase, **facts)
    return 0 if facts["ok"] else 1


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--toy", action="store_true",
                    help="tiny sizes (CPU rehearsal and tests)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help="(internal) run this one phase in this process")
    args = ap.parse_args(argv)
    if args.phase:
        return child_main(args)

    phases = ["sharded"] if args.chips == 4 else ["train", "serve"]
    device = None
    for phase in phases:
        cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
               "--seed", str(args.seed)] + (["--toy"] if args.toy else [])
        t0 = time.monotonic()
        try:
            proc = subprocess.run(cmd, stdout=subprocess.PIPE, text=True,
                                  timeout=PHASE_TIMEOUT_S[phase])
        except subprocess.TimeoutExpired as e:
            sys.stdout.write(e.stdout or "")
            print(f"chip_smoke: phase {phase} timed out after "
                  f"{PHASE_TIMEOUT_S[phase]} s", file=sys.stderr)
            return 1
        sys.stdout.write(proc.stdout)
        sys.stdout.flush()
        lines = proc.stdout.strip().splitlines()
        try:
            record = json.loads(lines[-1])
        except (IndexError, ValueError):
            record = {}
        if proc.returncode != 0 or not record.get("ok") \
                or record.get("phase") != phase:
            print(f"chip_smoke: phase {phase} failed (exit "
                  f"{proc.returncode}) after {time.monotonic() - t0:.0f} s",
                  file=sys.stderr)
            return 1
        dev = record["device"]
        if dev["platform"] != "tpu" or dev["count"] != args.chips:
            print(f"chip_smoke: phase {phase} ran on {dev}, wanted "
                  f"{args.chips} tpu device(s)", file=sys.stderr)
            return 1
        device = dev
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
