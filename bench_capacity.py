"""ZeRO-Infinity capacity probe — largest model that fine-tunes on ONE chip.

BASELINE.md config #3 ("Llama-3-70B ZeRO-Infinity fits and fine-tunes on
v5e-8; max params/chip tracked") needs a measured per-chip datapoint:
binary-search model size with ZeRO-2 + NVMe-offloaded optimizer state
(fp32 masters + Adam moments live in swap files via ``csrc/aio``; the chip
holds bf16 params, grads, and remat'd activations). Each candidate runs in
a SUBPROCESS so an HBM OOM kills only the trial.

The offload data path runs with ``offload.aio.autotune`` (cached
``aio_bench`` sweep per swap device) and the depth-k read/Adam/write
pipeline — the PR 10 overlapped path, NOT the serial path the original
0.81 B/chip figure was measured on; the aio knobs ride along in the result
so a ledger entry says which data path produced it.

Standalone and opt-in (minutes of runtime): prints one JSON line and
appends a ``bench_capacity`` ledger entry keyed per device kind
(``by_device``) — the dev CPU harness and real chips are separate trend
series. ``--ladder dev`` runs the CPU-feasible rung set; ``--ladder full``
(default) is the TPU ladder.
"""

import argparse
import json
import subprocess
import sys
import time

#: the depth-k pipeline + self-tuned IO shape every trial runs with
AIO_CONFIG = {"autotune": True, "prefetch_depth": 2, "upload_overlap": True}

CHILD = r"""
import json, sys, time
import numpy as np
import jax
import deepspeed_tpu as ds
from deepspeed_tpu.models import TransformerLM, TransformerConfig

hidden, layers = int(sys.argv[1]), int(sys.argv[2])
cfg = TransformerConfig(vocab_size=32000, hidden_size=hidden,
                        num_layers=layers, num_heads=hidden // 128,
                        num_kv_heads=max(1, hidden // 256),
                        max_seq_len=1024, arch="llama",
                        remat_policy="full")
model = TransformerLM(cfg)
engine, *_ = ds.initialize(model=model, config={
    "train_micro_batch_size_per_gpu": 1,
    "optimizer": {"type": "adamw", "params": {"lr": 1e-5}},
    "zero_optimization": {
        "stage": 2,
        "offload_optimizer": {"device": "nvme",
                              "nvme_path": "/tmp/dstpu_capacity_swap"},
    },
    # the closed tuning loop: the first trial sweeps the swap disk, every
    # later trial (and process) adopts the cached best threads x chunk_mb;
    # prefetch_depth k = the PR 10 read/Adam/write/upload pipeline
    "offload": {"aio": %AIO%},
    "steps_per_print": 10 ** 9,
})
rng = np.random.default_rng(0)
batch = {"input_ids": rng.integers(0, cfg.vocab_size, (1, 1024))
         .astype(np.int32)}

def one_step():
    loss = engine.forward(batch)
    engine.backward(loss)
    engine.step()
    return float(loss)

print("compiling + first step...", file=sys.stderr, flush=True)
l0 = one_step()                      # compile + first step
print(f"first step done loss={l0}", file=sys.stderr, flush=True)
t0 = time.perf_counter()
l1 = one_step()
dt = time.perf_counter() - t0
assert np.isfinite(l1), l1
# offload data-path health for the steady-state step: measured swap
# bandwidth (native per-direction busy-window stats) + how much of the
# host Adam loop sat blocked on IO (the overlap figure of merit)
rep = engine.offload_report()
sw = rep.get("swapper", {})
dev = jax.devices()[0]
print(json.dumps({"params_b": cfg.num_params_estimate() / 1e9,
                  "step_s": round(dt, 2), "loss0": round(l0, 3),
                  "loss1": round(l1, 3),
                  "device": getattr(dev, "device_kind", dev.platform),
                  "swap_read_MBps": sw.get("read_MBps", 0.0),
                  "swap_write_MBps": sw.get("write_MBps", 0.0),
                  "swap_threads": sw.get("threads"),
                  "swap_chunk_mb": sw.get("chunk_mb"),
                  "pipeline_stall_fraction":
                      rep.get("pipeline_stall_fraction", -1.0),
                  "adam_ms": rep.get("last_adam_ms"),
                  "upload_ms": rep.get("last_upload_ms")}))
"""

#: (hidden, layers) rungs with rising param counts; stop at first failure
LADDERS = {
    # TPU ladder: the 0.81 B/chip figure came from its first rungs
    "full": [(2048, 16), (2560, 20), (3072, 24), (3584, 28), (4096, 32),
             (4608, 36)],
    # CPU dev-harness ladder: same data path (NVMe swap, autotuned AIO,
    # depth-k pipeline), host-RAM-sized rungs so a restatement is minutes
    "dev": [(512, 4), (768, 6), (1024, 8)],
}


def try_size(hidden: int, layers: int, timeout: int = 2700):
    """One candidate in a subprocess (an HBM OOM kills only the trial)."""
    child = CHILD.replace("%AIO%", repr(AIO_CONFIG))  # Python literal, not JSON
    with open(f"/tmp/capacity_trial_{hidden}x{layers}.log", "w") as logf:
        try:
            p = subprocess.run([sys.executable, "-c", child, str(hidden),
                                str(layers)], stdout=subprocess.PIPE,
                               stderr=logf, text=True, timeout=timeout,
                               cwd="/root/repo")
        except subprocess.TimeoutExpired:
            return {"error": f"timeout after {timeout}s"}
    for line in reversed(p.stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except Exception:
            continue
    return {"error": "no output (see trial log)"}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ladder", choices=sorted(LADDERS), default="full",
                    help="rung set: 'full' (TPU-scale) or 'dev' (CPU "
                         "harness restatement)")
    ap.add_argument("--timeout", type=int, default=2700,
                    help="per-rung subprocess cap (seconds)")
    args = ap.parse_args(argv)
    results = []
    best = None
    for hidden, layers in LADDERS[args.ladder]:
        t0 = time.time()
        r = try_size(hidden, layers, timeout=args.timeout)
        r.update({"hidden": hidden, "layers": layers,
                  "wall_s": round(time.time() - t0, 1)})
        results.append(r)
        print(json.dumps(r), file=sys.stderr)
        if "error" in r:
            break
        best = r
    kind = (best or {}).get("device") or next(
        (r.get("device") for r in results if r.get("device")), "unknown")
    result = {"metric": "zero_infinity_capacity_per_chip",
              "ladder": args.ladder, "device": kind, "aio": AIO_CONFIG,
              "best": best, "trials": results,
              # per-(device kind, ladder) trend series (bench_trend.py
              # by_device.*.*.params_b): dev-harness and TPU restatements
              # — and the dev ladder vs the full ladder on one device —
              # have different achievable maxima and must never be
              # compared against each other
              "by_device": ({kind: {args.ladder: {
                  "params_b": best["params_b"],
                  "step_s": best["step_s"]}}} if best else {})}
    print(json.dumps(result))
    try:  # perf-trend ledger (best-effort; never sinks the bench)
        from bench import _ledger

        _ledger(result, "bench_capacity")
    except Exception:
        pass


if __name__ == "__main__":
    main()
