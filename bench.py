"""Benchmark harness — prints ONE JSON line with the headline metric.

Metric: training tokens/sec/chip on the flagship decoder LM (single-chip config),
with MFU derived from the model FLOPs estimate. ``vs_baseline`` is measured MFU over
the 45% north-star target (BASELINE.md: Llama-3-8B ZeRO-3 ≥45% MFU on v5e-256;
single-chip proxy here until multi-chip hardware is available).
"""

import json
import sys
import time

import numpy as np


# bf16 peak FLOP/s per chip by TPU generation (Google Cloud TPU docs)
PEAK_TFLOPS = {
    "v4": 275e12, "v5 lite": 197e12, "v5e": 197e12, "v5p": 459e12,
    "v6 lite": 918e12, "v6e": 918e12,
}


def detect_peak(device) -> float:
    kind = device.device_kind.lower()
    for key, val in PEAK_TFLOPS.items():
        if key in kind:
            return val
    raise ValueError(f"no peak FLOP/s on record for device_kind "
                     f"{device.device_kind!r}: add it to PEAK_TFLOPS with its "
                     f"source instead of guessing")


def require_chip_or_asked_cpu(dev) -> bool:
    """True on a TPU. On the CPU, False — but only when ``JAX_PLATFORMS=cpu``
    asked for it (the toy dev run, whose JSON then says ``"device": "cpu"``
    and carries no device-metric field); a run that merely FOUND no chip
    exits non-zero."""
    import os

    if dev.platform == "tpu":
        return True
    if dev.platform == "cpu" and os.environ.get("JAX_PLATFORMS") == "cpu":
        return False
    sys.exit(f"bench: JAX found no TPU (platform {dev.platform!r}); set "
             f"JAX_PLATFORMS=cpu to ask for the toy CPU run")


def main() -> None:
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM, TransformerConfig

    dev = jax.devices()[0]
    on_tpu = require_chip_or_asked_cpu(dev)

    if on_tpu:
        # flagship single-chip config tuned for v5e HBM/MXU: d=128 heads (MXU
        # lane-width), dots_and_attn_saveable remat (never recompute the
        # VPU-bound attention kernel), params cast once per step, ga=4 so the
        # in-jit microbatch scan amortizes the optimizer + cast over 4x tokens.
        # seq 8192 = Llama-3's native context (the BASELINE.md 8B north-star);
        # measured MFU ladder: 0.543 (b4 s2048 ga1) -> 0.600 (ga4) -> 0.634
        # (s8192 b1 ga4) -> 0.646 (ga8); seq 16384 compile-OOMs under this
        # remat policy
        cfg = TransformerConfig(
            vocab_size=32000, hidden_size=1536, num_layers=16, num_heads=12,
            num_kv_heads=6, max_seq_len=8192, arch="llama",
            remat_policy="dots_and_attn_saveable")
        batch, ga, seq, steps, warmup = 1, 8, 8192, 8, 2
    else:  # dev fallback so the harness is runnable anywhere
        cfg = TransformerConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                                num_heads=4, max_seq_len=256, arch="llama")
        batch, ga, seq, steps, warmup = 2, 1, 128, 3, 1

    model = TransformerLM(cfg)
    config = {
        "train_micro_batch_size_per_gpu": batch,
        "gradient_accumulation_steps": ga,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 0},
        "steps_per_print": 10 ** 9,
    }
    engine, *_ = ds.initialize(model=model, config=config)

    rng = np.random.default_rng(0)

    def make_batch():
        return {"input_ids": rng.integers(0, cfg.vocab_size,
                                          (batch * ga, seq)).astype(np.int32)}

    for _ in range(warmup):
        float(engine.fused_train_step(make_batch()))

    if not on_tpu:
        # asked-for CPU run: proves the harness's control flow, names its
        # device first and reports nothing under a device-metric name
        losses = [float(engine.fused_train_step(make_batch()))
                  for _ in range(steps)]
        print(json.dumps({"device": "cpu", "metric": "train_harness_dev_run",
                          "value": None, "extra": {
                              "loss": round(losses[-1], 4), "batch": batch,
                              "ga": ga, "seq": seq, "steps": steps}}))
        return

    peak = detect_peak(dev)
    n_params = cfg.num_params_estimate()
    # FLOPs/token: 6*N for the dense path + attention score/value term
    attn_flops_per_token = 12 * cfg.num_layers * seq * cfg.hidden_size
    flops_per_token = 6 * n_params + attn_flops_per_token
    tokens_per_step = batch * ga * seq

    def timed_run():
        t0 = time.perf_counter()
        losses = [engine.fused_train_step(make_batch()) for _ in range(steps)]
        vals = [float(l) for l in losses]  # materialize: see warmup note
        dt = time.perf_counter() - t0
        tps = tokens_per_step * steps / dt
        return tps, tps * flops_per_token / peak, vals[-1]

    # One timing window is fragile: a transient host-load dip silently halves
    # the reported number (round 3 lost 45% to exactly this). Take >=3
    # windows, report the MEDIAN, and keep sampling while the inter-window
    # spread exceeds 15% — a glitched window then shows up in `windows`/
    # `spread` instead of becoming the headline.
    windows, last_loss = [], 0.0
    for attempt in range(9):
        tps_i, mfu_i, last_loss = timed_run()
        if mfu_i > 1.0:      # physically impossible: clock/runtime glitch
            continue
        windows.append(tps_i)
        if len(windows) >= 3:
            med = float(np.median(windows[-5:]))
            spread = (max(windows[-5:]) - min(windows[-5:])) / med
            if spread <= 0.15 or len(windows) >= 7:
                break
    if not windows:
        raise RuntimeError("benchmark clock/runtime glitch: measured MFU "
                           "> 1.0 on every attempt")
    recent = windows[-5:]
    tokens_per_sec = float(np.median(recent))
    spread = (max(recent) - min(recent)) / tokens_per_sec
    mfu = tokens_per_sec * flops_per_token / peak

    result = {
        "metric": "train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.45, 4),
        "extra": {
            "mfu": round(mfu, 4),
            "model_params_m": round(n_params / 1e6, 1),
            "loss": round(last_loss, 4),
            "device": getattr(dev, "device_kind", str(dev)),
            "batch": batch, "ga": ga, "seq": seq, "steps": steps,
            "windows": [round(w, 1) for w in windows],
            "spread": round(spread, 4),
        },
    }

    import os
    import subprocess

    # ZeRO++ quantized collectives: comm-bytes + step-time vs the bf16
    # explicit-collective baseline (the DCN-volume lever for multi-slice
    # scaling). Runs on a forced 8-virtual-device CPU mesh — the byte
    # counters are exact there and a single chip cannot host an fsdp
    # axis; step-time is indicative, the volume reduction is the metric.
    # DSTPU_BENCH_ZPP=0 skips. Appends its own bench_zero_pp ledger entry.
    # The child is pinned to the CPU, so it never competes for the chip this
    # process holds; when it fails, the run fails.
    if os.environ.get("DSTPU_BENCH_ZPP", "1") == "1":
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": os.environ.get("XLA_FLAGS", "")
               + " --xla_force_host_platform_device_count=8",
               "DSTPU_BENCH_ZPP": "0"}
        r = subprocess.run([sys.executable, __file__, "--zero-pp"],
                           capture_output=True, text=True, timeout=1800,
                           env=env, check=True)
        result["extra"]["zero_pp"] = json.loads(
            r.stdout.strip().splitlines()[-1])

    print(json.dumps(result))
    _ledger(result, "bench")


def bench_scaling():
    """The ``--scaling`` mode: measured multi-chip scaling curves.

    Parent process re-execs itself onto the forced-8-virtual-device CPU mesh
    (the ``--zero-pp`` subprocess trick — a single chip cannot host an fsdp
    axis, and the byte counters are exact there); the child runs the sweep
    (world {1,2,4,8} × mesh shape {dp, fsdp, fsdp_qz, tp, pp×fsdp×tp,
    dp×sp, dp×ep×sp}), prints the curves as one JSON line, and appends a
    ``bench_scaling`` ledger entry that ``tools/bench_trend.py`` gates and
    the mesh cost model calibrates from. Set ``DSTPU_DRYRUN_TPU=1`` to run
    on real devices instead (same sweep, real ICI numbers)."""
    import os

    if (os.environ.get("DSTPU_SCALING_CHILD") != "1"
            and os.environ.get("DSTPU_DRYRUN_TPU") != "1"):
        import subprocess

        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": os.environ.get("XLA_FLAGS", "")
               + " --xla_force_host_platform_device_count=8",
               "DSTPU_SCALING_CHILD": "1"}
        r = subprocess.run([sys.executable, __file__, "--scaling"], env=env,
                           timeout=3600)
        return r.returncode
    import jax

    if os.environ.get("DSTPU_DRYRUN_TPU") != "1":
        jax.config.update("jax_platforms", "cpu")

    from deepspeed_tpu.autotuning.scaling import run_sweep

    res = run_sweep()
    print(json.dumps(res))
    _ledger(res, "bench_scaling")
    return 0 if any(res["curves"].values()) else 1


def bench_zero_pp():
    """The ``zero_pp`` bench section: baseline-vs-quantized comm bytes and
    step time through ``tools/comm_drill.measure_pair`` (qwZ int4 weight
    all-gather + hpZ slice-local secondary + qgZ int8 grad reduce-scatter
    vs the dense explicit bf16-collective region)."""
    import os

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    from comm_drill import measure_pair

    res = measure_pair(steps=6, timing=True)
    return {"metric": "zero_pp_comm_reduction", **res}


def bench_ep_sweep():
    """The ``--ep-sweep`` mode: expert-parallel MoE decode throughput sweep
    (expert count × world size × grouped kernel) through the packed-paged
    serving engine. Parent re-execs onto the forced-8-virtual-device CPU
    mesh (the ``--scaling`` trick); the child measures decode tokens/s for
    each (E, ep, kernel) cell — ``ragged`` = ``lax.ragged_dot`` dropless
    grouped GEMM, ``padded`` = the one-hot einsum reference — plus the
    ragged/padded speedup and the per-expert load ``balance`` (mean/max ∈
    (0, 1], 1.0 = perfectly even) from the AutoEP tracker, prints ONE JSON
    line, and appends a ``bench_moe`` ledger entry that
    ``tools/bench_trend.py`` gates."""
    import os

    if os.environ.get("DSTPU_EP_CHILD") != "1":
        import subprocess

        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": os.environ.get("XLA_FLAGS", "")
               + " --xla_force_host_platform_device_count=8",
               "DSTPU_EP_CHILD": "1"}
        r = subprocess.run([sys.executable, __file__, "--ep-sweep"], env=env,
                           timeout=3600)
        return r.returncode

    import jax

    jax.config.update("jax_platforms", "cpu")

    from deepspeed_tpu.config.config import ServingConfig
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import TransformerLM, get_preset
    from deepspeed_tpu.observability.registry import MetricsRegistry
    from deepspeed_tpu.serving import ContinuousBatcher

    # FFN wide enough that the padded reference's E-fold redundant FLOPs
    # dominate dispatch overhead, and a decode batch deep enough that the
    # grouped GEMM sees real row counts — the regime the dropless kernel
    # targets (a 8-seq batch at top_k=2 is only 16 rows/call)
    n_req, n_new, ffn, n_seq = 32, 32, 1024, 32
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 250, 24).tolist() for _ in range(n_req)]
    res = {"metric": "moe_decode_tokens_per_sec", "moe": {},
           "config": {"preset": "tiny", "top_k": 2, "requests": n_req,
                      "new_tokens": n_new, "intermediate_size": ffn,
                      "world": len(jax.devices())}}

    def one_pass(b):
        uids = [b.submit(p) for p in prompts]
        t0 = time.perf_counter()
        b.pump(max_steps=1200)
        dt = time.perf_counter() - t0
        toks = sum(len(b.manager.done[u].generated) for u in uids
                   if u in b.manager.done)
        for u in uids:
            b.manager.resolve(u)
        return toks, dt

    def run_cell(E, ep):
        # build BOTH kernels' engines up front and interleave the timed
        # passes (R,P,R,P,...) so slow machine-load drift cancels out of
        # the ragged/padded ratio instead of landing on whichever kernel
        # happened to run second
        bs, best = {}, {}
        for kernel in ("ragged", "padded"):
            eng = InferenceEngineV2(
                TransformerLM(get_preset("tiny", num_experts=E, top_k=2,
                                         intermediate_size=ffn,
                                         moe_dispatch="grouped")),
                max_sequences=n_seq, max_seq_len=128, block_size=16,
                num_blocks=8 * n_seq,
                mesh={"ep": ep, "dp": len(jax.devices()) // ep} if ep > 1
                else None,
                moe_kernel=kernel)
            reg = MetricsRegistry()
            eng.enable_metrics(registry=reg)
            bs[kernel] = ContinuousBatcher(eng, ServingConfig(
                prefill_chunk=32, default_max_new_tokens=n_new))
            one_pass(bs[kernel])  # compile warmup
        for _ in range(3):  # best-of-3, interleaved
            for kernel, b in bs.items():
                toks, dt = one_pass(b)
                if toks / dt > best.get(kernel, (0.0, 0.0))[0]:
                    best[kernel] = (toks / dt, dt)
        cells = {}
        for kernel, b in bs.items():
            eng = b.engine
            counts = eng._moe_tracker.snapshot() \
                if eng._moe_tracker is not None else None
            bal = (float(counts.mean() / counts.max())
                   if counts is not None and counts.max() > 0 else 1.0)
            cells[kernel] = {"tokens_per_sec": round(best[kernel][0], 2),
                             "decode_s": round(best[kernel][1], 4),
                             "kernel": eng.moe_kernel,
                             "balance": round(bal, 4)}
        return cells

    for E in (4, 8):
        for ep in (1, E):  # ep must divide the expert count
            cells = run_cell(E, ep)
            cells["ragged"]["ragged_speedup"] = round(
                cells["ragged"]["tokens_per_sec"]
                / max(cells["padded"]["tokens_per_sec"], 1e-9), 3)
            for k, cell in cells.items():
                res["moe"][f"E{E}-ep{ep}-{k}"] = cell

    print(json.dumps(res))
    _ledger(res, "bench_moe")
    return 0


def _ledger(result, bench):
    """Append to the perf-trend ledger (tools/bench_ledger.jsonl) —
    best-effort; the ledger must never sink the headline."""
    import os

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    try:
        from bench_ledger import append_ledger

        append_ledger(result, bench)
    except Exception:
        pass


def bench_offload(ds, TransformerLM, TransformerConfig, steps: int = 5):
    """ZeRO-Offload step time, synchronous vs ZenFlow overlap_step."""
    rng = np.random.default_rng(0)
    times = {}
    for mode in ("sync", "overlap"):
        cfg = TransformerConfig(vocab_size=32000, hidden_size=512,
                                num_layers=4, num_heads=8, max_seq_len=1024,
                                arch="llama")
        zo = {"stage": 2, "offload_optimizer": {"device": "cpu"}}
        if mode == "overlap":
            zo["zenflow"] = {"overlap_step": True}
        eng, *_ = ds.initialize(model=TransformerLM(cfg), config={
            "train_micro_batch_size_per_gpu": 4,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
            "zero_optimization": zo, "steps_per_print": 10 ** 9})
        batch = {"input_ids": rng.integers(
            0, cfg.vocab_size, (4, 1024)).astype(np.int32)}

        def one_step():
            loss = eng.forward(batch)
            eng.backward(loss)
            eng.step()
            return loss

        one_step(), one_step()                     # compile + fill pipeline
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = one_step()
        float(loss)                                # drain async work
        times[mode] = (time.perf_counter() - t0) / steps
    # isolate the Adam-stall itself (the cost ZenFlow exists to hide):
    # run the SAME csrc cpu_adam kernel on a same-sized flat shard.
    # stall_hidden_fraction reports how much of the pure host-Adam wall
    # time the overlap removed from the step.
    from deepspeed_tpu.offload.cpu_adam import DeepSpeedCPUAdam

    n = int(cfg.num_params_estimate())
    adam = DeepSpeedCPUAdam(lr=1e-4)
    flat = np.zeros(n, np.float32)
    g = rng.normal(size=n).astype(np.float32)
    m1 = np.zeros(n, np.float32)
    m2 = np.zeros(n, np.float32)
    adam.step(flat, g, m1, m2)                     # warm the omp pool
    t0 = time.perf_counter()
    for _ in range(steps):
        adam.step(flat, g, m1, m2)
    host_adam_ms = (time.perf_counter() - t0) / steps * 1e3
    saved_ms = (times["sync"] - times["overlap"]) * 1e3
    return {
        "sync_step_ms": round(times["sync"] * 1e3, 1),
        "overlap_step_ms": round(times["overlap"] * 1e3, 1),
        # fraction of the WHOLE synchronous step saved by the overlap
        "step_time_reduction": round(
            1.0 - times["overlap"] / times["sync"], 3),
        "host_adam_ms": round(host_adam_ms, 1),
        "stall_hidden_fraction": round(
            max(0.0, min(saved_ms / host_adam_ms, 1.0)), 3)
        if host_adam_ms > 0 else None,
        "model_params_m": round(cfg.num_params_estimate() / 1e6, 1),
    }


if __name__ == "__main__":
    if "--scaling" in sys.argv:
        sys.exit(bench_scaling())
    elif "--ep-sweep" in sys.argv:
        sys.exit(bench_ep_sweep())
    elif "--zero-pp" in sys.argv:
        import json as _json

        _res = bench_zero_pp()
        print(_json.dumps(_res))
        _ledger(_res, "bench_zero_pp")
    elif "--offload" in sys.argv:
        import json as _json

        import numpy as np  # noqa: F811 — standalone entry

        import deepspeed_tpu as ds
        from deepspeed_tpu.models import TransformerConfig, TransformerLM

        print(_json.dumps(bench_offload(ds, TransformerLM,
                                        TransformerConfig)))
    else:
        main()
