"""FPDT fused-projection capacity probe (VERDICT r3 missing #6).

Compares the compiled peak device memory of a 1-layer training step at
growing context lengths under (a) the pre-r4 seam path — full-T q/k/v
materialized at the projection boundary, then chunked ``fpdt_attention`` —
and (b) the fused per-chunk-projection path (``fpdt_block_attention``),
then RUNS a real forward+backward at a context where the seam path's
compiled peak exceeds the chip's HBM.

Run on the real chip: ``python bench_fpdt.py``. Prints one JSON line.
"""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

import dataclasses

from deepspeed_tpu.models.transformer import TransformerConfig, TransformerLM

HBM_BYTES = 15.0e9  # v5e usable HBM (16 GB nominal)
CHUNK = 4096


def make(T, impl):
    # MHA (K == H): the seam path's full-T k/v + their cotangents cost
    # ~12 KB/token extra at this width, so its OOM point sits well below
    # the fused path's — the capacity gap this probe demonstrates
    cfg = dataclasses.replace(
        TransformerConfig(vocab_size=8192, hidden_size=2048, num_layers=1,
                          num_heads=16, num_kv_heads=16, max_seq_len=T,
                          dtype="bfloat16", param_dtype="float32",
                          remat_policy="full", loss_tiling=32),
        attention_impl=impl, fpdt_chunk=CHUNK)
    return cfg, TransformerLM(cfg)


def step_fn(model, cfg):
    def loss_fn(params, ids):
        return model.loss_fn(params, {"input_ids": ids})

    def step(params, ids):
        loss, g = jax.value_and_grad(loss_fn)(params, ids)
        # SGD keeps the probe about activations, not optimizer tiers
        params = jax.tree_util.tree_map(lambda p, gg: p - 1e-4 * gg.astype(
            p.dtype), params, g)
        return loss, params

    return jax.jit(step, donate_argnums=(0,))


def compiled_peak(T, impl):
    cfg, model = make(T, impl)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((1, T), jnp.int32)
    c = step_fn(model, cfg).lower(params, ids).compile()
    ma = c.memory_analysis()
    peak = getattr(ma, "peak_memory_in_bytes", None)
    if peak is None or peak == 0:
        peak = ma.temp_size_in_bytes + ma.argument_size_in_bytes
    return float(peak)


def _try_peak(fn, *a):
    """Compiled peak bytes, or the HBM overrun the compiler reports when the
    program cannot be placed at all (this backend hard-fails such compiles)."""
    import re
    import sys

    try:
        return fn(*a), False
    except Exception as e:  # noqa: BLE001 — compile OOM is a datapoint
        m = re.search(r"Used ([0-9.]+)G of", str(e))
        print(f"compile failed: {str(e)[:200]}", file=sys.stderr)
        return (float(m.group(1)) * 1e9 if m else float("inf")), True


def run_step(run_T: int) -> dict:
    """Compile + run two fused-path training steps at ``run_T`` (fresh
    process: a failed oversized compile can poison this backend's device
    state, so the run must not share a process with the OOM probes)."""
    cfg, model = make(run_T, "fpdt")
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, 8192, (1, run_T), dtype=np.int32))
    step = step_fn(model, cfg)
    loss, params = step(params, ids)          # compile + step 1
    float(loss)          # forced fetch: the step is done
    t0 = time.perf_counter()
    loss, params = step(params, ids)
    float(loss)
    dt = time.perf_counter() - t0
    # throughput + MFU at the max-T point (r4 verdict missing #6 asked for
    # tokens/s, not just a capacity number). FLOPs: dense 2NT + causal
    # attention 2T^2*H*hd forward; remat_policy=full re-runs the forward in
    # backward -> total ~ 4x forward
    n_params = cfg.num_params_estimate()
    fwd = 2.0 * n_params * run_T + 2.0 * run_T * run_T \
        * cfg.num_heads * cfg.head_dim
    flops = 4.0 * fwd
    return {"T": run_T, "loss": float(loss), "step_s": dt,
            "tokens_per_sec": round(run_T / dt, 1),
            "mfu": round(flops / dt / 197e12, 4)}


def main():
    import subprocess
    import sys

    if len(sys.argv) > 2 and sys.argv[1] == "--run":
        print(json.dumps(run_step(int(sys.argv[2]))))
        return
    out = {"chunk": CHUNK, "hbm_bytes": HBM_BYTES, "points": []}
    run_T = None
    for T in (131072, 176128, 217088, 258048, 290816):
        row = {"T": T}
        row["fused_peak"], row["fused_oom"] = _try_peak(compiled_peak, T, "fpdt")
        row["seam_peak"], row["seam_oom"] = _try_peak(compiled_peak_seam, T)
        print(f"T={T}: {row}", file=sys.stderr)
        out["points"].append(row)
        # run at the LARGEST fused-feasible T (r4 mistakenly ran at the
        # first seam-OOM demo point instead of the fused path's own max)
        if not row["fused_oom"] and row["fused_peak"] < HBM_BYTES:
            run_T = T
        if row["fused_peak"] > HBM_BYTES:
            break
    if run_T is not None:
        r = subprocess.run([sys.executable, __file__, "--run", str(run_T)],
                           capture_output=True, text=True, timeout=3600)
        if r.returncode == 0 and r.stdout.strip():
            out["ran"] = json.loads(r.stdout.strip().splitlines()[-1])
        else:
            out["ran"] = {"T": run_T, "error": r.stderr[-400:]}
    print(json.dumps(out))


def compiled_peak_seam(T):
    """Pre-r4 behavior: full-T projections + chunked seam attention."""
    import deepspeed_tpu.models.transformer as tfm
    from deepspeed_tpu.sequence.fpdt import fpdt_attention

    def seam_attn(q, k, v, causal=True, **kw):
        return fpdt_attention(q, k, v, causal=causal, chunk=CHUNK,
                              offload=False)

    tfm.register_attention_impl("fpdt_seam", seam_attn)
    return compiled_peak(T, "fpdt_seam")


if __name__ == "__main__":
    main()
