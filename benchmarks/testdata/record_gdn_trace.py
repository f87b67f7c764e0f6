"""How ``gdn_tiny.xplane.pb.gz`` and ``gdn_tiny.json.gz`` beside this file
were recorded, on the chip (one TPU v5e):

    python3 benchmarks/testdata/record_gdn_trace.py chiprun_out/testdata

A model of the Qwen3-Next cell's kind at its head sizes (delta layers at
128 / 128, the full layer at d 256), its one period and its pattern but
narrow and short (hidden 256, 2 key heads serving 4 value heads, 2 query
heads over 1 key-value head, rows of 2,048 positions, 4 of 8 experts of
width 128 held at 2 a token, 512 rows of vocabulary), through the same path
as ``runners/train_gdn_moe.py``: two warm-up steps, then six fused steps of
which the profiler sees the first two, each on a fresh batch. The trace, and
beside it what the readers ask the program for (the step program's compiled
text, the step-program row's facts and counts), so that
``tests/test_qwen3_next.py`` runs ``readers/gdn.py`` on the CPU.
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402

SIZES = {"hidden_size": 256, "num_attention_heads": 2,
         "num_key_value_heads": 1, "linear_num_key_heads": 2,
         "linear_num_value_heads": 4, "intermediate_size": 512,
         "vocab_size": 512, "num_experts": 4, "router_width": 8,
         "num_experts_per_tok": 2, "moe_intermediate_size": 128,
         "shared_expert_intermediate_size": 128}
SEQ, STEPS, TRACED = 2048, 6, 2
NAME = "gdn_tiny"


def main(out_dir: str) -> None:
    import numpy as np

    jax, _, _ = harness.setup_jax(1, False)
    import deepspeed_tpu as ds
    from benchmarks import modelcfg_qwen3_next
    from benchmarks.runners import train_gdn_moe
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.observability import steplog

    cell = harness.load_cell("qwen3_next_80b_train_1chip")
    cfg = {**cell["config"], **SIZES}
    tcfg = modelcfg_qwen3_next.transformer_config(
        cfg, max_seq_len=SEQ, param_dtype="float32")
    engine, *_ = ds.initialize(model=TransformerLM(tcfg),
                               config=dict(cfg["deployment"]["ds_config"]))
    spans = harness.Spans()
    step = spans.wrap("fused_train_step", engine.fused_train_step)
    rng = np.random.default_rng(0)

    def make_batch():
        with spans.span("make_batch"):
            return {"input_ids": rng.integers(0, tcfg.vocab_size, (1, SEQ),
                                              dtype=np.int32)}

    for _ in range(2):
        jax.block_until_ready(step(make_batch()))
    trace = harness.TraceWindow(True, NAME, 1e9)
    trace.start()
    for i in range(STEPS):
        jax.block_until_ready(step(make_batch()))
        if i + 1 == TRACED:
            trace.stop()
    os.makedirs(out_dir, exist_ok=True)
    from benchmarks.readers import program

    with open(program.xplane_path(NAME), "rb") as src, gzip.open(
            os.path.join(out_dir, NAME + ".xplane.pb.gz"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    row = steplog.programs()[-1]
    parts = steplog.get_steplog().parts(last=STEPS)
    with gzip.open(os.path.join(out_dir, NAME + ".json.gz"), "wt") as f:
        json.dump({"name": row.name, "key": row.key,
                   "hlo_text": row.hlo_text(),
                   "facts": {k: row.facts[k] for k in (
                       "delta_heads", "delta_chunks_per_step",
                       "layer_pattern", "attn_widths")},
                   "delta_rule_lowering": {
                       str(k): v for k, v in
                       row.facts["delta_rule_lowering"].items()},
                   "counted": {k: row.counted.get(k) for k in (
                       "delta_qk_rows", "delta_scan", "flash_bwd")},
                   "flash_bwd_arm": {str(k): v for k, v in
                                     row.counted["flash_bwd_arm"].items()},
                   "qk_rows_per_step": train_gdn_moe.qk_rows_per_step(row, 3),
                   "config": {k: cfg[k] for k in SIZES}, "seq": SEQ,
                   "remat_policy": cfg["deployment"]["remat_policy"],
                   "pairs_per_step": float(np.mean(
                       [np.sum(p["pairs_here"]) for p in parts])),
                   "traced_steps": TRACED,
                   "device": jax.devices()[0].device_kind}, f)


if __name__ == "__main__":
    main(sys.argv[1])
