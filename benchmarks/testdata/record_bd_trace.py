"""How ``bd_tiny.xplane.pb.gz`` and ``bd_tiny.json.gz`` beside this file
were recorded, on the chip (one TPU v5e):

    python3 benchmarks/testdata/record_bd_trace.py chiprun_out/testdata

A model of the SDAR cell's kind at its head size, its block length and its
five layers but narrow and short (hidden 256, 2 query heads over 1 key-value
head, rows of 2,048 tokens as 4,096 positions: two query tiles a half, so a
tile above the rounded diagonal is dead; 4 of 8 experts of width 128 held,
512 rows of vocabulary, the last the mask token), through the same path as
``runners/train_bd_moe.py``: two warm-up steps, then six fused steps of which
the profiler sees the first two, each on a fresh batch noised on the host.
The trace, and beside it what the readers ask the program for (the step
program's compiled text, the step-program row's facts), so that
``tests/test_sdar.py`` runs ``readers/bd.py`` on the CPU.
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
         "num_key_value_heads": 1, "intermediate_size": 512,
         "vocab_size": 512, "mask_token_id": 511, "num_experts": 4,
         "router_width": 8, "num_experts_per_tok": 2,
         "moe_intermediate_size": 128}
SEQ, STEPS, TRACED = 2048, 6, 2
NAME = "bd_tiny"


def main(out_dir: str) -> None:
    import numpy as np

    jax, _, _ = harness.setup_jax(1, False)
    import deepspeed_tpu as ds
    from benchmarks import modelcfg_sdar
    from benchmarks.runners import train_bd_moe
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.observability import steplog

    cell = harness.load_cell("sdar_30b_train_1chip")
    cfg = {**cell["config"], **SIZES}
    tcfg = modelcfg_sdar.transformer_config(cfg, max_seq_len=SEQ,
                                            param_dtype="float32")
    engine, *_ = ds.initialize(model=TransformerLM(tcfg),
                               config=dict(cfg["deployment"]["ds_config"]))
    spans = harness.Spans()
    step = spans.wrap("fused_train_step", engine.fused_train_step)
    rng = np.random.default_rng(0)

    def make_batch():
        with spans.span("make_batch"):
            return train_bd_moe.make_rows(rng, cell["traffic"], cfg, 1, SEQ)

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
                       "diffusion_block", "positions_per_token", "head_rows",
                       "bd_mask_tiles", "layer_pattern")},
                   "config": {k: cfg[k] for k in SIZES}, "seq": SEQ,
                   "remat_policy": cfg["deployment"]["remat_policy"],
                   "pairs_per_step": float(np.mean(
                       [np.sum(p["pairs_here"]) for p in parts])),
                   "masked_targets_per_step": float(np.mean(
                       [p["bd_masked_targets"] for p in parts])),
                   "traced_steps": TRACED,
                   "device": jax.devices()[0].device_kind}, f)


if __name__ == "__main__":
    main(sys.argv[1])
