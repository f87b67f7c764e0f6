"""How ``program_tiny.xplane.pb.gz`` and ``program_tiny.json.gz`` beside this file
were recorded, on the chip (one TPU v5e):

    python3 benchmarks/testdata/record_program_trace.py chiprun_out/testdata

A two-layer model at Mistral's head size (so the flash kernels run) but
narrow and short, through the same path as ``runners/train.py``: two warm-up
steps, then six fused steps of which the profiler sees the first two. The
trace, and beside it what ``readers/program.py`` asks the program for (the
step program's compiled text and memory analysis, the step record, the pause
ring), so that ``tests/test_program_readers.py`` runs the readers on the CPU.
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

from benchmarks import harness, modelcfg  # noqa: E402

SIZES = {"hidden_size": 256, "intermediate_size": 512,
         "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 128,
         "vocab_size": 512, "num_hidden_layers": 2}
SEQ, STEPS, TRACED = 512, 6, 2


def main(out_dir: str) -> None:
    import numpy as np

    jax, _, _ = harness.setup_jax(1, False)
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.observability import steplog

    cell = harness.load_cell("mistral7b_train_1chip")
    cfg = {**cell["config"], **SIZES}
    tcfg = modelcfg.transformer_config(cfg, max_seq_len=SEQ,
                                       param_dtype="float32")
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
    trace = harness.TraceWindow(True, "program_tiny", 1e9)
    trace.start()
    for i in range(STEPS):
        jax.block_until_ready(step(make_batch()))
        if i + 1 == TRACED:
            trace.stop()
    os.makedirs(out_dir, exist_ok=True)
    from benchmarks.readers import program

    with open(program.xplane_path("program_tiny"), "rb") as src, gzip.open(
            os.path.join(out_dir, "program_tiny.xplane.pb.gz"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    row = steplog.programs()[-1]
    log = steplog.get_steplog()
    with gzip.open(os.path.join(out_dir, "program_tiny.json.gz"), "wt") as f:
        json.dump({"name": row.name, "key": row.key,
                   "memory": row.memory_analysis(),
                   "hlo_text": row.hlo_text(),
                   "steps": log.steps()[-STEPS:].tolist(),
                   "pauses": log.pauses().tolist(),
                   "traced_steps": TRACED,
                   "device": jax.devices()[0].device_kind}, f)


if __name__ == "__main__":
    main(sys.argv[1])
