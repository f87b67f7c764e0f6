"""The lowering registry (``ops/lowerings.py``) and the seam it makes: the
pickers report to it, the engine reads it round a step program's first call,
and neither the engine nor the step-program table names a kernel."""

import os
import subprocess
import sys

from deepspeed_tpu.ops import lowerings

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_a_count_is_in_since_when_made_after_the_snapshot():
    lowerings.count("site_a", "pallas", 3)          # before: not in it
    snap = lowerings.snapshot()
    assert lowerings.since(snap) == {}              # nothing counted: absent
    lowerings.count("site_a", "pallas")
    lowerings.count("site_a", "pallas", 2)
    lowerings.count("site_b", "xla")
    # an answer that counted nothing ("xla" of site_a) is left out
    assert lowerings.since(snap) == {"site_a": {"pallas": 3},
                                     "site_b": {"xla": 1}}
    assert lowerings.since(snap) == lowerings.since(snap)   # reading is free


def test_snapshots_nest():
    outer = lowerings.snapshot()
    lowerings.count("site_n", "fused")
    inner = lowerings.snapshot()
    lowerings.count("site_n", "split")
    lowerings.count("site_n", "fused")
    assert lowerings.since(inner) == {"site_n": {"split": 1, "fused": 1}}
    assert lowerings.since(outer) == {"site_n": {"split": 1, "fused": 2}}


def test_a_note_is_the_newest_written_since():
    lowerings.note("site_t", {"masked": 1})
    snap = lowerings.snapshot()
    assert "site_t" not in lowerings.since(snap)
    lowerings.note("site_t", {"masked": 2})
    lowerings.note("site_t", {"masked": 3})
    assert lowerings.since(snap) == {"site_t": {"masked": 3}}


def test_notes_by_a_key_are_the_newest_under_each_written_since():
    lowerings.note("site_k", {"live": 1}, by=512)
    snap = lowerings.snapshot()
    assert "site_k" not in lowerings.since(snap)
    lowerings.note("site_k", {"live": 2}, by="causal")
    lowerings.note("site_k", {"live": 3}, by=1024)
    lowerings.note("site_k", {"live": 4}, by=1024)
    assert lowerings.since(snap) == {
        "site_k": {"causal": {"live": 2}, 1024: {"live": 4}}}


def test_a_row_reads_its_two_dictionaries_as_attributes():
    from deepspeed_tpu.observability.steplog import StepProgram

    row = StepProgram("p", 0, lambda: None, None, layer_applications=4,
                      layer_pattern=["ssm", "full"])
    assert row.layer_applications == 4
    assert row.layer_pattern == ("ssm", "full")
    assert row.ssm_chunk is None and row.conv_lowerings is None
    row.counted = {"conv": {"xla": 3}, "flash_fwd_tiles": {"rows": True}}
    assert row.conv_lowerings == row.conv == {"xla": 3}
    assert row.flash_fwd_tiles == {"rows": True}
    assert not hasattr(row, "_private")


def test_a_dense_step_loads_no_mixer_or_expert_kernel():
    """``import deepspeed_tpu.runtime.engine`` and a dense model's first fused
    step (the trace, the registry read round it, the row's facts) load none
    of the modules whose counters the engine used to read by name."""
    code = (
        "import sys, jax, numpy as np\n"
        "import deepspeed_tpu.runtime.engine\n"
        "import deepspeed_tpu as ds\n"
        "from deepspeed_tpu.models import TransformerConfig, TransformerLM\n"
        "from deepspeed_tpu.observability import steplog\n"
        "from deepspeed_tpu.parallel import build_mesh\n"
        "m = TransformerLM(TransformerConfig(hidden_size=64, num_heads=4,"
        " num_layers=2, vocab_size=64, max_seq_len=32))\n"
        "eng, *_ = ds.initialize(model=m, config={"
        "'train_micro_batch_size_per_gpu': 1, 'steps_per_print': 10 ** 9,"
        " 'optimizer': {'type': 'adamw', 'params': {'lr': 1e-3}}},"
        " mesh=build_mesh(devices=jax.devices()[:1]))\n"
        "eng.fused_train_step({'input_ids': np.zeros((1, 8), np.int32)})\n"
        "row = steplog.programs()[-1]\n"
        # (only what every model counts: where its weights were cast; the
        # tied table is cast in the step, at the gather and at the head)
        "assert row.first_call_s is not None and row.counted == {"
        "'weight_cast': {'carried': 9, 'in_step': 2}}, row.counted\n"
        "assert row.facts == {'zero_stage': 0, 'mesh_axes': {},"
        " 'layer_applications': 2, 'layer_pattern': ('full',),"
        " 'working_copy_bytes': 2 * (2 * 16 * 64 * 64 + 4 * 64)}, row.facts\n"
        "print([k for k in sys.modules if k.rpartition('.')[2] in ("
        "'ssd_scan', 'delta_rule', 'causal_conv', 'grouped_matmul',"
        " 'moe_rows')])")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, text=True,
                         capture_output=True,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"
