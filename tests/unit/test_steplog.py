"""The per-step record, the pause ring, the slow-step arithmetic and the
step-program table (``observability/steplog.py``)."""

import gc
import tracemalloc

import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import TransformerLM, get_preset
from deepspeed_tpu.observability import steplog
from deepspeed_tpu.observability.events import EventBus
from deepspeed_tpu.observability.steplog import StepLog, slow_steps

# these cases count compiles, cache misses and build seconds: the run's
# persistent compile cache (tests/conftest.py) stays off around them
pytestmark = pytest.mark.usefixtures("no_compile_cache")


def test_ring_wraps_at_its_size():
    log = StepLog(size=8)
    for i in range(11):
        log.step(i, i + 0.1, i + 0.2, i + 0.3)
    rows = log.steps()
    assert log.n_steps == 11 and rows.shape == (8, 4)
    assert rows[:, 0].tolist() == list(range(3, 11))      # oldest first
    assert rows[-1].tolist() == [10, 10.1, 10.2, 10.3]
    log.pause(1.0, 0.5, 2)
    assert log.pauses().tolist() == [[1.0, 0.5, 2.0]]


def _write_rings():
    """1,000 step rows and 1,000 pause rows into rings that are not objects
    the cyclic collector tracks."""
    log = StepLog()
    assert not gc.is_tracked(log._steps) and not gc.is_tracked(log._pauses)

    def write(n):
        for i in range(n):
            log.step(i, i * 0.1, i * 0.1 + 0.01, i * 0.1 + 0.02)
            log.pause(i * 0.1, 0.001, 2)
    return write, 1000, 256             # two int counters, nothing per write


def _write_build_record():
    """10,000 ``jax.monitoring`` events of 24 programs under two spans into
    the build record: the 48 rows, and nothing an event."""
    names = [f"jit(kept_nothing_{i})" for i in range(24)]
    events = list(steplog._DURATIONS) + [steplog._CACHE_READ]
    bus = EventBus()

    def write(n):
        for i in range(n):
            if i % 2:
                steplog._on_event("/jax/compilation_cache/cache_hits")
                steplog._on_duration(events[i % 4], 0.001,
                                     fun_name=names[i % 24])
            else:
                with steplog.span(bus, "train", "build"):
                    steplog._on_duration(events[i % 4], 0.001,
                                         fun_name=names[i % 24])
    # a row: its key's tuple, its name, a list of eleven numbers
    return write, 10000, 48 * 256


@pytest.mark.parametrize("case", [_write_rings, _write_build_record])
def test_a_write_keeps_nothing(case, monkeypatch):
    """No net allocation a write beyond what the case allows in all."""
    monkeypatch.setattr(steplog, "_BUILDS", {})
    monkeypatch.setattr(steplog, "_build_names", set())
    write, n, allowed = case()
    write(10)                           # warm the interpreter's caches
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        write(n)
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    here = [tracemalloc.Filter(True, steplog.__file__)]
    grown = sum(s.size_diff for s in after.filter_traces(here)
                .compare_to(before.filter_traces(here), "filename"))
    assert 0 <= grown <= allowed, grown


def synthetic_record():
    """20 steps every 141 ms, the span taking 1.3 ms (0.5 put + 0.7 dispatch
    + 0.1 commit); step 7 takes 250 ms, with a 60 ms collection inside its
    span (between dispatch and commit), the other 49 ms spent waiting."""
    rows, t = [], 100.0
    for i in range(20):
        slow = i == 7
        exit_ = t + 0.0013 + (0.060 if slow else 0.0)
        rows.append([i, t, t + 0.0012, exit_])
        t += 0.250 if slow else 0.141
    pauses = np.array([[rows[7][1] + 0.0012, 0.060, 2.0],
                       [rows[12][1] + 0.050, 0.002, 1.0]])   # while waiting
    return np.array(rows), pauses


def test_slow_step_arithmetic_by_hand():
    rows, pauses = synthetic_record()
    r = slow_steps(rows, pauses)
    assert r["steps"] == 19 and r["median_ms"] == pytest.approx(141.0)
    assert [s["step"] for s in r["slow"]] == [7]
    s = r["slow"][0]
    assert s["period_ms"] == pytest.approx(250.0)
    assert s["excess_ms"] == pytest.approx(109.0)
    assert s["put_dispatch_ms"] == pytest.approx(1.2)
    assert s["commit_ms"] == pytest.approx(60.1)
    assert s["outside_ms"] == pytest.approx(250.0 - 61.3)
    assert s["pauses"] == [[pytest.approx(1.2), pytest.approx(60.0), 2]]
    # window 18 * 141 + 250 = 2788 ms; excess 109 ms; the span took 60 ms
    # more than the median span
    assert r["excess_share"] == pytest.approx(100 * 109 / 2788)
    assert r["host_share"] == pytest.approx(100 * 60 / 109)
    assert r["pause_ms_per_step"] == pytest.approx(62.0 / 19)
    assert r["pauses"] == 2


def test_a_pause_outside_the_span_counts_as_host_time():
    rows, _ = synthetic_record()
    rows[7, 3] = rows[7, 1] + 0.0013                  # the span was normal
    pauses = np.array([[rows[7][1] + 0.100, 0.030, 2.0]])
    r = slow_steps(rows, pauses)
    assert r["host_share"] == pytest.approx(100 * 30 / 109)


def test_no_slow_step_and_excluded_periods():
    rows, pauses = synthetic_record()
    r = slow_steps(rows, pauses, exclude=[7])
    assert r["slow"] == [] and r["host_share"] is None
    assert r["excess_share"] == 0.0 and r["steps"] == 18
    assert slow_steps(rows[:1], pauses) is None


def _engine(**model):
    import jax

    from deepspeed_tpu.parallel import build_mesh

    eng, *_ = ds.initialize(
        model=TransformerLM(get_preset("tiny", **model)),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True}, "steps_per_print": 10 ** 9,
                "zero_optimization": {"stage": 0}},
        mesh=build_mesh(devices=jax.devices()[:1]))
    return eng


def test_program_table_one_row_a_build_and_analysis_on_request(monkeypatch):
    calls = []
    real = steplog.StepProgram.compiled
    monkeypatch.setattr(steplog.StepProgram, "compiled",
                        lambda self: calls.append(self.name) or real(self))
    before = len(steplog.programs())
    log = steplog.get_steplog()
    n0 = log.n_steps
    eng = _engine()
    assert len(steplog.programs()) == before            # nothing at build
    batch = {"input_ids": np.zeros((2, 32), np.int32)}
    for _ in range(3):
        eng.fused_train_step(batch)
    rows = steplog.programs()[before:]
    assert [(r.name, r.key) for r in rows] == [("ds_train_step", "1")]
    assert calls == [] and rows[0]._compiled is None    # nothing computed
    # XLA attention on the CPU: the program holds no flash backward
    assert rows[0].flash_bwd_lowerings is None
    assert rows[0].flash_fwd_tiles is None
    assert rows[0].counted == {}
    # a model that says nothing of a mixer: its row answers None
    assert rows[0].ssm_chunk is None and "ssm_chunk" not in rows[0].facts
    assert rows[0].moe_grouped_lowerings is None      # no expert layer
    assert rows[0].moe_dispatch_lowerings is None
    assert rows[0].ssm_scan_lowerings is None         # no state-space layer
    assert rows[0].conv_lowerings is None             # nor a convolution
    assert log.n_steps == n0 + 3
    last = log.steps()[-3:]
    assert last[:, 0].tolist() == [0, 1, 2]
    assert (last[:, 1] <= last[:, 2]).all() and (last[:, 2] <= last[:, 3]).all()
    assert rows[0].built_at <= last[0, 2]
    mem = rows[0].memory_analysis()
    assert calls == ["ds_train_step"]
    assert set(mem) == {"temp", "argument", "output", "generated_code"}
    assert mem["argument"] > 0
    assert "ds_train_step" in rows[0].hlo_text()
    # the row does not keep the engine alive, and says so
    del eng
    gc.collect()
    fresh = steplog.StepProgram("gone", 0, lambda: None, None)
    assert fresh.memory_analysis() is None


def test_program_row_counts_the_flash_backwards_it_lowered():
    """The row of a program whose attention is the flash kernel says which
    backward kernel its trace took (counted in ``_bwd_pallas``) and which arms
    its forward's tiles take (``_fwd_pallas``), once the first call has traced
    it; later calls leave it alone."""
    before = len(steplog.programs())
    eng = _engine(attention_impl="flash_pallas")
    batch = {"input_ids": np.zeros((2, 32), np.int32)}
    eng.fused_train_step(batch)
    row, = steplog.programs()[before:]
    said = dict(row.flash_bwd_lowerings)
    assert said["fused"] >= 1 and "split" not in said
    # and the tiles one head of its forward takes by arm: 32 tokens are one
    # tile, which the diagonal crosses; a [1, 32] row is the whole sequence
    tiles = dict(row.flash_fwd_tiles)
    assert tiles == {"masked": 1, "unmasked": 0, "dead": 0, "rows": True}
    eng.fused_train_step(batch)
    assert row.flash_bwd_lowerings == said and row.flash_fwd_tiles == tiles


def test_gc_hook_records_generation_one_and_up():
    steplog.install_gc_hook()
    steplog.install_gc_hook()
    assert gc.callbacks.count(steplog._on_gc) == 1
    log = steplog.get_steplog()
    n = log.n_pauses
    gc.collect(0)
    assert log.n_pauses == n
    gc.collect(1)
    gc.collect(2)
    assert log.n_pauses == n + 2
    assert log.pauses()[-2:, 2].tolist() == [1.0, 2.0]
    assert (log.pauses()[-2:, 1] > 0).all()
