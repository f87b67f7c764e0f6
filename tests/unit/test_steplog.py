"""The per-step record, the pause ring, the slow-step arithmetic and the
step-program table (``observability/steplog.py``)."""

import gc
import threading
import time
import tracemalloc

import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu import _hoststate
from deepspeed_tpu.models import TransformerLM, get_preset
from deepspeed_tpu.observability import steplog
from deepspeed_tpu.observability.events import EventBus
from deepspeed_tpu.observability.steplog import (StepLog, host_states,
                                                 slow_steps)

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
    assert not gc.is_tracked(log._host)

    def write(n):
        for i in range(n):
            enter, exit_ = steplog.host_state(), steplog.thread_state()
            log.step(i, enter[0], i * 0.1 + 0.01, exit_[0],
                     (enter[1], enter[2], enter[3], i * 0.1 + 0.005,
                      exit_[1], exit_[2]))
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
    here = [tracemalloc.Filter(True, steplog.__file__),
            tracemalloc.Filter(True, _hoststate.__file__)]
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
    assert rows[0].flash_bwd_tiles is None
    # (only where the weights were cast: the stacks' nine leaves carried,
    # the tied table cast in the step at the gather and at the head)
    assert rows[0].counted == {"weight_cast": {"carried": 9, "in_step": 2}}
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
    its forward's and its fused backward's tiles take (``_fwd_pallas``,
    ``_bwd_fused_call``), once the first call has traced it; later calls
    leave it alone."""
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
    # the backward's: the one crossed tile is one sub-block, masked
    assert row.flash_bwd_tiles == {"causal": {
        "masked": 1, "unmasked": 0, "dead": 0, "sub_live": 1, "sub_dead": 0,
        "sub_inside": 0}}
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


# ---- the state of the host thread ------------------------------------------

def host_record(runnable=True):
    """Six steps, a period of 100 ms: put 0.5, dispatch 1.0, commit 0.5 and
    97.5 outside plus what is left; inside the span the thread is on a core
    for 1.5 ms, runnable for 0.25 and off it for 0.25, outside on a core for
    1 ms and off it for the rest; the other threads burn 0.5 ms a period.
    Step 3 takes 300 ms: its put 2 ms longer, of which 1 ms runnable and 1 ms
    off the core, and outside 198 ms longer: 10 ms on a core, 50 runnable,
    138 off the core, the other threads 100 ms."""
    steps, host = [], []
    t, cpu, run, proc = 50.0, 7e9, 3e9, 9e9          # counters run from before
    for i in range(6):
        slow = i == 3
        put = 0.0025 if slow else 0.0005
        span = put + 0.0015
        span_cpu = 1.5e6
        span_run = 1.25e6 if slow else 0.25e6
        out_wall = (0.300 if slow else 0.100) - span
        out_cpu = 11e6 if slow else 1e6
        out_run = 50e6 if slow else 0.0
        other = 100e6 if slow else 0.5e6
        steps.append([i, t, t + put + 0.001, t + span])
        host.append([cpu, run if runnable else np.nan, proc, t + put,
                     cpu + span_cpu,
                     run + span_run if runnable else np.nan])
        t += span + out_wall
        cpu += span_cpu + out_cpu
        run += span_run + out_run
        proc += span_cpu + out_cpu + other
    return np.array(steps), np.array(host)


def test_host_states_phases_and_states_by_hand():
    steps, host = host_record()
    r = host_states(steps, host)
    assert r["steps"] == 5 and r["runnable_read"] and r["slow"] == 1
    assert r["median_ms"] == {
        "put": pytest.approx(0.5), "dispatch": pytest.approx(1.0),
        "commit": pytest.approx(0.5), "outside": pytest.approx(98.0),
        "span": pytest.approx(2.0)}
    assert r["state_median_ms"]["span"] == {
        "cpu": pytest.approx(1.5), "runnable": pytest.approx(0.25),
        "off_cpu": pytest.approx(0.25)}
    assert r["state_median_ms"]["outside"] == {
        "cpu": pytest.approx(1.0), "runnable": pytest.approx(0.0),
        "off_cpu": pytest.approx(97.0)}
    # in every period the four phases sum to the period and the three states
    # to the span and to what lies outside it
    for w in r["worst"]:
        assert w["put_ms"] + w["dispatch_ms"] + w["commit_ms"] \
            + w["outside_ms"] == pytest.approx(w["period_ms"])
        assert sum(w["span"].values()) == pytest.approx(
            w["put_ms"] + w["dispatch_ms"] + w["commit_ms"])
        assert sum(w["outside"].values()) == pytest.approx(w["outside_ms"])
    worst = r["worst"][0]
    assert worst["step"] == 3 and worst["period_ms"] == pytest.approx(300.0)
    assert worst["put_ms"] == pytest.approx(2.5)
    assert worst["span"] == {"cpu": pytest.approx(1.5),
                             "runnable": pytest.approx(1.25),
                             "off_cpu": pytest.approx(1.25)}
    assert worst["outside"] == {"cpu": pytest.approx(11.0),
                                "runnable": pytest.approx(50.0),
                                "off_cpu": pytest.approx(235.0)}
    assert worst["other_cpu_ms"] == pytest.approx(100.0)
    # the sums over the window: 4 x 2 + 4 = 12 ms of spans
    assert r["sum_ms"]["span"] == {
        "wall": pytest.approx(12.0), "cpu": pytest.approx(7.5),
        "runnable": pytest.approx(2.25), "off_cpu": pytest.approx(2.25)}
    assert r["span_off_cpu_share"] == pytest.approx(100 * 2.25 / 12)
    assert r["span_runnable_share"] == pytest.approx(100 * 2.25 / 12)
    assert r["other_threads_cpu_share"] == pytest.approx(100 * 102 / 700)
    # the slow step's excess, 200 ms as slow_steps has it, shared out
    assert slow_steps(steps, np.zeros((0, 3)))["excess_ms"] \
        == pytest.approx(200.0)
    assert r["excess_ms"] == pytest.approx(200.0)
    assert r["excess_by_phase_ms"] == {
        "put": pytest.approx(2.0), "dispatch": pytest.approx(0.0),
        "commit": pytest.approx(0.0), "outside": pytest.approx(198.0)}
    assert r["excess_by_state_ms"]["span"] == {
        "cpu": pytest.approx(0.0), "runnable": pytest.approx(1.0),
        "off_cpu": pytest.approx(1.0)}
    assert r["excess_by_state_ms"]["outside"] == {
        "cpu": pytest.approx(10.0), "runnable": pytest.approx(50.0),
        "off_cpu": pytest.approx(138.0)}
    assert r["slow_off_cpu_share"] == pytest.approx(100 * 139 / 200)
    assert r["slow_runnable_share"] == pytest.approx(100 * 51 / 200)


def test_host_states_no_excess_reads_zero_and_exclude_is_honoured():
    steps, host = host_record()
    r = host_states(steps, host, exclude=[3])
    assert r["steps"] == 4 and r["slow"] == 0
    assert r["excess_ms"] == 0.0
    assert r["slow_off_cpu_share"] == 0.0 == r["slow_runnable_share"]
    assert set(r["excess_by_phase_ms"].values()) == {0.0}
    assert r["sum_ms"]["span"]["wall"] == pytest.approx(8.0)
    assert [w["step"] for w in r["worst"]] != [] \
        and 3 not in [w["step"] for w in r["worst"]]
    # the same periods as slow_steps, on its terms
    assert slow_steps(steps, np.zeros((0, 3)), exclude=[3])["steps"] == 4
    assert host_states(steps[:1], host[:1]) is None
    assert host_states(steps, np.full_like(host, np.nan)) is None


def test_host_states_without_schedstat_counts_runnable_as_off_the_core():
    steps, host = host_record(runnable=False)
    r = host_states(steps, host)
    assert not r["runnable_read"]
    assert r["span_runnable_share"] == 0.0 == r["slow_runnable_share"]
    assert r["sum_ms"]["span"]["off_cpu"] == pytest.approx(4.5)
    assert r["excess_by_state_ms"]["outside"]["off_cpu"] \
        == pytest.approx(188.0)
    assert r["slow_off_cpu_share"] == pytest.approx(100 * 190 / 200)


def test_host_ring_wraps_beside_the_step_ring():
    log = StepLog(size=8)
    for i in range(11):
        log.step(i, i + 0.1, i + 0.2, i + 0.3,
                 (i, i + 1, i + 2, i + 0.15, i + 4, i + 5))
    assert log.steps().shape == (8, 4) and log.host().shape == (8, 6)
    assert log.host()[:, 0].tolist() == log.steps()[:, 0].tolist() \
        == list(range(3, 11))
    assert log.host()[-1].tolist() == [10, 11, 12, 10.15, 14, 15]
    # a caller that takes no sample leaves NaN beside its row
    log.step(11, 11.1, 11.2, 11.3)
    assert log.steps()[-1].tolist() == [11, 11.1, 11.2, 11.3]
    assert np.isnan(log.host()[-1]).all()
    assert log.last_period(12, steplog.host_state()) is None
    # the period a sample closes: from the newest row's enter to it, where
    # that row is the step before
    log.step(12, 12.0, 12.2, 12.3, (1e9, 2e9, 9e9, 12.1, 1.1e9, 2e9))
    assert log.last_period(13, (12.5, 1.2e9, 2.05e9, 9.9e9)) \
        == (pytest.approx(250.0), pytest.approx(50.0))
    off, runnable = log.last_period(13, (12.5, 1.2e9, float("nan"), 9.9e9))
    assert off == pytest.approx(300.0) and runnable != runnable
    assert log.last_period(0, (12.5, 1.2e9, 2.05e9, 9.9e9)) is None


def _between(work):
    a = steplog.host_state()
    work()
    b = steplog.host_state()
    wall, cpu, runnable = ((b[0] - a[0]) * 1e3, (b[1] - a[1]) / 1e6,
                           (b[2] - a[2]) / 1e6)
    return wall, cpu, 0.0 if runnable != runnable else runnable


def _spin():
    t = time.perf_counter()
    while time.perf_counter() - t < 0.05:
        pass


@pytest.mark.parametrize("work,on_core", [
    (lambda: time.sleep(0.05), False), (_spin, True)], ids=["sleep", "spin"])
def test_a_sleeping_thread_reads_off_the_core_and_a_spinning_one_on(work,
                                                                    on_core):
    """Loose limits: six workers share the box."""
    wall, cpu, runnable = _between(work)
    assert wall >= 49.0 and len(steplog.host_state()) == 4
    if on_core:
        assert cpu + runnable > 0.6 * wall
    else:
        assert wall - cpu - runnable > 0.6 * wall


def test_a_missing_proc_source_reads_nan_and_raises_nothing(monkeypatch):
    monkeypatch.setattr(_hoststate, "SCHEDSTAT", "/proc/thread-self/no_such")
    monkeypatch.setattr(_hoststate, "_missing", set())
    got = []
    # a thread of its own: the descriptor is opened once a thread
    th = threading.Thread(target=lambda: got.extend(
        [steplog.host_state(), steplog.thread_state()]))
    th.start()
    th.join(10)
    whole, three = got
    assert whole[2] != whole[2] and three[2] != three[2]        # NaN
    assert whole[1] >= 0 and whole[3] > 0 and len(three) == 3
    assert steplog.unavailable() == ["/proc/thread-self/no_such"]


def test_a_threads_descriptor_is_closed_with_it():
    import os

    if not os.path.isdir("/proc/self/fd"):
        pytest.skip("no /proc/self/fd to count on this host")
    steplog.host_state()
    before = len(os.listdir("/proc/self/fd"))
    for _ in range(20):
        th = threading.Thread(target=steplog.host_state)
        th.start()
        th.join(10)
    assert len(os.listdir("/proc/self/fd")) <= before + 1


def test_a_setup_row_carries_the_samples_of_both_ends():
    with steplog.span(EventBus(), "setup", "sampled", why="a test"):
        row = steplog.setup()[-1]
        assert row["host_end"] is None and len(row["host_start"]) == 4
        assert row["start"] == row["host_start"][0]
        time.sleep(0.01)
    row = steplog.setup()[-1]
    assert row["name"] == "ds.setup.sampled" and row["why"] == "a test"
    assert row["end"] == row["host_end"][0] > row["start"]
    assert row["host_end"][1] >= row["host_start"][1]          # cpu_ns
    assert row["host_end"][3] >= row["host_start"][3]          # the process's
    # a sample taken earlier backdates the row, as the package's import does
    early = steplog.host_state()
    with steplog.span(EventBus(), "setup", "backdated", host_start=early):
        pass
    row = steplog.setup()[-1]
    assert row["start"] == early[0] and row["host_start"][1] == early[1]
    # the package's import is the process's first set-up span, whatever ran
    # in this worker since; the record keeps the last 256 rows, so after
    # enough engines it no longer lists it
    import deepspeed_tpu

    first = deepspeed_tpu._import_span.row
    assert first["id"] == 1 and first["name"] == "ds.setup.import"
    assert first["host_end"] is not None
    rows = steplog.setup()
    assert rows[0]["id"] == 1 or len(rows) == 256


class _Seen:
    """A gauge that keeps what it was set to."""

    def __init__(self):
        self.values = []

    def set(self, value):
        self.values.append(value)


def test_a_fused_step_leaves_its_samples_and_the_gauges():
    from collections import defaultdict

    log = steplog.get_steplog()
    eng = _engine()
    eng._obs = defaultdict(_Seen)       # as observability.enabled leaves it
    eng._last_commit_t = eng._comm_lat_base = 0.0
    batch = {"input_ids": np.zeros((2, 32), np.int32)}
    for _ in range(3):
        eng.fused_train_step(batch)
    steps, host = log.steps()[-3:], log.host()[-3:]
    assert steps.shape == (3, 4) and np.isfinite(host[:, [0, 2, 3, 4]]).all()
    assert (steps[:, 1] <= host[:, 3]).all()            # enter, then put,
    assert (host[:, 3] <= steps[:, 2]).all()            # then the dispatch
    assert (host[:, 4] >= host[:, 0]).all()             # the clocks run on
    assert (np.diff(host[:, 0]) >= 0).all()
    assert (host[:, 2] >= host[:, 0]).all()             # the process's >= own
    r = host_states(steps, host)
    assert r["steps"] == 2 and r["median_ms"]["put"] > 0
    assert eng._host_enter is None                      # only inside the span
    # the gauges hold the periods the second and third steps' enters closed
    assert len(eng._obs["host_off_cpu_ms"].values) == 2
    assert len(eng._obs["host_runnable_ms"].values) in (0, 2)
    wall = np.diff(steps[:, 1]) * 1e3
    assert (np.array(eng._obs["host_off_cpu_ms"].values) <= wall + 1e-6).all()
