"""The trace reduction on a synthetic trace with known answers and on a
small trace recorded on a TPU v5e (``testdata/tiny_tpu.xplane.pb``)."""

import os

import pytest

from benchmarks import trace_reduce as tr
from benchmarks.trace_reduce import Op, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "..", "testdata", "tiny_tpu.xplane.pb")
MS = 1_000_000


def synthetic() -> Trace:
    """One device, 100 ms window. A while loop [10, 60) holding two fusions
    and a custom call; an all-gather [60, 70) of which [60, 64) overlaps a
    fusion; idle [0, 10) under make_batch, [70, 100) under nothing."""
    ops = [
        Op("while.1", 10 * MS, 60 * MS),
        Op("fusion.1", 10 * MS, 30 * MS, "jit(f)/mlp/dot_general"),
        Op("custom-call.7", 30 * MS, 50 * MS, "jit(f)/attn/pallas_call"),
        Op("fusion.2", 50 * MS, 60 * MS, "jit(f)/mlp/dot_general"),
        Op("all-gather-start.3", 60 * MS, 70 * MS),
        Op("fusion.9", 60 * MS, 64 * MS),
    ]
    host = [
        Op("bench.window", 0, 100 * MS),
        Op("bench.make_batch", 0, 9 * MS),
        Op("bench.fused_train_step", 9 * MS, 80 * MS),
    ]
    return Trace(devices={"/device:TPU:0": sorted(
        ops, key=lambda o: (o.start, -o.end))}, host=host)


def test_busy_and_idle():
    t = synthetic()
    b = tr.busy(t, tr.window(t))
    assert b["window_s"] == pytest.approx(0.100)
    assert b["busy_s"] == pytest.approx(0.060)


def test_self_time_excludes_children():
    t = synthetic()
    by_name = {op.name: s for op, s in
               tr.self_times(t.devices["/device:TPU:0"])}
    assert by_name["while.1"] == 0
    assert by_name["fusion.1"] == 20 * MS
    assert by_name["all-gather-start.3"] == 6 * MS


def test_kernel_seconds_by_name_and_label():
    t = synthetic()
    w = tr.window(t)
    assert tr.kernel_seconds(t, w, r"^custom-call")["seconds"] \
        == pytest.approx(0.020)
    k = tr.kernel_seconds(t, w, r"/mlp/", "label")
    assert k["seconds"] == pytest.approx(0.030) and k["calls"] == 2


def test_top_ops_and_gaps():
    t = synthetic()
    w = tr.window(t)
    top = dict((k, v) for k, v in tr.top_device_ops(t, w))
    assert top["fusion mlp"] == pytest.approx(0.030)
    assert top["custom-call attn"] == pytest.approx(0.020)
    gaps = dict((k, v) for k, v in tr.idle_gaps(t, w))
    assert gaps["bench.make_batch"] == pytest.approx(0.009)
    assert gaps["bench.fused_train_step"] == pytest.approx(0.011)
    assert gaps["(no span)"] == pytest.approx(0.020)
    assert sum(gaps.values()) == pytest.approx(0.040)


def test_interval_helpers():
    assert tr._union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert tr._subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr._clip([(0, 10)], 3, 20) == [(3, 10)]


@pytest.mark.skipif(not os.path.isfile(RECORDED),
                    reason="no recorded trace in testdata")
def test_recorded_tpu_trace():
    t = tr.load_xplane(RECORDED)
    assert list(t.devices) == ["/device:TPU:0"]
    names = {h.name for h in t.host}
    assert {"bench.window", "bench.fused_train_step",
            "bench.make_batch"} <= names
    r = tr.reduce(t)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"] and r["device_ops"][0][1] > 0
    # three steps of three scanned iterations each; the device's clock runs
    # about 0.1 ms ahead of the host's here, so the first step's operations
    # fall before the host's window span and two steps (six iterations of
    # the loop body's fusion) are inside it
    ops = tr.named_ops(t, tuple(r["window_ns"]))
    assert any(name.startswith("fusion") and calls >= 6
               for name, _l, _s, calls in ops)
    assert all(not n.startswith("%") for n, *_ in ops)
    assert r["device_ops"][0][0] == "fusion bf16[512,512]"
    gaps = dict(tr.idle_gaps(t, tuple(r["window_ns"])))
    assert gaps.get("bench.make_batch", 0) > 0.004   # 3 sleeps of 2 ms
