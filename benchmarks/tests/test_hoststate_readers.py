"""``readers/hoststate.py``: set-up shared out by hand on plain rows, the
clocks' offset, a program that keeps no such record, the live record of this
process, and the manifest's entries."""

import json
import os

import numpy as np
import pytest

from benchmarks import harness
from benchmarks.readers import hoststate as hr
from benchmarks.trace_reduce import Op

T0 = 1000.0             # harness.T_PROCESS_START of the made-up run


def sample(t, cpu_s, runnable_s=0.0, process_s=None):
    return [T0 + t, cpu_s * 1e9, runnable_s * 1e9,
            (cpu_s if process_s is None else process_s) * 1e9]


def made_up():
    """Import 0.5-3.5 s, all of it on a core; the runtime's start 3.5-9 s,
    of which 0.5 s on a core, 0.25 runnable and the rest asleep while the
    other threads burn 1 s; initialize 9-11 s, 1 s on a core; the reference
    check 11-14 s on a core but for 0.5 s; two warm-up steps, the first 4 s
    long (3 on a core), the second 0.5 s after it and 0.0078125 s long; the
    window's first step enters at 20 s."""
    spans = [
        {"id": 1, "name": "ds.setup.import", "start": T0 + 0.5,
         "end": T0 + 3.5, "parent": None, "self_s": 3.0,
         "host_start": sample(0.5, 0.25), "host_end": sample(3.5, 3.25)},
        {"id": 2, "name": "ds.setup.initialize", "start": T0 + 9.0,
         "end": T0 + 11.0, "parent": None, "self_s": 0.5,
         "host_start": sample(9.0, 3.75, 0.25, 4.75),
         "host_end": sample(11.0, 4.75, 0.25, 5.75)},
        {"id": 3, "name": "ds.setup.engine.state", "start": T0 + 9.5,
         "end": T0 + 11.0, "parent": 2, "self_s": 1.5,
         "host_start": sample(9.5, 4.0, 0.25, 5.0),
         "host_end": sample(11.0, 4.75, 0.25, 5.75)}]
    # rows [step, enter, dispatched, exit] and, beside them, [enter cpu_ns,
    # enter runnable_ns, enter process_cpu_ns, t_put, exit cpu_ns, exit
    # runnable_ns]
    steps = [[0, T0 + 14.0, T0 + 17.9, T0 + 18.0],
             [1, T0 + 18.5, T0 + 18.50625, T0 + 18.5078125],
             [2, T0 + 20.0, T0 + 20.00625, T0 + 20.0078125],
             [3, T0 + 20.5, T0 + 20.50625, T0 + 20.5078125]]
    host = [[7.25e9, 0.25e9, 8.25e9, T0 + 14.001, 10.25e9, 0.25e9],
            [10.25e9, 0.25e9, 11.5e9, T0 + 18.501, 10.2578125e9, 0.25e9],
            [10.5078125e9, 0.5e9, 12.0e9, T0 + 20.001, 10.515625e9, 0.5e9],
            [10.515625e9, 0.5e9, 12.1e9, T0 + 20.501, 10.5234375e9, 0.5e9]]
    return dict(spans=spans, steps=steps, host=host, n_window=2, t_start=T0)


def test_setup_outside_the_program_by_hand():
    a = hr.setup_part(**made_up())
    said = a["setup"]
    # the whole of set-up: 20 s, of which 10.2578125 on a core and 0.5
    # runnable since the first sample, which had counted 0.25 s by then
    assert said["before_the_clock"] == {"cpu_s": 0.25, "process_cpu_s": 0.25}
    assert said["whole"]["off_cpu_s"] == pytest.approx(20 - 10.2578125 - 0.5)
    inside = {m["name"]: m for m in said["inside"]}
    assert list(inside) == ["ds.setup.import", "ds.setup.initialize",
                            "warmup_step_0", "warmup_step_1"]
    assert inside["ds.setup.import"]["off_cpu_s"] == pytest.approx(0.0)
    assert inside["ds.setup.initialize"]["off_cpu_s"] == pytest.approx(1.0)
    assert inside["warmup_step_0"]["off_cpu_s"] == pytest.approx(1.0)
    assert inside["warmup_step_1"]["off_cpu_s"] == pytest.approx(0.0)
    # what is left: 0.5 before the import, 4.75 the runtime's start, 0.5 the
    # reference check, 0.5 and 0.9921875 after the warm-up steps
    assert a["setup_outside_off_cpu_s"] == pytest.approx(
        0.5 + 4.75 + 0.5 + 0.5 + 0.9921875)
    assert a["setup_outside_runnable_s"] == pytest.approx(0.5)
    between = {(b["from"], b["to"]): b for b in said["between"]}
    start = between[("ds.setup.import", "ds.setup.initialize")]
    assert start["wall_s"] == pytest.approx(5.5)
    assert start["cpu_s"] == pytest.approx(0.5)
    assert start["runnable_s"] == pytest.approx(0.25)
    assert start["off_cpu_s"] == pytest.approx(4.75)
    assert start["other_cpu_s"] == pytest.approx(1.0)
    assert between[("ds.setup.initialize", "warmup_step_0")]["cpu_s"] \
        == pytest.approx(2.5)
    assert list(between)[-1] == ("warmup_step_1", "window")
    # the pieces and the spans sum to the whole
    for key in ("wall_s", "cpu_s", "runnable_s", "off_cpu_s"):
        assert sum(m[key] for m in said["inside"]) \
            + sum(b[key] for b in said["between"]) \
            == pytest.approx(said["whole"][key])
    # every span with both samples is printed with its own split
    assert [s["name"] for s in said["spans"]] == [
        "ds.setup.import", "ds.setup.initialize", "ds.setup.engine.state"]
    assert said["spans"][2]["cpu_s"] == pytest.approx(0.75)
    json.dumps(hr._sayable(a))


def test_a_host_without_schedstat_and_a_record_without_samples():
    data = made_up()
    for s in data["spans"]:
        s["host_start"][2] = s["host_end"][2] = None
    for h in data["host"]:
        h[1] = h[5] = float("nan")
    a = hr.setup_part(**data)
    assert a["setup_outside_runnable_s"] == 0.0
    assert a["setup_outside_off_cpu_s"] == pytest.approx(
        0.5 + 5.0 + 0.5 + 0.5 + 1.2421875)
    assert "NaN" not in json.dumps(hr._sayable(a))
    data["host"][2][0] = float("nan")       # the window's first row: none
    assert hr.setup_part(**data) == {}
    assert hr.setup_part(**{**made_up(), "n_window": 0}) == {}


def test_the_records_place_on_the_profilers_clock():
    steps = made_up()["steps"]
    off = 7_000_000_000_000                  # the trace's clock runs ahead
    spans = [Op("ds.train.step", off + round((T0 + 20.0) * 1e9) + 1500,
                0, "2"),
             Op("ds.train.step", off + round((T0 + 20.5) * 1e9) + 2500,
                0, "3"),
             Op("ds.train.step", 5, 6, "99"), Op("ds.train.step", 5, 6, "")]
    got = hr.clock_offset(spans, steps)
    assert got == {"median_us": pytest.approx(off / 1e3 + 2.0),
                   "spread_us": pytest.approx(1.0), "steps": 2}
    assert hr.clock_offset([], steps) is None


def test_a_program_without_the_record_gives_no_metric(monkeypatch):
    from deepspeed_tpu.observability import steplog

    monkeypatch.delattr(steplog, "host_states")
    said = []
    monkeypatch.setattr(harness, "say", lambda **kw: said.append(kw))
    ctx = {"values": {"steps": 5}, "cell": {"name": "no_such_cell"}}
    assert hr.analysis(ctx) == {} and said == []
    assert all(hr.value(ctx, k) is None
               for k in hr.METRIC_KEYS + hr.RUNNABLE_KEYS)


def test_the_live_record_of_this_process_reduces(monkeypatch):
    """Rows written through the program's own sampler, read as a run's
    window: every listed metric a number, one line, and in every period the
    phases sum to the period and the states to their phase."""
    import time

    from deepspeed_tpu.observability import steplog

    log = steplog.StepLog()
    monkeypatch.setattr(steplog, "get_steplog", lambda: log)
    monkeypatch.setattr(harness, "T_PROCESS_START",
                        time.perf_counter() - 1.0)
    for i in range(12):
        enter = steplog.host_state()
        t_put = time.perf_counter()
        t_disp = time.perf_counter()
        exit_ = steplog.thread_state()
        log.step(i, enter[0], t_disp, exit_[0],
                 (enter[1], enter[2], enter[3], t_put, exit_[1], exit_[2]))
        time.sleep(0.03 if i == 6 else 0.002)
    said = []
    monkeypatch.setattr(harness, "say", lambda **kw: said.append(kw))
    ctx = {"values": {"steps": 10}, "cell": {"name": "no_such_cell"}}
    values = {k: hr.value(ctx, k) for k in hr.METRIC_KEYS}
    assert all(isinstance(v, float) for v in values.values()), values
    assert len(said) == 1 and set(said[0]) == {"host_state"}
    line = said[0]["host_state"]
    json.loads(json.dumps(line, allow_nan=False))
    window = line["window"]
    assert window["steps"] == 9 and window["slow"] >= 1
    assert values["slow_step_off_cpu_share"] > 50.0      # it slept
    for w in window["worst"]:
        assert w["put_ms"] + w["dispatch_ms"] + w["commit_ms"] \
            + w["outside_ms"] == pytest.approx(w["period_ms"], abs=0.05)
        assert sum(w["span"].values()) == pytest.approx(
            w["put_ms"] + w["dispatch_ms"] + w["commit_ms"], abs=0.05)
        assert sum(w["outside"].values()) == pytest.approx(w["outside_ms"],
                                                           abs=0.05)
    assert line["clock_offset"] is None and "unavailable" in line
    assert set(hr.RUNNABLE_KEYS) <= set(line) or not window["runnable_read"]


def test_the_manifest_lists_the_six_for_every_training_cell():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = [w["name"] for w in manifest["workloads"]]
    keys = {}
    for m in manifest["per_layer"]:
        spec = harness._load("metrics", m["name"])
        if spec["reader"] != "readers.hoststate:value":
            continue
        assert m["workloads"] == cells and m["layer"] == "train engine"
        assert m["better"] == "lower" and m["source"] == "host_clock"
        keys[spec["args"]["key"]] = m["moves"]
    assert set(keys) == set(hr.METRIC_KEYS)
    assert keys.pop("setup_outside_off_cpu_s") == "setup_s"
    assert set(keys.values()) == {"train_tok_s_chip"}
    # none of the runnable ones while the chip's host cannot tell
    assert not set(hr.RUNNABLE_KEYS) & {
        harness._load("metrics", m["name"])["args"].get("key")
        for m in manifest["per_layer"]}
