"""``readers/setup.py``: the arithmetic on plain data with answers by hand, a
program that keeps no set-up record, and the manifest's eight entries."""

import json
import os

import pytest

from benchmarks import harness
from benchmarks.readers import setup as sr

T0 = 1000.0             # harness.T_PROCESS_START of the made-up run
SETUP_S = 20.0          # the window starts at 1020


def span(i, name, start, end, parent=None, self_s=None, **facts):
    return {"id": i, "name": name, "start": T0 + start, "end": T0 + end,
            "parent": parent, "self_s": self_s, **facts}


def row(name, where, first, last, traces=1, trace_s=0.0, lowers=1,
        lower_s=0.0, compiles=1, compile_s=0.0, hits=1, read_s=0.0):
    return {"name": name, "span": where, "traces": traces,
            "trace_s": trace_s, "lowers": lowers, "lower_s": lower_s,
            "compiles": compiles, "compile_s": compile_s, "cache_hits": hits,
            "cache_read_s": read_s, "cache_misses": 0, "first": T0 + first,
            "last": T0 + last}


def made_up():
    """Import 0.5-3.5 s, the runtime's start outside, initialize 9-11.5 s
    with four programs, a reference check outside, two warm-up steps of
    4.25 and 0.015625 s of host time, and in the window a third step, a
    one-op program and the reader's own look at the step program."""
    spans = [span(1, "ds.setup.import", 0.5, 3.5, self_s=3.0,
                  jax_preloaded=False),
             span(2, "ds.setup.initialize", 9.0, 11.5, self_s=0.0),
             span(3, "ds.setup.config", 9.0, 9.25, 2, 0.25),
             span(4, "ds.setup.engine.plan", 9.25, 9.75, 2, 0.5),
             span(5, "ds.setup.engine.state", 9.75, 11.25, 2, 1.5),
             span(6, "ds.setup.engine.rest", 11.25, 11.5, 2, 0.25)]
    builds = [
        row("convert_element_type", "outside", 4.0, 25.0, traces=9,
            trace_s=0.004, lowers=3, lower_s=0.003, compiles=3,
            compile_s=0.002),
        row("_threefry_seed", "ds.setup.engine.plan", 9.5, 9.5,
            lower_s=0.005),
        row("init_fn", "ds.setup.engine.state", 10.0, 10.5, trace_s=0.125,
            lower_s=0.25, compile_s=0.5),
        row("opt_init", "ds.setup.engine.state", 10.5, 11.0, lowers=2,
            compiles=2, compile_s=0.0625),
        row("forward", "outside", 12.0, 13.0, compile_s=0.75),
        row("ds_train_step", "ds.train.build", 14.5, 18.0, trace_s=0.5,
            lower_s=2.5, compile_s=1.0, read_s=0.75),
        row("multiply", "ds.train.build", 14.25, 14.75, traces=40,
            trace_s=0.002, lowers=0, compiles=0, hits=0),
        row("late_one_op", "outside", 21.0, 21.0, compile_s=0.5),
        row("ds_train_step", "ds.train.inspect", 80.0, 82.0, trace_s=0.25,
            lower_s=1.0, compile_s=2.0)]
    program = {"name": "ds_train_step", "first_call_s": 4.125,
               "second_call_s": 0.0078125, "build": {"lowers": 1}}
    steps = [[0, T0 + 14.0, T0 + 18.1875, T0 + 18.25],
             [1, T0 + 19.0, T0 + 19.0078125, T0 + 19.015625],
             [2, T0 + 20.5, T0 + 20.5078125, T0 + 20.515625]]
    return dict(spans=spans, builds=builds, program=program, steps=steps,
                t_start=T0, setup_s=SETUP_S)


def test_the_eight_metrics_by_hand():
    a = sr.reduce(**made_up())
    assert a["setup_import_s"] == 3.0
    assert a["setup_engine_build_s"] == 2.5
    assert a["setup_engine_programs"] == 4           # 1 + 1 + 2
    assert a["setup_step_program_build_s"] == 4.0      # 0.5 + 2.5 + 1.0
    assert a["setup_step_program_builds"] == 1
    assert a["setup_step_first_call_s"] == 4.125
    assert a["setup_step_second_call_s"] == 0.0078125
    assert a["setup_outside_program_s"] == 20.0 - 3.0 - 2.5 - 4.265625
    assert a["setup_step_program_build_s"] <= a["setup_step_first_call_s"] \
        + a["setup_step_second_call_s"]
    assert set(sr.METRIC_KEYS) == set(a) - {"said"}


def test_the_four_parts_sum_to_setup_s_to_the_microsecond():
    data = made_up()
    data["setup_s"] = 20.000_000_7          # not a round number of seconds
    a = sr.reduce(**data)
    parts = a["setup_import_s"] + a["setup_engine_build_s"] \
        + a["said"]["warmup_steps_s"] + a["setup_outside_program_s"]
    assert abs(parts - data["setup_s"]) < 1e-6
    assert a["said"]["warmup_steps_s"] == 4.265625


def test_what_happened_in_the_window_is_left_out():
    a = sr.reduce(**made_up())
    said = a["said"]
    assert [s["step"] for s in said["warmup_steps"]] == [0, 1]
    names = [(b["name"], b["span"]) for b in said["builds_over_10ms"]]
    assert ("late_one_op", "outside") not in names
    assert ("ds_train_step", "ds.train.inspect") not in names
    assert names == [("init_fn", "ds.setup.engine.state"),
                     ("opt_init", "ds.setup.engine.state"),
                     ("forward", "outside"),
                     ("ds_train_step", "ds.train.build")]
    by = said["programs_by_span"]
    assert "ds.train.inspect" not in by
    assert by["outside"]["lowers"] == 4 and by["outside"]["build_s"] \
        == pytest.approx(0.759)
    assert by["ds.train.build"] == {"lowers": 1, "compiles": 1,
                                    "cache_hits": 1, "build_s": 4.002}
    # a row holds all of a name's builds under one span: one with events on
    # both sides of the window's start counts whole, and is named
    assert said["straddling_rows"] == [["convert_element_type", "outside"]]
    # a span still open at the window's start, or one that began before the
    # process's clock did, counts with its part inside
    data = made_up()
    data["spans"][0]["start"] = T0 - 1.0
    data["spans"][1]["end"] = T0 + 30.0
    b = sr.reduce(**data)
    assert b["setup_import_s"] == 3.5 and b["setup_engine_build_s"] == 11.0


def test_spans_are_printed_with_self_times_and_parents():
    said = sr.reduce(**made_up())["said"]
    assert [(s["name"], s["parent"], s["start_s"], s["length_s"],
             s["self_s"]) for s in said["spans"][:3]] == [
        ("ds.setup.import", None, 0.5, 3.0, 3.0),
        ("ds.setup.initialize", None, 9.0, 2.5, 0.0),
        ("ds.setup.config", "ds.setup.initialize", 9.0, 0.25, 0.25)]
    assert said["spans"][0]["jax_preloaded"] is False
    json.dumps(said)                                  # an earlier line


def test_nothing_built_reads_zero_and_never_absent():
    a = sr.reduce(spans=[], builds=[], program=None, steps=[], t_start=T0,
                  setup_s=5.0)
    assert {k: a[k] for k in sr.METRIC_KEYS} == {
        **dict.fromkeys(sr.METRIC_KEYS, 0.0),
        "setup_outside_program_s": 5.0}
    # one call made, the second not yet
    data = made_up()
    data["program"] = {"name": "ds_train_step", "first_call_s": 4.0,
                       "second_call_s": None}
    assert sr.reduce(**data)["setup_step_second_call_s"] == 0.0


def test_a_program_without_the_record_gives_no_metric(monkeypatch):
    from deepspeed_tpu.observability import steplog

    monkeypatch.delattr(steplog, "setup")
    said = []
    monkeypatch.setattr(harness, "say", lambda **kw: said.append(kw))
    ctx = {"values": {"setup_s": 20.0}}
    assert sr.analysis(ctx) == {} and said == []
    assert all(sr.value(ctx, k) is None for k in sr.METRIC_KEYS)


def test_the_live_record_of_this_process_reduces(monkeypatch):
    """The program's own record, whatever this process has built so far:
    every metric a number, and the line printed once."""
    said = []
    monkeypatch.setattr(harness, "say", lambda **kw: said.append(kw))
    ctx = {"values": {"setup_s": 3600.0}}
    values = {k: sr.value(ctx, k) for k in sr.METRIC_KEYS}
    assert all(isinstance(v, float) and v >= 0 for v in values.values())
    assert len(said) == 1 and set(said[0]) == {"setup_program"}
    line = said[0]["setup_program"]
    assert line["listener_calls"]["duration"] >= 0
    json.dumps(line)


def test_the_manifest_lists_the_eight_for_every_training_cell():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = [w["name"] for w in manifest["workloads"]]
    mine = [m for m in manifest["per_layer"] if m["moves"] == "setup_s"]
    assert len(mine) == 8 and manifest["per_layer"][-8:] == mine
    keys = set()
    for m in mine:
        assert m["workloads"] == cells and m["layer"] == "train engine"
        assert m["better"] == "lower"
        spec = harness._load("metrics", m["name"])
        assert spec["reader"] == "readers.setup:value"
        keys.add(spec["args"]["key"])
    assert keys == set(sr.METRIC_KEYS)
