"""``readers/program.py`` on a trace recorded on a TPU v5e by
``testdata/record_program_trace.py`` (``program_tiny.*``), on small synthetic
inputs with answers by hand, and on a program that has none of what it
reads."""

import gzip
import json
import os
import shutil

import numpy as np
import pytest

from benchmarks import trace_reduce as tr
from benchmarks.readers import program as pg
from benchmarks.trace_reduce import Op

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "..", "testdata")
MS = 1_000_000


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    with gzip.open(os.path.join(DATA, "program_tiny.json.gz"), "rt") as f:
        facts = json.load(f)
    path = str(tmp_path_factory.mktemp("trace") / "program_tiny.xplane.pb")
    with gzip.open(os.path.join(DATA, "program_tiny.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    trace = tr.load_xplane(path)
    return facts, trace, pg.load_program_events(path), tr.window(trace)


def test_spans_and_modules_of_the_recorded_trace(recorded):
    facts, _, events, win = recorded
    n = facts["traced_steps"]
    steps = [s for s in events["spans"] if s.name == pg.STEP_SPAN]
    assert len(steps) == n
    numbers = [int(s.label) for s in steps]
    assert numbers == list(range(numbers[0], numbers[0] + n))
    for s in steps:
        inner = [c.name for c in events["spans"]
                 if c is not s and s.start <= c.start and c.end <= s.end
                 and c.name != "ds.gc"]
        assert inner == ["ds.train.put_batch", "ds.train.dispatch",
                         "ds.train.commit"]
    name, runs = pg.step_modules(events["modules"], win)
    assert name == facts["name"] == "ds_train_step" and len(runs) == n
    assert pg.last_traced_step(events["spans"]) == [numbers[-1]]


def test_device_time_by_scope_of_the_recorded_trace(recorded):
    facts, trace, events, win = recorded
    a = pg.trace_part(trace, events, win, facts["hlo_text"])
    assert a["module"] == "ds_train_step"
    by = a["device_ms_by_scope"]
    assert by.pop("unscoped_ops") == {} and a["unscoped_device_ms"] == 0
    assert {"attn", "mlp", "lm_head", "loss", "optimizer", "embed",
            "layers"} <= set(by)
    assert set(by["attn"]) == {"forward", "backward"}
    assert set(by["optimizer"]) == {"forward"}
    groups = [a[f"{g}_device_ms"] for g in ("attn", "mlp", "head_loss",
                                            "optimizer")]
    assert all(g > 0 for g in groups)
    # self times inside the module's runs: they cannot exceed the runs, and
    # on this small program the gaps between operations are the rest
    assert 0.7 * a["train_step_device_ms"] < sum(groups) \
        <= a["train_step_device_ms"] * 1.0001
    # the flash kernels are custom calls under attn, forward and backward
    scopes = pg.instruction_scopes(facts["hlo_text"])
    flash = [scopes[o.name] for o in trace.devices["/device:TPU:0"]
             if "tpu_custom_call" in o.label]
    assert flash and {s for s, _ in flash} == {"attn"}
    assert {d for _, d in flash} == {"forward", "backward"}


def test_host_phases_gaps_and_clock_offset_of_the_recorded_trace(recorded):
    facts, trace, events, win = recorded
    a = pg.trace_part(trace, events, win, facts["hlo_text"])
    ph = a["host_phases_ms"]
    assert a["train_host_ms"] == ph["ds.train.step"]
    assert ph["ds.train.step"] >= ph["ds.train.put_batch"] \
        + ph["ds.train.dispatch"] + ph["ds.train.commit"] - 0.2
    assert {name for name, _ in a["idle_gaps_by_span"]} <= {
        "(no span)", "ds.train.step", "ds.train.put_batch",
        "ds.train.dispatch", "ds.train.commit", "ds.gc"}
    off = a["device_start_after_dispatch_ms"]
    assert off["runs"] == facts["traced_steps"]
    assert -5.0 < off["min"] <= off["median"] <= off["max"] < 50.0


def test_the_period_in_which_the_profiler_stopped_is_left_out(recorded):
    from deepspeed_tpu.observability import steplog

    facts, _, events, _ = recorded
    rows, pauses = np.array(facts["steps"]), np.array(facts["pauses"])
    pauses = pauses.reshape(-1, 3)
    stop = pg.last_traced_step(events["spans"])
    with_it = steplog.slow_steps(rows, pauses)
    without = steplog.slow_steps(rows, pauses, exclude=stop)
    assert stop[0] in [s["step"] for s in with_it["slow"]]
    assert stop[0] not in [s["step"] for s in without["slow"]]
    assert without["steps"] == with_it["steps"] - 1


SMALL_HLO = """
HloModule jit_ds_train_step

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %m = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(ds_train_step)/jvp(layers)/while/body/mlp/mul"}
  %n = f32[8]{0} add(%m, %p), metadata={op_name="jit(ds_train_step)/jvp(layers)/while/body/mlp/add"}
  ROOT %d = f32[2,8]{1,0} dynamic-update-slice(%n), metadata={op_name="jit(ds_train_step)/jvp(layers)/while/body/dynamic_update_slice"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.1 = f32[2,8]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(ds_train_step)/jvp(layers)/while/body/dynamic_update_slice"}
  %copy-start.1 = (f32[2,8]{1,0}, f32[2,8]{1,0}) copy-start(%fusion.1)
  %copy-done.1 = f32[2,8]{1,0} copy-done(%copy-start.1)
  %broadcast.1 = f32[8]{0} broadcast(%constant.1), dimensions={}
  %g = f32[8]{0} add(%broadcast.1, %a), metadata={op_name="jit(ds_train_step)/transpose(jvp(layers))/while/body/attn/add_any"}
  %loop = f32[8]{0} add(%g, %g), metadata={op_name="jit(ds_train_step)/transpose(jvp(layers))/while/body/dynamic_slice"}
  %opt = f32[8]{0} multiply(%loop, %loop), metadata={op_name="jit(ds_train_step)/optimizer/mul"}
  ROOT %lost = f32[8]{0} negate(%constant.2)
}
"""


def test_scopes_from_hlo_text_by_hand():
    assert pg.scope_of("jit(f)/jvp(layers)/while/body/attn/dot_general") \
        == ("attn", "forward")
    assert pg.scope_of("jit(f)/transpose(jvp(lm_head))/dot_general") \
        == ("lm_head", "backward")
    assert pg.scope_of("jit(f)/jvp(layers)/while/body/dynamic_slice") \
        == ("layers", "forward")
    assert pg.scope_of("reduce_sum") == (None, "forward")
    s = pg.instruction_scopes(SMALL_HLO)
    assert s["fusion.1"] == ("mlp", "forward")        # what it fuses
    assert s["copy-done.1"] == ("mlp", "forward")     # its operand's
    assert s["broadcast.1"] == ("attn", "backward")   # its user's
    assert s["loop"] == ("layers", "backward")        # the loop's own
    assert s["opt"] == ("optimizer", "forward")
    assert s["lost"] == (None, "forward")             # nothing to go by


def test_device_ms_by_scope_by_hand():
    scopes = pg.instruction_scopes(SMALL_HLO)
    runs = [Op("jit_ds_train_step(1)", 0, 10 * MS),
            Op("jit_ds_train_step(1)", 20 * MS, 30 * MS)]
    one = [("while.1", 0, 8), ("fusion.1", 0, 4), ("g", 4, 6), ("loop", 6, 8),
           ("opt", 8, 9), ("lost", 9, 10)]
    ops = sorted([Op(n, (off + a) * MS, (off + b) * MS)
                  for off in (0, 20) for n, a, b in one]
                 + [Op("fusion.1", 12 * MS, 14 * MS)],    # outside the runs
                 key=lambda o: (o.start, -o.end))
    by = pg.device_ms_by_scope(ops, runs, scopes)
    assert by == {"mlp": {"forward": 4.0}, "attn": {"backward": 2.0},
                  "layers": {"backward": 2.0}, "optimizer": {"forward": 1.0},
                  "unscoped": {"forward": 1.0}, "unscoped_ops": {"lost": 1.0}}
    name, found = pg.step_modules(
        runs + [Op("jit_convert(2)", 11 * MS, 12 * MS)], (0, 30 * MS))
    assert name == "ds_train_step" and found == runs


def test_a_run_without_a_slow_step_still_reports_every_steplog_metric(
        monkeypatch):
    """A result line carries every metric the manifest lists for the cell:
    with an even record the host's share of the (absent) excess reads 0
    beside an excess share of 0, and is not left out."""
    from deepspeed_tpu.observability import steplog

    log = steplog.StepLog(size=64)
    for i in range(20):
        t = 0.141 * i
        log.step(i, t, t + 0.001, t + 0.0015)
    monkeypatch.setattr(steplog, "_LOG", log)
    part = pg._steplog_part({"values": {"steps": 20}}, [19])
    assert part["steplog"]["slow"] == []
    assert part["slow_step_excess_share"] == 0.0
    assert part["slow_step_host_share"] == 0.0
    assert part["gc_pause_ms"] == 0.0


def test_a_program_without_any_of_it_gives_nothing(monkeypatch, tmp_path):
    """The parent of this PR: no ``ds.`` span, another module name, no
    ``steplog``. Every metric is left out and nothing is raised."""
    import builtins

    real_import = builtins.__import__

    def no_steplog(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "deepspeed_tpu.observability" and "steplog" in fromlist:
            raise ImportError("no steplog in this program")
        return real_import(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_steplog)
    old = os.path.join(DATA, "tiny_tpu.xplane.pb")      # recorded by PR 24
    monkeypatch.setattr(pg, "xplane_path", lambda cell: old)
    trace = tr.load_xplane(old)
    ctx = {"cell": {"name": "x"}, "values": {"steps": 5}, "trace": trace,
           "reduced": tr.reduce(trace)}
    for key in ("train_step_device_ms", "attn_device_ms", "train_host_ms",
                "unscoped_device_ms", "slow_step_excess_share",
                "gc_pause_ms", "step_program_temp_bytes"):
        assert pg.value(ctx, key) is None
    assert ctx["program"] == {}
    # and with no trace at all
    monkeypatch.setattr(pg, "xplane_path", lambda cell: None)
    assert pg.value({"cell": {"name": "x"}, "values": {}, "trace": None,
                     "reduced": {}}, "train_host_ms") is None
