"""Set-up told by the program (``observability/steplog.py``): the build
record the ``jax.monitoring`` listeners keep, the set-up spans, and what a
step program's row says of its first two calls."""

import glob
import threading

import jax
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import TransformerLM, get_preset
from deepspeed_tpu.observability import steplog
from deepspeed_tpu.observability.events import EventBus
from deepspeed_tpu.parallel import build_mesh

# these cases count compiles, cache misses and build seconds: the run's
# persistent compile cache (tests/conftest.py) stays off around them
pytestmark = pytest.mark.usefixtures("no_compile_cache")

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"


@pytest.fixture
def record(monkeypatch):
    """An empty build record for one test; the process's own is put back."""
    monkeypatch.setattr(steplog, "_BUILDS", {})
    monkeypatch.setattr(steplog, "_build_names", set())
    return steplog._BUILDS


def build(name, hit=True):
    """The events of one program's build as jax 0.9.0 sends them: the trace
    under the function's name, lowering and backend compile under the
    module's, the cache's events without a name inside the compile."""
    steplog._on_duration(TRACE, 0.25, fun_name=name)
    steplog._on_duration(LOWER, 0.5, fun_name=f"jit({name})")
    steplog._on_event("/jax/compilation_cache/compile_requests_use_cache")
    if hit:
        steplog._on_event(HIT)
        steplog._on_duration(CACHE_SAVED, 3.0)
        steplog._on_duration(CACHE_READ, 0.125)
    else:
        steplog._on_event(MISS)
    steplog._on_duration(COMPILE, 1.0, fun_name=f"jit({name})")


def test_one_row_a_name_and_span_with_every_phase(record):
    build("f")
    build("f", hit=False)
    build("g")
    rows = steplog.builds()
    assert [(r["name"], r["span"]) for r in rows] == [("f", "outside"),
                                                      ("g", "outside")]
    f, g = rows
    assert (f["traces"], f["lowers"], f["compiles"]) == (2, 2, 2)
    assert (f["trace_s"], f["lower_s"], f["compile_s"]) == (0.5, 1.0, 2.0)
    assert (f["cache_hits"], f["cache_read_s"], f["cache_misses"]) \
        == (1, 0.125, 1)
    assert (g["cache_hits"], g["cache_read_s"], g["cache_misses"]) \
        == (1, 0.125, 0)
    assert f["first"] <= f["last"] <= g["first"]
    assert steplog.builds("g") == [g]


@pytest.mark.parametrize("sent,kept", [
    ({"fun_name": "jit(f)"}, "f"), ({"fun_name": "f"}, "f"), ({}, "?"),
    ({"fun_name": "jit(jit(f))"}, "jit(f)"), ({"fun_name": "pmap(f)"},
                                              "pmap(f)")])
def test_a_module_and_its_function_are_one_name(record, sent, kept):
    steplog._on_duration(LOWER, 0.5, **sent)
    assert [r["name"] for r in steplog.builds()] == [kept]


def test_name_513_goes_under_other(record):
    for i in range(steplog.BUILD_NAMES + 40):
        steplog._on_duration(TRACE, 0.001, fun_name=f"f{i}")
    steplog._on_duration(TRACE, 0.001, fun_name="f3")      # a name it has
    rows = {r["name"]: r for r in steplog.builds()}
    assert len(rows) == steplog.BUILD_NAMES + 1
    assert rows["_other_"]["traces"] == 40 and rows["f3"]["traces"] == 2
    assert f"f{steplog.BUILD_NAMES}" not in rows


def test_the_innermost_recorded_span_names_the_row(record):
    bus = EventBus()
    with steplog.span(bus, "train", "dispatch"):
        build("f")
        with steplog.span(bus, "train", "build", program="f"):
            build("f")
        build("g")
    build("f")
    assert [(r["name"], r["span"], r["lowers"]) for r in steplog.builds()] \
        == [("f", "ds.train.dispatch", 1), ("f", "ds.train.build", 1),
            ("g", "ds.train.dispatch", 1), ("f", "outside", 1)]


def test_a_span_closes_on_an_exception_and_leaves_the_stack(record):
    with pytest.raises(ValueError):
        with steplog.span(EventBus(), "train", "build"):
            raise ValueError("x")
    build("f")
    assert steplog.builds()[0]["span"] == "outside"


def test_spans_and_cache_events_stay_on_their_thread(record):
    """Another thread's open span does not name this thread's builds, and
    its cache hit waits for its own backend compile."""
    opened, go = threading.Event(), threading.Event()

    def other():
        with steplog.span(EventBus(), "train", "build"):
            steplog._on_event(HIT)
            steplog._on_duration(CACHE_READ, 0.5)
            opened.set()
            go.wait(10)
            steplog._on_duration(COMPILE, 1.0, fun_name="jit(theirs)")

    t = threading.Thread(target=other)
    t.start()
    assert opened.wait(10)
    steplog._on_duration(COMPILE, 1.0, fun_name="jit(mine)")
    go.set()
    t.join()
    mine, theirs = steplog.builds()
    assert (mine["span"], mine["cache_hits"]) == ("outside", 0)
    assert (theirs["span"], theirs["cache_hits"], theirs["cache_read_s"]) \
        == ("ds.train.build", 1, 0.5)


def test_the_hook_registers_once_and_sees_a_real_build(record):
    from jax._src import monitoring

    steplog.install_build_hook()
    steplog.install_build_hook()
    assert monitoring.get_event_duration_listeners().count(
        steplog._on_duration) == 1
    assert monitoring.get_event_listeners().count(steplog._on_event) == 1
    seen = steplog.build_events()["duration"]

    def a_program_of_this_test(x):
        return x * 2 + 1

    jax.jit(a_program_of_this_test)(np.ones(3, np.float32))
    row, = steplog.builds("a_program_of_this_test")
    assert (row["traces"], row["lowers"], row["compiles"]) == (1, 1, 1)
    assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["compile_s"] > 0
    assert steplog.build_events()["duration"] >= seen + 3


def _engine():
    eng, *_ = ds.initialize(
        model=TransformerLM(get_preset("tiny")),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True}, "steps_per_print": 10 ** 9,
                "zero_optimization": {"stage": 0}},
        mesh=build_mesh(devices=jax.devices()[:1]))
    return eng


def _lowers_by_span(name=None):
    """The process's record is shared by every test of the process: a test
    reads what it added."""
    out = {}
    for r in steplog.builds(name):
        out[r["span"]] = out.get(r["span"], 0) + r["lowers"]
    return out


def _added(before, after):
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def test_setup_spans_nest_and_self_times_add_up():
    n = len(steplog.setup())
    t0 = steplog.time.perf_counter()
    lowers = _lowers_by_span()
    _engine()
    lowers = _added(lowers, _lowers_by_span())
    first = steplog.setup()[0]
    assert first["name"] == "ds.setup.import" and first["parent"] is None
    assert first["jax_preloaded"] in (True, False)
    assert 0 < first["end"] - first["start"] < 600
    rows = steplog.setup()[n:]
    assert [r["name"] for r in rows] == [
        "ds.setup.initialize", "ds.setup.config", "ds.setup.engine.plan",
        "ds.setup.engine.state", "ds.setup.engine.rest"]
    top, children = rows[0], rows[1:]
    assert top["parent"] is None
    assert all(c["parent"] == top["id"] for c in children)
    for a, b in zip(children, children[1:]):            # in order, disjoint
        assert top["start"] <= a["start"] <= a["end"] <= b["start"]
    assert t0 <= top["start"] and children[-1]["end"] <= top["end"]
    assert all(c["self_s"] == c["end"] - c["start"] for c in children)
    assert top["self_s"] + sum(c["self_s"] for c in children) \
        == pytest.approx(top["end"] - top["start"], abs=1e-9)
    assert top["self_s"] >= 0       # the engine module's first import
    # the programs the engine's build built are under its spans (a mesh
    # for one device is built before it, outside)
    assert {"ds.setup.engine.state"} <= set(lowers) <= {
        "ds.setup.engine.plan", "ds.setup.engine.state", "outside"}


def test_an_open_setup_span_has_no_end_yet():
    with steplog.span(EventBus(), "setup", "a_test", why="open"):
        row = steplog.setup()[-1]
        assert (row["name"], row["end"], row["self_s"], row["why"]) \
            == ("ds.setup.a_test", None, None, "open")
    assert steplog.setup()[-1]["end"] is not None


def test_a_step_program_row_says_how_it_was_built(monkeypatch):
    eng = _engine()
    before = len(steplog.programs())
    lowers = _lowers_by_span("ds_train_step")
    batch = {"input_ids": np.zeros((2, 32), np.int32)}
    eng.fused_train_step(batch)
    row, = steplog.programs()[before:]
    assert row.first_call_s > 0 and row.second_call_s is None
    assert list(eng._uncaptured.values()) == [row]      # kept for one call
    eng.fused_train_step(batch)
    assert 0 < row.second_call_s < row.first_call_s
    assert eng._uncaptured == {}                        # and forgotten
    said = row.build()
    assert (said["traces"], said["lowers"], said["compiles"]) == (1, 1, 1)
    assert said["trace_s"] + said["lower_s"] + said["compile_s"] \
        <= row.first_call_s
    assert _added(lowers, _lowers_by_span("ds_train_step")) \
        == {"ds.train.build": 1}
    # from the third call on the step takes the branch it took before the
    # row learned this: nothing watches it
    monkeypatch.setattr(eng, "_watched", None)
    calls = (row.first_call_s, row.second_call_s)
    for _ in range(1000):
        eng.fused_train_step(batch)
    assert (row.first_call_s, row.second_call_s) == calls
    assert row.build() == said


def test_a_second_lowering_inside_jit_shows_in_the_record_only():
    """A batch of another length under the same ``_fused_step_cache`` key:
    ``jax.jit`` lowers again, the step-program table gets no row (so
    ``step_program_builds_in_window`` would read 0), and the build record
    says 2."""
    eng = _engine()
    before = len(steplog.programs())
    for length in (32, 32, 48):
        eng.fused_train_step({"input_ids": np.zeros((2, length), np.int32)})
    row, = steplog.programs()[before:]
    assert len(eng._fused_step_cache) == 1
    assert row.build()["lowers"] == 2 and row.build()["compiles"] == 2
    # a reader's own look at the compiled program is kept apart
    assert row.memory_analysis()["argument"] > 0
    assert row.build()["lowers"] == 2
    assert steplog.INSPECT_SPAN in _lowers_by_span("ds_train_step")
    # an engine built later starts its own count
    eng2 = _engine()
    eng2.fused_train_step({"input_ids": np.zeros((2, 32), np.int32)})
    assert steplog.programs()[-1].build()["lowers"] == 1
    assert row.build()["lowers"] == 2


def test_the_first_steps_dispatch_holds_a_build_span_in_a_live_trace(
        tmp_path):
    from jax.profiler import ProfileData

    eng = _engine()
    batch = {"input_ids": np.zeros((2, 32), np.int32)}
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            loss = eng.fused_train_step(batch)
        jax.block_until_ready(loss)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                           ev.name, dict(ev.stats)) for ev in line.events
                          if ev.name.startswith("ds.train.")]
    spans.sort()
    dispatches = [s for s in spans if s[2] == "ds.train.dispatch"]
    builds = [s for s in spans if s[2] == "ds.train.build"]
    assert len(dispatches) == 2 and len(builds) == 1
    assert builds[0][3] == {"program": "ds_train_step"}
    assert dispatches[0][0] <= builds[0][0] and builds[0][1] \
        <= dispatches[0][1] < dispatches[1][0]
