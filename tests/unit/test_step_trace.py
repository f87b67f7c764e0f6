"""The one span path: ``EventBus.span`` always enters a profiler annotation
``ds.<cat>.<name>``, records into the rings only when tracing is enabled,
and the fused train step's spans reach the profiler's trace nested and
numbered, without a device sync."""

import glob

import jax
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import TransformerLM, get_preset
from deepspeed_tpu.observability import events
from deepspeed_tpu.observability.events import EventBus
from deepspeed_tpu.parallel import build_mesh

# these cases count compiles, cache misses and build seconds: the run's
# persistent compile cache (tests/conftest.py) stays off around them
pytestmark = pytest.mark.usefixtures("no_compile_cache")


class Recorder:
    """Stands in for ``TraceAnnotation``: the order of enters and exits."""

    log = []

    def __init__(self, name, **kw):
        self.name, self.kw = name, kw

    def __enter__(self):
        Recorder.log.append(("enter", self.name, self.kw))
        return self

    def __exit__(self, *exc):
        Recorder.log.append(("exit", self.name, self.kw))
        return False


@pytest.fixture
def recorder(monkeypatch):
    Recorder.log = []
    monkeypatch.setattr(events, "TraceAnnotation", Recorder)
    return Recorder.log


@pytest.mark.parametrize("enabled", [False, True])
def test_span_always_annotates_and_rings_only_when_enabled(recorder, enabled):
    bus = EventBus(enabled=enabled)
    with bus.span("train", "step", step=7):
        with bus.span("train", "dispatch"):
            pass
    assert [(a, n) for a, n, _ in recorder] == [
        ("enter", "ds.train.step"), ("enter", "ds.train.dispatch"),
        ("exit", "ds.train.dispatch"), ("exit", "ds.train.step")]
    assert recorder[0][2] == {"step": 7} and recorder[1][2] == {}
    got = [(e.ph, e.cat, e.name) for e in bus.events()]
    if enabled:
        assert got == [("B", "train", "step"), ("B", "train", "dispatch"),
                       ("E", "train", "dispatch"), ("E", "train", "step")]
    else:
        assert got == [] and bus.total_events() == 0


def test_span_closes_on_an_exception(recorder):
    bus = EventBus(enabled=True)
    with pytest.raises(ValueError):
        with bus.span("train", "step"):
            raise ValueError("x")
    assert [a for a, _, _ in recorder] == ["enter", "exit"]
    assert bus.events()[-1].args["error"].startswith("ValueError")


def test_disabled_span_is_the_real_annotation():
    ann = EventBus(enabled=False).span("train", "step", step=1)
    assert isinstance(ann, jax.profiler.TraceAnnotation)
    with ann:
        pass


def _engine():
    eng, *_ = ds.initialize(
        model=TransformerLM(get_preset("tiny")),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True}, "steps_per_print": 10 ** 9,
                "zero_optimization": {"stage": 0}},
        mesh=build_mesh(devices=jax.devices()[:1]))
    return eng


def test_fused_step_spans_nest_with_step_numbers(recorder):
    eng = _engine()
    batch = {"input_ids": np.zeros((2, 32), np.int32)}
    for _ in range(2):
        eng.fused_train_step(batch)
    # the engine's build: ``ds.setup.initialize`` around its four parts
    parts = ["config", "engine.plan", "engine.state", "engine.rest"]
    want = [("enter", "ds.setup.initialize", {})] + [
        (act, f"ds.setup.{part}", {}) for part in parts
        for act in ("enter", "exit")] + [("exit", "ds.setup.initialize", {})]
    for step in (0, 1):
        # the program's first call, and only that, is a build
        build = [(act, "ds.train.build", {"program": "ds_train_step"})
                 for act in ("enter", "exit")] if step == 0 else []
        want += [("enter", "ds.train.step", {"step": step}),
                 ("enter", "ds.train.put_batch", {}),
                 ("exit", "ds.train.put_batch", {}),
                 ("enter", "ds.train.dispatch", {}),
                 *build,
                 ("exit", "ds.train.dispatch", {}),
                 ("enter", "ds.train.commit", {}),
                 ("exit", "ds.train.commit", {}),
                 ("exit", "ds.train.step", {"step": step})]
    assert recorder == want


def test_fused_steps_in_a_live_profiler_trace_without_a_sync(
        tmp_path, monkeypatch):
    """Three steps under ``jax.profiler.start_trace``: the host plane holds
    ``ds.train.step`` > ``put_batch`` / ``dispatch`` / ``commit`` with the
    step numbers, and none of them read a device value back (every such read
    goes through ``ArrayImpl._value``)."""
    from jax._src.array import ArrayImpl
    from jax.profiler import ProfileData

    eng = _engine()
    batch = {"input_ids": np.zeros((2, 32), np.int32)}
    eng.fused_train_step(batch)                     # compile outside
    reads = []
    real = ArrayImpl._value
    monkeypatch.setattr(ArrayImpl, "_value", property(
        lambda self: reads.append(self.shape) or real.fget(self)))
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            loss = eng.fused_train_step(batch)
        synced = list(reads)
        jax.block_until_ready(loss)
    finally:
        jax.profiler.stop_trace()
    assert synced == []
    assert np.isfinite(float(loss)) and reads     # the probe does see reads
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("ds.train."):
                        spans.append((int(ev.start_ns),
                                      int(ev.start_ns + ev.duration_ns),
                                      ev.name, dict(ev.stats)))
    spans.sort()
    steps = [s for s in spans if s[2] == "ds.train.step"]
    assert [s[3]["step"] for s in steps] == [1, 2, 3]
    for lo, hi, _, _ in steps:
        inner = [s[2] for s in spans
                 if s[2] != "ds.train.step" and lo <= s[0] and s[1] <= hi]
        assert inner == ["ds.train.put_batch", "ds.train.dispatch",
                         "ds.train.commit"]
