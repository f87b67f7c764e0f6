"""Causal event tracing + flight recorder (ISSUE 13).

Three layers:

* event-bus semantics — disabled no-op, deterministic sampling, span
  pairing on every exit path, ring boundedness under an event storm,
  cross-thread appends;
* trace export + grammar — the exporter repairs ring-evicted halves of
  B/E and async pairs, and ``validate_trace`` enforces the drill grammar
  (every B matched on its tid, async ids balanced);
* the flight recorder — dump contents, the exactly-once ``key=`` guard,
  and the bounded-ledger fix (a uid evicted from ``RequestManager.done``
  still resolves through the recorder's retained terminal spans).

Slow wrappers at the bottom run ``tools/trace_drill.py`` (storm trace,
abort dump, disabled-no-events) and the ``obs_drill`` tracing-overhead
budget; the CLIs are the invariant authority.
"""

import json
import os
import sys
import threading

import pytest

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
TOOLS = os.path.join(REPO, "tools")
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)

from deepspeed_tpu.observability import (configure_tracing,  # noqa: E402
                                         flight_dump, get_bus,
                                         get_flight_recorder,
                                         set_flight_recorder, trace_export,
                                         validate_trace)
from deepspeed_tpu.observability.events import EventBus  # noqa: E402
from deepspeed_tpu.observability.trace import FlightRecorder  # noqa: E402

pytestmark = pytest.mark.obs


@pytest.fixture()
def traced(tmp_path):
    """Tracing on for the test, reliably off (and clean) after it —
    tier-1 runs everything in one process."""
    bus = configure_tracing(enabled=True, ring_size=512, sample=1,
                            dump_dir=str(tmp_path / "flight"),
                            retain_terminal=8)
    bus.clear()
    yield bus
    configure_tracing(enabled=False)
    bus.clear()


# ---------------------------------------------------------------------------
# event bus
# ---------------------------------------------------------------------------
class TestEventBus:
    def test_disabled_records_nothing(self):
        bus = EventBus(enabled=False)
        bus.instant("c", "n")
        bus.begin("c", "n")
        with bus.span("c", "s"):
            pass
        assert bus.total_events() == 0
        assert bus.mint_trace() is None

    def test_enabled_records_typed_events(self):
        bus = EventBus(enabled=True, ring_size=64)
        t = bus.mint_trace()
        assert t is not None
        bus.async_begin("request", "request", t, args={"uid": 1})
        bus.instant("c", "mark")
        bus.async_end("request", "request", t)
        evs = bus.events()
        assert [e.ph for e in evs] == ["b", "i", "e"]
        assert evs[0].trace_id == t and evs[0].tid == threading.get_ident()
        assert evs[0].ts <= evs[1].ts <= evs[2].ts

    def test_sampling_is_deterministic(self):
        bus = EventBus(enabled=True, sample=4)
        minted = [bus.mint_trace() for _ in range(16)]
        kept = [t for t in minted if t is not None]
        assert len(kept) == 4                 # exactly every 4th id
        assert all(t % 4 == 0 for t in kept)

    def test_span_closes_on_exception(self):
        bus = EventBus(enabled=True)
        with pytest.raises(ValueError):
            with bus.span("c", "op"):
                raise ValueError("boom")
        evs = bus.events()
        assert [e.ph for e in evs] == ["B", "E"]
        assert "boom" in evs[1].args["error"]

    def test_ring_bounded_under_10k_storm(self):
        bus = EventBus(enabled=True, ring_size=256)
        for i in range(10_000):
            bus.instant("storm", "evt", args={"i": i})
        assert bus.total_events() == 256
        # the ring keeps the NEWEST events
        assert bus.events()[-1].args["i"] == 9_999
        assert bus.events()[0].args["i"] == 10_000 - 256

    def test_cross_thread_appends_and_snapshot(self):
        bus = EventBus(enabled=True, ring_size=4096)
        stop = threading.Event()

        def writer(k):
            i = 0
            while not stop.is_set():
                bus.instant("t", "evt", args={"k": k, "i": i})
                i += 1

        threads = [threading.Thread(target=writer, args=(k,))
                   for k in range(4)]
        [t.start() for t in threads]
        try:
            for _ in range(50):               # snapshots race the writers
                evs = bus.events()
                assert all(e.cat == "t" for e in evs)
        finally:
            stop.set()
            [t.join(timeout=5) for t in threads]
        assert bus.total_events() <= 4096

    def test_configure_mutates_in_place(self, tmp_path):
        cached = get_bus()                    # a call site's cached ref
        assert cached.enabled is False
        configure_tracing(enabled=True, ring_size=128,
                          dump_dir=str(tmp_path))
        try:
            assert cached.enabled is True and cached.ring_size == 128
            assert get_flight_recorder() is not None
        finally:
            configure_tracing(enabled=False)
        assert cached.enabled is False and get_flight_recorder() is None
        cached.clear()


# ---------------------------------------------------------------------------
# export + grammar
# ---------------------------------------------------------------------------
class TestTraceExport:
    def test_export_is_grammar_valid(self):
        bus = EventBus(enabled=True)
        t = bus.mint_trace()
        bus.async_begin("request", "request", t)
        with bus.span("batcher", "step"):
            bus.instant("engine", "mark")
        bus.async_end("request", "request", t)
        doc = trace_export(bus)
        assert validate_trace(doc) == []
        assert len(doc["traceEvents"]) == 5
        assert doc["otherData"]["enabled"] is True

    def test_orphans_are_repaired(self):
        bus = EventBus(enabled=True)
        bus.end("c", "stray")                 # E with no B: dropped
        bus.begin("c", "open")                # B with no E: closed
        bus.async_end("a", "x", 7)            # stray async e: dropped
        bus.async_begin("a", "y", 8)          # open async b: closed
        doc = trace_export(bus)
        assert validate_trace(doc) == []
        phs = sorted(e["ph"] for e in doc["traceEvents"])
        assert phs == ["B", "E", "b", "e"]
        synth = [e for e in doc["traceEvents"]
                 if e.get("args", {}).get("synthetic_end")]
        assert len(synth) == 2

    def test_validator_catches_violations(self):
        base = {"cat": "c", "name": "n", "ts": 1, "pid": 1, "tid": 1}
        assert validate_trace({}) != []
        assert validate_trace(
            {"traceEvents": [{**base, "ph": "E"}]})        # E w/o B
        assert validate_trace(
            {"traceEvents": [{**base, "ph": "b"}]})        # b w/o id or e
        assert validate_trace(
            {"traceEvents": [{**base, "ph": "Z"}]})        # unknown phase
        assert validate_trace(
            {"traceEvents": [{**base, "ph": "i", "ts": -5}]})  # bad ts
        ok = [{**base, "ph": "B"}, {**base, "ph": "E", "ts": 2},
              {**base, "ph": "b", "id": 1},
              {**base, "ph": "e", "id": 1, "ts": 3}]
        assert validate_trace({"traceEvents": ok}) == []


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
class TestFlightRecorder:
    def test_dump_carries_events_and_terminals(self, tmp_path):
        bus = EventBus(enabled=True)
        rec = FlightRecorder(bus, str(tmp_path), retain_terminal=4)
        bus.instant("resilience", "bad_step", args={"step": 3})
        rec.record_terminal(11, {"uid": 11, "state": "completed"})
        path = rec.dump("unit", extra={"why": "test"})
        assert path and os.path.exists(path)
        doc = json.load(open(path))
        assert doc["reason"] == "unit" and doc["extra"] == {"why": "test"}
        assert validate_trace(doc["trace"]) == []
        assert doc["terminal_spans"]["11"]["state"] == "completed"
        names = [e["name"] for e in doc["trace"]["traceEvents"]]
        assert "bad_step" in names

    def test_key_dedups_one_incident(self, tmp_path):
        rec = FlightRecorder(EventBus(enabled=True), str(tmp_path))
        p1 = rec.dump("abort", key="abort-step5")
        p2 = rec.dump("abort", key="abort-step5")   # second layer, same
        p3 = rec.dump("abort", key="abort-step6")   # a NEW incident
        assert p1 and p2 is None and p3
        assert rec.dumps == 2

    def test_terminal_retention_is_bounded(self, tmp_path):
        rec = FlightRecorder(EventBus(), str(tmp_path), retain_terminal=3)
        for uid in range(10):
            rec.record_terminal(uid, {"uid": uid})
        assert rec.terminal_trace(0) is None
        assert sorted(rec.terminal_spans()) == [7, 8, 9]

    def test_flight_dump_helper_without_recorder(self):
        set_flight_recorder(None)
        assert flight_dump("nothing") is None


# ---------------------------------------------------------------------------
# bounded terminal ledger + recorder fallback (the ISSUE 13 fix)
# ---------------------------------------------------------------------------
class TestBoundedLedger:
    def _manager(self, max_done):
        from deepspeed_tpu.serving.manager import RequestManager

        return RequestManager(max_queue_depth=64, max_done_history=max_done)

    def test_eviction_keeps_traces_resolvable(self, traced):
        mgr = self._manager(max_done=2)
        uids = [mgr.submit([1, 2, 3]) for _ in range(6)]
        for u in uids:
            assert mgr.cancel(u)
        assert len(mgr.done) == 2             # ledger bounded
        for u in uids:                        # ALL uids still answer
            assert mgr.resolve(u) == "cancelled"
            tr = mgr.trace(u)
            assert tr is not None and tr["state"] == "cancelled"

    def test_eviction_without_recorder_is_bounded_but_forgets(self):
        mgr = self._manager(max_done=2)
        uids = [mgr.submit([1, 2, 3]) for _ in range(4)]
        for u in uids:
            mgr.cancel(u)
        assert len(mgr.done) == 2
        assert mgr.resolve(uids[-1]) == "cancelled"
        assert mgr.resolve(uids[0]) is None   # documented: no recorder

    def test_request_track_events_balance(self, traced):
        mgr = self._manager(max_done=64)
        u = mgr.submit([1, 2, 3, 4])
        mgr.cancel(u)
        doc = trace_export(traced)
        assert validate_trace(doc) == []
        req = [e for e in doc["traceEvents"] if e["cat"] == "request"]
        assert [e["ph"] for e in req] == ["b", "e"]
        assert req[0]["args"]["uid"] == u
        assert req[1]["args"]["state"] == "cancelled"

    def test_queued_uid_membership_mirror(self):
        # the router's GIL-atomic liveness probe: a uid is ALWAYS in at
        # least one of _queued_uids/active/done across its lifecycle
        mgr = self._manager(max_done=8)
        u = mgr.submit([1, 2])
        assert u in mgr._queued_uids
        req = mgr.queue[0]
        mgr.admit(req)
        assert u not in mgr._queued_uids and u in mgr.active
        mgr.release_fn = lambda uids: None
        mgr.complete(req)
        assert u in mgr.done and u not in mgr._queued_uids


# ---------------------------------------------------------------------------
# serving e2e: causal chain + /v1/trace over HTTP
# ---------------------------------------------------------------------------
def test_traced_serving_chain_and_http_export(tmp_path):
    import urllib.request

    import numpy as np

    from deepspeed_tpu.config.config import ServingConfig
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import TransformerLM, get_preset
    from deepspeed_tpu.observability import MetricsRegistry
    from deepspeed_tpu.serving import ContinuousBatcher

    bus = configure_tracing(enabled=True, ring_size=2048, sample=1,
                            dump_dir=str(tmp_path / "flight"))
    bus.clear()
    try:
        eng = InferenceEngineV2(TransformerLM(get_preset("tiny")),
                                max_sequences=8, max_seq_len=128,
                                block_size=16)
        b = ContinuousBatcher(eng, ServingConfig(
            prefill_chunk=32, default_max_new_tokens=4),
            registry=MetricsRegistry())
        rng = np.random.default_rng(0)
        uids = [b.submit(rng.integers(0, 250, 24)) for _ in range(3)]
        b.pump(max_steps=100)
        assert all(b.manager.resolve(u) == "completed" for u in uids)
        # per-request async track spans serving + batcher subsystems, and
        # joins the engine's put spans by uid
        req = [e for e in bus.events(["request"])]
        by_trace = {}
        for e in req:
            if e.args and "subsys" in e.args:
                by_trace.setdefault(e.trace_id, set()).add(
                    e.args["subsys"])
        assert by_trace and all({"serving", "batcher"} <= s
                                for s in by_trace.values())
        eng_uids = set()
        for e in bus.events(["engine"]):
            if e.ph == "B" and e.args:
                eng_uids.update(e.args.get("uids", ()))
        assert set(uids) <= eng_uids
        # the /v1/trace mount serves the same document over HTTP
        srv = b.serve_metrics_http()
        try:
            resp = urllib.request.urlopen(srv.url + "/v1/trace", timeout=10)
            doc = json.loads(resp.read().decode())
        finally:
            b.close()
        assert resp.status == 200
        assert validate_trace(doc) == []
        assert any(e["cat"] == "batcher" and e["name"] == "step"
                   for e in doc["traceEvents"])
    finally:
        configure_tracing(enabled=False)
        bus.clear()


# ---------------------------------------------------------------------------
# drill wrappers (slow; the CLI is the invariant authority)
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.parametrize("scenario", ["storm-trace", "abort-dump",
                                      "disabled-no-events"])
def test_trace_drill_scenarios(scenario, tmp_path):
    from trace_drill import run_scenario

    verdict = run_scenario(scenario, workdir=str(tmp_path))
    assert verdict["ok"], json.dumps(verdict, indent=2, default=str)


@pytest.mark.slow
def test_tracing_overhead_budget(tmp_path):
    from obs_drill import run_scenario

    verdict = run_scenario("tracing-overhead", workdir=str(tmp_path))
    assert verdict["ok"], json.dumps(verdict, indent=2, default=str)
