"""Network serving front-end tests (``deepspeed_tpu/serving`` HTTP layer).

Three tiers:

* **wire protocol** (no engine): request/response JSON schema round-trip,
  tenant-priority resolution (api-key table, ``x-priority``), SSE framing
  (``sse_event`` and ``iter_sse`` must agree by construction), and the
  ShedError → 429/``Retry-After`` / oversize → 413 / deadline → 504
  status mapping;
* **HTTP over real sockets** (tiny engine): unary + streaming generate on
  the shared probe mux, 429 + ``Retry-After`` on a full queue, router
  failover when a replica enters DRAINING;
* **end-to-end acceptance**: N concurrent mixed-priority clients against
  a 2-replica router — ≥1 429 under an induced ``shed_storm``, a SIGTERM
  drain of one replica with its queued requests migrated to the sibling,
  every admitted uid resolving, pools restored. Real sockets throughout;
  no mocked transport.

The heavier storm drill lives in ``tools/serve_drill.py frontend-storm``
(slow-marked wrapper at the bottom).
"""

import http.client
import io
import json
import os
import signal
import threading
import time

import pytest

from deepspeed_tpu.config.config import (FrontendConfig, RouterConfig,
                                         ServingConfig)
from deepspeed_tpu.serving import (COMPLETED, DRAINING, ContinuousBatcher,
                                   FrontendError, GenerateClient, Replica,
                                   ReplicaRouter, ServingFrontend,
                                   ShedError)
from deepspeed_tpu.serving.protocol import (GENERATE_PATH, ProtocolError,
                                            iter_sse, parse_generate_request,
                                            response_for_record,
                                            shed_response, sse_event)

pytestmark = pytest.mark.frontend

_TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "tools")

TERMINAL = ("completed", "shed", "expired", "cancelled")


# ---------------------------------------------------------------------------
# wire protocol (no engine, no sockets)
# ---------------------------------------------------------------------------

class TestProtocol:
    CFG = FrontendConfig()

    def test_request_schema_roundtrip(self):
        body = json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 7,
                           "deadline_s": 2.5, "stream": True}).encode()
        r = parse_generate_request(body, {}, self.CFG)
        assert r.prompt == [1, 2, 3] and r.max_new_tokens == 7
        assert r.deadline_s == 2.5 and r.stream and r.priority == 0

    @pytest.mark.parametrize("body,status", [
        (b"{not json", 400),
        (json.dumps({"prompt": "a string"}).encode(), 400),
        (json.dumps({"prompt": []}).encode(), 400),
        (json.dumps({"prompt": [1, "x"]}).encode(), 400),
        (json.dumps({"prompt": [1], "max_new_tokens": 0}).encode(), 400),
        (json.dumps({"prompt": [1], "max_new_tokens": True}).encode(), 400),
        (json.dumps({"prompt": [1], "deadline_s": -1}).encode(), 400),
        (json.dumps({"prompt": list(range(9000))}).encode(), 413),
    ])
    def test_bad_requests_get_typed_4xx(self, body, status):
        with pytest.raises(ProtocolError) as ei:
            parse_generate_request(body, {}, self.CFG)
        assert ei.value.status == status
        assert "error" in ei.value.body()

    def test_tenant_priority_resolution(self):
        cfg = FrontendConfig(api_keys={"gold": 9}, default_priority=1,
                             max_header_priority=5, min_header_priority=-2)
        body = json.dumps({"prompt": [1]}).encode()
        # api key wins over everything
        assert parse_generate_request(
            body, {"x-api-key": "gold", "x-priority": "3"},
            cfg).priority == 9
        # header override when allowed
        assert parse_generate_request(
            body, {"x-priority": "3"}, cfg).priority == 3
        # ...but clamped both ways: self-PROMOTION can never outrank the
        # paying tenants, and the floor stops unbounded negative values
        # from minting per-priority metric labels
        assert parse_generate_request(
            body, {"x-priority": "999"}, cfg).priority == 5
        assert parse_generate_request(
            body, {"x-priority": "-2"}, cfg).priority == -2
        assert parse_generate_request(
            body, {"x-priority": "-999"}, cfg).priority == -2
        # body override
        assert parse_generate_request(
            json.dumps({"prompt": [1], "priority": 4}).encode(), {},
            cfg).priority == 4
        # default
        assert parse_generate_request(body, {}, cfg).priority == 1
        # override path closed
        off = FrontendConfig(allow_priority_header=False,
                             default_priority=1)
        assert parse_generate_request(
            body, {"x-priority": "3"}, off).priority == 1
        # tenant auth required
        gated = FrontendConfig(api_keys={"gold": 9}, require_api_key=True)
        with pytest.raises(ProtocolError) as ei:
            parse_generate_request(body, {"x-api-key": "wrong"}, gated)
        assert ei.value.status == 401

    def test_shed_maps_to_429_with_retry_after(self):
        status, headers, body = shed_response(
            ShedError("queue_full", retryable=True, retry_after_s=2.3))
        assert status == 429
        assert headers["Retry-After"] == "3"     # integer ceil on the wire
        assert body["error"]["retryable"] and \
            body["error"]["reason"] == "queue_full"
        status, headers, body = shed_response(
            ShedError("oversize", retryable=False))
        assert status == 413 and not body["error"]["retryable"]

    def test_terminal_record_status_mapping(self):
        ok = {"state": "completed", "tokens": [1, 2], "error": None}
        assert response_for_record(7, ok)[0] == 200
        shed = {"state": "shed", "tokens": [],
                "error": {"reason": "kv_pressure", "retryable": True,
                          "retry_after_s": 5.0}}
        status, headers, body = response_for_record(7, shed)
        assert status == 429 and headers["Retry-After"] == "5"
        assert body["id"] == 7
        assert response_for_record(7, {"state": "expired"})[0] == 504
        assert response_for_record(7, {"state": "cancelled"})[0] == 499

    def test_sse_framing_roundtrip(self):
        frames = (sse_event({"token": 5, "index": 0}, event="token")
                  + sse_event({"note": "no event name"})
                  + sse_event({"state": "completed"}, event="end"))
        # the exact frame grammar, not just the parse
        assert frames.startswith(b"event: token\ndata: ")
        assert frames.endswith(b"\n\n")
        evs = list(iter_sse(io.BytesIO(frames)))
        assert [e["event"] for e in evs] == ["token", None, "end"]
        assert evs[0]["data"] == {"token": 5, "index": 0}
        assert evs[2]["data"]["state"] == "completed"


def test_frontend_config_block_consumed():
    """`serving.frontend` / `serving.router` ride the root config; the
    front-end builder requires the explicit enable."""
    from deepspeed_tpu.config import DeepSpeedTpuConfig

    class _Backend:
        health = "ready"

        def report(self):
            return {}

    cfg = DeepSpeedTpuConfig(train_batch_size=8, serving={
        "enabled": True,
        "frontend": {"enabled": True, "api_keys": {"k": 3}},
        "router": {"failover_attempts": 2}})
    assert cfg.serving.router.failover_attempts == 2
    assert cfg.serving.router.migrate_on_drain
    fe = ServingFrontend.from_deepspeed_config(_Backend(), cfg)
    try:
        assert fe.cfg.api_keys == {"k": 3}
    finally:
        fe.close()
    with pytest.raises(ValueError, match="serving.frontend.enabled"):
        ServingFrontend.from_deepspeed_config(
            _Backend(), DeepSpeedTpuConfig(train_batch_size=8))


# ---------------------------------------------------------------------------
# HTTP over real sockets (tiny engine)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engines():
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import TransformerLM, get_preset

    # what the front end and the router do: the first replica attends with
    # the XLA twin; the second, which the two-replica cases (fail-over, the
    # storm) reach, keeps the kernel (interpreted here)
    return [InferenceEngineV2(TransformerLM(get_preset("tiny")),
                              max_sequences=8, max_seq_len=128,
                              block_size=16, decode_kernel=kernel)
            for kernel in ("xla", "pallas")]


def _batcher(engine, **kw):
    cfg = ServingConfig(**{"prefill_chunk": 32, "default_max_new_tokens": 4,
                           **kw})
    return ContinuousBatcher(engine, cfg)


def _pool_restored(engine):
    alloc = engine.state.allocator
    return (alloc.free_blocks == alloc.num_blocks
            and not engine.state.sequences)


@pytest.fixture()
def clean_pools(engines):
    yield
    for eng in engines:
        assert _pool_restored(eng), "test leaked KV blocks/sequences"


def test_unary_generate_on_shared_mux(engines, clean_pools):
    """POST /v1/generate next to /metrics + /readyz on ONE port; the
    response carries tokens, usage, and the span."""
    from deepspeed_tpu.observability import MetricsRegistry

    reg = MetricsRegistry()
    b = ContinuousBatcher(engines[0], ServingConfig(
        prefill_chunk=32, default_max_new_tokens=4), registry=reg)
    rep = Replica("solo", b).start()
    try:
        with ServingFrontend(rep, FrontendConfig(), registry=reg) as fe:
            cli = GenerateClient(fe.url, timeout_s=120)
            out = cli.generate(list(range(1, 17)), max_new_tokens=3)
            assert out["state"] == COMPLETED and len(out["tokens"]) == 3
            assert out["usage"] == {"prompt_tokens": 16,
                                    "completion_tokens": 3}
            assert out["span"]["ttft_ms"] is not None
            # same port: scrape + probes + state
            conn = http.client.HTTPConnection(fe.server.host,
                                              fe.server.port, timeout=10)
            conn.request("GET", "/metrics")
            scrape = conn.getresponse()
            text = scrape.read().decode()
            assert scrape.status == 200
            assert "serving_queue_depth" in text
            assert 'frontend_http_requests_total{code="200"} 1' in text
            conn.request("GET", "/readyz")
            assert conn.getresponse().read() and True
            conn.close()
            assert cli.state()["health"] == "ready"
    finally:
        rep.close()


def test_queue_full_surfaces_429_with_load_aware_retry_after(
        engines, clean_pools):
    b = _batcher(engines[0], max_queue_depth=2, retry_after_s=0.5)
    rep = Replica("solo", b).start()
    rep.paused = True                 # nothing admits: the queue IS full
    try:
        with ServingFrontend(rep, FrontendConfig()) as fe:
            for _ in range(2):
                rep.submit(list(range(8)), max_new_tokens=2)
            cli = GenerateClient(fe.url, timeout_s=30)
            with pytest.raises(FrontendError) as ei:
                cli.generate(list(range(8)), max_new_tokens=2)
            e = ei.value
            assert e.status == 429 and e.retryable
            # Retry-After header made it back, scaled above the 0.5s base
            assert e.retry_after_s is not None and e.retry_after_s >= 1
            assert e.body["error"]["reason"] == "queue_full"
            assert e.body["error"]["retry_after_s"] > 0.5
        rep.paused = False
        _wait(lambda: rep.stats["active"] == 0
              and rep.stats["queue_depth"] == 0)
    finally:
        rep.close()


def test_streaming_sse_chunked_over_http(engines, clean_pools):
    """The streaming variant really is chunked SSE on the wire: token
    events arrive one per generated token, then the end record."""
    b = _batcher(engines[0])
    rep = Replica("solo", b).start()
    try:
        with ServingFrontend(rep, FrontendConfig()) as fe:
            conn = http.client.HTTPConnection(fe.server.host,
                                              fe.server.port, timeout=60)
            conn.request("POST", GENERATE_PATH, body=json.dumps(
                {"prompt": list(range(1, 13)), "max_new_tokens": 3,
                 "stream": True}),
                headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            assert resp.getheader("Content-Type").startswith(
                "text/event-stream")
            assert resp.getheader("Transfer-Encoding") == "chunked"
            evs = list(iter_sse(resp))
            conn.close()
            tokens = [e for e in evs if e["event"] == "token"]
            assert len(tokens) == 3
            assert [t["data"]["index"] for t in tokens] == [0, 1, 2]
            end = evs[-1]
            assert end["event"] == "end"
            assert end["data"]["state"] == COMPLETED
            assert end["data"]["tokens"] == [t["data"]["token"]
                                             for t in tokens]
    finally:
        rep.close()


def test_deadline_expiry_maps_to_504(engines, clean_pools):
    b = _batcher(engines[0])
    rep = Replica("solo", b).start()
    try:
        with ServingFrontend(rep, FrontendConfig()) as fe:
            cli = GenerateClient(fe.url, timeout_s=60)
            with pytest.raises(FrontendError) as ei:
                cli.generate(list(range(1, 97)), max_new_tokens=8,
                             deadline_s=0.001)   # expires mid-prefill
            assert ei.value.status == 504
        _wait(lambda: rep.stats["active"] == 0
              and rep.stats["queue_depth"] == 0)
    finally:
        rep.close()


# ---------------------------------------------------------------------------
# cancellation: server timeout + client disconnect must reach backend.cancel
# ---------------------------------------------------------------------------

class _StallBackend:
    """Admits and then never resolves: forces the front-end's server-side
    timeout. ``cancel`` records the uid and can be armed to raise — the
    front-end's best-effort cancel must swallow a failing backend instead
    of crashing the handler mid-response."""
    health = "ready"

    def __init__(self, cancel_raises=None):
        self.cancelled = []
        self.submitted = []
        self._raise = cancel_raises

    def submit(self, prompt, *, max_new_tokens=None, deadline_s=None,
               priority=0, events=None, trace_id=None):
        self.submitted.append(prompt)
        return 42

    def cancel(self, uid):
        self.cancelled.append(uid)
        if self._raise is not None:
            raise self._raise
        return True

    def report(self):
        return {}


class _ChattyBackend(_StallBackend):
    """Streams token events until cancelled: the handler is always
    writing, so a client disconnect surfaces as a broken pipe."""

    def submit(self, prompt, *, max_new_tokens=None, deadline_s=None,
               priority=0, events=None, trace_id=None):
        def pump():
            i = 0
            while not self.cancelled and i < 100_000:
                events.put({"event": "token", "token": 1, "index": i})
                i += 1
                time.sleep(0.001)

        threading.Thread(target=pump, daemon=True).start()
        return 42


def test_unary_server_timeout_cancels_backend_and_maps_504():
    be = _StallBackend()
    with ServingFrontend(be, FrontendConfig(request_timeout_s=0.1)) as fe:
        with pytest.raises(FrontendError) as ei:
            GenerateClient(fe.url, timeout_s=30).generate([1, 2, 3])
        assert ei.value.status == 504
        assert ei.value.body["error"]["type"] == "server_timeout"
        assert be.cancelled == [42]


def test_stream_server_timeout_cancels_even_when_cancel_raises():
    """The stream-timeout path must still deliver a clean terminal SSE
    event (not a raw 500 injected into the chunked body) even when the
    backend's cancel itself blows up with an arbitrary exception."""
    be = _StallBackend(cancel_raises=RuntimeError("backend gone"))
    with ServingFrontend(be, FrontendConfig(request_timeout_s=0.1)) as fe:
        conn = http.client.HTTPConnection(fe.server.host, fe.server.port,
                                          timeout=30)
        conn.request("POST", GENERATE_PATH,
                     body=json.dumps({"prompt": [1], "stream": True}),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        evs = list(iter_sse(resp))      # terminated chunked stream parses
        conn.close()
        assert be.cancelled == [42]     # cancel reached the backend...
        assert evs[-1]["event"] == "end"   # ...and its raise stayed quiet
        assert evs[-1]["data"]["finish_reason"] == "server_timeout"


def test_unary_client_disconnect_cancels_backend():
    """The unary wait never touches the socket until the terminal send —
    the disconnect must be peeked for between event polls, or the request
    generates to completion for nobody."""
    be = _StallBackend()
    with ServingFrontend(be, FrontendConfig()) as fe:
        conn = http.client.HTTPConnection(fe.server.host, fe.server.port,
                                          timeout=30)
        conn.request("POST", GENERATE_PATH,
                     body=json.dumps({"prompt": [1]}),
                     headers={"Content-Type": "application/json"})
        time.sleep(0.2)                 # handler is in the event wait...
        conn.sock.close()               # ...and the client vanishes
        conn.close()
        _wait(lambda: be.cancelled == [42], timeout=30)


def test_bad_content_length_maps_to_400():
    import socket as socket_mod

    be = _StallBackend()
    with ServingFrontend(be, FrontendConfig()) as fe:
        s = socket_mod.create_connection((fe.server.host, fe.server.port),
                                         timeout=10)
        s.sendall(b"POST " + GENERATE_PATH.encode() + b" HTTP/1.1\r\n"
                  b"Host: x\r\nContent-Length: abc\r\n\r\n")
        status = s.recv(4096).split(b"\r\n", 1)[0]
        s.close()
        assert b" 400 " in status, status
        assert be.submitted == [] and be.cancelled == []


def test_client_disconnect_mid_stream_cancels_backend():
    be = _ChattyBackend()
    with ServingFrontend(be, FrontendConfig()) as fe:
        conn = http.client.HTTPConnection(fe.server.host, fe.server.port,
                                          timeout=30)
        conn.request("POST", GENERATE_PATH,
                     body=json.dumps({"prompt": [1], "stream": True}),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        resp.read(64)                   # stream is live...
        conn.sock.close()               # ...then the client vanishes
        conn.close()
        _wait(lambda: be.cancelled == [42], timeout=30)


def test_router_routes_away_from_draining_and_fails_over(
        engines, clean_pools):
    """Readiness semantics at the router: a DRAINING replica gets no new
    traffic; retryable sheds fail over to a sibling; when every routable
    replica refuses, the 429 carries the pool-wide hint."""
    b0 = _batcher(engines[0], max_queue_depth=2)
    b1 = _batcher(engines[1], max_queue_depth=2)
    r0, r1 = Replica("r0", b0), Replica("r1", b1)
    router = ReplicaRouter([r0, r1], RouterConfig()).start()
    try:
        router.drain_replica("r1", "test")
        _wait(lambda: r1.stats["health"] == DRAINING)
        assert not r1.routable
        with ServingFrontend(router, FrontendConfig()) as fe:
            out = GenerateClient(fe.url, timeout_s=120).generate(
                list(range(1, 9)), max_new_tokens=2)
            assert out["state"] == COMPLETED     # r0 took it
            _wait(lambda: router.health == "ready")   # r0 served → READY
            # now fill r0 while paused: every routable replica refuses
            r0.paused = True
            for _ in range(2):
                r0.submit(list(range(8)), max_new_tokens=2)
            with pytest.raises(FrontendError) as ei:
                GenerateClient(fe.url, timeout_s=30).generate(
                    list(range(8)), max_new_tokens=2)
            assert ei.value.status == 429
            assert ei.value.retry_after_s is not None
            assert router.counters["rejected"] == 1
            r0.paused = False
            _wait(lambda: r0.stats["active"] == 0
                  and r0.stats["queue_depth"] == 0)
    finally:
        router.close()


def _wait(cond, timeout=60.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(interval)
    raise AssertionError("condition not reached in time")


# ---------------------------------------------------------------------------
# end-to-end acceptance: storm + SIGTERM drain + migration, real sockets
# ---------------------------------------------------------------------------

def test_e2e_storm_sigterm_drain_migration(engines, clean_pools):
    """N concurrent mixed-priority clients against a 2-replica router:
    ≥1 429+Retry-After under an induced shed_storm, then a SIGTERM drain
    of one replica migrates its queued requests to the sibling, every
    admitted uid resolves, and the pools come back empty."""
    from deepspeed_tpu.resilience import FaultInjector, set_injector

    b0 = _batcher(engines[0], max_queue_depth=8, default_max_new_tokens=3)
    b1 = _batcher(engines[1], max_queue_depth=8, default_max_new_tokens=3)
    r0, r1 = Replica("r0", b0), Replica("r1", b1)
    router = ReplicaRouter([r0, r1], RouterConfig()).start()
    fe = ServingFrontend(router, FrontendConfig(
        api_keys={"gold": 5}, max_header_priority=4)).start()
    results, lock = [], threading.Lock()

    def unary(i, key=None):
        cli = GenerateClient(fe.url, api_key=key, timeout_s=120)
        try:
            out = cli.generate(list(range(1, 10 + i % 3)),
                               max_new_tokens=3,
                               priority=(i % 2) * 3 if key is None
                               else None)
            with lock:
                results.append(("ok", out))
        except FrontendError as e:
            with lock:
                results.append(("err", e))

    def streamer(i):
        try:
            evs = list(GenerateClient(fe.url, timeout_s=120).stream(
                list(range(1, 12)), max_new_tokens=3))
            with lock:
                results.append(("stream", evs))
        except FrontendError as e:
            with lock:
                results.append(("err", e))

    try:
        # ---- phase 1: storm. Queues fill while the workers are paused,
        # then shed_storm sheds them — every client sees a 429 one way
        # (queue_full at submit, after sibling failover) or the other
        # (shed_storm terminal record).
        r0.paused = r1.paused = True
        storm = [threading.Thread(target=unary, args=(i, None))
                 for i in range(20)]
        for t in storm:
            t.start()
        _wait(lambda: r0.stats["queue_depth"] + r1.stats["queue_depth"]
              + sum(1 for r in results if r[0] == "err") >= 20)
        set_injector(FaultInjector([{"kind": "shed_storm", "times": 2}]))
        r0.paused = r1.paused = False
        for t in storm:
            t.join(timeout=120)
        set_injector(None)
        errs = [r[1] for r in results if r[0] == "err"]
        assert len(errs) >= 1
        assert all(e.status == 429 for e in errs)
        assert all(e.retry_after_s is not None and e.retry_after_s >= 1
                   for e in errs)                       # Retry-After header
        reasons = {(e.body.get("error") or {}).get("reason")
                   for e in errs}
        assert "shed_storm" in reasons          # the induced storm showed
        # admitted-then-shed 429 bodies carry the router uid: none lost
        for e in errs:
            if "id" in e.body:
                assert router.resolve(e.body["id"]) in TERMINAL

        # ---- phase 2: SIGTERM drains r0 mid-flight; its queued requests
        # migrate to r1 and still complete for their clients.
        results.clear()
        r0.paused = r1.paused = True
        wave = ([threading.Thread(target=unary, args=(i, "gold"))
                 for i in range(4)]
                + [threading.Thread(target=streamer, args=(i,))
                   for i in range(4)])
        for t in wave:
            t.start()
        _wait(lambda: r0.stats["queue_depth"] + r1.stats["queue_depth"]
              >= 8)
        queued_r0 = r0.stats["queue_depth"]
        assert queued_r0 >= 1                   # something TO migrate
        router.install_signal_handlers(drain="r0")
        os.kill(os.getpid(), signal.SIGTERM)
        _wait(lambda: router.counters["migrated"]
              + router.counters["migration_failed"] >= queued_r0)
        r0.paused = r1.paused = False
        for t in wave:
            t.join(timeout=120)
        assert router.counters["migrated"] >= 1
        oks = [r[1] for r in results if r[0] == "ok"]
        streams = [r[1] for r in results if r[0] == "stream"]
        assert len(oks) == 4 and len(streams) == 4
        for out in oks:
            assert out["state"] == COMPLETED and len(out["tokens"]) == 3
            assert router.resolve(out["id"]) == COMPLETED
        for evs in streams:
            assert evs[-1]["event"] == "end"
            assert evs[-1]["data"]["state"] == COMPLETED
        # a drained r0 leaves the pool ready (r1 serves) — probe semantics
        conn = http.client.HTTPConnection(fe.server.host, fe.server.port,
                                          timeout=10)
        conn.request("GET", "/readyz")
        assert conn.getresponse().status == 200
        conn.close()
        assert r0.stats["health"] == DRAINING
        _wait(lambda: r1.stats["active"] == 0
              and r1.stats["queue_depth"] == 0)
    finally:
        set_injector(None)
        router.restore_signal_handlers()
        fe.close()
        fe.close()                              # idempotent, no double-free
        router.close()
        router.close()


# ---------------------------------------------------------------------------
# drill wrapper (slow; the CLI is the invariant authority)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_frontend_storm_drill(tmp_path):
    import sys

    sys.path.insert(0, _TOOLS)
    from serve_drill import run_scenario

    verdict = run_scenario("frontend-storm", workdir=str(tmp_path))
    assert verdict["ok"], verdict


def test_router_route_reads_hold_the_lock():
    """dslint burn-down (lock-discipline): ``cancel``/``resolve`` used to
    probe ``_routes`` and then read ``route.replica``/``route.uid`` with NO
    lock, racing ``submit(_ruid=...)``'s migration rewrite of that pair
    under ``_lock`` — a torn read aims the command at the wrong replica.
    Both now snapshot (replica, uid) via ``_route_loc`` under the lock;
    this pins the contract with a dict proxy that asserts the lock is held
    on every route-table probe."""
    from deepspeed_tpu.serving.router import ReplicaRouter, _Route

    class _StubReplica:
        def __init__(self, name):
            self.name = name
            self.incarnation = 0
            self.cancelled = []
            self.resolved = []

        def cancel(self, uid):
            self.cancelled.append(uid)
            return True

        def resolve(self, uid):
            self.resolved.append(uid)
            return COMPLETED

    rep = _StubReplica("r0")
    router = ReplicaRouter([rep], RouterConfig())

    class _LockAssertingRoutes(dict):
        def get(self, key, default=None):
            assert router._lock.locked(), \
                "_routes probed outside 'with self._lock:'"
            return super().get(key, default)

    routes = _LockAssertingRoutes()
    routes[7] = _Route("r0", rep.incarnation, 42, None)
    router._routes = routes

    assert router.cancel(7) is True
    assert rep.cancelled == [42]
    assert router.resolve(7) == COMPLETED
    assert rep.resolved == [42]
    # unknown ruids stay well-behaved through the locked path too
    assert router.cancel(999) is False
    assert router.resolve(999) is None
