"""Serving-resilience unit tests (``deepspeed_tpu/serving``).

Fast tests pin the request-lifecycle contracts directly: the
:class:`RequestManager` ledger (every uid resolves; typed retryable
``ShedError`` refusals), the satellite invariant that a deadline landing
MID-chunked-prefill releases every KV block through the engine's own flush
path (asserted via ``SequenceManager`` + allocator accounting), the typed
:class:`CapacityError` overload surface on ``InferenceEngineV2.put``, and
the ``serving/*`` monitor stream + ``serving_report()`` acceptance shape.

The end-to-end overload/failure scenarios live in ``tools/serve_drill.py``;
the ``slow``-marked wrappers at the bottom run them under pytest the way
``test_chaos_drill.py`` wraps the training drills.
"""

import os

import numpy as np
import pytest

from deepspeed_tpu.config.config import MonitorConfig, ServingConfig
from deepspeed_tpu.serving import (COMPLETED, EXPIRED, QUEUED, SHED,
                                   ContinuousBatcher, RequestManager,
                                   ShedError)

pytestmark = pytest.mark.serving

_TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "tools")


# ---------------------------------------------------------------------------
# RequestManager: ledger + typed refusals (no engine needed)
# ---------------------------------------------------------------------------

class TestRequestManager:
    def test_queue_full_raises_typed_retryable_shed(self):
        mgr = RequestManager(max_queue_depth=2, retry_after_s=2.5)
        for _ in range(2):
            mgr.submit([1, 2, 3])
        with pytest.raises(ShedError) as ei:
            mgr.submit([1, 2, 3])
        e = ei.value
        assert isinstance(e, RuntimeError)      # legacy catch-surface holds
        assert e.reason == "queue_full" and e.retryable
        # the hint is load-aware: base 2.5 scaled UP by the full queue
        assert e.retry_after_s > 2.5
        assert mgr.counters["rejected"] == 1

    def test_retry_after_hint_scales_with_pressure(self):
        """Satellite: ``Retry-After`` reflects load. Idle → the configured
        base; full queue → larger; repeated rejects (shed rate) → larger
        still, monotonically."""
        mgr = RequestManager(max_queue_depth=4, retry_after_s=1.0)
        assert mgr.current_retry_after() == 1.0      # idle = base
        for _ in range(4):
            mgr.submit([1])
        full = mgr.current_retry_after()
        assert full > 1.0                            # queue fullness
        hints = []
        for _ in range(6):
            with pytest.raises(ShedError) as ei:
                mgr.submit([1])
            hints.append(ei.value.retry_after_s)
        assert hints[0] > full                       # reject adds shed rate
        assert hints == sorted(hints)                # pressure only grows
        assert hints[-1] <= 4.0                      # bounded at 4x base

    def test_queue_depth_by_priority_breakdown(self):
        mgr = RequestManager()
        for prio in (0, 5, 0, 2):
            mgr.submit([1], priority=prio)
        assert mgr.queue_depth_by_priority() == {0: 2, 5: 1, 2: 1}
        rep = mgr.report()
        assert rep["queue_depth_by_priority"] == {0: 2, 5: 1, 2: 1}
        assert rep["retry_after_s"] > 0

    def test_closed_manager_refuses_with_draining(self):
        mgr = RequestManager()
        mgr.close("preemption")
        with pytest.raises(ShedError) as ei:
            mgr.submit([1])
        assert ei.value.reason == "draining" and ei.value.retryable

    def test_every_uid_resolves_and_inflight_release_goes_through_flush(self):
        released = []
        now = [0.0]
        mgr = RequestManager(release_fn=released.append,
                             clock=lambda: now[0])
        u_queued = mgr.submit([1, 2], deadline_s=5.0)
        u_active = mgr.submit([3, 4])
        u_done = mgr.submit([5, 6])
        for uid in (u_active, u_done):
            mgr.admit(mgr.result(uid))
        mgr.complete(mgr.result(u_done))
        mgr.shed(mgr.result(u_active), "kv_pressure")
        now[0] = 10.0                       # the queued request's deadline
        expired = mgr.expire()
        assert [r.uid for r in expired] == [u_queued]
        assert mgr.resolve(u_queued) == EXPIRED
        assert mgr.resolve(u_active) == SHED
        assert mgr.resolve(u_done) == COMPLETED
        assert mgr.resolve(999) is None
        # only ADMITTED work holds engine resources: the completed and the
        # shed request released through flush, the queued one never held any
        assert released == [[u_done], [u_active]]
        assert mgr.counters == {"submitted": 3, "rejected": 0, "admitted": 2,
                                "completed": 1, "shed": 1, "expired": 1,
                                "cancelled": 0, "paused": 0, "resumed": 0,
                                "adopted": 0, "rebalanced": 0,
                                "reprefills": 0}

    def test_shed_order_is_lowest_priority_then_newest(self):
        now = [0.0]
        mgr = RequestManager(clock=lambda: now[0])
        lo_old = mgr.submit([1], priority=0)
        now[0] = 1.0
        hi = mgr.submit([1], priority=5)
        now[0] = 2.0
        lo_new = mgr.submit([1], priority=0)
        order = [r.uid for r in mgr.queued_by_shed_order()]
        assert order == [lo_new, lo_old, hi]
        assert mgr.resolve(hi) == QUEUED


# ---------------------------------------------------------------------------
# engine-backed contracts
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_engine():
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import TransformerLM, get_preset

    # what the batcher and the manager do: the XLA twin attends (the SLO
    # tentpole case below keeps the kernel, interpreted here)
    return InferenceEngineV2(TransformerLM(get_preset("tiny")),
                             max_sequences=8, max_seq_len=128, block_size=16,
                             decode_kernel="xla")


def test_put_overload_raises_typed_capacity_error(tiny_engine):
    from deepspeed_tpu.inference import CapacityError

    demand = tiny_engine.max_seq_len + 8    # can never fit one sequence
    with pytest.raises(CapacityError) as ei:
        tiny_engine.put([999], [np.zeros(demand, np.int32)])
    e = ei.value
    assert isinstance(e, RuntimeError)      # compatibility base class
    assert e.uids == [999] and e.token_demand == [demand]
    assert 999 not in tiny_engine.state.sequences   # refused, not leaked


def test_deadline_expiry_mid_chunked_prefill_releases_all_kv(tiny_engine):
    """Satellite invariant: a request whose deadline lands while its prompt
    is only PARTIALLY prefilled must give back every KV block and its slot
    — asserted via the SequenceManager/allocator accounting itself."""
    alloc = tiny_engine.state.allocator
    free0 = alloc.free_blocks
    live0 = set(tiny_engine.state.sequences)
    now = [0.0]
    cfg = ServingConfig(prefill_chunk=32, default_max_new_tokens=4)
    b = ContinuousBatcher(tiny_engine, cfg, clock=lambda: now[0])
    uid = b.submit(np.arange(96) % 250, deadline_s=5.0)   # 3 chunks of 32
    assert b.step()                          # admit + first prefill chunk
    req = b.manager.active[uid]
    assert 0 < req.prefilled < req.prompt_len
    assert alloc.free_blocks < free0         # chunk really holds blocks
    now[0] = 10.0                            # deadline passes mid-prefill
    b.step()
    assert b.manager.resolve(uid) == EXPIRED
    done = b.manager.done[uid]
    assert 0 < done.prefilled < done.prompt_len   # expired MID-prefill
    assert alloc.free_blocks == free0             # no pool leak
    assert set(tiny_engine.state.sequences) == live0  # slot given back


def test_from_deepspeed_config_consumes_serving_section(tiny_engine):
    from deepspeed_tpu.config import DeepSpeedTpuConfig

    cfg = DeepSpeedTpuConfig(train_batch_size=8, serving={
        "enabled": True, "max_queue_depth": 7, "prefill_chunk": 16})
    b = ContinuousBatcher.from_deepspeed_config(tiny_engine, cfg)
    assert b.cfg.max_queue_depth == 7 and b.manager.max_queue_depth == 7
    disabled = DeepSpeedTpuConfig(train_batch_size=8)
    with pytest.raises(ValueError, match="serving.enabled"):
        ContinuousBatcher.from_deepspeed_config(tiny_engine, disabled)


def test_unadmittable_head_is_shed_terminal_not_livelocked(tiny_engine):
    """A head-of-line request that fits ``max_seq_len`` but can NEVER fit
    the KV budget must be shed terminally (``oversize``) — and ``pump()``
    must terminate instead of spinning on an unadmittable head."""
    cfg = ServingConfig(prefill_chunk=32, kv_high_watermark=0.05,
                        kv_low_watermark=0.04)   # budget: 3 of 64 blocks
    b = ContinuousBatcher(tiny_engine, cfg)
    uid = b.submit(np.arange(60) % 250, max_new_tokens=8)  # needs 5 blocks
    b.pump(max_steps=10)                         # must return, not spin
    assert b.manager.resolve(uid) == SHED
    done = b.manager.done[uid]
    assert done.error.reason == "oversize" and not done.error.retryable


def test_admission_budgets_projected_demand_not_live_occupancy(tiny_engine):
    """Admitting N requests in one sweep must charge each one's worst-case
    KV demand against the budget — live occupancy alone would admit them
    all and strand them mid-generation under kv_pressure sheds."""
    cfg = ServingConfig(prefill_chunk=32, default_max_new_tokens=4,
                        kv_high_watermark=0.10,  # budget: 6.4 of 64 blocks
                        kv_low_watermark=0.05)
    b = ContinuousBatcher(tiny_engine, cfg)
    uids = [b.submit(np.arange(60) % 250) for _ in range(2)]  # 4 blocks each
    b.step()
    assert len(b.manager.active) == 1            # joint worst case > budget
    assert b.manager.resolve(uids[1]) == QUEUED  # waiting, not shed
    b.pump(max_steps=60)
    assert all(b.manager.resolve(u) == COMPLETED for u in uids)
    assert b.manager.counters["shed"] == 0       # nobody was stranded


def test_serving_report_and_monitor_stream(tiny_engine, tmp_path):
    """Acceptance shape: ``serving_report()`` carries the lifecycle counters
    + queue/KV occupancy, and the SAME counters stream through a real
    monitor backend (CSV) under the ``serving/*`` prefix."""
    from deepspeed_tpu.monitor.monitor import MonitorMaster

    mon = MonitorMaster(MonitorConfig(csv_monitor={
        "enabled": True, "output_path": str(tmp_path), "job_name": "serve"}))
    cfg = ServingConfig(prefill_chunk=32, default_max_new_tokens=4,
                        monitor_interval=1)
    b = ContinuousBatcher(tiny_engine, cfg, monitor=mon)
    uids = [b.submit(np.arange(20) % 250) for _ in range(3)]
    b.pump(max_steps=50)
    rep = b.serving_report()
    assert all(b.manager.resolve(u) == COMPLETED for u in uids)
    for key in ("admitted", "shed", "expired", "completed"):
        assert key in rep["counters"]
    assert rep["counters"]["admitted"] == rep["counters"]["completed"] == 3
    assert rep["queue_depth"] == 0
    assert 0.0 <= rep["kv"]["occupancy"] <= 1.0
    assert rep["latency_ms"]["p99"] >= rep["latency_ms"]["p50"] >= 0.0
    # the same counters, as serving/* events, through the CSV backend
    outdir = tmp_path / "serve"
    for tag in ("serving_admitted", "serving_shed", "serving_expired",
                "serving_completed", "serving_queue_depth",
                "serving_kv_occupancy", "serving_health",
                "serving_step_p99_ms"):
        assert (outdir / f"{tag}.csv").exists(), tag
    last = (outdir / "serving_completed.csv").read_text().strip(
        ).splitlines()[-1]
    assert float(last.split(",")[1]) == 3.0


def test_per_priority_queue_depth_gauges(tiny_engine):
    """Satellite: the queue-depth breakdown lands in the registry as
    ``serving/queue_depth{priority=}`` children next to the unlabeled
    total, and a priority class that empties is zeroed, not stale."""
    from deepspeed_tpu.observability import MetricsRegistry

    reg = MetricsRegistry()
    cfg = ServingConfig(prefill_chunk=32, default_max_new_tokens=2,
                        max_active_requests=1)
    b = ContinuousBatcher(tiny_engine, cfg, registry=reg)
    uids = [b.submit(np.arange(12) % 250, priority=p) for p in (0, 0, 7)]
    assert b.step()                   # admits the head; two stay queued
    fam = reg.get("serving/queue_depth")
    series = {dict(i.labels).get("priority"): i.value
              for i in fam.series.values()}
    assert series[None] == 2.0        # unlabeled total alongside children
    assert series["0"] == 1.0 and series["7"] == 1.0
    assert b.serving_report()["queue_depth_by_priority"] == {0: 1, 7: 1}
    b.pump(max_steps=60)
    assert all(b.manager.resolve(u) == COMPLETED for u in uids)
    series = {dict(i.labels).get("priority"): i.value
              for i in fam.series.values()}
    assert series["0"] == 0.0 and series["7"] == 0.0


def test_prefix_aware_admission_admits_mostly_cached_request():
    """Prefix-aware admission: with a warm cache, a request whose prompt is
    ~85% resident counts only its uncached share against the KV budget —
    it admits immediately while an equal-size COLD request must wait for
    in-flight work to finish. Cache-held blocks never count as load."""
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import TransformerLM, get_preset

    eng = InferenceEngineV2(TransformerLM(get_preset("tiny")),
                            max_sequences=8, max_seq_len=128, block_size=16,
                            prefix_cache=True, decode_kernel="xla")
    # warm the cache: 96-token prompt -> 6 published blocks (80 attachable
    # under the len-1 cap)
    shared = np.arange(96) % 250
    eng.put([900], [shared])
    eng.flush([900])
    # budget = 0.3 * 64 = 19.2 blocks. Cold demand ceil((96+8)/16) = 7;
    # warm demand = ceil((96-80+8)/16) = 2 NEW blocks (its 5 attached
    # blocks count once, as pinned pool use, after it admits: A(7) +
    # warm(5+2) = 14 projected -> +7 cold would cross the budget, +2 warm
    # does not; peak occupancy 14/64 stays under the pressure watermark)
    cfg = ServingConfig(prefill_chunk=32, default_max_new_tokens=8,
                        kv_high_watermark=0.30, kv_low_watermark=0.20)
    b = ContinuousBatcher(eng, cfg)
    # cache-held blocks are reclaimable capacity, not occupancy
    assert b.reclaimable_blocks == 6 and b.kv_occupancy == 0.0
    a = b.submit((np.arange(96) + 7) % 250)    # cold A: 7 of 9.6 blocks
    b.step()
    assert b.manager.resolve(a) in ("prefilling", "decoding")
    warm = b.submit(shared)                    # 2 more blocks: fits
    cold = b.submit((np.arange(96) + 31) % 250)  # 7 more: must wait
    b.step()
    assert b.manager.resolve(warm) in ("prefilling", "decoding")
    assert b.manager.resolve(cold) == QUEUED
    assert b.counters["prefix_hit_requests"] == 1
    assert b.counters["prefix_hit_tokens"] == 80
    b.pump(max_steps=200)                      # blocks free -> cold admits
    for uid in (a, warm, cold):
        assert b.manager.resolve(uid) == COMPLETED
    assert b.manager.counters["shed"] == 0
    eng.prefix_cache.clear()
    alloc = eng.state.allocator
    assert alloc.free_blocks == alloc.num_blocks


# ---------------------------------------------------------------------------
# SLO tiers + preemptible requests (pause/resume through the KV tier store)
# ---------------------------------------------------------------------------

def _slo_batcher(decode_kernel="xla", **serving):
    """fp32 engine (bit-identical greedy across pause/resume) + a batcher
    with the SLO block enabled."""
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import TransformerLM, get_preset

    eng = InferenceEngineV2(
        TransformerLM(get_preset("tiny", dtype="float32")),
        max_sequences=8, max_seq_len=128, block_size=16,
        decode_kernel=decode_kernel)
    cfg = ServingConfig(**{
        "prefill_chunk": 32, "default_max_new_tokens": 8,
        "slo": {"enabled": True, "preempt": True}, **serving})
    return ContinuousBatcher(eng, cfg)


@pytest.mark.slo
class TestSLOPreemption:
    def test_pause_resume_greedy_bit_identical_fp32(self):
        """Tentpole invariant: pause -> demote through the tier store ->
        promote -> resume reproduces the EXACT greedy token sequence of an
        unpreempted run (fp32; KV bytes round-trip unquantized)."""
        b = _slo_batcher(decode_kernel="pallas")
        rng = np.random.default_rng(7)
        prompt = list(rng.integers(0, 250, 40))
        base_uid = b.submit(prompt, max_new_tokens=8, tier="batch")
        b.pump(max_steps=50)
        base = list(b.manager.result(base_uid).generated)
        assert len(base) == 8

        uid = b.submit(prompt, max_new_tokens=8, tier="batch")
        for _ in range(4):
            b.step()                       # prefill + a few decode tokens
        req = b.manager.active[uid]
        mid = len(req.generated)
        assert 0 < mid < 8                 # genuinely mid-decode
        assert b.engine.pause_request(uid)
        b.manager.pause(req)
        # demoted: no device blocks for the uid, entries parked in the store
        assert uid not in b.engine.state.sequences
        assert b.engine.is_paused(uid)
        assert b.engine.paused_blocks(uid) > 0
        b.pump(max_steps=60)               # _resume_paused brings it back
        res = b.manager.result(uid)
        assert b.manager.resolve(uid) == COMPLETED
        assert list(res.generated) == base  # bit-identical greedy
        assert res.pause_count == 1
        alloc = b.engine.state.allocator
        assert alloc.free_blocks == alloc.num_blocks
        assert b.engine._tier_store.entries() == 0   # no parked leftovers
        assert b.manager.counters["paused"] == 1
        assert b.manager.counters["resumed"] == 1
        b.engine.close()

    def test_preempt_mid_chunked_prefill_releases_everything(self):
        """A victim caught MID-chunked-prefill pauses without leaking a
        block or a slot, resumes into PREFILLING, and still matches the
        unpreempted greedy output."""
        b = _slo_batcher(default_max_new_tokens=4)
        rng = np.random.default_rng(3)
        prompt = list(rng.integers(0, 250, 96))    # 3 chunks of 32
        base_uid = b.submit(prompt, tier="batch")
        b.pump(max_steps=40)
        base = list(b.manager.result(base_uid).generated)

        alloc = b.engine.state.allocator
        free0 = alloc.free_blocks
        uid = b.submit(prompt, tier="batch")
        b.step()                                   # exactly one chunk in KV
        req = b.manager.active[uid]
        assert req.state == "prefilling" and req.prefilled == 32
        assert b.engine.pause_request(uid)
        b.manager.pause(req)
        # the device side is fully released while paused
        assert uid not in b.engine.state.sequences
        assert alloc.free_blocks == free0
        b.pump(max_steps=60)
        assert b.manager.resolve(uid) == COMPLETED
        assert list(b.manager.result(uid).generated) == base
        assert alloc.free_blocks == alloc.num_blocks
        assert b.engine._tier_store.entries() == 0
        b.engine.close()

    def test_double_preempt_starvation_guard(self):
        """A request that was preempted may not be preempted again before
        it makes progress — two back-to-back ``preempt_storm`` steps pause
        it once, and only post-resume progress re-arms the guard."""
        from deepspeed_tpu.resilience import FaultInjector, set_injector

        b = _slo_batcher()
        try:
            rng = np.random.default_rng(5)
            victim = b.submit(list(rng.integers(0, 250, 40)),
                              max_new_tokens=8, tier="batch")
            other = b.submit(list(rng.integers(0, 250, 40)),
                             max_new_tokens=8, tier="latency")
            for _ in range(3):
                b.step()
            req = b.manager.active[victim]
            assert req.pause_allowed()             # never paused yet
            set_injector(FaultInjector([{"kind": "preempt_storm",
                                         "times": 2}]))
            b.step()                               # storm #1: pauses victim
            assert b.manager.counters["paused"] == 1
            assert req.pause_count == 1
            assert not req.pause_allowed()         # no progress since pause
            b.step()                               # storm #2: guard holds
            assert b.manager.counters["paused"] == 1   # NOT paused again
            # nobody was shed by the storms — preemption is not data loss
            assert b.manager.counters["shed"] == 0
            set_injector(None)
            b.pump(max_steps=80)
            assert b.manager.resolve(victim) == COMPLETED
            assert b.manager.resolve(other) == COMPLETED
            # once it decoded past the pause point the guard re-arms
            assert b.manager.result(victim).progress \
                > b.manager.result(victim).progress_at_last_pause
        finally:
            set_injector(None)
            b.engine.close()

    def test_resume_io_error_sheds_retryably_no_zero_fill(self):
        """Lost/unreadable demoted entries surface as a retryable
        ``resume_io_error`` shed — never a silent zero-filled KV resume —
        and the pool is fully restored."""
        from deepspeed_tpu.resilience import FaultInjector, set_injector

        b = _slo_batcher()
        try:
            rng = np.random.default_rng(11)
            uid = b.submit(list(rng.integers(0, 250, 40)),
                           max_new_tokens=8, tier="batch")
            for _ in range(3):
                b.step()
            assert b.engine.pause_request(uid)
            b.manager.pause(b.manager.active[uid])
            set_injector(FaultInjector([{"kind": "resume_io_error",
                                         "times": 8}]))
            b.pump(max_steps=20)
            req = b.manager.result(uid)
            assert b.manager.resolve(uid) == SHED
            assert req.error.reason == "resume_io_error"
            assert req.error.retryable
            assert b.counters["resume_failures"] >= 1
            alloc = b.engine.state.allocator
            assert alloc.free_blocks == alloc.num_blocks
            assert not b.engine.state.sequences
            assert b.engine._tier_store.entries() == 0
        finally:
            set_injector(None)
            b.engine.close()

    def test_tier_flows_submit_to_request_and_retry_after(self):
        """Satellite: tiers flow through submit; unknown/absent tiers take
        the configured default; the 429 Retry-After hint scales by tier —
        batch backs off harder than latency."""
        mgr = RequestManager(retry_after_s=1.0, default_tier="throughput",
                             retry_after_tier_factor={"batch": 4.0})
        u_lat = mgr.submit([1, 2], tier="latency")
        u_def = mgr.submit([1, 2])
        u_bad = mgr.submit([1, 2], tier="hyperspeed")
        assert mgr.result(u_lat).tier == "latency"
        assert mgr.result(u_def).tier == "throughput"
        assert mgr.result(u_bad).tier == "throughput"   # unknown -> default
        assert mgr.current_retry_after("batch") \
            == 4.0 * mgr.current_retry_after("latency")
        assert mgr.queue_depth_by_tier() == {"latency": 1, "throughput": 2}

    def test_per_tier_admission_budget_waits_never_sheds(self):
        """A tier over its admission budget WAITS while other tiers admit
        past its queued head; when capacity frees it completes — the budget
        is backpressure, not a shed."""
        b = _slo_batcher(
            default_max_new_tokens=4,
            slo={"enabled": True, "preempt": True,
                 "budgets": {"batch": 0.10}})   # batch: ~6 of 64 blocks
        bat = [b.submit(np.arange(60) % 250, tier="batch")
               for _ in range(2)]               # 4 blocks each, 2nd > 6
        lat = b.submit(np.arange(60) % 250, tier="latency")
        b.step()
        assert b.manager.resolve(bat[0]) in ("prefilling", "decoding")
        assert b.manager.resolve(bat[1]) == QUEUED  # over tier budget
        assert b.manager.resolve(lat) in ("prefilling", "decoding",
                                          COMPLETED)  # admitted PAST it
        b.pump(max_steps=80)
        for uid in bat + [lat]:
            assert b.manager.resolve(uid) == COMPLETED
        assert b.manager.counters["shed"] == 0
        b.engine.close()

    def test_preempt_victim_order_prefers_batch_most_remaining_no_deadline(
            self):
        """Victim selection is deadline- and progress-aware: batch tier
        before latency, no-deadline before deadlined, most remaining work
        first."""
        from deepspeed_tpu.serving.request import ServeRequest

        def req(tier, deadline, remaining, uid):
            r = ServeRequest(uid=uid, prompt=[1], submitted_at=0.0,
                             max_new_tokens=remaining, tier=tier,
                             deadline=deadline)
            return r

        lat = req("latency", None, 8, 1)
        bat_big = req("batch", None, 64, 2)
        bat_small = req("batch", None, 4, 3)
        bat_deadline = req("batch", 99.0, 64, 4)
        order = sorted([lat, bat_big, bat_small, bat_deadline],
                       key=ServeRequest.preempt_key)
        # batch before latency; within batch, no-deadline before deadlined,
        # and more remaining work first
        assert [r.uid for r in order] == [2, 3, 4, 1]


# ---------------------------------------------------------------------------
# drill wrappers (slow; the CLI is the invariant authority)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("scenario", ["deadline-storm",
                                      "shed-under-kv-pressure",
                                      "sigterm-drain"])
def test_serve_drill_scenario(scenario, tmp_path):
    import sys

    sys.path.insert(0, _TOOLS)
    from serve_drill import run_scenario

    verdict = run_scenario(scenario, workdir=str(tmp_path))
    assert verdict["ok"], verdict


@pytest.mark.slo
@pytest.mark.slow
def test_serve_drill_slo_storm(tmp_path):
    """Tier-1 authority for the preemption subsystem: zero latency-tier
    sheds under a preempt storm, >= 1 pause -> resume round-trip, streams
    bit-identical to an injection-free replay, pools/store restored."""
    import sys

    sys.path.insert(0, _TOOLS)
    from serve_drill import run_scenario

    verdict = run_scenario("slo-storm", workdir=str(tmp_path))
    assert verdict["ok"], verdict
