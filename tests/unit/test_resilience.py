"""Resilience-layer tests: fault injection drills for the retry policy, the
preemption-safe CheckpointManager, the self-healing step guard, and the
closed elastic-agent recovery loop (the analog of the reference's elastic
agent + checkpoint-commit integration tests, with deterministic faults in
place of real host losses)."""

import json
import os
import signal
import textwrap
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu import comm
from deepspeed_tpu.elasticity import ElasticAgent, subprocess_spawn
from deepspeed_tpu.models import TransformerLM, get_preset
from deepspeed_tpu.resilience import (ABORT, CONTINUE, SAVE,
                                      CheckpointManager, CoordinatedAbort,
                                      FaultInjector, InjectedIOError,
                                      ResilienceCoordinator,
                                      RetryDeadlineExceeded, RetryPolicy,
                                      TooManyBadSteps, retry_call,
                                      set_injector)
from deepspeed_tpu.resilience.faults import tear_checkpoint_dir
from deepspeed_tpu.resilience.manager import STAGING_FILE, verify_tag_dir


@pytest.fixture(autouse=True)
def _clean_injector():
    """Every test starts and ends with an inert process-wide injector."""
    set_injector(None)
    yield
    set_injector(None)
    comm.set_retry_policy(None)


def make_config(stage=2, mesh=None, resilience=None, **over):
    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": stage},
        "mesh": mesh or {"fsdp": 8},
        "steps_per_print": 100,
        "resilience": {"enabled": True, **(resilience or {})},
    }
    cfg.update(over)
    return cfg


def data_iter(batch, seq=32, seed=0):
    rng = np.random.default_rng(seed)
    fixed = {"input_ids": rng.integers(0, 256, (batch, seq))}
    while True:
        yield fixed


def train_steps(engine, steps, seed=0):
    it = data_iter(engine.train_micro_batch_size_per_gpu()
                   * engine.topology.dp_world_size, seed=seed)
    losses = []
    while len(losses) < steps:
        loss = engine.forward(next(it))
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    return losses


# ---------------------------------------------------------------------------
# RetryPolicy
# ---------------------------------------------------------------------------

class TestRetry:
    def test_succeeds_after_transient_failures(self):
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "ok"

        out = retry_call(flaky, policy=RetryPolicy(max_attempts=5,
                                                   base_delay_s=0.001))
        assert out == "ok" and len(calls) == 3

    def test_attempt_budget_exhausted(self):
        def always():
            raise OSError("down")

        with pytest.raises(RetryDeadlineExceeded):
            retry_call(always, policy=RetryPolicy(max_attempts=2,
                                                  base_delay_s=0.001))

    def test_non_retryable_passes_through(self):
        def bad():
            raise ValueError("logic bug")

        with pytest.raises(ValueError):
            retry_call(bad, policy=RetryPolicy(max_attempts=3,
                                               base_delay_s=0.001))

    def test_backoff_grows_and_caps(self):
        p = RetryPolicy(base_delay_s=0.1, multiplier=2.0, max_delay_s=0.3,
                        jitter=0.0)
        assert p.delay(0) == pytest.approx(0.1)
        assert p.delay(1) == pytest.approx(0.2)
        assert p.delay(5) == pytest.approx(0.3)  # capped

    def test_comm_retry_succeeds_after_two_injected_failures(self):
        """The acceptance drill: a host collective fails twice, the armed
        policy retries, the third attempt lands."""
        set_injector(FaultInjector([
            {"kind": "failed_collective", "times": 2}]))
        comm.set_retry_policy(RetryPolicy(max_attempts=3, base_delay_s=0.001))
        out = comm.all_reduce_host(np.int64(7))
        assert int(out) == 7
        assert comm.get_retry_stats()["retries"] == 2

    def test_comm_failure_without_policy_raises(self):
        set_injector(FaultInjector([{"kind": "failed_collective"}]))
        comm.set_retry_policy(None)
        with pytest.raises(InjectedIOError):
            comm.all_reduce_host(np.int64(1))


# ---------------------------------------------------------------------------
# Step guard
# ---------------------------------------------------------------------------

class TestStepGuard:
    def test_nan_step_skipped_without_corrupting_state(self, eight_devices):
        """A poisoned-gradient step must be dropped whole: params and
        optimizer state identical to before, LR schedule not ticked, and
        training healthy afterwards."""
        eng, *_ = ds.initialize(
            model=TransformerLM(get_preset("tiny")),
            config=make_config(
                scheduler={"type": "WarmupLR",
                           "params": {"warmup_num_steps": 100}},
                resilience={"faults": [{"kind": "nan_grads", "step": 2}]}))
        import jax

        train_steps(eng, 2)
        p_before = [np.asarray(x) for x in jax.tree_util.tree_leaves(eng.params)]
        o_before = [np.asarray(x)
                    for x in jax.tree_util.tree_leaves(eng.opt_state)]
        lr_before = eng.get_lr()[0]
        it = data_iter(16)
        loss = eng.forward(next(it))
        eng.backward(loss)
        eng.step()  # global_steps==2 → fault fires → skip
        assert eng.skipped_steps == 1
        assert eng.global_steps == 2
        for got, want in zip(jax.tree_util.tree_leaves(eng.params), p_before):
            np.testing.assert_array_equal(np.asarray(got), want)
        for got, want in zip(jax.tree_util.tree_leaves(eng.opt_state), o_before):
            np.testing.assert_array_equal(np.asarray(got), want)
        assert eng.get_lr()[0] == lr_before  # the LR rewind
        losses = train_steps(eng, 2, seed=5)
        assert all(np.isfinite(losses))
        rep = eng.resilience_report()
        assert rep["guard"]["bad_steps_skipped"] == 1
        assert rep["faults_fired"] == ["nan_grads@grads:step=2"]

    def test_persistent_nan_aborts_to_agent(self, eight_devices, tmp_path):
        """Every step poisoned: after max_consecutive_bad_steps the guard
        writes the report and raises for the elastic agent."""
        os.environ["DSTPU_CHECKPOINT_DIR"] = str(tmp_path)
        try:
            eng, *_ = ds.initialize(
                model=TransformerLM(get_preset("tiny")),
                config=make_config(resilience={
                    "max_consecutive_bad_steps": 2,
                    "faults": [{"kind": "nan_grads", "step": -1,
                                "times": 99}]}))
            with pytest.raises(TooManyBadSteps):
                train_steps(eng, 3)
        finally:
            del os.environ["DSTPU_CHECKPOINT_DIR"]
        rep = json.load(open(tmp_path / "resilience_report.json"))
        assert rep["aborted"] is True
        assert rep["guard"]["bad_steps_skipped"] == 2
        assert rep["consecutive_bad_steps"] == 2

    def test_injected_soft_crash(self, eight_devices):
        from deepspeed_tpu.resilience import InjectedCrash

        eng, *_ = ds.initialize(
            model=TransformerLM(get_preset("tiny")),
            config=make_config(resilience={
                "faults": [{"kind": "crash", "step": 1}]}))
        train_steps(eng, 1)
        with pytest.raises(InjectedCrash):
            train_steps(eng, 1)


# ---------------------------------------------------------------------------
# CheckpointManager
# ---------------------------------------------------------------------------

class TestCheckpointManager:
    def test_latest_pointer_atomic(self, tmp_path):
        from deepspeed_tpu.runtime.checkpoint import (read_latest_tag,
                                                      write_latest_atomic)

        write_latest_atomic(str(tmp_path), "global_step1")
        write_latest_atomic(str(tmp_path), "global_step2")
        assert read_latest_tag(str(tmp_path)) == "global_step2"
        # no torn tmp residue
        assert [f for f in os.listdir(tmp_path) if ".tmp" in f] == []

    def test_manifest_verification(self, tmp_path, eight_devices):
        eng, *_ = ds.initialize(model=TransformerLM(get_preset("tiny")),
                                config=make_config())
        train_steps(eng, 1)
        eng.save_checkpoint(str(tmp_path))
        tag_dir = str(tmp_path / "global_step1")
        ok, why = verify_tag_dir(tag_dir)
        assert ok, why
        tear_checkpoint_dir(tag_dir, mode="corrupt")
        ok, why = verify_tag_dir(tag_dir)
        assert not ok and "mismatch" in why

    def test_torn_newest_falls_back_to_previous_tag(self, tmp_path,
                                                    eight_devices):
        """The acceptance drill: newest checkpoint torn → load steps back to
        the previous verified tag instead of crashing."""
        eng, *_ = ds.initialize(model=TransformerLM(get_preset("tiny")),
                                config=make_config())
        train_steps(eng, 2)
        eng.save_checkpoint(str(tmp_path))          # global_step2 (good)
        train_steps(eng, 1)
        eng.save_checkpoint(str(tmp_path))          # global_step3 (newest)
        tear_checkpoint_dir(str(tmp_path / "global_step3"), mode="truncate")

        eng2, *_ = ds.initialize(model=TransformerLM(get_preset("tiny")),
                                 config=make_config())
        path, _ = eng2.load_checkpoint(str(tmp_path))
        assert path is not None and path.endswith("global_step2")
        assert eng2.global_steps == 2
        rep = eng2.resilience_report()["checkpoint"]
        assert rep["verify_failures"] >= 1
        assert rep["load_fallbacks"] == 1
        # latest was repointed at the good tag
        from deepspeed_tpu.runtime.checkpoint import read_latest_tag

        assert read_latest_tag(str(tmp_path)) == "global_step2"

    def test_keep_last_k_gc(self, tmp_path, eight_devices):
        eng, *_ = ds.initialize(
            model=TransformerLM(get_preset("tiny")),
            config=make_config(resilience={"checkpoint": {"keep_last_k": 2}}))
        for _ in range(4):
            train_steps(eng, 1)
            eng.save_checkpoint(str(tmp_path))
        tags = sorted(d for d in os.listdir(tmp_path)
                      if os.path.isdir(tmp_path / d))
        assert tags == ["global_step3", "global_step4"]
        assert eng.resilience_report()["checkpoint"]["gc_removed"] == 2
        for t in tags:
            ok, why = verify_tag_dir(str(tmp_path / t))
            assert ok, why

    def test_io_error_retried(self, tmp_path, eight_devices):
        eng, *_ = ds.initialize(
            model=TransformerLM(get_preset("tiny")),
            config=make_config(resilience={
                "retry": {"max_attempts": 3, "base_delay_s": 0.001},
                "faults": [{"kind": "io_error", "times": 2}]}))
        train_steps(eng, 1)
        eng.save_checkpoint(str(tmp_path))  # survives 2 injected IO errors
        assert eng.resilience_report()["checkpoint"]["io_retries"] == 2
        ok, why = verify_tag_dir(str(tmp_path / "global_step1"))
        assert ok, why

    def test_legacy_checkpoint_loads_unverified(self, tmp_path,
                                                eight_devices):
        """Tags saved BEFORE resilience was enabled have no manifest; turning
        verification on must warn-and-load them, not strand the run."""
        legacy_cfg = make_config()
        legacy_cfg["resilience"] = {"enabled": False}
        eng, *_ = ds.initialize(model=TransformerLM(get_preset("tiny")),
                                config=legacy_cfg)
        train_steps(eng, 1)
        eng.save_checkpoint(str(tmp_path))          # no manifest written

        eng2, *_ = ds.initialize(model=TransformerLM(get_preset("tiny")),
                                 config=make_config())
        path, _ = eng2.load_checkpoint(str(tmp_path))
        assert path is not None and eng2.global_steps == 1

    def test_fp16_overflow_calibration_not_aborted(self, eight_devices):
        """fp16 dynamic-scale walk-down overflows are the loss scaler
        working; they must not burn the guard's abort budget."""
        eng, *_ = ds.initialize(
            model=TransformerLM(get_preset("tiny")),
            config=make_config(
                0, {"dp": 8},
                fp16={"enabled": True, "initial_scale_power": 126},
                bf16={"enabled": False},
                resilience={"max_consecutive_bad_steps": 1}))
        losses = train_steps(eng, 3)  # pre-fix: TooManyBadSteps on step 1
        assert eng.skipped_steps >= 1
        assert float(eng.scaler_state["scale"]) < 2.0 ** 126
        assert np.isfinite(losses[-1])

    def test_sigterm_emergency_save_is_loadable(self, tmp_path,
                                                eight_devices):
        """SIGTERM mid-training → emergency checkpoint at the next step
        boundary → a fresh engine resumes from it."""
        eng, *_ = ds.initialize(model=TransformerLM(get_preset("tiny")),
                                config=make_config())
        train_steps(eng, 1)
        eng.save_checkpoint(str(tmp_path))  # creates the manager + handler
        os.kill(os.getpid(), signal.SIGTERM)
        train_steps(eng, 1)                 # boundary fires the armed save
        assert eng.resilience_report()["checkpoint"]["emergency_saves"] == 1
        tags = [d for d in os.listdir(tmp_path) if d.startswith("preempt")]
        assert tags == ["preempt_step2"]
        ok, why = verify_tag_dir(str(tmp_path / tags[0]))
        assert ok, why

        eng2, *_ = ds.initialize(model=TransformerLM(get_preset("tiny")),
                                 config=make_config())
        path, _ = eng2.load_checkpoint(str(tmp_path))
        assert path is not None and path.endswith("preempt_step2")
        assert eng2.global_steps == 2
        losses = train_steps(eng2, 1, seed=3)
        assert np.isfinite(losses[0])


# ---------------------------------------------------------------------------
# Multi-host coordination (simulated processes)
# ---------------------------------------------------------------------------

class ThreadFleet:
    """Barrier-backed max-reduce over N thread-simulated processes — the test
    stand-in for ``comm.all_reduce_host(code, op=MAX)`` on a real slice."""

    def __init__(self, n):
        self.n = n
        self.barrier = threading.Barrier(n, timeout=30)
        self.vals = [0] * n

    def reducer(self, rank):
        def reduce(code):
            self.vals[rank] = int(code)
            self.barrier.wait()
            out = max(self.vals)
            self.barrier.wait()   # nobody rearms vals before everyone read
            return out
        return reduce

    def run(self, proc):
        """Run ``proc(rank)`` on N threads; re-raise the first failure."""
        errors = []

        def body(rank):
            try:
                proc(rank)
            except BaseException as e:  # noqa: BLE001 - surfaced below
                errors.append(e)

        threads = [threading.Thread(target=body, args=(r,))
                   for r in range(self.n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        if errors:
            raise errors[0]


def _fake_engine(step=5):
    """The minimal engine surface ``CheckpointManager.save`` touches —
    lets coordination drills run one simulated process per thread without
    paying an XLA compile per 'host'."""
    import jax.numpy as jnp

    return SimpleNamespace(
        params={"w": jnp.arange(4.0)},
        opt_state={"m": jnp.zeros(4)},
        scaler_state={"scale": jnp.float32(1.0), "good_steps": jnp.int32(0)},
        global_steps=step, global_samples=step * 8, micro_steps=step,
        skipped_steps=0, zero_stage=0,
        topology=SimpleNamespace(axis_sizes={}),
        lr_scheduler=None, _offload=None, _pending_ckpt=None,
        config=SimpleNamespace(checkpoint=SimpleNamespace(async_save=False)))


class TestCoordination:
    def test_divergent_preempt_signal_commits_identical_tag(self, tmp_path):
        """The acceptance drill: one simulated process gets the SIGTERM, its
        peer does not — the max-reduce turns the split-brain into a fleet
        SAVE, and every process commits the IDENTICAL tag with the decision
        recorded in its manifest."""
        fleet = ThreadFleet(2)
        tags = [None, None]
        # the simulated processes are threads of ONE process: two orbax saves
        # at once race on its process-wide temp-dir bookkeeping ("Checkpoint
        # directory does not exist ... orbax-checkpoint-tmp", seen under
        # load), which real processes cannot do to each other
        one_save_at_a_time = threading.Lock()

        def proc(rank):
            eng = _fake_engine(step=5)
            mgr = CheckpointManager(str(tmp_path / f"host{rank}"))
            coord = ResilienceCoordinator(reduce_fn=fleet.reducer(rank))
            if rank == 0:
                mgr.preempted = True          # only host 0 was preempted
            local = SAVE if mgr.preempted else CONTINUE
            decision = coord.decide(eng.global_steps, local,
                                    "preemption notice" if local else "")
            assert decision == SAVE           # ...but BOTH agree to save
            mgr.preempted = False
            tag = f"preempt_step{eng.global_steps}"
            with one_save_at_a_time:
                mgr.save(eng, tag=tag, emergency=True,
                         decision=coord.decision_record())
            tags[rank] = tag

        fleet.run(proc)
        assert tags[0] == tags[1] == "preempt_step5"
        from deepspeed_tpu.runtime.checkpoint import read_latest_tag

        for rank in range(2):
            host = tmp_path / f"host{rank}"
            ok, why = verify_tag_dir(str(host / tags[rank]))
            assert ok, why
            assert read_latest_tag(str(host)) == tags[rank]
            manifest = json.load(open(host / tags[rank] / "manifest.json"))
            # the decision + step are fleet-identical; the reason is local
            # (only the code crosses the wire) — the unsignaled peer records
            # that it acted on a peer's signal
            assert manifest["coordination"]["decision"] == "SAVE"
            assert manifest["coordination"]["step"] == 5
        m0 = json.load(open(tmp_path / "host0" / tags[0] / "manifest.json"))
        m1 = json.load(open(tmp_path / "host1" / tags[1] / "manifest.json"))
        assert m0["coordination"]["reason"] == "preemption notice"
        assert m1["coordination"]["reason"] == "peer signal"

    def test_peer_abort_vote_reaches_everyone(self):
        """An abort signaled on ONE process (watchdog hang, guard budget)
        aborts EVERY process at the same agreement step."""
        fleet = ThreadFleet(3)
        decisions = [None] * 3

        def proc(rank):
            coord = ResilienceCoordinator(reduce_fn=fleet.reducer(rank))
            if rank == 1:
                coord.signal_abort("hang: stuck collective all_reduce_host")
            decisions[rank] = coord.decide(7)

        fleet.run(proc)
        assert decisions == [ABORT, ABORT, ABORT]

    def test_abort_dominates_save(self):
        """One host preempted, another wedged: the fleet must ABORT (the
        wedged host cannot participate in a coherent save)."""
        fleet = ThreadFleet(2)
        decisions = [None, None]

        def proc(rank):
            coord = ResilienceCoordinator(reduce_fn=fleet.reducer(rank))
            (coord.signal_save if rank == 0 else coord.signal_abort)("x")
            decisions[rank] = coord.decide(3)

        fleet.run(proc)
        assert decisions == [ABORT, ABORT]

    def test_interval_holds_signal_until_scheduled_step(self):
        coord = ResilienceCoordinator(reduce_fn=lambda c: c, interval_steps=2)
        coord.signal_save("preempt")
        assert coord.decide(3) == CONTINUE    # off-interval: held, not lost
        assert coord.decide(4) == SAVE        # scheduled boundary: fires
        assert coord.counters["collectives"] == 1

    def test_single_process_decide_rides_comm_hooks(self):
        """Decide goes through ``all_reduce_host`` even at world=1, so the
        fault-injection and retry plumbing applies to the decision plane."""
        set_injector(FaultInjector([{"kind": "failed_collective", "times": 1}]))
        comm.set_retry_policy(RetryPolicy(max_attempts=2, base_delay_s=0.001))
        coord = ResilienceCoordinator()
        coord.signal_abort("drill")
        assert coord.decide(1) == ABORT
        assert comm.get_retry_stats()["retries"] >= 1


# ---------------------------------------------------------------------------
# Async checkpointing (manifest-committed background saves)
# ---------------------------------------------------------------------------

class TestAsyncCheckpoint:
    CFG = {"checkpoint": {"async_save": True}}

    def test_async_save_commits_in_background(self, tmp_path, eight_devices):
        eng, *_ = ds.initialize(model=TransformerLM(get_preset("tiny")),
                                config=make_config(resilience=dict(self.CFG)))
        train_steps(eng, 1)
        eng.save_checkpoint(str(tmp_path))
        mgr = eng._primary_mgr
        assert mgr.counters["async_saves"] == 1
        mgr.drain()
        from deepspeed_tpu.runtime.checkpoint import read_latest_tag

        ok, why = verify_tag_dir(str(tmp_path / "global_step1"))
        assert ok, why
        assert read_latest_tag(str(tmp_path)) == "global_step1"
        assert not (tmp_path / "global_step1" / STAGING_FILE).exists()
        rep = eng.resilience_report()
        # satellite: one call returns the full picture
        assert rep["checkpoint"]["async_saves"] == 1
        assert rep["checkpoint_async"]["commits"] == 1
        assert rep["checkpoint_async"]["last_latency_s"] > 0
        assert "retries" in rep["comm"] and "inflight" in rep["comm"]
        assert rep["coordination"]["counters"]["collectives"] >= 1
        eng.shutdown()

    def test_crash_between_stage_and_commit_falls_back(self, tmp_path,
                                                       eight_devices):
        """The acceptance drill: the commit thread dies between the staged
        data and the manifest — after 'restart', load lands on the PREVIOUS
        verified tag and the staged tag is rejected, not mistaken for a
        legacy pre-manifest checkpoint."""
        eng, *_ = ds.initialize(model=TransformerLM(get_preset("tiny")),
                                config=make_config(resilience=dict(self.CFG)))
        train_steps(eng, 2)
        eng.save_checkpoint(str(tmp_path))          # global_step2
        eng._primary_mgr.drain()                    # committed + verified
        train_steps(eng, 1)
        set_injector(FaultInjector(
            [{"kind": "io_error", "site": "async_commit"}]))
        eng.save_checkpoint(str(tmp_path))          # global_step3: stage only
        eng._primary_mgr.drain(raise_on_error=False)
        set_injector(None)
        assert eng._primary_mgr.counters["async_commit_failures"] == 1
        from deepspeed_tpu.runtime.checkpoint import read_latest_tag

        assert (tmp_path / "global_step3" / STAGING_FILE).exists()
        assert not (tmp_path / "global_step3" / "manifest.json").exists()
        assert read_latest_tag(str(tmp_path)) == "global_step2"

        # restart-and-load: the previous verified tag comes back
        eng2, *_ = ds.initialize(model=TransformerLM(get_preset("tiny")),
                                 config=make_config(resilience=dict(self.CFG)))
        path, _ = eng2.load_checkpoint(str(tmp_path))
        assert path is not None and path.endswith("global_step2")
        assert eng2.global_steps == 2
        # asking for the staged tag explicitly is refused, not half-loaded
        with pytest.raises(RuntimeError, match="uncommitted async stage"):
            eng2.load_checkpoint(str(tmp_path), tag="global_step3")
        assert eng2.resilience_report()["checkpoint"]["staged_rejected"] == 1
        eng2.shutdown()
        eng.shutdown()

    def test_emergency_save_drains_pending_and_commits_sync(self, tmp_path,
                                                            eight_devices):
        """SIGTERM with an async commit in flight: the emergency save fences
        the committer first and commits synchronously — the grace window
        never races a background thread."""
        eng, *_ = ds.initialize(model=TransformerLM(get_preset("tiny")),
                                config=make_config(resilience=dict(self.CFG)))
        train_steps(eng, 1)
        eng.save_checkpoint(str(tmp_path))          # async, maybe in flight
        os.kill(os.getpid(), signal.SIGTERM)
        train_steps(eng, 1)                         # boundary: agreed SAVE
        mgr = eng._primary_mgr
        assert mgr.counters["emergency_saves"] == 1
        assert mgr._pending_async is None
        ok, why = verify_tag_dir(str(tmp_path / "preempt_step2"))
        assert ok, why
        manifest = json.load(open(tmp_path / "preempt_step2" / "manifest.json"))
        assert manifest["coordination"]["decision"] == "SAVE"
        assert manifest["coordination"]["step"] == 2
        eng.shutdown()


# ---------------------------------------------------------------------------
# Heartbeat + hang watchdog
# ---------------------------------------------------------------------------

class TestHeartbeatWatchdog:
    def _cfg(self, tmp_path, faults=None, **hb):
        base = {"enabled": True, "dir": str(tmp_path / "hb"),
                "interval_s": 0.05, "poll_s": 0.05,
                "deadline_s": 30.0, "collective_deadline_s": None}
        base.update(hb)
        res = {"heartbeat": base}
        if faults:
            res["faults"] = faults
        return make_config(resilience=res)

    def test_stall_escalates_to_coordinated_abort(self, tmp_path,
                                                  eight_devices):
        eng, *_ = ds.initialize(model=TransformerLM(get_preset("tiny")),
                                config=self._cfg(tmp_path, deadline_s=0.4))
        train_steps(eng, 1)       # arm: stall detection needs one boundary
        time.sleep(0.8)           # wedge the 'step loop'
        with pytest.raises(CoordinatedAbort):
            train_steps(eng, 1)   # next boundary: fleet-agreed ABORT
        rep = eng.resilience_report()
        assert rep["aborted"] is True
        assert rep["heartbeat"]["counters"]["hangs_detected"] == 1
        assert rep["coordination"]["last_reason"].startswith("hang")
        assert "no step boundary" in rep["heartbeat"]["last_cause"]
        # the liveness file is on disk for peers/operators
        hb = json.load(open(tmp_path / "hb" / "heartbeat_0.json"))
        assert hb["rank"] == 0 and hb["step"] >= 1
        eng.shutdown()

    def test_stuck_collective_classified_and_aborted(self, tmp_path,
                                                     eight_devices):
        """A host collective that outlives its deadline (injected
        slow_collective riding the decision reduce) is detected WHILE in
        flight, classified by name, and escalated."""
        eng, *_ = ds.initialize(
            model=TransformerLM(get_preset("tiny")),
            config=self._cfg(tmp_path, collective_deadline_s=0.15,
                             faults=[{"kind": "slow_collective",
                                      "delay_s": 0.6}]))
        with pytest.raises(CoordinatedAbort):
            train_steps(eng, 2)
        rep = eng.resilience_report()
        assert rep["heartbeat"]["counters"]["stuck_collectives"] >= 1
        assert "all_reduce_host" in rep["heartbeat"]["last_cause"]
        eng.shutdown()

    def test_startup_compile_does_not_trip_stall_deadline(self, tmp_path,
                                                          eight_devices):
        """XLA compilation before the first boundary routinely exceeds any
        step deadline; the watchdog must stay disarmed until step 1."""
        eng, *_ = ds.initialize(model=TransformerLM(get_preset("tiny")),
                                config=self._cfg(tmp_path, deadline_s=0.05))
        time.sleep(0.3)           # 'compiling' — way past the deadline
        assert eng._watchdog.hang_detected is False
        losses = train_steps(eng, 1)
        assert np.isfinite(losses[0])
        eng.shutdown()


# ---------------------------------------------------------------------------
# Monitor surfacing (resilience/* event stream)
# ---------------------------------------------------------------------------

class TestMonitorEvents:
    def test_resilience_counters_flow_through_csv_monitor(self, tmp_path,
                                                          eight_devices):
        """ROADMAP item: resilience counters surface through the monitor
        backends — `resilience/*` gauges land in the CSV backend at the
        steps_per_print cadence."""
        eng, *_ = ds.initialize(
            model=TransformerLM(get_preset("tiny")),
            config=make_config(
                steps_per_print=1,
                monitor_config={"csv_monitor": {
                    "enabled": True, "output_path": str(tmp_path / "csv"),
                    "job_name": "drill"}},
                resilience={"faults": [{"kind": "nan_grads", "step": 1}]}))
        train_steps(eng, 2)   # one skipped (injected), two committed
        out = tmp_path / "csv" / "drill"
        names = {p.name for p in out.iterdir()}
        assert "resilience_skipped_steps.csv" in names
        assert "resilience_guard_bad_steps_skipped.csv" in names
        assert "resilience_comm_retries.csv" in names
        rows = (out / "resilience_skipped_steps.csv").read_text().splitlines()
        # header + one row per printed step; the last gauge shows the skip
        assert rows[0].startswith("step,value")
        assert float(rows[-1].split(",")[1]) == 1.0
        eng.shutdown()


# ---------------------------------------------------------------------------
# Elastic agent decision loop
# ---------------------------------------------------------------------------

class TestAgentDecisions:
    ECFG = {"max_train_batch_size": 32, "micro_batch_sizes": [1, 2, 4],
            "min_gpus": 1, "max_gpus": 8, "prefer_larger_batch": True}

    def test_gives_up_on_deterministic_abort(self, tmp_path):
        """Two step-guard aborts at the same step with the same exit code →
        respawning is pointless; the agent stops early with budget left."""
        report = str(tmp_path / "resilience_report.json")

        def spawn(chips, micro, idx):
            json.dump({"aborted": True, "global_steps": 5},
                      open(report, "w"))
            return 17

        agent = ElasticAgent(self.ECFG, max_restarts=5, report_path=report)
        res = agent.run(spawn, chips=8)
        assert not res.succeeded
        assert "deterministic failure" in res.gave_up_reason
        assert len(res.history) == 2  # gave up well under the budget of 5

    def test_respawns_when_progress_made(self, tmp_path):
        """Aborts at ADVANCING steps are worth respawning (data-dependent
        NaN moving past the bad batch via the fallback checkpoint)."""
        report = str(tmp_path / "resilience_report.json")
        steps = iter([3, 6, 9])

        def spawn(chips, micro, idx):
            json.dump({"aborted": True, "global_steps": next(steps)},
                      open(report, "w"))
            return 17 if idx < 2 else 0

        agent = ElasticAgent(self.ECFG, max_restarts=5, report_path=report)
        res = agent.run(spawn, chips=8)
        assert res.succeeded and res.restarts == 2

    def test_hang_abort_always_respawns(self, tmp_path):
        """Hang-triggered coordinated aborts are environmental, not
        deterministic: identical steps + identical exit codes must still get
        their respawn (the wedge was a lost host, not a poisoned batch)."""
        report = str(tmp_path / "resilience_report.json")
        calls = []

        def spawn(chips, micro, idx):
            json.dump({"aborted": True, "global_steps": 5,
                       "coordination": {"last_reason":
                                        "hang: stuck collective"}},
                      open(report, "w"))
            calls.append(idx)
            return 17 if idx < 2 else 0

        agent = ElasticAgent(self.ECFG, max_restarts=5, report_path=report)
        res = agent.run(spawn, chips=8)
        assert res.succeeded and res.restarts == 2  # no early give-up

    def test_restart_cap_stops_hot_loop(self):
        calls = []
        agent = ElasticAgent(self.ECFG, max_restarts=2,
                             respawn_backoff_s=0.001)
        res = agent.run(lambda c, m, i: calls.append(i) or 9, chips=8)
        assert not res.succeeded
        assert len(calls) == 3  # initial + 2 respawns, then the cap
        assert res.gave_up_reason == "restart budget spent"


# ---------------------------------------------------------------------------
# End-to-end recovery (the acceptance scenario)
# ---------------------------------------------------------------------------

TRAINER = textwrap.dedent("""
    import json, os, sys
    chips = int(os.environ["DSTPU_ELASTIC_CHIPS"])
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count={chips}")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM, get_preset
    from deepspeed_tpu.resilience import TooManyBadSteps

    ckpt = os.environ["DSTPU_CHECKPOINT_DIR"]
    restart = int(os.environ["DSTPU_RESTART_COUNT"])
    # restart 0: tear the step-3 checkpoint as it commits, then lose the
    # host DURING step 4 (the crash fault keys on global_steps, which still
    # reads 3 inside step 4 — before the step-4 save can land).
    # restart 1: clean run, but one NaN step to heal.
    faults = ([{"kind": "torn_checkpoint", "step": 3},
               {"kind": "crash", "step": 3, "hard": True, "exit_code": 43}]
              if restart == 0 else
              [{"kind": "nan_grads", "step": 4}])
    eng, *_ = ds.initialize(model=TransformerLM(get_preset("tiny")), config={
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
        "elasticity": {"enabled": True, "max_train_batch_size": 32,
                       "micro_batch_sizes": [1, 2, 4],
                       "min_gpus": 1, "max_gpus": 8},
        "resilience": {"enabled": True, "faults": faults,
                       "checkpoint": {"keep_last_k": 3}},
        "mesh": {"fsdp": chips}, "steps_per_print": 100})
    if os.path.exists(os.path.join(ckpt, "latest")):
        eng.load_checkpoint(ckpt)
    rec = {"chips": chips, "global_batch": eng.train_batch_size(),
           "micro": eng.train_micro_batch_size_per_gpu(),
           "start_step": eng.global_steps}
    rng = np.random.default_rng(0)
    B = eng.train_micro_batch_size_per_gpu() * eng.topology.dp_world_size
    while eng.global_steps < 6:
        for _ in range(eng.gradient_accumulation_steps()):
            loss = eng.forward({"input_ids": rng.integers(0, 256, (B, 16))})
            eng.backward(loss)
        eng.step()
        eng.save_checkpoint(ckpt)
    rec["end_step"] = eng.global_steps
    rec["report"] = eng.resilience_report()
    eng.write_resilience_report(ckpt)
    json.dump(rec, open(os.path.join(ckpt, f"run{restart}.json"), "w"))
""")


def test_e2e_crash_torn_checkpoint_recovery(tmp_path):
    """Acceptance: host crash at step 4 + torn step-3 checkpoint. The agent
    respawns at a smaller world size; the trainer's load falls back from the
    torn step-3 tag to the verified step-2 tag, heals one injected NaN step,
    and reaches step 6 with the global batch constant and the report showing
    the crash/fallback/skip counts."""
    script = tmp_path / "trainer.py"
    script.write_text(TRAINER)
    ckpt = str(tmp_path / "ckpt")
    os.makedirs(ckpt, exist_ok=True)
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["PYTHONPATH"] = (os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
        + os.pathsep + env.get("PYTHONPATH", ""))
    agent = ElasticAgent(
        {"max_train_batch_size": 32, "micro_batch_sizes": [1, 2, 4],
         "min_gpus": 1, "max_gpus": 8, "prefer_larger_batch": True},
        max_restarts=2, respawn_backoff_s=0.01,
        report_path=os.path.join(ckpt, "resilience_report.json"))
    res = agent.run(subprocess_spawn(str(script), [], env, ckpt), chips=8,
                    lost_per_failure=4)
    assert res.succeeded, [h.exit_code for h in res.history]
    assert res.restarts == 1
    assert [h.exit_code for h in res.history] == [43, 0]
    assert [h.chips for h in res.history] == [8, 4]

    rec = json.load(open(os.path.join(ckpt, "run1.json")))
    # resumed from the VERIFIED step-2 tag, not the torn step-3 one
    assert rec["start_step"] == 2, rec
    assert rec["end_step"] == 6
    assert rec["global_batch"] == res.history[0].global_batch
    report = rec["report"]
    assert report["checkpoint"]["verify_failures"] >= 1
    assert report["checkpoint"]["load_fallbacks"] == 1
    assert report["guard"]["bad_steps_skipped"] == 1  # the healed NaN step
    assert report["skipped_steps"] == 1
    # the agent saw the same report (its respawn-vs-give-up input)
    assert res.history[1].report["checkpoint"]["load_fallbacks"] == 1


def test_signal_counters_survive_thread_contention():
    """dslint burn-down (lock-discipline): ``signal_save``/``signal_abort``
    used to bump ``counters`` BEFORE taking ``_lock`` — a dict-slot ``+=``
    is read/add/store, so concurrent signal threads (SIGTERM handler,
    watchdog, guard) lost increments. The counters are ``guarded_by:
    _lock`` now; under a hostile switch interval every increment must
    land."""
    import sys

    from deepspeed_tpu.resilience.coordinator import ResilienceCoordinator

    coord = ResilienceCoordinator(reduce_fn=lambda c: c)
    n_threads, n_each = 8, 400
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)       # force preemption inside the +=
    try:
        def hammer():
            for _ in range(n_each):
                coord.signal_save("t")
                coord.signal_abort("t")
        ts = [threading.Thread(target=hammer) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    finally:
        sys.setswitchinterval(old)
    assert coord.counters["signals_save"] == n_threads * n_each
    assert coord.counters["signals_abort"] == n_threads * n_each
    # the pending escalation itself also made it through intact
    assert coord.decide(0) == ABORT
