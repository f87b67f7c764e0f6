"""Durable cross-replica request migration tests.

Fast tests pin the portable-resume contracts directly: the manifest
protocol in ``deepspeed_tpu/inference/kv_tier.py`` (canonical-JSON sha256
roundtrip, torn/skewed/tampered docs raise :class:`ManifestError`, POSIX
rename claim = exactly one winner, TTL sweep reclaims both manifests and
their durable KV files), and the batcher-level adoption ladder in
``deepspeed_tpu/serving/batcher.py`` + ``inference/engine_v2.py``
(export on donor A -> adopt on sibling B promotes KV that B never
produced, greedy tokens bit-identical in fp32; donor-GC'd / torn / IO-err
paths all unwind to re-prefill from token history, never zero-fill).

The two-replica crash storm lives in ``tools/serve_drill.py``
(``--scenario crash-migrate``); the ``slow``-marked wrapper at the bottom
runs it under pytest the way the slo-storm wrapper does.
"""

import json
import os
import threading

import numpy as np
import pytest

from deepspeed_tpu.config.config import ServingConfig
from deepspeed_tpu.inference.kv_tier import (ManifestError, claim_manifest,
                                             load_manifest, manifest_dir,
                                             sweep_manifests, write_manifest)
from deepspeed_tpu.serving import COMPLETED, ContinuousBatcher, ShedError

pytestmark = [pytest.mark.migrate, pytest.mark.serving]

_TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "tools")


# ---------------------------------------------------------------------------
# manifest protocol (no engine needed)
# ---------------------------------------------------------------------------

def _payload(uid="u1"):
    return {"uid": uid, "seen_tokens": 11,
            "hist": [3, 1, 4, 1, 5], "entries": [
                {"name": f"{uid}-k0", "tier": "nvme", "nbytes": 64}]}


class TestManifestProtocol:
    def test_roundtrip(self, tmp_path):
        shared = str(tmp_path)
        path = write_manifest(shared, _payload())
        assert os.path.dirname(path) == manifest_dir(shared)
        assert os.path.basename(path) == "u1.json"
        assert load_manifest(path) == _payload()
        # committed atomically: no .tmp droppings beside it
        assert not [f for f in os.listdir(manifest_dir(shared))
                    if f.endswith(".tmp")]

    def test_torn_write_raises(self, tmp_path):
        path = write_manifest(str(tmp_path), _payload())
        raw = open(path).read()
        with open(path, "w") as f:
            f.write(raw[:len(raw) // 2])     # torn mid-document
        with pytest.raises(ManifestError):
            load_manifest(path)

    def test_sha_mismatch_raises(self, tmp_path):
        path = write_manifest(str(tmp_path), _payload())
        doc = json.load(open(path))
        doc["payload"]["seen_tokens"] = 999   # tampered after commit
        with open(path, "w") as f:
            json.dump(doc, f)
        with pytest.raises(ManifestError):
            load_manifest(path)

    def test_version_skew_raises(self, tmp_path):
        path = write_manifest(str(tmp_path), _payload())
        doc = json.load(open(path))
        doc["version"] = 999
        with open(path, "w") as f:
            json.dump(doc, f)
        with pytest.raises(ManifestError):
            load_manifest(path)

    def test_missing_raises_filenotfound(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            load_manifest(str(tmp_path / "manifests" / "nope.json"))

    def test_claim_exactly_one_winner(self, tmp_path):
        path = write_manifest(str(tmp_path), _payload())
        claimed = claim_manifest(path)
        assert claimed == path + ".claimed" and os.path.exists(claimed)
        assert claim_manifest(path) is None   # second claimant loses
        assert load_manifest(claimed) == _payload()

    def test_claim_race_threaded(self, tmp_path):
        """Satellite: two siblings race one manifest — POSIX rename makes
        exactly one the adopter, every time."""
        for round_ in range(8):
            path = write_manifest(str(tmp_path), _payload(f"r{round_}"))
            wins, barrier = [], threading.Barrier(2)

            def race():
                barrier.wait()
                got = claim_manifest(path)
                if got is not None:
                    wins.append(got)

            ts = [threading.Thread(target=race) for _ in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            assert len(wins) == 1
            os.remove(wins[0])

    def test_sweep_reclaims_aged_manifests_and_kv(self, tmp_path):
        shared = str(tmp_path)
        kv = os.path.join(shared, "kv")
        os.makedirs(kv)
        swp = os.path.join(kv, "u1-k0.swp")
        open(swp, "wb").write(b"\0" * 64)
        path = write_manifest(shared, _payload())
        claimed_src = write_manifest(shared, _payload("u2"))
        claim_manifest(claimed_src)           # orphaned claim ages out too
        stray = os.path.join(manifest_dir(shared), "junk.json.tmp")
        open(stray, "w").write("{")
        now = os.path.getmtime(path) + 100.0
        assert sweep_manifests(shared, ttl_s=1e9, now=now) == 0
        assert sweep_manifests(shared, ttl_s=0, now=now) == 0   # disabled
        assert sweep_manifests(shared, ttl_s=50.0, now=now) == 2
        assert not os.path.exists(path)
        assert not os.path.exists(swp)        # entries' KV died with it
        assert not os.path.exists(stray)
        survivors = write_manifest(shared, _payload("u3"))
        assert sweep_manifests(shared, ttl_s=1e6,
                               now=os.path.getmtime(survivors) + 1) == 0
        assert os.path.exists(survivors)      # fresh manifests survive


# ---------------------------------------------------------------------------
# batcher-level A -> B adoption (fp32 engines, bit-identical greedy)
# ---------------------------------------------------------------------------

def _mig_batcher(shared, decode_kernel="xla", **serving):
    """fp32 engine + SLO preemption + migration pointed at ``shared``. What
    these cases test is what the router and the store do, so the engine
    attends with the XLA twin; the tentpole case asks for the kernel
    (interpreted here), whose parity is ``test_decode_kernel.py``'s."""
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.models import TransformerLM, get_preset

    eng = InferenceEngineV2(
        TransformerLM(get_preset("tiny", dtype="float32")),
        max_sequences=8, max_seq_len=128, block_size=16,
        decode_kernel=decode_kernel)
    cfg = ServingConfig(**{
        "prefill_chunk": 32, "default_max_new_tokens": 8,
        "slo": {"enabled": True, "preempt": True},
        "migration": {"enabled": True, "shared_nvme_path": shared,
                      "manifest_ttl_s": 300.0}, **serving})
    return ContinuousBatcher(eng, cfg)


_BASELINES = {}


def _baseline(shared, prompt, n=8):
    """The greedy tokens of an unmigrated fp32 run (the engines' weights are
    seeded alike); kept by prompt, so the cases that migrate the same prompt
    build one solo engine between them."""
    key = (tuple(int(t) for t in prompt), n)
    if key not in _BASELINES:
        solo = _mig_batcher(shared)
        uid = solo.submit(prompt, max_new_tokens=n, tier="batch")
        solo.pump(max_steps=80)
        _BASELINES[key] = list(solo.manager.result(uid).generated)
        solo.engine.close()
        assert len(_BASELINES[key]) == n
    return list(_BASELINES[key])


# the 40-token prompt of the cases that sever a request mid-decode: one
# prompt, so one baseline (what differs between the cases is the seam that
# fails, not the tokens)
PROMPT = list(np.random.default_rng(7).integers(0, 250, 40))


def _pause_mid_decode(b, uid):
    """Step until ``uid`` is genuinely mid-decode, then pause + export."""
    for _ in range(4):
        b.step()
    req = b.manager.active[uid]
    assert 0 < len(req.generated) < req.max_new_tokens
    assert b.engine.pause_request(uid)
    b.manager.pause(req)
    b._export_manifest(req)
    return req


class TestCrossReplicaAdoption:
    def test_durable_migrate_mid_decode_bit_identical(self, tmp_path):
        """Tentpole invariant: pause on A, crash-style export, adopt on B
        through the claimed manifest — B promotes KV it never produced
        through the same ``_flush_promotes`` fence and finishes the exact
        greedy sequence of an unmigrated fp32 run."""
        shared = str(tmp_path)
        prompt = PROMPT
        base = _baseline(shared, prompt)

        # the file's end-to-end case on the kernel: both replicas attend with
        # it, and finish the XLA twin's baseline to the token
        a = _mig_batcher(shared, decode_kernel="pallas")
        uid = a.submit(prompt, max_new_tokens=8, tier="batch")
        req = _pause_mid_decode(a, uid)
        mid = len(req.generated)
        # ownership transfer (what capture_dead does for a dead donor)
        path = a.engine.export_paused(
            uid, f"{a.migration_tag}-{uid}",
            a._mig.shared_nvme_path, keep=False)
        assert path is not None and os.path.exists(path)
        assert a.counters["pause_exports"] == 1

        b = _mig_batcher(shared, decode_kernel="pallas")
        claimed = claim_manifest(path)
        assert claimed is not None
        payload = load_manifest(claimed)
        assert payload["seen_tokens"] > 0 and payload["entries"]
        new = b.adopt_inflight(req, payload, claimed, migrated_from="a")
        assert new.migrated_from == "a"    # fresh uid in B's own namespace
        assert list(new.generated) == list(req.generated)
        assert b.engine.is_paused(new.uid)
        b.pump(max_steps=80)
        res = b.manager.result(new.uid)
        assert b.manager.resolve(new.uid) == COMPLETED
        assert list(res.generated) == base        # bit-identical greedy
        assert len(res.generated) > mid           # B actually decoded
        assert b.manager.counters["adopted"] == 1
        assert b.counters["reprefill_fallbacks"] == 0
        # sibling-side discard reclaimed the donor's durable files
        assert b.engine._tier_store.entries() == 0
        alloc = b.engine.state.allocator
        assert alloc.free_blocks == alloc.num_blocks
        assert not os.path.exists(claimed)
        a.engine.close()
        b.engine.close()

    def test_adopt_after_donor_gc_falls_back_to_reprefill(self, tmp_path):
        """Satellite: a manifest whose tier entries were swept (donor GC /
        cap eviction) adopts as a clean re-prefill — recompute from token
        history, never zero-fill — and still matches the baseline."""
        shared = str(tmp_path)
        prompt = PROMPT
        base = _baseline(shared, prompt)

        a = _mig_batcher(shared)
        uid = a.submit(prompt, max_new_tokens=8, tier="batch")
        req = _pause_mid_decode(a, uid)
        path = a.engine.export_paused(
            uid, f"{a.migration_tag}-{uid}",
            a._mig.shared_nvme_path, keep=False)
        kv_dir = os.path.join(shared, "kv")
        for f in os.listdir(kv_dir):          # donor-side GC swept the KV
            os.remove(os.path.join(kv_dir, f))

        b = _mig_batcher(shared)
        claimed = claim_manifest(path)
        payload = load_manifest(claimed)
        with pytest.raises(Exception):
            b.adopt_inflight(req, payload, claimed, migrated_from="a")
        # the failed adopt unwound: the fresh uid was never exposed
        assert not b.manager.active and not b.manager.queue
        new = b.adopt_inflight(req, None, None, migrated_from="a")
        assert new.replay is not None          # re-prefill armed
        b.pump(max_steps=120)
        assert b.manager.resolve(new.uid) == COMPLETED
        assert list(b.manager.result(new.uid).generated) == base
        a.engine.close()
        b.engine.close()

    def test_reprefill_mid_chunked_prefill_bit_identical(self, tmp_path):
        """Satellite: a request severed MID-chunked-prefill (no generated
        tokens yet, partial KV lost with the donor) re-prefills from its
        prompt on the sibling and matches the baseline."""
        shared = str(tmp_path)
        prompt = list(np.random.default_rng(13).integers(0, 250, 96))
        base = _baseline(shared, prompt)

        a = _mig_batcher(shared)
        uid = a.submit(prompt, max_new_tokens=8, tier="batch")
        a.step()                               # one 32-token chunk
        req = a.manager.active[uid]
        assert 0 < req.prefilled < len(prompt)   # genuinely mid-prefill

        b = _mig_batcher(shared)
        new = b.adopt_inflight(req, None, None, migrated_from="a")
        b.pump(max_steps=120)
        assert b.manager.resolve(new.uid) == COMPLETED
        assert list(b.manager.result(new.uid).generated) == base
        a.engine.close()
        b.engine.close()

    def test_double_adopt_guard_exactly_one_wins(self, tmp_path):
        """Satellite: two siblings race one exported manifest; the rename
        claim lets exactly one adopt durable KV, the loser re-prefills."""
        shared = str(tmp_path)
        prompt = PROMPT
        base = _baseline(shared, prompt)

        a = _mig_batcher(shared)
        uid = a.submit(prompt, max_new_tokens=8, tier="batch")
        req = _pause_mid_decode(a, uid)
        path = a.engine.export_paused(
            uid, f"{a.migration_tag}-{uid}",
            a._mig.shared_nvme_path, keep=False)
        c1, c2 = claim_manifest(path), claim_manifest(path)
        assert (c1 is None) != (c2 is None)    # exactly one winner
        winner = c1 or c2

        b1, b2 = _mig_batcher(shared), _mig_batcher(shared)
        w = b1.adopt_inflight(req, load_manifest(winner), winner,
                              migrated_from="a")
        loser = b2.adopt_inflight(req, None, None, migrated_from="a")
        b1.pump(max_steps=80)
        b2.pump(max_steps=120)
        assert list(b1.manager.result(w.uid).generated) == base
        assert list(b2.manager.result(loser.uid).generated) == base
        a.engine.close()
        b1.engine.close()
        b2.engine.close()


# ---------------------------------------------------------------------------
# failure ladder: injected faults at every migration seam
# ---------------------------------------------------------------------------

class TestMigrationFaults:
    def test_crash_during_pause_export_leaves_no_debris(self, tmp_path):
        """``crash_during_pause_export`` dies between the KV demote and
        the manifest commit: no manifest, no orphaned durable KV, and the
        request is still locally resumable (pause intact)."""
        from deepspeed_tpu.resilience.faults import (FaultInjector,
                                                     set_injector)

        shared = str(tmp_path)
        prompt = PROMPT
        base = _baseline(shared, prompt)
        a = _mig_batcher(shared)
        uid = a.submit(prompt, max_new_tokens=8, tier="batch")
        for _ in range(4):
            a.step()
        req = a.manager.active[uid]
        assert a.engine.pause_request(uid)
        a.manager.pause(req)
        try:
            set_injector(FaultInjector(
                [{"kind": "crash_during_pause_export"}]))
            a._export_manifest(req)            # swallowed + logged
        finally:
            set_injector(None)
        assert a.counters["pause_exports"] == 0
        mdir = manifest_dir(shared)
        assert not os.path.exists(os.path.join(
            mdir, f"{a.migration_tag}-{uid}.json"))
        kv_dir = os.path.join(shared, "kv")
        assert not (os.path.isdir(kv_dir) and os.listdir(kv_dir))
        a.pump(max_steps=80)                   # pause itself survived
        assert a.manager.resolve(uid) == COMPLETED
        assert list(a.manager.result(uid).generated) == base
        a.engine.close()

    def test_manifest_torn_detected_on_load(self, tmp_path):
        """``manifest_torn`` truncates a just-committed manifest; the
        sibling's load detects it (sha/json) instead of adopting garbage."""
        from deepspeed_tpu.resilience.faults import (FaultInjector,
                                                     set_injector)

        shared = str(tmp_path)
        a = _mig_batcher(shared)
        prompt = list(np.random.default_rng(23).integers(0, 250, 40))
        uid = a.submit(prompt, max_new_tokens=8, tier="batch")
        for _ in range(4):
            a.step()
        req = a.manager.active[uid]
        assert a.engine.pause_request(uid)
        a.manager.pause(req)
        try:
            set_injector(FaultInjector([{"kind": "manifest_torn"}]))
            path = a.engine.export_paused(
                uid, f"{a.migration_tag}-{uid}",
                a._mig.shared_nvme_path, keep=False)
        finally:
            set_injector(None)
        assert path is not None
        with pytest.raises(ManifestError):
            load_manifest(path)
        a.engine.close()

    def test_migrate_io_error_unwinds_to_reprefill(self, tmp_path):
        """``migrate_io_error`` fails the adopted tier read mid-promote:
        the resume unwinds (cancel, not zero-fill), the batcher requeues
        the MIGRATED request for re-prefill, and it still completes
        bit-identical with ``reprefill_fallbacks`` counted."""
        from deepspeed_tpu.resilience.faults import (FaultInjector,
                                                     set_injector)

        shared = str(tmp_path)
        prompt = PROMPT
        base = _baseline(shared, prompt)
        a = _mig_batcher(shared)
        uid = a.submit(prompt, max_new_tokens=8, tier="batch")
        req = _pause_mid_decode(a, uid)
        path = a.engine.export_paused(
            uid, f"{a.migration_tag}-{uid}",
            a._mig.shared_nvme_path, keep=False)

        b = _mig_batcher(shared)
        claimed = claim_manifest(path)
        new = b.adopt_inflight(req, load_manifest(claimed), claimed,
                               migrated_from="a")
        try:
            set_injector(FaultInjector([{"kind": "migrate_io_error"}]))
            b.pump(max_steps=10)               # resume attempt fails
        finally:
            set_injector(None)
        b.pump(max_steps=120)                  # re-prefill completes it
        assert b.manager.resolve(new.uid) == COMPLETED
        assert list(b.manager.result(new.uid).generated) == base
        assert b.counters["reprefill_fallbacks"] == 1
        assert b.manager.counters["reprefills"] == 1
        alloc = b.engine.state.allocator
        assert alloc.free_blocks == alloc.num_blocks
        a.engine.close()
        b.engine.close()


# ---------------------------------------------------------------------------
# voluntary rebalance + trace continuity
# ---------------------------------------------------------------------------

class TestRebalanceAndTrace:
    def test_voluntary_rebalance_transfers_paused_work(self, tmp_path):
        """Satellite: A exports a paused batch-tier request with ownership
        transferred — resolved locally as ``rebalanced`` with its HBM and
        slot already free — and B resumes it bit-identical."""
        shared = str(tmp_path)
        prompt = PROMPT
        base = _baseline(shared, prompt)

        a = _mig_batcher(shared)
        uid = a.submit(prompt, max_new_tokens=8, tier="batch")
        req = _pause_mid_decode(a, uid)
        # no step in between: on an otherwise-idle pool the resume pump
        # would bring the pause straight back before the export ran
        out = a.export_paused_for_rebalance()
        assert len(out) == 1 and out[0][0].uid == uid
        req, path = out[0]
        assert a.manager.resolve(uid) == "shed"
        assert a.manager.result(uid).finish_reason == "rebalanced"
        assert a.manager.counters["rebalanced"] == 1
        # donor side fully released BEFORE the sibling touches anything
        assert not a.engine.is_paused(uid)
        assert uid not in a.engine.state.sequences
        alloc = a.engine.state.allocator
        assert alloc.free_blocks == alloc.num_blocks

        b = _mig_batcher(shared)
        claimed = claim_manifest(path)
        new = b.adopt_inflight(req, load_manifest(claimed), claimed,
                               migrated_from="a")
        b.pump(max_steps=80)
        assert list(b.manager.result(new.uid).generated) == base
        a.engine.close()
        b.engine.close()

    def test_trace_id_spans_donor_to_sibling(self, tmp_path):
        """Satellite: the adopted request re-opens the DONOR's trace id,
        so one ``/v1/trace`` chain shows export -> adopt -> resumed
        tokens."""
        from deepspeed_tpu.observability import configure_tracing, get_bus

        shared = str(tmp_path)
        bus = configure_tracing(enabled=True)
        bus.clear()
        try:
            a = _mig_batcher(shared)
            prompt = list(np.random.default_rng(37).integers(0, 250, 40))
            uid = a.submit(prompt, max_new_tokens=8, tier="batch")
            req = _pause_mid_decode(a, uid)
            donor_trace = req.trace_id
            assert donor_trace is not None
            path = a.engine.export_paused(
                uid, f"{a.migration_tag}-{uid}",
                a._mig.shared_nvme_path, keep=False)

            b = _mig_batcher(shared)
            claimed = claim_manifest(path)
            new = b.adopt_inflight(req, load_manifest(claimed), claimed,
                                   migrated_from="a")
            assert new.trace_id == donor_trace   # one chain, two replicas
            b.pump(max_steps=80)
            evs = [e for e in get_bus().events()
                   if e.trace_id == donor_trace]
            whats = [(e.args or {}).get("what") for e in evs]
            assert "pause" in whats              # donor side
            assert "adopt" in whats              # sibling side
            assert "resume" in whats             # first resumed step
            a.engine.close()
            b.engine.close()
        finally:
            configure_tracing(enabled=False)
            bus.clear()


# ---------------------------------------------------------------------------
# slow wrapper: the two-replica crash-migrate storm
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_serve_drill_crash_migrate(tmp_path):
    """Tier-1 (slow) wrapper for ``serve_drill --scenario crash-migrate``:
    storm two replicas sharing an NVMe namespace, kill one mid-decode;
    the sibling resumes >= 1 request from its durable manifest and
    recovers the manifest-less rest by re-prefill — zero lost uids,
    tokens bit-identical to an uncrashed replay, every pool block, tier
    entry and manifest reclaimed."""
    import sys

    sys.path.insert(0, _TOOLS)
    from serve_drill import run_scenario

    verdict = run_scenario("crash-migrate", workdir=str(tmp_path))
    assert verdict["ok"], verdict
