"""Prefix-cache KV reuse + n-gram speculative decoding tests.

Cache-exactness is the contract under test: the same prompt served cold vs
prefix-cached, and greedy decode with speculation on vs off, must produce
IDENTICAL tokens — sharing/drafting may only change how much work it takes
to produce them. Exactness tests run the tiny model in float32: in bf16 a
random-init model's near-tied logits can flip argmax between the (all
numerically-equivalent) attention kernel variants, which is a test-model
artifact, not a property of the mechanism (a trained model's logit margins
dwarf kernel rounding).

Also here: the refcounted-allocator satellite (double-free raises), the
duplicate-uid ``can_schedule_batch`` satellite, LRU eviction under pool
pressure, and refcount-leak-free pool restoration. The end-to-end
``prefix-storm`` drill lives in ``tools/serve_drill.py``; its slow wrapper
is at the bottom under the ``perf`` marker.
"""

import os

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference import (BlockedAllocator, InferenceEngineV2,
                                     PrefixCache, SequenceManager,
                                     ngram_draft)
from deepspeed_tpu.models import TransformerLM, get_preset

_TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "tools")


# ---------------------------------------------------------------------------
# refcounted allocator (satellite: double-free must raise)
# ---------------------------------------------------------------------------

class TestRefcountedAllocator:
    def test_double_free_raises(self):
        alloc = BlockedAllocator(num_blocks=4, block_size=8)
        a = alloc.allocate(2)
        alloc.free(a)
        assert alloc.free_blocks == 4
        with pytest.raises(RuntimeError, match="double free"):
            alloc.free(a)              # second free of the same blocks
        # the failed free must not have corrupted the free list
        assert alloc.free_blocks == 4
        with pytest.raises(RuntimeError, match="double free"):
            alloc.free([0])            # never-reallocated block

    def test_shared_block_needs_one_free_per_owner(self):
        alloc = BlockedAllocator(num_blocks=2, block_size=8)
        [b] = alloc.allocate(1)
        alloc.incref([b])              # second owner (e.g. the prefix tree)
        alloc.free([b])                # first owner releases
        assert alloc.free_blocks == 1  # still held by the second owner
        assert alloc.refcount(b) == 1
        alloc.free([b])
        assert alloc.free_blocks == 2
        with pytest.raises(RuntimeError, match="double free"):
            alloc.free([b])

    def test_incref_of_free_block_raises(self):
        alloc = BlockedAllocator(num_blocks=2, block_size=8)
        with pytest.raises(RuntimeError, match="unallocated"):
            alloc.incref([0])


# ---------------------------------------------------------------------------
# duplicate-uid joint schedulability (satellite)
# ---------------------------------------------------------------------------

class TestDuplicateUidBatch:
    def test_duplicate_uid_blocks_costed_cumulatively(self):
        """A uid listed twice must be costed against its PROJECTED state
        after the first occurrence — the old per-occurrence check read the
        original ``seen_tokens`` twice and undercounted block demand."""
        sm = SequenceManager(max_sequences=2, max_seq_len=64, block_size=8,
                             num_blocks=8)
        sm.schedule(1, 4)
        sm.commit(1)                   # seen=4, holds 1 block (4/8 used)
        taken = sm.allocator.allocate(sm.allocator.free_blocks)  # drain pool
        # two 4-token chunks: cumulative 4+8=12 tokens -> needs a 2nd block;
        # per-occurrence math said ceil(8/8)-1 = 0 twice -> "schedulable"
        assert not sm.can_schedule_batch([1, 1], [4, 4])
        sm.allocator.free(taken)
        assert sm.can_schedule_batch([1, 1], [4, 4])

    def test_duplicate_uid_seq_len_costed_cumulatively(self):
        sm = SequenceManager(max_sequences=2, max_seq_len=32, block_size=8)
        sm.schedule(1, 30)
        sm.commit(1)
        # each occurrence alone fits (30+2 <= 32); jointly 34 > 32
        assert sm.can_schedule_batch([1], [2])
        assert not sm.can_schedule_batch([1, 1], [2, 2])

    def test_duplicate_new_uid_counts_one_slot(self):
        sm = SequenceManager(max_sequences=1, max_seq_len=32, block_size=8)
        assert sm.can_schedule_batch([7, 7], [4, 4])   # one slot, not two


# ---------------------------------------------------------------------------
# PrefixCache state machine (no engine)
# ---------------------------------------------------------------------------

class TestPrefixCacheState:
    def _cache(self, num_blocks=8, bs=4, **kw):
        alloc = BlockedAllocator(num_blocks, bs)
        return alloc, PrefixCache(alloc, **kw)

    def test_full_block_granularity_and_roundtrip(self):
        alloc, pc = self._cache()
        toks = np.arange(10, dtype=np.int32)          # 2 full blocks + tail 2
        blocks = alloc.allocate(3)
        assert pc.insert(toks, blocks) == 2           # tail block not cached
        got, n = pc.peek(toks)
        assert n == 8 and got == blocks[:2]
        # a diverging second block matches only the first
        other = np.concatenate([toks[:4], toks[4:8] + 1])
        _, n2 = pc.peek(other)
        assert n2 == 4
        # acquire takes a reference per matched block
        acq, n3 = pc.acquire(toks)
        assert n3 == 8
        assert alloc.refcount(blocks[0]) == 3         # owner + tree + acquire
        assert pc.counters["hits"] == 1 and pc.counters["hit_tokens"] == 8

    def test_max_tokens_caps_at_full_blocks(self):
        alloc, pc = self._cache()
        toks = np.arange(8, dtype=np.int32)
        pc.insert(toks, alloc.allocate(2))
        # cap 7 (len-1): only 1 full block may match — the tail block is
        # recomputed, never shared (copy-on-write by recompute)
        _, n = pc.peek(toks, max_tokens=7)
        assert n == 4

    def test_lru_eviction_spares_referenced_blocks(self):
        alloc, pc = self._cache(num_blocks=4, bs=4)
        a = alloc.allocate(1)
        b = alloc.allocate(1)
        pc.insert(np.arange(4), a)
        pc.insert(np.arange(100, 104), b)
        alloc.free(a)                  # tree is now block a's only owner
        alloc.free(b)
        pc.acquire(np.arange(100, 104))   # pin b via a live reference, bump LRU
        assert pc.evictable_blocks() == 1
        assert pc.evict(2) == 1        # only a can go; b is pinned
        assert pc.peek(np.arange(4))[1] == 0
        assert pc.peek(np.arange(100, 104))[1] == 4

    def test_lru_order(self):
        alloc, pc = self._cache(num_blocks=4, bs=4)
        a, b = alloc.allocate(1), alloc.allocate(1)
        pc.insert(np.arange(4), a)
        pc.insert(np.arange(100, 104), b)
        alloc.free(a)
        alloc.free(b)
        got, _ = pc.acquire(np.arange(4))   # refresh a: b is now LRU
        alloc.free(got)
        assert pc.evict(1) == 1
        assert pc.peek(np.arange(4))[1] == 4          # a survived
        assert pc.peek(np.arange(100, 104))[1] == 0   # b evicted

    def test_interior_nodes_evict_only_after_leaves(self):
        alloc, pc = self._cache(num_blocks=4, bs=4)
        blocks = alloc.allocate(2)
        pc.insert(np.arange(8), blocks)    # chain: parent -> child
        alloc.free(blocks)
        assert pc.evict(1) == 1            # must take the LEAF (child)
        assert pc.peek(np.arange(8))[1] == 4   # parent still matches
        assert pc.evict(1) == 1
        assert alloc.free_blocks == 4

    def test_max_blocks_cap(self):
        alloc, pc = self._cache(num_blocks=8, bs=4, max_blocks=2)
        a = alloc.allocate(3)
        pc.insert(np.arange(12), a)
        assert pc._nodes == 2              # third block refused at the cap
        alloc.free(a)                      # tree keeps refs on the first two
        b = alloc.allocate(1)
        pc.insert(np.arange(100, 104), b)  # evicts LRU to stay at cap
        assert pc._nodes == 2
        assert pc.counters["evicted_blocks"] == 1

    def test_max_blocks_insert_never_orphans_descent_path(self):
        """At the cap, insert must NOT evict a node on the prefix it is
        descending — the new node would attach to a detached parent, an
        unreachable subtree whose cache references could never be released
        (review regression)."""
        alloc, pc = self._cache(num_blocks=8, bs=4, max_blocks=1)
        a = alloc.allocate(1)
        pc.insert(np.arange(4), a)         # node A fills the cap
        alloc.free(a)                      # A rc1: the sole evictable leaf
        b = alloc.allocate(2)
        pc.insert(np.arange(8), b)         # descends THROUGH A at the cap
        alloc.free(b)
        pc.clear()
        assert alloc.free_blocks == 8
        assert not alloc.leaked_blocks()

    def test_clear_releases_only_tree_refs(self):
        alloc, pc = self._cache(num_blocks=4, bs=4)
        a = alloc.allocate(1)
        pc.insert(np.arange(4), a)
        assert pc.clear() == 1
        assert alloc.refcount(a[0]) == 1   # the live owner's ref remains
        alloc.free(a)
        assert alloc.free_blocks == 4 and not alloc.leaked_blocks()


# ---------------------------------------------------------------------------
# n-gram drafter
# ---------------------------------------------------------------------------

class TestNgramDraft:
    def test_draft_follows_most_recent_occurrence(self):
        h = [1, 2, 3, 9, 1, 2, 4, 7, 1, 2]
        d = list(ngram_draft(h, ngram=2, max_draft=3))
        assert d == [4, 7, 1]              # continuation of the LATEST [1,2]

    def test_backoff_to_shorter_ngram(self):
        h = [5, 6, 7, 8, 6]                # [8, 6] never repeats; [6] does
        assert list(ngram_draft(h, ngram=2, max_draft=2)) == [7, 8]

    def test_no_repeat_no_draft(self):
        assert ngram_draft([1, 2, 3, 4], ngram=3, max_draft=4).size == 0
        assert ngram_draft([1], ngram=3, max_draft=4).size == 0
        assert ngram_draft([1, 1], ngram=2, max_draft=0).size == 0


# ---------------------------------------------------------------------------
# engine integration (fp32 tiny model: exactness without bf16 tie noise;
# module-scoped SHARED engines — every fresh InferenceEngineV2 re-jits its
# whole step family, so tests reuse engines and reset state between them)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def f32_lm():
    model = TransformerLM(get_preset("tiny", dtype="float32"))
    params = model.init(jax.random.key(0))
    return model, params


_SPEC = {"enabled": True, "ngram": 2, "max_draft": 4, "fallback_steps": 4}


def _engine(model, params, **kw):
    """The cache's, the drafter's and the tiers' cases test what the store
    does, so their engines attend with the XLA twin; ``tier_eng`` keeps the
    kernel (interpreted here), with which promotions ride the step's own
    dispatch."""
    base = dict(max_sequences=8, max_seq_len=128, block_size=16,
                decode_kernel="xla")
    base.update(kw)
    return InferenceEngineV2(model, params=params, **base)


def _reset(eng):
    """Back to a cold engine: flush every sequence, drop the prefix tree,
    zero the feature counters (they are lifetime-cumulative)."""
    eng.flush(list(eng.state.sequences))
    if eng.prefix_cache is not None:
        eng.prefix_cache.clear()
        for k in eng.prefix_cache.counters:
            eng.prefix_cache.counters[k] = 0
    for k in eng.spec_stats:
        eng.spec_stats[k] = 0
    alloc = eng.state.allocator
    assert alloc.free_blocks == alloc.num_blocks, "leak from previous test"
    return eng


@pytest.fixture(scope="module")
def feat_eng(f32_lm):
    model, params = f32_lm
    return _engine(model, params, prefix_cache=True, speculative=_SPEC)


@pytest.fixture(scope="module")
def plain_eng(f32_lm):
    model, params = f32_lm
    return _engine(model, params)


@pytest.fixture(scope="module")
def small_eng(f32_lm):
    """Small pool for eviction-pressure tests."""
    model, params = f32_lm
    return _engine(model, params, prefix_cache=True, num_blocks=12,
                   max_seq_len=64)


def test_warm_prefix_cache_is_token_identical(feat_eng):
    """Same prompt cold vs prefix-cached: identical first token and
    identical greedy continuation, with the warm put skipping the cached
    full blocks (cache-exactness satellite)."""
    eng = _reset(feat_eng)
    rng = np.random.default_rng(0)
    prompt = np.concatenate([rng.integers(0, 250, 48),   # 3 full blocks
                             rng.integers(0, 250, 5)])
    r1 = eng.put([1], [prompt])
    t1 = int(np.argmax(r1[1]))
    cold = [int(x) for x in
            eng.decode_batch([1], [t1], steps=8, speculative=False)[1]]
    eng.flush([1])
    r2 = eng.put([2], [prompt])
    t2 = int(np.argmax(r2[2]))
    assert eng.prefix_cache.counters["hit_tokens"] == 48
    assert eng.state.sequences[2].seen_tokens == len(prompt)
    warm = [int(x) for x in
            eng.decode_batch([2], [t2], steps=8, speculative=False)[2]]
    assert t1 == t2 and cold == warm
    # shared blocks really are shared: the warm sequence holds the cached
    # prefix blocks at refcount >= 2 (sequence + tree)
    seq = eng.state.sequences[2]
    assert all(eng.state.allocator.refcount(b) >= 2 for b in seq.blocks[:3])
    eng.flush([2])
    assert eng.prefix_cache.clear() > 0
    alloc = eng.state.allocator
    assert alloc.free_blocks == alloc.num_blocks
    assert not alloc.leaked_blocks()


def test_partial_prefix_match_prefills_only_suffix(feat_eng, plain_eng):
    eng = _reset(feat_eng)
    rng = np.random.default_rng(1)
    shared = rng.integers(0, 250, 32)                    # 2 full blocks
    p_a = np.concatenate([shared, rng.integers(0, 250, 20)])
    p_b = np.concatenate([shared, rng.integers(0, 250, 24)])
    ra = eng.put([1], [p_a])
    rb = eng.put([2], [p_b])                             # shares 32 tokens
    assert eng.prefix_cache.counters["hit_tokens"] == 32
    # exactness of the shared-prefix serve vs a cold engine
    cold = _reset(plain_eng)
    ca = cold.put([1], [p_a])
    cb = cold.put([2], [p_b])
    cold.flush([1, 2])
    assert int(np.argmax(ra[1])) == int(np.argmax(ca[1]))
    assert int(np.argmax(rb[2])) == int(np.argmax(cb[2]))


def test_fully_cached_prompt_still_computes_last_token(feat_eng):
    """A prompt that is one long cached prefix (length a block multiple)
    must cap the match below the prompt length so the forward still runs
    and yields first-token logits."""
    eng = _reset(feat_eng)
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 250, 64)                    # exactly 4 blocks
    r1 = eng.put([1], [prompt])
    eng.flush([1])
    r2 = eng.put([2], [prompt])                          # 100% published
    # matched capped at 48 (< 64): the tail block is recomputed
    assert eng.state.sequences[2].seen_tokens == 64
    assert eng.prefix_cache.counters["hit_tokens"] == 48
    assert int(np.argmax(r1[1])) == int(np.argmax(r2[2]))


def test_speculative_greedy_token_identical(feat_eng):
    """Greedy decode with speculation on vs off is token-identical — on
    repetitive text (where n-gram drafting fires) AND on random text (where
    rounds mostly fall back). Satellite: >1 token emitted per verify round
    on repetitive text."""
    eng = _reset(feat_eng)
    for seed, prompt in ((3, np.tile([5, 6, 7, 8], 8)),
                         (4, np.random.default_rng(4).integers(0, 250, 30))):
        r = eng.put([1], [np.asarray(prompt)])
        t = int(np.argmax(r[1]))
        ref = [int(x) for x in
               eng.decode_batch([1], [t], steps=20, speculative=False)[1]]
        eng.flush([1])
        eng.put([2], [np.asarray(prompt)])
        got = [int(x) for x in
               eng.decode_batch([2], [t], steps=20, speculative=True)[2]]
        assert got == ref, (seed, got, ref)
        eng.flush([2])
    assert eng.spec_stats["rounds"] > 0
    # acceptance win on the repetitive prompt, measured in isolation
    _reset(eng)
    eng.put([1], [np.tile([5, 6, 7, 8], 8)])
    eng.decode_batch([1], [1], steps=24)
    s2 = eng.spec_stats
    assert s2["emitted"] / max(1, s2["rounds"]) > 1.0, s2


def test_spec_partial_accept_leaves_consistent_state(feat_eng, plain_eng):
    """After rounds with rejected drafts (stale KV beyond the frontier),
    continued decode must still match the non-speculative stream — the
    frontier math masks and later overwrites the stale rows."""
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 250, 20)
    eng = _reset(feat_eng)
    eng.put([1], [prompt])
    a = [int(x) for x in eng.decode_batch([1], [3], steps=10)[1]]
    b = [int(x) for x in eng.decode_batch([1], [a[-1]], steps=10)[1]]
    ref_eng = _reset(plain_eng)
    ref_eng.put([1], [prompt])
    ra = [int(x) for x in ref_eng.decode_batch([1], [3], steps=10)[1]]
    rb = [int(x) for x in ref_eng.decode_batch([1], [ra[-1]], steps=10)[1]]
    assert a == ra and b == rb
    assert eng.state.sequences[1].seen_tokens \
        == ref_eng.state.sequences[1].seen_tokens


def test_prefix_eviction_under_pool_pressure(small_eng):
    """Distinct published prefixes overflow a small pool: scheduling must
    reclaim LRU cache blocks instead of failing, and the pool must restore
    fully afterwards (no refcount leak)."""
    eng = _reset(small_eng)
    rng = np.random.default_rng(6)
    for uid in range(8):                       # 8 x 2 published blocks > 12
        eng.put([uid], [rng.integers(0, 250, 40)])
        eng.flush([uid])
    assert eng.prefix_cache.counters["evicted_blocks"] > 0
    assert len(eng.state.sequences) == 0
    eng.prefix_cache.clear()
    alloc = eng.state.allocator
    assert alloc.free_blocks == alloc.num_blocks
    assert not alloc.leaked_blocks()


def test_shared_blocks_never_evicted_or_double_freed(small_eng):
    """A block a live sequence shares (refcount > 1) must survive cache
    eviction pressure; flushing both owners releases it exactly once."""
    eng = _reset(small_eng)
    rng = np.random.default_rng(7)
    shared = rng.integers(0, 250, 32)          # 2 blocks published
    eng.put([1], [np.concatenate([shared, rng.integers(0, 250, 4)])])
    eng.put([2], [np.concatenate([shared, rng.integers(0, 250, 4)])])
    pinned = eng.state.sequences[2].blocks[:2]
    assert all(eng.state.allocator.refcount(b) >= 3 for b in pinned)
    assert eng.prefix_cache.evict(12) == 0     # everything is pinned
    eng.flush([1, 2])
    eng.prefix_cache.clear()
    alloc = eng.state.allocator
    assert alloc.free_blocks == alloc.num_blocks


def test_put_reject_is_side_effect_free_with_warm_cache(small_eng):
    """A fresh-uid put() that raises CapacityError must leave NO state —
    no slot, no cache refs, no seen_tokens — even when the prompt has a
    warm cached prefix, so the caller can free capacity and retry the
    SAME call (review regression: auto-attach used to run before the
    capacity check)."""
    from deepspeed_tpu.inference import CapacityError

    eng = _reset(small_eng)
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, 250, 40)          # 3 blocks, 2 published
    r1 = eng.put([1], [prompt])
    hog = eng.state.allocator.allocate(eng.state.allocator.free_blocks)
    with pytest.raises(CapacityError):
        eng.put([2], [prompt])                 # warm prefix, no room
    assert 2 not in eng.state.sequences        # no slot consumed
    assert eng._hist is not None and 2 not in eng._hist
    eng.state.allocator.free(hog)
    r2 = eng.put([2], [prompt])                # retry: attaches + succeeds
    assert eng.state.sequences[2].seen_tokens == 40
    assert eng.prefix_cache.counters["hit_tokens"] == 32
    assert int(np.argmax(r2[2])) == int(np.argmax(r1[1]))
    eng.flush([1, 2])


def test_config_blocks_reach_engine(f32_lm):
    from deepspeed_tpu.config import DeepSpeedTpuConfig

    cfg = DeepSpeedTpuConfig(train_batch_size=8, inference={
        "prefix_cache": {"enabled": True, "max_blocks": 32},
        "speculative": {"enabled": True, "ngram": 4, "max_draft": 6}})
    assert cfg.inference.prefix_cache.max_blocks == 32
    assert cfg.inference.speculative.max_draft == 6
    model, params = f32_lm
    eng = InferenceEngineV2(model, params=params, max_sequences=2,
                            max_seq_len=64, block_size=16,
                            prefix_cache=cfg.inference.prefix_cache,
                            speculative=cfg.inference.speculative)
    assert eng.prefix_cache is not None and eng.prefix_cache.max_blocks == 32
    assert eng.spec_cfg.max_draft == 6
    with pytest.raises(ValueError, match="max_draft"):
        DeepSpeedTpuConfig(train_batch_size=8, inference={
            "speculative": {"enabled": True, "max_draft": 0}})


# ---------------------------------------------------------------------------
# serving integration
# ---------------------------------------------------------------------------

@pytest.mark.serving
def test_serving_prefix_spec_exact_and_metered(feat_eng, plain_eng):
    """The batcher with prefix cache + speculation serves the same token
    streams as the plain batcher, and the ``serving/spec_*`` +
    ``inference/prefix_cache_*`` metrics populate."""
    from deepspeed_tpu.config.config import ServingConfig
    from deepspeed_tpu.observability import MetricsRegistry
    from deepspeed_tpu.serving import ContinuousBatcher

    rng = np.random.default_rng(8)
    system = rng.integers(0, 250, 48)
    prompts = [np.concatenate([system, rng.integers(0, 250, 6)])
               for _ in range(3)]

    def run(eng, registry=None):
        b = ContinuousBatcher(
            eng, ServingConfig(prefill_chunk=32, default_max_new_tokens=6),
            registry=registry)
        outs = []
        for p in prompts:              # sequential: later ones hit the cache
            uid = b.submit(p)
            b.pump(max_steps=100)
            outs.append(list(b.manager.done[uid].generated))
        return b, outs

    _, base = run(_reset(plain_eng))
    reg = MetricsRegistry()
    b, got = run(_reset(feat_eng), registry=reg)
    assert got == base
    rep = b.serving_report()
    assert rep["counters"]["prefix_hit_requests"] == 2
    assert rep["counters"]["prefix_hit_tokens"] == 96
    assert rep["counters"]["spec_rounds"] > 0
    assert rep["prefix_cache"]["hit_tokens"] == 96
    assert rep["speculative"]["rounds"] > 0
    assert reg.get("serving/spec_rounds") is not None
    # prefix-aware admission: a mostly-cached request's projected demand
    # counts only the uncached share
    req = type("R", (), {})()
    req.prompt = prompts[0]
    req.prompt_len = len(prompts[0])
    req.total_token_demand = len(prompts[0]) + 6
    assert b._blocks_needed(req) < b._blocks_for(req.total_token_demand)
    # cache-held blocks are reclaimable capacity, not load
    assert rep["kv"]["reclaimable_blocks"] > 0
    assert rep["kv"]["occupancy"] == 0.0
    b.engine.prefix_cache.clear()
    alloc = b.engine.state.allocator
    assert alloc.free_blocks == alloc.num_blocks


# ---------------------------------------------------------------------------
# tiered KV spill: KVTierStore semantics (host budget, NVMe spill, loans)
# ---------------------------------------------------------------------------

def _payload(v, shape=(2, 4)):
    return {"k": np.full(shape, v, np.float32),
            "v": np.full(shape, -v, np.float32)}


class TestKVTierStore:
    def test_host_roundtrip_and_discard(self):
        from deepspeed_tpu.inference.kv_tier import KVTierStore

        store = KVTierStore(host_mb=1.0)
        assert store.put(0, _payload(3))
        f = store.fetch_start(0)
        assert f.tier == "host"
        parts = f.wait()
        assert np.array_equal(parts["k"], _payload(3)["k"])
        f.release()
        store.discard(0)
        assert store.entries() == 0
        assert store.pool.report()["outstanding"] == 0

    def test_spill_to_nvme_and_promote(self, tmp_path):
        from deepspeed_tpu.inference.kv_tier import KVTierStore

        # budget holds ~1 entry (64 B payloads): older entries must spill
        store = KVTierStore(host_mb=100 / 2**20, nvme_path=str(tmp_path))
        for i in range(3):
            store.put(i, _payload(i))
        rep = store.report()
        assert rep["nvme_entries"] >= 1 and rep["nvme_demotions"] >= 1
        f = store.fetch_start(0)              # oldest: must be on NVMe
        assert f.tier == "nvme"
        assert np.array_equal(f.wait()["k"], _payload(0)["k"])
        f.release()
        store.close()
        assert store.pool.report()["outstanding"] == 0

    def test_host_budget_without_nvme_drops_via_callback(self):
        from deepspeed_tpu.inference.kv_tier import KVTierStore

        dropped = []
        store = KVTierStore(host_mb=100 / 2**20, on_drop=dropped.append)
        for i in range(4):
            store.put(i, _payload(i))
        assert dropped and all(store.tier_of(k) is None for k in dropped)
        assert store.counters["dropped"] == len(dropped)
        # the survivors still fetch
        live = [k for k in range(4) if store.has(k)]
        assert live
        f = store.fetch_start(live[-1])
        f.wait()
        f.release()
        store.close()
        assert store.pool.report()["outstanding"] == 0

    def test_loaned_entry_never_spilled(self):
        from deepspeed_tpu.inference.kv_tier import KVTierStore

        dropped = []
        store = KVTierStore(host_mb=100 / 2**20, on_drop=dropped.append)
        store.put(0, _payload(0))
        f = store.fetch_start(0)              # pins entry 0
        parts = f.wait()
        before = parts["k"].copy()
        for i in range(1, 5):                 # budget pressure on top
            store.put(i, _payload(i))
        # the loaned entry survived and its bytes were never recycled
        assert store.has(0) and 0 not in dropped
        assert np.array_equal(parts["k"], before)
        # a discard mid-loan defers until the fetch releases
        store.discard(0)
        assert store.has(0)
        f.release()
        assert not store.has(0)
        store.close()
        assert store.pool.report()["outstanding"] == 0

    def test_put_never_drops_its_own_entry_mid_spill(self):
        # host budget below one entry, no NVMe, every older entry pinned
        # by a live fetch: the spill inside put() must not drop the entry
        # being inserted — on_drop would fire before the radix cache has
        # recorded the handle, leaving a demoted node with a dead handle
        from deepspeed_tpu.inference.kv_tier import KVTierStore

        store = KVTierStore(host_mb=40 / 2**20)   # < one 64-byte entry
        dropped = []
        store.on_drop = dropped.append
        store.put(1, _payload(1))
        f = store.fetch_start(1)                  # pins entry 1
        store.put(2, _payload(2))                 # over budget, 1 pinned
        assert store.has(2) and not dropped       # 2 survives its own put
        f.release()
        store.put(3, _payload(3))                 # older entries now fair game
        assert store.has(3) and set(dropped) == {1, 2}
        store.close()
        assert store.pool.report()["outstanding"] == 0

    def test_promote_depth_defers_read_submission(self, tmp_path):
        from deepspeed_tpu.inference.kv_tier import KVTierStore

        store = KVTierStore(host_mb=1 / 2**20, nvme_path=str(tmp_path),
                            promote_depth=1)
        for i in range(3):
            store.put(i, _payload(i))
        assert store.report()["nvme_entries"] >= 2
        f0 = store.fetch_start(0)
        f1 = store.fetch_start(1)
        assert f0.submitted and not f1.submitted   # depth 1: second defers
        assert np.array_equal(f0.wait()["k"], _payload(0)["k"])
        assert np.array_equal(f1.wait()["k"], _payload(1)["k"])
        f0.release()
        f1.release()
        store.close()
        assert store.pool.report()["outstanding"] == 0


class TestNvmeBoundsAndBatchedPromotes:
    """PR-12 follow-ups: NVMe entry cap + TTL (tiers.nvme_max_mb /
    tiers.nvme_ttl_s, LRU+TTL enforced in _spill) and one AIO ticket per
    promote chain instead of one per block."""

    def test_nvme_cap_lru_drops_oldest(self, tmp_path):
        from deepspeed_tpu.inference.kv_tier import KVTierStore

        dropped = []
        # host holds ~1 entry; NVMe capped at ~2 entries (64 B payloads)
        store = KVTierStore(host_mb=100 / 2**20, nvme_path=str(tmp_path),
                            nvme_max_mb=150 / 2**20,
                            on_drop=dropped.append)
        for i in range(6):
            store.put(i, _payload(i))
        rep = store.report()
        assert rep["nvme_cap_dropped"] >= 1
        assert rep["nvme_bytes"] <= store.nvme_max_bytes
        assert dropped and dropped == sorted(dropped)   # oldest-first LRU
        # survivors still fetch bit-exact
        live = [k for k in range(6) if store.tier_of(k) == "nvme"]
        assert live
        f = store.fetch_start(live[-1])
        assert np.array_equal(f.wait()["k"], _payload(live[-1])["k"])
        f.release()
        store.close()
        assert store.pool.report()["outstanding"] == 0

    def test_nvme_ttl_drops_idle_entries(self, tmp_path):
        from deepspeed_tpu.inference.kv_tier import KVTierStore

        clock = [0.0]
        dropped = []
        store = KVTierStore(host_mb=100 / 2**20, nvme_path=str(tmp_path),
                            nvme_ttl_s=10.0, on_drop=dropped.append)
        store._now = lambda: clock[0]
        store.put(0, _payload(0))
        store.put(1, _payload(1))           # 0 spills to NVMe
        assert store.tier_of(0) == "nvme"
        clock[0] = 5.0
        f = store.fetch_start(0)            # touch refreshes the TTL clock
        f.wait()
        f.release()
        clock[0] = 12.0                     # 0 idle 7s, fresh enough
        store.put(2, _payload(2))           # spill -> bounds sweep
        assert store.has(0)
        clock[0] = 30.0                     # idle 18s > ttl
        store.put(3, _payload(3))
        assert not store.has(0)
        assert store.counters["nvme_ttl_dropped"] >= 1
        assert 0 in dropped
        store.close()
        assert store.pool.report()["outstanding"] == 0

    def test_chain_batches_reads_into_one_ticket(self, tmp_path):
        from deepspeed_tpu.inference.kv_tier import KVTierStore

        store = KVTierStore(host_mb=100 / 2**20, nvme_path=str(tmp_path))
        for i in range(4):
            store.put(i, _payload(i))
        keys = [k for k in range(4) if store.tier_of(k) == "nvme"]
        assert len(keys) >= 3
        singles, batches = [], []
        orig_one = store.swapper.swap_in_start
        orig_many = store.swapper.swap_in_start_many
        store.swapper.swap_in_start = \
            lambda n: singles.append(n) or orig_one(n)
        store.swapper.swap_in_start_many = \
            lambda ns: batches.append(list(ns)) or orig_many(ns)
        assert store.begin_chain(keys)
        try:
            fetches = [store.fetch_start(k) for k in keys]
            for k, f in zip(keys, fetches):
                assert f.tier == "nvme"
                assert np.array_equal(f.wait()["k"], _payload(k)["k"])
        finally:
            store.end_chain()
        for f in fetches:
            f.release()
        assert len(batches) == 1 and len(batches[0]) == len(keys)
        assert not singles                   # ONE ticket for the chain
        assert store.counters["batched_reads"] == 1
        assert store._reads_inflight == 0
        store.close()
        assert store.pool.report()["outstanding"] == 0

    def test_chain_lazy_past_promote_depth(self, tmp_path):
        from deepspeed_tpu.inference.kv_tier import KVTierStore

        store = KVTierStore(host_mb=100 / 2**20, nvme_path=str(tmp_path),
                            promote_depth=1)
        for i in range(4):
            store.put(i, _payload(i))
        keys = [k for k in range(4) if store.tier_of(k) == "nvme"][:2]
        blocker = store.fetch_start(keys[0])     # occupies the one slot
        assert store.begin_chain(keys)           # arms LAZY (depth hit)
        try:
            f = store.fetch_start(keys[1])
            assert f._batch is not None and f._batch.ticket is None
            blocker.wait()
            blocker.release()
            # first wait submits the batch at the fence
            assert np.array_equal(f.wait()["k"], _payload(keys[1])["k"])
        finally:
            store.end_chain()
        f.release()
        assert store._reads_inflight == 0
        store.close()
        assert store.pool.report()["outstanding"] == 0

    def test_nvme_bounds_survive_reentrant_discard(self, tmp_path):
        """Evicting one NVMe entry fires on_drop -> _drop_subtree, which
        can discard OTHER NVMe entries (demoted descendants) while the
        TTL/cap sweep iterates its key snapshot — the sweep must skip
        the vanished keys, not KeyError on the serving hot path."""
        from deepspeed_tpu.inference.kv_tier import KVTierStore

        clock = [0.0]
        store = KVTierStore(host_mb=100 / 2**20, nvme_path=str(tmp_path),
                            nvme_ttl_s=5.0)
        store._now = lambda: clock[0]
        # dropping either of {0, 1} discards the other (the radix tree
        # dropping a parent's demoted descendant subtree)
        store.on_drop = lambda k: store.discard(1 - k) if k in (0, 1) \
            else None
        for i in range(3):
            store.put(i, _payload(i))
        assert store.tier_of(0) == "nvme" and store.tier_of(1) == "nvme"
        clock[0] = 30.0                       # both expired
        store.put(3, _payload(3))             # sweep runs — must not raise
        assert not store.has(0) and not store.has(1)
        assert store.counters["nvme_ttl_dropped"] >= 1
        store.close()
        assert store.pool.report()["outstanding"] == 0

    def test_lazy_chain_submits_only_ridden_names(self, tmp_path):
        """A LAZY batch submits at the first rider's fence-time wait —
        by then end_chain has unpinned the chain members nothing rode,
        and those may have been evicted (their _meta gone). The submit
        must cover only the CLAIMED names or one stale member poisons
        every intact rider."""
        from deepspeed_tpu.inference.kv_tier import KVTierStore

        store = KVTierStore(host_mb=100 / 2**20, nvme_path=str(tmp_path),
                            promote_depth=1)
        for i in range(5):
            store.put(i, _payload(i))
        keys = [k for k in range(5) if store.tier_of(k) == "nvme"]
        assert len(keys) >= 4
        blocker = store.fetch_start(keys[0])   # occupies the one slot
        assert store.begin_chain(keys[1:4])    # arms LAZY
        try:
            f1 = store.fetch_start(keys[1])
            f2 = store.fetch_start(keys[2])    # keys[3] never ridden
        finally:
            store.end_chain()
        store.discard(keys[3])                 # unridden member vanishes
        blocker.wait()
        blocker.release()
        assert np.array_equal(f1.wait()["k"], _payload(keys[1])["k"])
        assert np.array_equal(f2.wait()["k"], _payload(keys[2])["k"])
        f1.release()
        f2.release()
        assert store._reads_inflight == 0
        store.close()
        assert store.pool.report()["outstanding"] == 0

    def test_begin_chain_survives_failed_demote_write(self, tmp_path):
        """A torn demote write (failed wticket) must degrade to a
        per-block tier miss inside begin_chain — raising would crash the
        whole serving acquire, and the pre-existing single-read paths
        already degrade."""
        from deepspeed_tpu.inference.kv_tier import KVTierStore

        store = KVTierStore(host_mb=100 / 2**20, nvme_path=str(tmp_path))
        for i in range(5):
            store.put(i, _payload(i))
        keys = [k for k in range(5) if store.tier_of(k) == "nvme"]
        assert len(keys) >= 3

        class BoomTicket:
            def wait(self):
                raise IOError("torn demote write")

        store._nvme[keys[0]].wticket = BoomTicket()
        assert store.begin_chain(keys)        # must not raise
        try:
            assert not store.has(keys[0])     # torn entry -> miss/drop
            assert store.counters["nvme_misses"] >= 1
            f = store.fetch_start(keys[1])    # survivors still serve
            assert np.array_equal(f.wait()["k"], _payload(keys[1])["k"])
        finally:
            store.end_chain()
        f.release()
        assert store._reads_inflight == 0
        store.close()
        assert store.pool.report()["outstanding"] == 0

    def test_unridden_batch_members_pinned_until_ticket_release(
            self, tmp_path):
        """An EAGER batch submits preads for every chain member; members
        nothing rode must stay pinned past end_chain until the shared
        ticket dies — evicting one would unlink a file a pread still
        targets (AsyncTensorSwapper.discard's documented contract)."""
        from deepspeed_tpu.inference.kv_tier import KVTierStore

        # cap holds the 3-entry chain (64 B each) with no slack for more
        store = KVTierStore(host_mb=100 / 2**20, nvme_path=str(tmp_path),
                            nvme_max_mb=200 / 2**20)
        for i in range(4):
            store.put(i, _payload(i))
        keys = [k for k in range(4) if store.tier_of(k) == "nvme"][:3]
        assert len(keys) == 3
        assert store.begin_chain(keys)
        f = store.fetch_start(keys[0])        # only keys[0] rides
        store.end_chain()
        # cap pressure while the shared ticket is alive: the unridden
        # members' reads are in flight — the sweep must skip them
        store.put(8, _payload(8))
        store.put(9, _payload(9))
        assert store.has(keys[1]) and store.has(keys[2])
        assert np.array_equal(f.wait()["k"], _payload(keys[0])["k"])
        f.release()                           # ticket dies: members unpin
        store.put(10, _payload(10))           # sweep can now enforce cap
        assert store.report()["nvme_bytes"] <= store.nvme_max_bytes
        assert store._reads_inflight == 0
        store.close()
        assert store.pool.report()["outstanding"] == 0

    def test_acquire_pins_chain_before_deficit_eviction(self):
        """acquire's make-room eviction demotes blocks, which can push
        the NVMe tier over its cap — the LRU sweep must not drop the
        very chain entries this acquire is about to promote (they are
        the LRU-oldest). begin_chain pins them FIRST."""
        import tempfile

        with tempfile.TemporaryDirectory() as nvme:
            alloc, pc, store, publish = _tiered_cache(
                num_blocks=4, host_mb=40 / 2**20, nvme_path=nvme,
                nvme_max_mb=150 / 2**20)
            toksA = np.arange(12, dtype=np.int32)
            publish(toksA, 1)
            pc.evict(3)                   # A: 2 entries NVMe + 1 host
            assert store.report()["nvme_entries"] == 2
            publish(np.arange(100, 112, dtype=np.int32), 2)  # B fills pool
            assert alloc.free_blocks == 1
            # acquire A: deficit eviction demotes B -> host spill -> NVMe
            # over cap -> sweep; A's batched entries must survive it
            blocks, n = pc.acquire(toksA)
            assert n >= 8                 # the pinned chain promoted
            recs = pc.drain_promotes()
            for r in recs:
                r.fetch.wait()
                r.fetch.release()
                store.discard(r.key)
            pc.mark_uploaded(recs)
            if blocks:
                alloc.free(blocks)
            pc.clear()
            assert not alloc.leaked_blocks()
            assert store.pool.report()["outstanding"] == 0
            store.close()

    def test_acquire_chain_uses_one_ticket(self):
        """End-to-end through PrefixCache.acquire: a 2-block demoted NVMe
        chain promotes through ONE batched read, promote_ms semantics
        unchanged (each record still carries its own fetch + t_start)."""
        import tempfile

        with tempfile.TemporaryDirectory() as nvme:
            alloc, pc, store, publish = _tiered_cache(
                host_mb=40 / 2**20, nvme_path=nvme)
            toks = np.arange(12, dtype=np.int32)
            publish(toks, 9)
            pc.evict(3)
            # _spill keeps one entry host-resident; the older two hit NVMe
            assert store.report()["nvme_entries"] == 2
            singles = []
            orig_one = store.swapper.swap_in_start
            store.swapper.swap_in_start = \
                lambda n: singles.append(n) or orig_one(n)
            blocks, n = pc.acquire(toks)
            assert n == 12
            recs = pc.drain_promotes()
            assert len(recs) == 3
            assert store.counters["batched_reads"] == 1 and not singles
            for r in recs:
                assert r.fetch.t_start > 0     # promote_ms anchor intact
                assert np.array_equal(r.fetch.wait()["k"],
                                      _payload(9)["k"])
                r.fetch.release()
                store.discard(r.key)
            pc.mark_uploaded(recs)
            alloc.free(blocks)
            pc.clear()
            assert not alloc.leaked_blocks() and store.entries() == 0
            assert store.pool.report()["outstanding"] == 0
            store.close()


# ---------------------------------------------------------------------------
# tiered PrefixCache semantics (fake extract: no device in the loop)
# ---------------------------------------------------------------------------

def _tiered_cache(num_blocks=8, block_size=4, **store_kw):
    from deepspeed_tpu.inference.kv_tier import KVTierStore

    alloc = BlockedAllocator(num_blocks, block_size=block_size)
    pc = PrefixCache(alloc)
    store = KVTierStore(**{"host_mb": 1.0, **store_kw})
    payloads = {}

    def extract(blocks):
        return [dict(payloads[b]) for b in blocks]

    pc.attach_tier_store(store, extract)

    def publish(toks, val):
        blks = alloc.allocate(len(toks) // block_size)
        for b in blks:
            payloads[b] = _payload(val)
        pc.insert(toks, blks)
        alloc.free(blks)
        return blks

    publish.payloads = payloads
    return alloc, pc, store, publish


class TestTieredPrefixCache:
    def test_demote_instead_of_evict_keeps_nodes(self):
        alloc, pc, store, publish = _tiered_cache()
        publish(np.arange(8, dtype=np.int32), 1)
        assert pc.evict(2) == 2                  # HBM blocks freed...
        assert alloc.free_blocks == alloc.num_blocks
        rep = pc.report()
        assert rep["blocks"] == 0 and rep["demoted_nodes"] == 2
        assert rep["demoted_blocks"] == 2 and store.entries() == 2
        # ...but the prefix still matches, as warm-not-resident
        info = pc.peek_tiers(np.arange(8, dtype=np.int32))
        assert info["matched_tokens"] == 8
        assert info["resident_tokens"] == 0 and info["demoted_blocks"] == 2

    def test_acquire_promotes_with_pending_upload(self):
        alloc, pc, store, publish = _tiered_cache()
        toks = np.arange(8, dtype=np.int32)
        publish(toks, 7)
        pc.evict(2)
        blocks, n = pc.acquire(toks)
        assert n == 8 and len(blocks) == 2
        recs = pc.drain_promotes()
        assert len(recs) == 2 and pc.report()["promoted_blocks"] == 2
        for r in recs:
            assert np.array_equal(r.fetch.wait()["k"], _payload(7)["k"])
            r.fetch.release()
            store.discard(r.key)
        # promoted blocks are live (cache + acquirer refs) and pinned
        assert all(alloc.refcount(b) == 2 for b in blocks)
        assert pc.evictable_blocks() == 0
        alloc.free(blocks)
        assert pc.evictable_blocks() == 2
        pc.clear()
        assert alloc.free_blocks == alloc.num_blocks
        assert not alloc.leaked_blocks() and store.entries() == 0

    def test_cancel_promotes_redemotes_and_frees(self):
        alloc, pc, store, publish = _tiered_cache()
        toks = np.arange(8, dtype=np.int32)
        publish(toks, 5)
        pc.evict(2)
        blocks, n = pc.acquire(toks)
        recs = pc.drain_promotes()
        # the acquirer fails before the upload fence: free its refs, then
        # cancel — nodes re-demote onto their still-live store entries
        alloc.free(blocks)
        pc.cancel_promotes(recs)
        assert alloc.free_blocks == alloc.num_blocks
        rep = pc.report()
        assert rep["blocks"] == 0 and rep["demoted_nodes"] == 2
        assert store.entries() == 2
        # and the prefix is still servable afterwards
        blocks2, n2 = pc.acquire(toks)
        assert n2 == 8
        for r in pc.drain_promotes():
            assert np.array_equal(r.fetch.wait()["k"], _payload(5)["k"])
            r.fetch.release()
            store.discard(r.key)
        alloc.free(blocks2)
        pc.clear()
        assert not alloc.leaked_blocks() and store.entries() == 0

    def test_republish_readopts_demoted_nodes(self):
        alloc, pc, store, publish = _tiered_cache()
        toks = np.arange(8, dtype=np.int32)
        publish(toks, 2)
        pc.evict(2)
        assert store.entries() == 2
        # a second sequence publishes identical content: nodes re-adopt its
        # private blocks — no tier fetch, store entries released
        publish(toks, 2)
        rep = pc.report()
        assert rep["readopted_blocks"] == 2 and rep["demoted_nodes"] == 0
        assert store.entries() == 0
        info = pc.peek_tiers(toks)
        assert info["resident_tokens"] == 8

    def test_dropped_tier_entry_detaches_subtree(self):
        # no NVMe + tiny host budget: demotions past the budget drop the
        # oldest entries, and the radix tree must forget those nodes
        alloc, pc, store, publish = _tiered_cache(
            num_blocks=16, host_mb=150 / 2**20)
        for i in range(4):
            publish(np.arange(i * 100, i * 100 + 8, dtype=np.int32), i)
            pc.evict(2)
        assert store.counters["dropped"] >= 1
        assert pc.report()["tier_lost_blocks"] >= 1
        # every remaining match still resolves cleanly (dead prefixes miss)
        total = 0
        for i in range(4):
            toks = np.arange(i * 100, i * 100 + 8, dtype=np.int32)
            blocks, n = pc.acquire(toks)
            for r in pc.drain_promotes():
                r.fetch.wait()
                r.fetch.release()
                store.discard(r.key)
            total += n
            alloc.free(blocks)
        assert 0 < total < 4 * 8
        pc.clear()
        assert alloc.free_blocks == alloc.num_blocks
        assert store.pool.report()["outstanding"] == 0

    def test_deep_chain_demotes_leaf_first_bottom_up(self):
        # demoted children must not pin their parents: a fully-unreferenced
        # chain demotes bottom-up until the whole path is in the store
        alloc, pc, store, publish = _tiered_cache(num_blocks=8)
        toks = np.arange(16, dtype=np.int32)          # 4-block chain
        publish(toks, 9)
        assert pc.evict(4) == 4
        rep = pc.report()
        assert rep["blocks"] == 0 and rep["demoted_nodes"] == 4
        info = pc.peek_tiers(toks)
        assert info["matched_tokens"] == 16 and info["demoted_blocks"] == 4

    def test_pending_upload_blocks_resist_eviction_until_fence(self):
        # an acquirer shed between attach and the engine's fence leaves the
        # cache sole owner of promoted blocks whose payload was NEVER
        # uploaded: demoting one would extract garbage, freeing one would
        # let the deferred scatter overwrite whoever gets the block next
        alloc, pc, store, publish = _tiered_cache()
        toks = np.arange(8, dtype=np.int32)
        publish(toks, 3)
        pc.evict(2)
        blocks, n = pc.acquire(toks)
        recs = pc.drain_promotes()
        alloc.free(blocks)                 # acquirer gone, rc back to 1
        assert pc.evict(2) == 0            # fence pending: untouchable
        assert pc.report()["blocks"] == 2
        for r in recs:                     # the fence: upload + finalize
            assert np.array_equal(r.fetch.wait()["k"], _payload(3)["k"])
            r.fetch.release()
            store.discard(r.key)
            publish.payloads[r.block] = _payload(3)
        pc.mark_uploaded(recs)
        assert pc.evict(2) == 2            # ordinary cache blocks again
        pc.clear()
        assert alloc.free_blocks == alloc.num_blocks
        assert not alloc.leaked_blocks() and store.entries() == 0

    def test_deep_chain_eviction_has_no_recursion_limit(self):
        # candidate gathering must be iterative: one shared system prompt
        # can be a chain far deeper than the interpreter's recursion limit
        alloc, pc, store, publish = _tiered_cache(num_blocks=1300,
                                                  block_size=4,
                                                  host_mb=4.0)
        toks = np.arange(4800, dtype=np.int32)        # 1200-block chain
        publish(toks, 1)
        assert pc.evict(1200) == 1200
        rep = pc.report()
        assert rep["blocks"] == 0 and rep["demoted_nodes"] == 1200
        pc.clear()
        assert store.entries() == 0 and not alloc.leaked_blocks()

    def test_demote_failure_drops_orphaned_demoted_descendants(self):
        # when the store cannot take a victim (copy failure) the fallback
        # is plain eviction — but the victim can carry DEMOTED children,
        # and unlinking just the victim would orphan them: unreachable
        # nodes whose tier entries leak until clear()
        alloc, pc, store, publish = _tiered_cache()
        toks = np.arange(12, dtype=np.int32)          # 3-block chain
        publish(toks, 4)
        assert pc.evict(1) == 1                       # leaf -> demoted
        assert store.entries() == 1

        def broken_put(key, parts):
            raise RuntimeError("pinned copy failed")

        store.put = broken_put
        assert pc.evict(1) == 1                       # plain-evict fallback
        rep = pc.report()
        assert store.entries() == 0                   # child went with it
        assert rep["demoted_nodes"] == 0 and rep["blocks"] == 1
        blocks, n = pc.acquire(toks)
        assert n == 4                                 # only the head serves
        assert not pc.drain_promotes()
        alloc.free(blocks)
        pc.clear()
        assert not alloc.leaked_blocks()


# ---------------------------------------------------------------------------
# tiered KV through the engine (fp32: promote must be bit-exact)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tier_eng(f32_lm, tmp_path_factory):
    model, params = f32_lm
    nvme = tmp_path_factory.mktemp("kv_tier_nvme")
    # host budget ~2 blocks (tiny block = 2*16*64*4*2 bytes) so a few
    # demotions reach NVMe too
    eng = _engine(model, params, num_blocks=24, decode_kernel="pallas",
                  prefix_cache={"enabled": True,
                                "tiers": {"enabled": True,
                                          "host_mb": 2 * 16384 / 2**20,
                                          "nvme_path": str(nvme),
                                          "promote_depth": 2}})
    yield eng
    eng.close()


def _gen(eng, uid, prompt, steps=6):
    r = eng.put([uid], [prompt])
    out = [int(np.argmax(r[uid]))]
    toks = eng.decode_batch([uid], [out[0]], steps=steps)
    out += [int(t) for t in toks[uid]]
    eng.flush([uid])
    return out


def test_tiered_demote_promote_token_identical(tier_eng, plain_eng):
    """The correctness bar: the SAME prompt served (a) cold on a plain
    engine, (b) publishing, (c) after full demotion to host+NVMe via
    promote — all three token streams identical, pool and store restored."""
    eng = _reset(tier_eng)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 250, 52)
    base = _gen(_reset(plain_eng), 0, prompt)
    first = _gen(eng, 1, prompt)
    assert first == base
    pc = eng.prefix_cache
    assert pc.report()["blocks"] == 3
    pc.evict(10)                       # demote everything (host + NVMe)
    rep = pc.report()
    assert rep["demoted_nodes"] == 3 and rep["blocks"] == 0
    tiers = rep["tiers"]
    assert tiers["host_entries"] + tiers["nvme_entries"] == 3
    promoted = _gen(eng, 2, prompt)
    assert promoted == base
    rep = pc.report()
    assert rep["promoted_blocks"] == 3
    assert rep["tiers"]["host_hits"] + rep["tiers"]["nvme_hits"] == 3
    pc.clear()
    alloc = eng.state.allocator
    assert alloc.free_blocks == alloc.num_blocks
    assert not alloc.leaked_blocks()
    assert eng._tier_store.entries() == 0
    assert eng._tier_store.pool.report()["outstanding"] == 0


def test_tier_metrics_render_in_prometheus(tier_eng):
    """Acceptance: inference/prefix_cache_tier_{hits,promote_ms} appear in
    the Prometheus exposition with per-tier labels."""
    from deepspeed_tpu.observability import get_registry

    eng = _reset(tier_eng)
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, 250, 52)
    _gen(eng, 10, prompt)
    eng.prefix_cache.evict(10)
    _gen(eng, 11, prompt)              # promote -> hits + promote_ms
    text = get_registry().render_prometheus()
    assert 'inference_prefix_cache_tier_hits_total{tier="host"}' in text \
        or 'inference_prefix_cache_tier_hits_total{tier="nvme"}' in text
    assert 'inference_prefix_cache_tier_demotions_total{tier="host"}' \
        in text
    assert 'inference_prefix_cache_tier_promote_ms_count{tier=' in text
    assert 'inference_prefix_cache_tier_bytes{tier="host"}' in text
    eng.prefix_cache.clear()


def test_tiers_config_reaches_engine(f32_lm, tmp_path):
    from deepspeed_tpu.config.config import DeepSpeedTpuConfig

    cfg = DeepSpeedTpuConfig(**{
        "inference": {"prefix_cache": {
            "enabled": True,
            "tiers": {"enabled": True, "host_mb": 0.5,
                      "nvme_path": str(tmp_path), "promote_depth": 3}}}})
    t = cfg.inference.prefix_cache.tiers
    assert t.enabled and t.host_mb == 0.5 and t.promote_depth == 3
    model, params = f32_lm
    eng = _engine(model, params, prefix_cache=cfg.inference.prefix_cache)
    try:
        assert eng._tier_store is not None
        assert eng._tier_store.host_bytes == int(0.5 * 2**20)
        assert eng._tier_store.promote_depth == 3
        assert eng._tier_store.swapper is not None
        assert eng.prefix_cache.tier_store is eng._tier_store
    finally:
        eng.close()
    assert eng._tier_store is None     # close() is the teardown seam


def test_tiers_config_validation():
    from deepspeed_tpu.config.config import KVTierConfig

    with pytest.raises(ValueError):
        KVTierConfig(host_mb=0)
    with pytest.raises(ValueError):
        KVTierConfig(promote_depth=0)


def test_batcher_projection_counts_demoted_as_block_demand(tier_eng):
    """Admission math: resident cached blocks are free capacity; demoted
    blocks stay in the block projection (a promote allocates a block) but
    the request is still a prefix hit — the promote-latency tax, not cold
    prefill demand."""
    from deepspeed_tpu.serving import ContinuousBatcher

    eng = _reset(tier_eng)
    b = ContinuousBatcher(eng)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 250, 52)
    _gen(eng, 20, prompt)
    req = type("R", (), {})()
    req.prompt = prompt
    req.prompt_len = len(prompt)
    req.total_token_demand = len(prompt) + 6
    resident_need = b._blocks_needed(req)
    assert resident_need < b._blocks_for(req.total_token_demand)
    eng.prefix_cache.evict(10)         # all demoted now
    demoted_need = b._blocks_needed(req)
    # demoted blocks cost pool blocks again (promotes allocate), so the
    # projected need returns to the full worst case
    assert demoted_need == b._blocks_for(req.total_token_demand)
    eng.prefix_cache.clear()


def test_promote_read_failure_zero_fills_and_restores_loans(tier_eng):
    """A promote fetch failing with a NON-IO error at the fence (the lazy
    NVMe path submits inside wait(): pool.get can raise under host-memory
    pressure) must zero-fill that block and still finalize every other
    record — an escape would strand the whole batch's loans and leave
    garbage blocks attached to live sequences."""
    eng = _reset(tier_eng)
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, 250, 52)
    _gen(eng, 30, prompt)
    eng.prefix_cache.evict(10)
    hit = eng.prefix_attach(31, prompt)
    assert hit > 0 and eng._promote_q

    bad = eng._promote_q[0]

    class _BoomFetch:                      # KVFetch is slotted: wrap it
        def __init__(self, inner):
            self.inner = inner
            self.tier = inner.tier
            self.t_start = inner.t_start

        def wait(self):
            raise RuntimeError("pinned pool exhausted")

        def release(self):
            self.inner.release()

    bad.fetch = _BoomFetch(bad.fetch)
    misses = lambda: (eng._tier_store.counters["host_misses"]
                      + eng._tier_store.counters["nvme_misses"])
    m0 = misses()
    eng._flush_promotes()                  # must not raise
    assert not eng._promote_q
    assert misses() == m0 + 1
    assert not eng.prefix_cache._pending_upload
    # the zero-filled node (and, being the chain head, everything under
    # it) must leave the tree: published, every FUTURE match would read
    # zeros as KV — only the in-flight acquirer computes on zeros
    pc = eng.prefix_cache
    assert pc.counters["tier_lost_blocks"] >= 1
    assert pc.peek_tiers(prompt, max_tokens=len(prompt) - 1)[
        "matched_tokens"] == 0
    eng.flush([31])
    eng.prefix_cache.clear()
    alloc = eng.state.allocator
    assert alloc.free_blocks == alloc.num_blocks
    assert not alloc.leaked_blocks()
    assert eng._tier_store.entries() == 0
    assert eng._tier_store.pool.report()["outstanding"] == 0
    assert eng._tier_store.swapper is None \
        or eng._tier_store.swapper.report()["loaned_read_buffers"] == 0


def test_clear_between_attach_and_fence_discards_stale_promotes(tier_eng):
    """An ops cache flush (clear()) landing between prefix_attach and the
    engine's next dispatch releases the promoted blocks back to the pool —
    the fence must RELEASE the stale records, never scatter their payloads
    over blocks that may belong to another sequence by then."""
    eng = _reset(tier_eng)
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 250, 52)
    ref = _gen(eng, 40, prompt)
    eng.prefix_cache.evict(10)
    hit = eng.prefix_attach(41, prompt)
    assert hit > 0 and eng._promote_q
    eng.prefix_cache.clear()
    eng.flush([41])                        # blocks fully free for reuse

    orig = eng._promote_step

    def must_not_scatter(*a, **kw):
        raise AssertionError("fence scattered a stale promote")

    eng._promote_step = must_not_scatter
    try:
        eng._flush_promotes()
    finally:
        eng._promote_step = orig
    assert not eng._promote_q
    assert eng._tier_store.pool.report()["outstanding"] == 0
    # and the engine serves cleanly on the recycled blocks
    assert _gen(eng, 42, prompt) == ref
    eng.prefix_cache.clear()
    alloc = eng.state.allocator
    assert alloc.free_blocks == alloc.num_blocks
    assert not alloc.leaked_blocks()


def test_close_with_pending_promotes_drops_garbage_nodes(tier_eng):
    """close() before the fence: the queued promotions' blocks were never
    uploaded, and the prefix cache stays usable after a tier-only close —
    the garbage nodes must leave the tree, not get published. (Runs LAST
    among the tier_eng tests: it closes the shared engine.)"""
    eng = _reset(tier_eng)
    rng = np.random.default_rng(8)
    prompt = rng.integers(0, 250, 52)
    _gen(eng, 50, prompt)
    eng.prefix_cache.evict(10)
    hit = eng.prefix_attach(51, prompt)
    assert hit > 0 and eng._promote_q
    pc = eng.prefix_cache
    eng.flush([51])
    eng.close()
    assert pc.peek_tiers(prompt, max_tokens=len(prompt) - 1)[
        "matched_tokens"] == 0
    assert not pc._pending_upload
    alloc = eng.state.allocator
    assert alloc.free_blocks == alloc.num_blocks
    assert not alloc.leaked_blocks()


# ---------------------------------------------------------------------------
# drill wrappers (slow; the CLI is the invariant authority)
# ---------------------------------------------------------------------------

@pytest.mark.perf
@pytest.mark.slow
def test_prefix_storm_drill(tmp_path):
    import sys

    sys.path.insert(0, _TOOLS)
    from serve_drill import run_scenario

    verdict = run_scenario("prefix-storm", workdir=str(tmp_path))
    assert verdict["ok"], verdict


@pytest.mark.perf
@pytest.mark.slow
def test_kv_tier_drill(tmp_path):
    import sys

    sys.path.insert(0, _TOOLS)
    from serve_drill import run_scenario

    verdict = run_scenario("kv-tier", workdir=str(tmp_path))
    assert verdict["ok"], verdict
