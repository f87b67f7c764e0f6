"""Inference tests (pattern: reference ``tests/unit/inference/`` + ``v2/ragged``
behavior tests): cached decode must match full-sequence forward; the continuous
batching engine must serve interleaved prefill/decode correctly."""

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference import BlockedAllocator, InferenceEngine, InferenceEngineV2, SequenceManager
from deepspeed_tpu.models import TransformerLM, get_preset


def jnp_f(x):
    import jax.numpy as jnp

    return jnp.asarray(np.asarray(x, np.float32))


def jnp_np(x):
    import jax.numpy as jnp

    return jnp.asarray(x)


@pytest.fixture(scope="module")
def tiny_lm():
    model = TransformerLM(get_preset("tiny"))
    params = model.init(jax.random.key(0))
    return model, params


def _engine_on_the_xla_twin(model, params, **kw):
    """An engine for a case that tests what stands round the step (the pool,
    the buckets, sampling, capacity): attention is the XLA twin's. The parity
    cases (``*_parity*``, ``*matches*``, the quantised pools) build theirs
    with the kernel, interpreted here."""
    return InferenceEngineV2(model, params=params, decode_kernel="xla", **kw)


def test_cached_forward_matches_full(tiny_lm):
    model, params = tiny_lm
    ids = np.random.default_rng(0).integers(0, 256, (2, 12)).astype(np.int32)
    full = np.asarray(model.logits(params, ids), np.float32)
    cache = model.init_kv_cache(2, 32)
    logits, cache = model.forward_with_cache(params, ids, cache)
    np.testing.assert_allclose(np.asarray(logits, np.float32), full, atol=3e-2)
    assert np.all(np.asarray(cache["pos"]) == 12)


def test_incremental_decode_matches_full(tiny_lm):
    model, params = tiny_lm
    ids = np.random.default_rng(1).integers(0, 256, (1, 8)).astype(np.int32)
    full = np.asarray(model.logits(params, ids), np.float32)
    cache = model.init_kv_cache(1, 16)
    outs = []
    for t in range(8):
        lg, cache = model.forward_with_cache(params, ids[:, t:t + 1], cache)
        outs.append(np.asarray(lg[:, 0], np.float32))
    inc = np.stack(outs, axis=1)
    np.testing.assert_allclose(inc, full, atol=3e-2)


def test_generate_greedy_deterministic(tiny_lm):
    model, params = tiny_lm
    eng = InferenceEngine(model, params=params, config={"mesh": {}})
    prompt = np.random.default_rng(2).integers(0, 256, (2, 4))
    out1 = eng.generate(prompt, max_new_tokens=6)
    out2 = eng.generate(prompt, max_new_tokens=6)
    assert out1.shape == (2, 10)
    np.testing.assert_array_equal(out1, out2)
    np.testing.assert_array_equal(out1[:, :4], prompt)


def test_blocked_allocator():
    alloc = BlockedAllocator(num_blocks=10, block_size=4)
    a = alloc.allocate(3)
    assert alloc.free_blocks == 7
    alloc.free(a)
    assert alloc.free_blocks == 10
    with pytest.raises(RuntimeError):
        alloc.allocate(11)


def test_sequence_manager_capacity():
    sm = SequenceManager(max_sequences=2, max_seq_len=16, block_size=4)
    assert sm.can_schedule(1, 8)
    sm.schedule(1, 8)
    sm.commit(1)
    assert not sm.can_schedule(1, 16)  # would exceed max_seq_len
    sm.schedule(2, 4)
    sm.commit(2)
    assert not sm.can_schedule(3, 4)  # no free slots
    sm.flush(1)
    assert sm.can_schedule(3, 4)


def test_continuous_batching_matches_sequential(tiny_lm):
    """Interleaved ragged scheduling must reproduce the isolated decode results."""
    model, params = tiny_lm
    rng = np.random.default_rng(3)
    p1 = rng.integers(0, 256, 6)
    p2 = rng.integers(0, 256, 3)

    # reference: each prompt alone through the cached path
    def solo(prompt):
        cache = model.init_kv_cache(1, 32)
        lg, _ = model.forward_with_cache(params, prompt[None].astype(np.int32), cache)
        return np.asarray(lg[0, len(prompt) - 1], np.float32)

    eng = InferenceEngineV2(model, params=params, max_sequences=4, max_seq_len=32)
    # prefill uid 1, then interleave uid 2's prefill with uid 1's decode
    r1 = eng.put([1], [p1])
    next1 = int(np.argmax(r1[1]))
    r = eng.put([2, 1], [p2, np.array([next1])])
    np.testing.assert_allclose(np.asarray(r[2], np.float32), solo(p2), atol=3e-2)

    # uid 1's step must equal running [p1, next1] through a fresh cache
    cache = model.init_kv_cache(1, 32)
    seq = np.concatenate([p1, [next1]])[None].astype(np.int32)
    lg, _ = model.forward_with_cache(params, seq, cache)
    np.testing.assert_allclose(np.asarray(r[1], np.float32),
                               np.asarray(lg[0, -1], np.float32), atol=3e-2)

    # flush frees capacity
    eng.flush([1, 2])
    assert eng.state.allocator.free_blocks == eng.state.allocator.num_blocks


@pytest.mark.parametrize("seed", [
    pytest.param(4, id="paged_matches_dense"),
    pytest.param(7, id="packed_matches_tile")])
def test_engine_matches_dense_cache_forward(tiny_lm, seed):
    """The engine (token-packed step over the paged pool) must reproduce the
    dense-cache forward's next-token logits across interleaved prefill/decode
    scheduling."""
    model, params = tiny_lm
    rng = np.random.default_rng(seed)
    p1 = rng.integers(0, 256, 7)
    p2 = rng.integers(0, 256, 5)
    eng = InferenceEngineV2(model, params=params, max_sequences=4,
                            max_seq_len=32, block_size=8)
    r1 = eng.put([1], [p1])
    r2 = eng.put([2, 1], [p2, np.array([7])])
    r3 = eng.put([1, 2], [np.array([3]), np.array([11])])

    def dense(seq):
        lg, _ = model.forward_with_cache(
            params, np.asarray(seq, np.int32)[None], model.init_kv_cache(1, 32))
        return np.asarray(lg[0, -1], np.float32)

    for got, seq in ((r1[1], p1), (r2[2], p2), (r2[1], [*p1, 7]),
                     (r3[1], [*p1, 7, 3]), (r3[2], [*p2, 11])):
        np.testing.assert_allclose(np.asarray(got, np.float32), dense(seq),
                                   atol=3e-2)


def test_paged_pool_smaller_than_dense(tiny_lm):
    """HBM footprint must follow allocated blocks, not max_seqs x max_seq_len:
    a pool sized for half the dense capacity still serves short sequences."""
    model, params = tiny_lm
    eng = _engine_on_the_xla_twin(
        model, params, max_sequences=8, max_seq_len=64, block_size=8, num_blocks=16)
    dense_blocks = 8 * (64 // 8)
    assert eng.cache["k"].shape[1] == 16 + 1 < dense_blocks
    # 5 sequences x 2 blocks each fit with 6 blocks spare
    for uid in range(5):
        eng.put([uid], [np.arange(16) % 250])
    assert eng.state.allocator.free_blocks == 16 - 5 * 2
    # a 64-token sequence (8 blocks) cannot be scheduled until a flush frees
    assert not eng.query(99, 64)
    eng.flush([0, 1])
    assert eng.state.allocator.free_blocks == 16 - 3 * 2
    assert eng.query(99, 64)


def test_paged_block_reuse_after_flush(tiny_lm):
    """Blocks freed by flush are re-allocated and re-written correctly."""
    model, params = tiny_lm
    rng = np.random.default_rng(5)
    eng = InferenceEngineV2(model, params=params, max_sequences=2,
                            max_seq_len=32, block_size=8, num_blocks=8)
    p = rng.integers(0, 256, 9)
    eng.put([1], [p])
    eng.flush([1])
    # same prompt through the recycled blocks must give the same logits
    q = rng.integers(0, 256, 9)
    ra = eng.put([2], [q])
    cache = model.init_kv_cache(1, 32)
    lg, _ = model.forward_with_cache(params, q[None].astype(np.int32), cache)
    np.testing.assert_allclose(np.asarray(ra[2], np.float32),
                               np.asarray(lg[0, -1], np.float32), atol=3e-2)


def test_paged_engine_tp2(tiny_lm, eight_devices):
    """v2 paged step under tensor parallelism must match the single-device
    engine (reference: v2 model sharding, engine_v2 TP allreduce)."""
    model, params = tiny_lm
    rng = np.random.default_rng(6)
    p1 = rng.integers(0, 256, 6)
    e_tp = InferenceEngineV2(model, params=params, max_sequences=2,
                             max_seq_len=32, block_size=8, mesh={"tp": 2})
    e_1 = InferenceEngineV2(model, params=params, max_sequences=2,
                            max_seq_len=32, block_size=8)
    ra = e_tp.put([1], [p1]); rb = e_1.put([1], [p1])
    np.testing.assert_allclose(np.asarray(ra[1], np.float32),
                               np.asarray(rb[1], np.float32), atol=3e-2)
    ra = e_tp.put([1], [np.array([9])]); rb = e_1.put([1], [np.array([9])])
    np.testing.assert_allclose(np.asarray(ra[1], np.float32),
                               np.asarray(rb[1], np.float32), atol=3e-2)


@pytest.mark.parametrize("tq", [1, 4])
def test_paged_attention_window_parity(tq):
    """Sliding-window paged attention (mistral/qwen2 serving): the work-list
    kernels' output (decode atoms, ``tq=1``; chunk atoms, ``tq=4``) matches
    the dense-gather reference with the same window mask."""
    from deepspeed_tpu.ops.paged_attention import (ragged_paged_attention,
                                                   xla_ragged_attention)

    rng = np.random.default_rng(0)
    kp, vp, bt = TestRaggedKernels._pools(rng)
    A, H, K, d = 3, 4, 2, 16
    q = jnp_f(rng.normal(size=(A * tq, H, d)))
    ks = jnp_f(rng.normal(size=(A * tq, K, d)))
    vs = jnp_f(rng.normal(size=(A * tq, K, d)))
    # slot 0 deep (pos 20), slot 1 shallow (pos 3), slot 2 fresh
    a_slot = jnp_np(np.array([0, 1, 2], np.int32))
    a_pos0 = jnp_np(np.array([20, 3, 0], np.int32))
    a_len = jnp_np(np.array([tq, 1, tq], np.int32))
    # window=None unchanged vs plain causal
    for window in (1, 6, 17, 1000, None):
        out = ragged_paged_attention(q, ks, vs, kp, vp, bt, a_slot, a_pos0,
                                     a_len, tq=tq, window=window)
        ref = xla_ragged_attention(q, ks, vs, kp, vp, bt, a_slot, a_pos0,
                                   a_len, tq, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5,
                                   err_msg=f"window={window}")


def test_windowed_family_serves_through_paged_engine():
    """A sliding-window (mistral/qwen2-style) model must serve through the
    paged v2 engine with the same logits as the full forward windowed mask —
    past-window context must NOT leak into the attention."""
    cfg = get_preset("tiny", sliding_window=6)
    model = TransformerLM(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 256, 16)
    eng = InferenceEngineV2(model, params=params, max_sequences=2,
                            max_seq_len=32, block_size=8)
    r = eng.put([1], [prompt])
    # reference: full forward with the window applied
    full = np.asarray(model.logits(params, prompt[None].astype(np.int32)),
                      np.float32)
    np.testing.assert_allclose(np.asarray(r[1], np.float32), full[0, -1],
                               atol=3e-2)
    # decode a few steps; each must match a fresh dense windowed cache run
    seq = list(prompt)
    for _ in range(3):
        nxt = int(np.argmax(np.asarray(r[1])))
        seq.append(nxt)
        r = eng.put([1], [np.array([nxt])])
        full = np.asarray(model.logits(
            params, np.asarray(seq)[None].astype(np.int32)), np.float32)
        np.testing.assert_allclose(np.asarray(r[1], np.float32),
                                   full[0, -1], atol=3e-2)


def test_packed_flops_scale_with_tokens(tiny_lm):
    """A mixed prefill+decode step's compiled FLOPs must follow total
    scheduled tokens, not max_sequences × t_max: one 64-token prefill + 7
    decodes packs into 128 token rows vs the 8×64 dense tile (4× the rows) —
    reference ragged_wrapper.py packs exactly total_tokens."""
    import jax.numpy as jnp

    from deepspeed_tpu.profiling import profile_fn

    model, params = tiny_lm
    Bs, t_max, bsz = 8, 64, 8
    nb_max = 64 // bsz
    cache = model.init_paged_kv_cache(Bs * nb_max, bsz)
    bt = np.arange(Bs * nb_max, dtype=np.int32).reshape(Bs, nb_max)

    # dense tile: [8, 64] rows through the dense-cache forward
    tile_cost = profile_fn(model.forward_with_cache, params,
                           jnp.zeros((Bs, t_max), jnp.int32),
                           model.init_kv_cache(Bs, t_max))

    # packed: 64 + 7 = 71 tokens → 128 bucket
    npad = 128
    tok_ids = np.zeros((npad,), np.int32)
    tok_slot = np.zeros((npad,), np.int32)
    tok_pos = np.zeros((npad,), np.int32)
    valid_p = np.zeros((npad,), bool)
    tok_slot[64:71] = np.arange(1, 8)
    tok_pos[:64] = np.arange(64)
    valid_p[:71] = True
    gather = np.zeros((Bs,), np.int32)
    packed_cost = profile_fn(model.forward_with_packed_cache, params,
                             jnp.asarray(tok_ids), cache, jnp.asarray(bt),
                             jnp.asarray(tok_slot), jnp.asarray(tok_pos),
                             jnp.asarray(valid_p), jnp.asarray(gather))
    assert packed_cost["flops"] > 0 and tile_cost["flops"] > 0
    # 128 packed rows vs 512 tile rows + per-row logits head → well under half
    assert packed_cost["flops"] < 0.5 * tile_cost["flops"], (
        packed_cost, tile_cost)


def test_packed_jit_cache_bounded(tiny_lm):
    """Power-of-two bucketing keeps the packed step's jit cache at
    O(log max_batched_tokens) entries regardless of chunk-length variety."""
    model, params = tiny_lm
    eng = _engine_on_the_xla_twin(
        model, params, max_sequences=4, max_seq_len=64, block_size=8)
    rng = np.random.default_rng(8)
    for uid, n in enumerate([3, 5, 7, 6]):        # all bucket to 8
        eng.put([uid], [rng.integers(0, 256, n)])
    for uid in range(4):                           # 4 decodes → 8 bucket too
        eng.put([uid], [np.array([uid + 1])])
    eng.put([0, 1], [rng.integers(0, 256, 9), np.array([2])])  # mixed step
    # 3 layout buckets — (tile-only 32), (decode-only 8), (mixed 8+32) — + 1:
    # the first call's freshly-placed cache signs differently from the
    # steady-state donated cache (an extra trace-cache entry, no extra XLA
    # compile)
    assert eng._step_packed._cache_size() <= 4, \
        eng._step_packed._cache_size()


def test_decode_batch_matches_sequential_puts(tiny_lm):
    """The fused on-device decode loop (CUDA-graph-replay parity) must
    produce exactly the tokens that per-step greedy put() calls produce."""
    model, params = tiny_lm
    rng = np.random.default_rng(10)
    p1 = rng.integers(0, 256, 6)
    p2 = rng.integers(0, 256, 4)

    def run_sequential():
        eng = InferenceEngineV2(model, params=params, max_sequences=4,
                                max_seq_len=32, block_size=8)
        r = eng.put([1, 2], [p1, p2])
        toks = {1: [], 2: []}
        cur = {u: int(np.argmax(r[u])) for u in (1, 2)}
        for _ in range(5):
            r = eng.put([1, 2], [np.array([cur[1]]), np.array([cur[2]])])
            for u in (1, 2):
                cur[u] = int(np.argmax(r[u]))
                toks[u].append(cur[u])
        return toks

    def run_fused():
        eng = InferenceEngineV2(model, params=params, max_sequences=4,
                                max_seq_len=32, block_size=8)
        r = eng.put([1, 2], [p1, p2])
        first = {u: int(np.argmax(r[u])) for u in (1, 2)}
        out = eng.decode_batch([1, 2], [first[1], first[2]], steps=5)
        return {u: list(out[u]) for u in (1, 2)}

    seq_toks, fused_toks = run_sequential(), run_fused()
    for u in (1, 2):
        assert seq_toks[u] == fused_toks[u], (u, seq_toks[u], fused_toks[u])


_BF16_OUTS = {}


def _kv_cache_outs(model, params, mode):
    """(engine, the logits of a whole prefill, a decode step and a mixed
    continuation) under a pool of ``mode``; the bf16 engine's are kept for
    the next case that compares with them (one build for both)."""
    if mode in _BF16_OUTS:
        return _BF16_OUTS[mode]
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, 256, n) for n in (21, 9)]
    cont = rng.integers(0, 256, 5)
    eng = InferenceEngineV2(model, params=params, max_sequences=4,
                            max_seq_len=64, block_size=8, kv_dtype=mode)
    outs = [eng.put([1, 2], prompts)]                    # whole prefill
    outs.append(eng.put([1, 2], [np.array([3]), np.array([4])]))
    outs.append(eng.put([1, 2], [cont, np.array([7])]))  # w/ past
    if mode == "bf16":
        _BF16_OUTS[mode] = eng, outs
    return eng, outs


# int8: per-token dequant scales, the error on the logits small relative to
# their scale; int4: per-head lane-paired nibbles + per-token scales, ~16x
# coarser than int8: loose but bounded
@pytest.mark.parametrize("mode,bound", [("int8", 0.15), ("int4", 0.6)])
def test_quantized_kv_cache_tracks_bf16(tiny_lm, mode, bound):
    """The int8 and the int4 paged pool must track the full-precision engine
    through prefill, mixed continuation and the fused decode loop, within
    quantization tolerance (a pool that dequantizes with the wrong scale, or
    pairs the wrong nibbles, is off by the logits' own size)."""
    model, params = tiny_lm
    _, want = _kv_cache_outs(model, params, "bf16")
    eng, got = _kv_cache_outs(model, params, mode)
    if mode == "int4":
        assert eng.cache["k"].shape[-1] \
            == model.cfg.num_kv_heads * model.cfg.head_dim // 2
    for step_a, step_b in zip(want, got):
        for u in (1, 2):
            a = np.asarray(step_a[u], np.float32)
            b = np.asarray(step_b[u], np.float32)
            assert np.abs(a - b).max() < bound * max(np.abs(a).max(), 1.0), \
                (u, np.abs(a - b).max())
    # fused decode loop runs on the quantized pool
    out = eng.decode_batch([1, 2], [1, 2], steps=4)
    assert all(len(out[u]) == 4 for u in (1, 2))


def test_int4_append_roundtrip():
    """bits=4 packed_kv_append_quant: unpacking the pool row reproduces the
    source row within its per-token scale (per-head lane pairing)."""
    from deepspeed_tpu.ops.paged_attention import (_unpack_int4_lanes_xla,
                                                   packed_kv_append_quant)

    L, N, K, d, bs, nb = 2, 6, 2, 16, 8, 4
    rng = np.random.default_rng(5)
    rows = jnp_f(rng.normal(size=(L, N, K, d)))
    pool = jnp_np(np.zeros((L, nb + 1, bs, K * d // 2), np.int8))
    scales = jnp_f(np.zeros((L, nb + 1, 1, 2 * bs)))
    bt = jnp_np(np.arange(8, dtype=np.int32).reshape(2, 4))
    tok_slot = jnp_np(np.array([0] * N, np.int32))
    tok_pos = jnp_np(np.arange(N, dtype=np.int32))
    npool, nsc = packed_kv_append_quant(pool, scales, rows, bt, tok_slot,
                                        tok_pos, 0, bits=4)
    got = np.asarray(_unpack_int4_lanes_xla(npool[:, 0, :N], K, d))
    sc = np.asarray(nsc[:, 0, 0, :N])                       # [L, N]
    recon = got * sc[..., None]
    ref = np.asarray(rows, np.float32).reshape(L, N, K * d)
    err = np.abs(recon - ref).max()
    tol = (np.abs(ref).max() / 7.0) * 0.51 + 1e-6
    assert err <= tol, (err, tol)


def test_decode_batch_sampling(tiny_lm):
    """Sampling inside the fused loop (reference FastGen serves sampled
    tokens): deterministic per seed, greedy at temperature 0, and the
    first sampled token's empirical distribution matches direct
    sample_token draws from the same logits."""
    from deepspeed_tpu.inference.engine import sample_token

    model, params = tiny_lm
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, 256, 6)
    B = 8

    eng = _engine_on_the_xla_twin(
        model, params, max_sequences=B, max_seq_len=64, block_size=8)
    uids = list(range(B))
    r = eng.put(uids, [prompt] * B)        # identical context per row
    logits = np.asarray(r[0], np.float32)  # [V] — same for every row
    first = int(np.argmax(logits))

    # determinism + greedy equivalence
    s1 = eng.decode_batch(uids, [first] * B, steps=3, temperature=0.8,
                          top_k=16, seed=7)
    eng.flush(uids)
    eng.put(uids, [prompt] * B)
    s2 = eng.decode_batch(uids, [first] * B, steps=3, temperature=0.8,
                          top_k=16, seed=7)
    for u in uids:
        assert list(s1[u]) == list(s2[u]), "same seed must reproduce"

    # distribution: first sampled token across rows x seeds vs direct
    # sample_token draws from the same logits, under top_k=8 (bounded
    # support makes small-sample statistics meaningful)
    draws = []
    for seed in range(6):
        eng.flush(uids)
        eng.put(uids, [prompt] * B)
        out = eng.decode_batch(uids, [first] * B, steps=1, temperature=0.7,
                               top_k=8, seed=seed)
        draws += [int(out[u][0]) for u in uids]
    # direct draws from the same next-token logits (the row after `first`
    # is appended — recompute via a put of `first`)
    eng.flush(uids)
    r2 = eng.put([0], [np.concatenate([prompt, [first]])])
    base_logits = np.asarray(r2[0], np.float32)
    top8 = set(np.argsort(base_logits)[-8:].tolist())
    assert set(draws) <= top8, (set(draws) - top8,
                                "sampled outside the top-k support")
    ref_draws = []
    for seed in range(96):
        tok = sample_token(jnp_f(base_logits)[None], 0.7, 8,
                           jax.random.key(1000 + seed))
        ref_draws.append(int(tok[0]))
    import collections
    ca = collections.Counter(draws)
    cb = collections.Counter(ref_draws)
    tvd = 0.5 * sum(abs(ca[t] / len(draws) - cb[t] / len(ref_draws))
                    for t in top8 | set(ca) | set(cb))
    assert tvd < 0.45, (tvd, ca, cb)


class TestRaggedKernels:
    """Numeric parity of the atom-based serving kernels (reference
    v2/kernels/ragged_ops/blocked_flash + atom_builder) against the dense
    gather implementation."""

    @staticmethod
    def _pools(rng, nbp1=17, bs=8, K=2, d=16):
        kp = jnp_f(rng.normal(size=(nbp1, bs, K, d)))
        vp = jnp_f(rng.normal(size=(nbp1, bs, K, d)))
        bt = np.asarray(rng.permutation(16)[:12].reshape(3, 4), np.int32)
        return kp, vp, jnp_np(bt)

    def test_chunk_atoms_match_reference(self):
        from deepspeed_tpu.ops.paged_attention import (
            ragged_paged_attention, xla_ragged_attention)

        rng = np.random.default_rng(0)
        kp, vp, bt = self._pools(rng)
        tq, A, H, d = 4, 3, 4, 16
        q = jnp_f(rng.normal(size=(A * tq, H, d)))
        ks = jnp_f(rng.normal(size=(A * tq, 2, d)))
        vs = jnp_f(rng.normal(size=(A * tq, 2, d)))
        a_slot = jnp_np(np.array([0, 1, 0], np.int32))
        a_pos0 = jnp_np(np.array([4, 9, 0], np.int32))
        a_len = jnp_np(np.array([4, 1, 0], np.int32))   # incl. pad atom
        for win in (None, 5):
            got = np.asarray(ragged_paged_attention(
                q, ks, vs, kp, vp, bt, a_slot, a_pos0, a_len, tq=tq,
                window=win))
            ref = np.asarray(xla_ragged_attention(
                q, ks, vs, kp, vp, bt, a_slot, a_pos0, a_len, tq,
                window=win))
            np.testing.assert_allclose(got, ref, atol=2e-5, rtol=2e-5)

    def test_decode_atoms_match_reference(self):
        from deepspeed_tpu.ops.paged_attention import (
            ragged_paged_attention, xla_ragged_attention)

        rng = np.random.default_rng(1)
        kp, vp, bt = self._pools(rng)
        q = jnp_f(rng.normal(size=(4, 4, 16)))
        ks = jnp_f(rng.normal(size=(4, 2, 16)))
        vs = jnp_f(rng.normal(size=(4, 2, 16)))
        s1 = jnp_np(np.array([0, 1, 2, 0], np.int32))
        p1 = jnp_np(np.array([8, 3, 0, 15], np.int32))  # incl. pos0=0
        l1 = jnp_np(np.array([1, 1, 1, 0], np.int32))   # incl. pad row
        got = np.asarray(ragged_paged_attention(q, ks, vs, kp, vp, bt,
                                                s1, p1, l1, tq=1))
        ref = np.asarray(xla_ragged_attention(q, ks, vs, kp, vp, bt,
                                              s1, p1, l1, 1))
        np.testing.assert_allclose(got, ref, atol=2e-5, rtol=2e-5)

    def test_packed_kv_append_scatter(self):
        from deepspeed_tpu.ops.paged_attention import packed_kv_append

        rng = np.random.default_rng(2)
        _, _, bt = self._pools(rng)
        L, nbp1, bs, K, d = 2, 17, 8, 2, 16
        pool = jnp_f(np.zeros((L, nbp1, bs, K, d)))
        rows = jnp_f(rng.normal(size=(L, 5, K, d)))
        ts = jnp_np(np.array([0, 0, 2, 1, 1], np.int32))
        tp = jnp_np(np.array([10, 11, 3, 0, 1], np.int32))
        va = jnp_np(np.array([1, 1, 1, 0, 0], bool))
        out = np.asarray(packed_kv_append(pool, rows, bt, ts, tp, va))
        btn = np.asarray(bt)
        b, o = int(btn[0, 10 // bs]), 10 % bs
        np.testing.assert_allclose(out[1, b, o], np.asarray(rows[1, 0]))
        b, o = int(btn[2, 0]), 3
        np.testing.assert_allclose(out[0, b, o], np.asarray(rows[0, 2]))
        # invalid rows dropped: total mass == the three valid rows' mass
        np.testing.assert_allclose(np.abs(out).sum(),
                                   float(jnp_f(np.abs(
                                       np.asarray(rows[:, :3]))).sum()),
                                   rtol=1e-6)


def test_joint_capacity_rejected_before_any_scheduling(tiny_lm):
    """Per-uid capacity checks can each pass while the aggregate demand
    exceeds the pool; the engine must reject the batch atomically instead
    of failing mid-prompt with sequences half-prefilled (review finding)."""
    model, params = tiny_lm
    rng = np.random.default_rng(11)
    # pool fits ONE 64-token prompt (8 blocks) but not two
    eng = _engine_on_the_xla_twin(
        model, params, max_sequences=4, max_seq_len=600, block_size=8, num_blocks=10)
    p = rng.integers(0, 256, 64)
    with pytest.raises(RuntimeError, match="cannot schedule"):
        eng.put([1, 2], [p, p])
    # nothing was scheduled or allocated
    assert eng.state.allocator.free_blocks == 10
    assert not eng.state.sequences
    # a single prompt still fits
    eng.put([1], [p])
    assert eng.state.sequences[1].seen_tokens == 64


class TestWeightQuantServing:
    """int8/int4 weight serving through the linear() seam (reference
    ``init_inference(dtype=torch.int8)`` + the cutlass mixed-GEMM path):
    the engine swaps matmul leaves for packed QuantizedWeight nodes and
    every forward path (prefill, packed put, fused decode loop) consumes
    them via the fused dequant-matmul kernel."""

    @staticmethod
    def _model():
        from deepspeed_tpu.models import TransformerConfig

        cfg = TransformerConfig(vocab_size=512, hidden_size=128,
                                num_layers=2, num_heads=4, max_seq_len=256,
                                arch="llama", tie_embeddings=False)
        model = TransformerLM(cfg)
        return model, model.init(jax.random.key(0))

    @pytest.mark.parametrize("wd", ["int8", "int4"])
    def test_quant_engine_serves(self, wd):
        model, params = self._model()
        eng = InferenceEngineV2(model, params=params, max_sequences=4,
                                max_seq_len=256, block_size=32,
                                weight_dtype=wd)
        prompt = np.random.default_rng(0).integers(0, 512, 48)
        first = eng.put([1], [prompt])[1]
        assert np.isfinite(np.asarray(first, np.float32)).all()
        toks = eng.decode_batch([1], [int(np.argmax(first))], steps=6)[1]
        assert toks.shape == (6,)
        # the packed tree must actually be smaller than the served bf16 tree
        dense = InferenceEngineV2(model, params=params, max_sequences=4,
                                  max_seq_len=256, block_size=32)

        def nbytes(tree):
            return sum(leaf.nbytes
                       for leaf in jax.tree_util.tree_leaves(tree))

        ratio = nbytes(eng.params) / nbytes(dense.params)
        assert ratio < (0.75 if wd == "int8" else 0.55), ratio

    def test_int8_matches_dequant_reference(self):
        """Same effective (rounded) weights served dense vs packed must give
        matching logits — isolates the kernel from the quantization loss."""
        from deepspeed_tpu.ops.quant_matmul import (
            dequantize_matmul_weight, quantize_matmul_weight)

        model, params = self._model()
        eng_q = InferenceEngineV2(model, params=params, max_sequences=4,
                                  max_seq_len=256, block_size=32,
                                  weight_dtype="int8")

        import jax.numpy as jnp

        def rq(w):  # round-trip a stacked [L, Din, F] leaf through int8,
            # replicating the engine's compute-dtype scale storage
            outs = []
            for i in range(w.shape[0]):
                p, s = quantize_matmul_weight(w[i].astype(np.float32), bits=8)
                s = s.astype(jnp.bfloat16).astype(jnp.float32)
                outs.append(dequantize_matmul_weight(p, s, 8, w.shape[1]))
            return jnp.stack(outs).astype(w.dtype)

        ref = jax.tree_util.tree_map(lambda p: p, params)
        for grp in ("attn", "mlp"):
            for name in InferenceEngineV2._QUANT_LEAVES:
                if name in ref["layers"][grp]:
                    ref["layers"][grp][name] = rq(ref["layers"][grp][name])
        p, s = quantize_matmul_weight(
            np.asarray(ref["lm_head"], np.float32), bits=8)
        s = s.astype(jnp.bfloat16).astype(jnp.float32)
        ref["lm_head"] = dequantize_matmul_weight(
            p, s, 8, ref["lm_head"].shape[0]).astype(ref["lm_head"].dtype)
        eng_d = InferenceEngineV2(model, params=ref, max_sequences=4,
                                  max_seq_len=256, block_size=32)
        prompt = np.random.default_rng(1).integers(0, 512, 40)
        lq = np.asarray(eng_q.put([1], [prompt])[1], np.float32)
        ld = np.asarray(eng_d.put([1], [prompt])[1], np.float32)
        # identical effective weights; the residual spread is bf16
        # accumulation order (kernel sums per 128-row group, XLA in one dot)
        np.testing.assert_allclose(lq, ld, atol=0.2, rtol=0.2)
        assert float(np.mean(np.abs(lq - ld))) < 2e-2

    def test_v1_engine_int8_dtype(self):
        """``init_inference(dtype='int8')`` parity surface: the v1 engine's
        generate() serves packed weights through the same seam."""
        import deepspeed_tpu as ds

        model, params = self._model()
        eng = ds.init_inference(model=model, dtype="int8", params=params)
        ids = np.random.default_rng(3).integers(0, 512, (1, 16))
        out = eng.generate(ids, max_new_tokens=4)
        assert out.shape == (1, 20)
        from deepspeed_tpu.models.transformer import QuantizedWeight

        assert isinstance(eng.params["layers"]["attn"]["wqkv"],
                          QuantizedWeight)
        assert isinstance(eng.params["lm_head_q"], QuantizedWeight)

    def test_moe_model_quant_serves(self):
        """MoE expert stacks ([L, E, D, F]) quantize to int8 leaf pairs
        (w_*_q packed + w_*_s scales — reference cutlass moe_gemm W8A16)
        consumed by the grouped-GEMM dequant seam; they are never
        gate|up-fused. Served logits must stay close to the bf16 engine's
        (expert weights carry most of a MoE model's read bandwidth)."""
        from deepspeed_tpu.models import TransformerConfig

        cfg = TransformerConfig(vocab_size=512, hidden_size=128,
                                num_layers=2, num_heads=4, max_seq_len=256,
                                arch="llama", num_experts=4, top_k=2)
        model = TransformerLM(cfg)
        params = model.init(jax.random.key(0))
        prompt = np.random.default_rng(4).integers(0, 512, 40)
        ref_eng = InferenceEngineV2(model, params=params, max_sequences=4,
                                    max_seq_len=256, block_size=32)
        ref = np.asarray(ref_eng.put([1], [prompt])[1], np.float32)
        del ref_eng
        eng = InferenceEngineV2(model, params=params,
                                max_sequences=4, max_seq_len=256,
                                block_size=32, weight_dtype="int8")
        from deepspeed_tpu.models.transformer import QuantizedWeight

        mlp = eng.params["layers"]["mlp"]
        assert isinstance(eng.params["layers"]["attn"]["wqkv"],
                          QuantizedWeight)
        assert "w_gateup" not in mlp and "w_gate" not in mlp
        assert str(mlp["w_gate_q"].dtype) == "int8"
        assert mlp["w_gate_q"].shape == (2, 4, 128, mlp["w_gate_s"].shape[-1])
        assert str(mlp["w_down_q"].dtype) == "int8"
        # the dequant seam must reconstruct the dense stack to int8 accuracy
        from deepspeed_tpu.moe.sharded_moe import _expert_weight

        import jax.numpy as jnp

        dense = params["layers"]["mlp"]["w_gate"][0]      # [E, D, F]
        recon = np.asarray(_expert_weight(
            {k: v[0] for k, v in mlp.items() if k.startswith("w_gate")},
            "w_gate", jnp.float32), np.float32)
        wrel = (np.abs(recon - np.asarray(dense, np.float32)).max()
                / np.abs(np.asarray(dense)).max())
        assert wrel < 0.02, f"expert dequant off: {wrel}"
        # end-to-end only loosely: on a RANDOM-INIT router, int8 noise in h
        # flips top-2 expert selection (near-uniform router logits), which
        # swings logits far beyond the per-path quantization error — a
        # trained MoE's routing margins make this a non-issue
        first = eng.put([1], [prompt])[1]
        rel = (np.abs(np.asarray(first, np.float32) - ref).max()
               / (np.abs(ref).max() + 1e-9))
        assert rel < 0.6, f"int8-expert logits diverged: rel={rel}"
        toks = eng.decode_batch([1], [int(np.argmax(first))], steps=4)[1]
        assert toks.shape == (4,)

    def test_quant_engine_tp2(self, eight_devices):
        model, params = self._model()
        eng = InferenceEngineV2(model, params=params, max_sequences=4,
                                max_seq_len=256, block_size=32,
                                weight_dtype="int8", mesh={"tp": 2})
        prompt = np.random.default_rng(2).integers(0, 512, 32)
        first = eng.put([1], [prompt])[1]
        toks = eng.decode_batch([1], [int(np.argmax(first))], steps=4)[1]
        assert toks.shape == (4,)


# ---- one layer loop, every program -----------------------------------------

@pytest.fixture(scope="module")
def loop_models():
    """Two models that make the shared serving layer loop do more than one
    plain scan, each with its engine, the parameter tree the serving
    forwards take, the tree of the reference and the tolerance."""
    from deepspeed_tpu.models.transformer import QuantizedWeight
    from deepspeed_tpu.ops.quant_matmul import dequantize_matmul_weight

    def dense(w):
        """A (layer-stacked) QuantizedWeight expanded back to dense."""
        if not isinstance(w, QuantizedWeight):
            return w
        if w.packed.ndim == 2:
            return dequantize_matmul_weight(w.packed, w.scales, w.bits, w.din)
        return jax.numpy.stack([
            dequantize_matmul_weight(p, sc, w.bits, w.din)
            for p, sc in zip(w.packed, w.scales)])

    out = {}
    # layer 0 full attention, layers 1-2 windowed: one period of three
    # blocks, each under its kind's config
    model = TransformerLM(get_preset(
        "tiny", dtype="float32", num_layers=3, sliding_window=6,
        attn_pattern=("full", "window", "window")))
    params = model.init(jax.random.key(0))
    eng = InferenceEngineV2(model, params=params, max_sequences=2,
                            max_seq_len=32, block_size=8)
    out["mixed_window"] = (model, eng, params, params, dict(atol=2e-3))
    # window and full layers in turn (two periods of three blocks), the full
    # layer's rope yarn with its attention factor, a held share of the
    # experts: prefill, then decode through the cache
    model = TransformerLM(get_preset(
        "tiny", dtype="float32", num_layers=6, sliding_window=6,
        attn_pattern=("window", "window", "full"),
        rope_by_kind={"full": {"rope_type": "yarn", "rope_theta": 1e4,
                               "factor": 4.0, "beta_fast": 4, "beta_slow": 1,
                               "original_max_position_embeddings": 16,
                               "attention_factor": 1.2}},
        num_experts=8, top_k=2, moe_dispatch="grouped",
        moe_intermediate_size=32, moe_experts_held=4, moe_first_expert=2))
    params = model.init(jax.random.key(1))
    eng = InferenceEngineV2(model, params=params, max_sequences=2,
                            max_seq_len=32, block_size=8)
    out["pattern_yarn_share"] = (model, eng, params, params, dict(atol=2e-3))
    # quantized layer leaves: the loop threads QuantLayerRef into each layer
    model, params = TestWeightQuantServing._model()
    eng = InferenceEngineV2(model, params=params, max_sequences=2,
                            max_seq_len=32, block_size=8,
                            weight_dtype="int8")
    ref = jax.tree_util.tree_map(
        dense, eng.params, is_leaf=lambda w: isinstance(w, QuantizedWeight))
    out["int8_leaves"] = (model, eng, eng.params, ref,
                          dict(atol=0.2, rtol=0.2))
    return out


def _serve(path, model, eng, params, prompt):
    """Drive ``prompt`` down one serving program; returns [(tokens of the
    sequence so far, next-token logits)]."""
    n = len(prompt)
    if path == "forward_with_cache":
        cache = model.init_kv_cache(1, 32)
        got = []
        for lo, hi in [(0, 7)] + [(i, i + 1) for i in range(7, n)]:
            lg, cache = model.forward_with_cache(
                params, prompt[None, lo:hi].astype(np.int32), cache)
            got.append((prompt[:hi], lg[0, -1]))
        return got
    try:
        if path == "forward_prefill":           # a fresh whole prompt
            return [(prompt, eng.put([1], [prompt])[1])]
        if path == "forward_with_packed_cache":  # a decode row, a tile, rows
            return [(prompt[:hi], eng.put([1], [prompt[lo:hi]])[1])
                    for lo, hi in [(0, 1), (1, 8)]
                    + [(i, i + 1) for i in range(8, n)]]
        assert path == "forward_decode_tail"
        first = int(np.argmax(np.asarray(eng.put([1], [prompt])[1])))
        toks = eng.decode_batch([1], [first], steps=3)[1]
        # what the tail left in the pool, read back by one more step
        seq = np.concatenate([prompt, [first], toks])
        return [(seq, eng.put([1], [toks[-1:]])[1])]
    finally:
        eng.flush([1])


@pytest.mark.parametrize("path", ["forward_with_cache", "forward_prefill",
                                  "forward_with_packed_cache",
                                  "forward_decode_tail"])
@pytest.mark.parametrize("kind", ["mixed_window", "int8_leaves",
                                  "pattern_yarn_share"])
def test_every_serving_program_matches_the_whole_sequence_forward(
        loop_models, kind, path):
    """The four serving forwards share one layer loop: down each of them the
    next-token logits agree with ``model.logits`` on the whole sequence (one
    forward over the longest sequence served: the layers are causal, so the
    row of a prefix's last token is what the prefix alone gives). A program
    that read a stale or misplaced cache row, or a window's wrong edge, is
    off at the first length that reaches it."""
    model, eng, params, ref, tol = loop_models[kind]
    prompt = np.random.default_rng(12).integers(0, 256, 12)
    served = _serve(path, model, eng, params, prompt)
    longest = max((seq for seq, _ in served), key=len)
    assert all((longest[:len(seq)] == seq).all() for seq, _ in served)
    whole = jax.jit(model.logits)(ref, np.asarray(longest, np.int32)[None])[0]
    for seq, got in served:
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(whole[len(seq) - 1],
                                              np.float32), **tol,
                                   err_msg=f"{len(seq)} tokens")


def test_the_engine_has_one_cache_mode(tiny_lm):
    model, params = tiny_lm
    for mode in ("paged", "packed"):
        with pytest.raises(TypeError, match=mode):
            InferenceEngineV2(model, params=params, **{mode: False})


def test_init_inference_checkpoint_surfaces(tmp_path, eight_devices):
    """init_inference(checkpoint=...) loads engine checkpoints (given the
    model) and HF checkpoint dirs (self-describing) — round-2 weak #7."""
    import deepspeed_tpu as ds

    # engine checkpoint route
    model = TransformerLM(get_preset("tiny"))
    eng, *_ = ds.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 0}, "mesh": {"dp": 8},
        "steps_per_print": 100})
    b = {"input_ids": np.random.default_rng(0).integers(0, 256, (16, 16))}
    loss = eng.forward(b); eng.backward(loss); eng.step()
    ck = str(tmp_path / "engine_ck")
    eng.save_checkpoint(ck)
    ieng = ds.init_inference(model=TransformerLM(get_preset("tiny")),
                             checkpoint=ck, config={"mesh": {}})
    trained = np.asarray(jax.tree_util.tree_leaves(eng.params)[0])
    loaded = np.asarray(jax.tree_util.tree_leaves(ieng.params)[0])
    np.testing.assert_allclose(loaded, trained, rtol=1e-6)
    out = ieng.generate(np.random.default_rng(1).integers(0, 256, (1, 4)),
                        max_new_tokens=3)
    assert out.shape == (1, 7)

    # HF checkpoint route (model auto-built)
    import torch
    import transformers as tr

    torch.manual_seed(0)
    hf = tr.LlamaForCausalLM(tr.LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=32))
    hf_dir = str(tmp_path / "hf_ck")
    hf.save_pretrained(hf_dir)
    ieng2 = ds.init_inference(checkpoint=hf_dir, config={"mesh": {}})
    ids = np.random.default_rng(2).integers(0, 128, (1, 8))
    out2 = np.asarray(ieng2.generate(ids, max_new_tokens=3))
    with torch.no_grad():
        ref = hf.generate(torch.tensor(ids), max_new_tokens=3,
                          do_sample=False).numpy()
    np.testing.assert_array_equal(out2, ref)


def test_quantize_jit_wrapper_count_does_not_scale_with_leaves(monkeypatch):
    """dslint burn-down (recompile-hazard): ``quantize_serving_params``
    built ``jax.jit(q_stacked)`` INSIDE the per-leaf loop (and the head
    lambda inline), so every leaf traced+compiled against a fresh empty
    cache. The wrappers are now bound once before the loops — exactly
    three ``jax.jit`` calls regardless of how many leaves quantize, and
    same-geometry leaves share one compilation."""
    from deepspeed_tpu.inference.quant import quantize_serving_params

    model, params = TestWeightQuantServing._model()
    dense = InferenceEngineV2(model, params=params, max_sequences=2,
                              max_seq_len=256, block_size=32)
    real_jit = jax.jit
    calls = []

    def counting_jit(*a, **k):
        calls.append(a)
        return real_jit(*a, **k)
    monkeypatch.setattr(jax, "jit", counting_jit)
    q = quantize_serving_params(params, dense.cfg, 8, dense.mesh)
    monkeypatch.undo()
    # q_stacked + expert-layer vmap + lm-head lambda; with >3 quantizable
    # leaves in this model, the old per-leaf jit would exceed this
    assert len(calls) == 3, [getattr(a[0], "__name__", a[0]) for a in calls]
    from deepspeed_tpu.models.transformer import QuantizedWeight
    n_quant = sum(isinstance(leaf, QuantizedWeight)
                  for leaf in jax.tree_util.tree_leaves(
                      q, is_leaf=lambda x: isinstance(x, QuantizedWeight)))
    assert n_quant > len(calls)     # more leaves quantized than jits built
