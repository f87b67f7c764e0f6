"""MoE tests (pattern: reference ``tests/unit/moe/test_moe.py`` — gating invariants +
tiny MoE model training)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import TransformerLM, get_preset
from deepspeed_tpu.moe import moe_mlp_block, top1_gating, topk_gating


def test_topk_gating_invariants():
    S, E, k = 64, 4, 2
    logits = jax.random.normal(jax.random.key(0), (S, E))
    dispatch, combine, aux, stats = topk_gating(logits, k=k, capacity_factor=2.0)
    C = dispatch.shape[-1]
    # each token dispatched at most k times, each slot holds at most one token
    assert dispatch.shape == (S, E, C)
    assert float(dispatch.sum(axis=(1, 2)).max()) <= k + 1e-6
    assert float(dispatch.sum(axis=0).max()) <= 1 + 1e-6  # slot occupancy
    # combine weights match dispatch support and sum to <= 1 per token
    assert np.all((np.asarray(combine) > 0) <= (np.asarray(dispatch) > 0))
    per_token = np.asarray(combine.sum(axis=(1, 2)))
    assert per_token.max() <= 1 + 1e-5
    assert float(aux) > 0


def test_capacity_drops_tokens():
    S, E = 64, 2
    # all tokens want expert 0 → capacity must drop most
    logits = jnp.stack([jnp.ones(S), -jnp.ones(S)], axis=1)
    dispatch, _, _, stats = top1_gating(logits, capacity_factor=0.5, min_capacity=4)
    kept = float(dispatch.sum())
    assert kept <= max(int(np.ceil(S / E * 0.5)), 4) + 1e-6


def test_moe_block_shapes_and_grads():
    cfg = get_preset("tiny-moe")
    model = TransformerLM(cfg, moe_fn=moe_mlp_block)
    params = model.init(jax.random.key(0))
    E = cfg.num_experts
    assert params["layers"]["mlp"]["w_up"].shape[1] == E
    batch = {"input_ids": np.random.default_rng(0).integers(0, 256, (2, 16))}
    loss, grads = jax.value_and_grad(model.loss_fn)(params, batch)
    assert np.isfinite(float(loss))
    # router must receive gradient (aux loss + combine weights)
    rg = np.asarray(grads["layers"]["mlp"]["router"])
    assert np.abs(rg).sum() > 0


def test_moe_ep_training(eight_devices):
    """tiny MoE model trains on an ep×fsdp mesh (AutoEP-style EP×DP algebra)."""
    cfg = get_preset("tiny-moe")
    model = TransformerLM(cfg, moe_fn=moe_mlp_block)
    eng, *_ = ds.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
        "mesh": {"ep": 4, "fsdp": 2},
        "steps_per_print": 100,
    })
    rng = np.random.default_rng(0)
    fixed = {"input_ids": rng.integers(0, 256, (2 * eng.topology.dp_world_size, 16))}
    losses = []
    for _ in range(4):
        loss = eng.forward(fixed)
        eng.backward(loss)
        eng.step()
        losses.append(float(loss))
    assert losses[-1] < losses[0]


class TestGroupedDispatch:
    def test_grouped_matches_capacity_when_no_drops(self, eight_devices):
        """With capacity high enough that nothing drops, the grouped
        (ragged_dot) path computes the same function as the capacity einsum."""
        import jax
        import jax.numpy as jnp

        from deepspeed_tpu.moe import grouped_moe_mlp_block, moe_mlp_block

        class Cfg:
            top_k = 2
            capacity_factor = 8.0  # no drops
            min_capacity = 4

        rng = jax.random.split(jax.random.key(0), 5)
        D, F, E = 16, 32, 4
        w = {"router": jax.random.normal(rng[0], (D, E)) * 0.1,
             "w_gate": jax.random.normal(rng[1], (E, D, F)) / 4,
             "w_up": jax.random.normal(rng[2], (E, D, F)) / 4,
             "w_down": jax.random.normal(rng[3], (E, F, D)) / 6}
        h = jax.random.normal(rng[4], (2, 16, D))
        yc, auxc = moe_mlp_block(h, w, Cfg())
        yg, auxg = jax.jit(grouped_moe_mlp_block, static_argnums=2)(h, w, Cfg())
        np.testing.assert_allclose(np.asarray(yg), np.asarray(yc),
                                   rtol=2e-3, atol=2e-5)
        np.testing.assert_allclose(float(auxg), float(auxc), rtol=1e-5)

    def test_grouped_is_dropless(self, eight_devices):
        """At a starvation capacity the einsum path drops tokens; the grouped
        path computes all of them (the cutlass moe_gemm property)."""
        import jax
        import jax.numpy as jnp

        from deepspeed_tpu.moe import grouped_moe_mlp_block, moe_mlp_block
        from deepspeed_tpu.moe.sharded_moe import topk_gating

        class Tight:
            top_k = 2
            capacity_factor = 0.1
            min_capacity = 1

        rng = jax.random.split(jax.random.key(1), 5)
        D, F, E = 16, 32, 4
        w = {"router": jax.random.normal(rng[0], (D, E)) * 0.1,
             "w_gate": jax.random.normal(rng[1], (E, D, F)) / 4,
             "w_up": jax.random.normal(rng[2], (E, D, F)) / 4,
             "w_down": jax.random.normal(rng[3], (E, F, D)) / 6}
        h = jax.random.normal(rng[4], (1, 64, D))
        x = np.asarray(h.reshape(-1, D))
        logits = jnp.asarray(x) @ w["router"]
        _, _, _, stats = topk_gating(logits, k=2, capacity_factor=0.1,
                                     min_capacity=1)
        assert float(stats["drop_fraction"]) > 0.1  # einsum path drops
        yg, _ = grouped_moe_mlp_block(h, w, Tight())
        # every token got its full top-2 contribution: output differs from the
        # dropping path and is finite everywhere
        yc, _ = moe_mlp_block(h, w, Tight())
        assert np.isfinite(np.asarray(yg)).all()
        assert not np.allclose(np.asarray(yg), np.asarray(yc))

    def test_grouped_trains(self, eight_devices):
        """End to end under the engine with moe_dispatch='grouped'."""
        import dataclasses

        import deepspeed_tpu as ds
        from deepspeed_tpu.models import TransformerLM, get_preset
        from deepspeed_tpu.moe import moe_block_for

        cfg = dataclasses.replace(get_preset("tiny-moe"),
                                  moe_dispatch="grouped")
        model = TransformerLM(cfg, moe_fn=moe_block_for(cfg))
        eng, *_ = ds.initialize(model=model, config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0}, "mesh": {"dp": 8},
            "steps_per_print": 100})
        b = {"input_ids": np.random.default_rng(0).integers(0, 256, (16, 32))}
        losses = []
        for _ in range(4):
            loss = eng.forward(b)
            eng.backward(loss)
            eng.step()
            losses.append(float(loss))
        assert losses[-1] < losses[0]

class TestGroupedEP:
    """Expert-parallel dropless dispatch (reference ``_AllToAll``
    moe/sharded_moe.py:97 + cutlass moe_gemm, as a padded a2a over ``ep``)."""

    @staticmethod
    def _weights(key, D=16, F=32, E=8):
        rng = jax.random.split(key, 5)
        w = {"router": jax.random.normal(rng[0], (D, E)) * 0.1,
             "w_gate": jax.random.normal(rng[1], (E, D, F)) / 4,
             "w_up": jax.random.normal(rng[2], (E, D, F)) / 4,
             "w_down": jax.random.normal(rng[3], (E, F, D)) / 6}
        return w, rng[4]

    @staticmethod
    def _ep_mesh(devices, ep=4, dp=2):
        from jax.sharding import Mesh

        return Mesh(np.array(devices[:ep * dp]).reshape(ep, dp), ("ep", "dp"))

    def test_ep_matches_single_shard(self, eight_devices):
        from deepspeed_tpu.moe import grouped_moe_mlp_block

        class Cfg:
            top_k = 2
            moe_ep_capacity_factor = 0.0

        w, hk = self._weights(jax.random.key(0))
        h = jax.random.normal(hk, (4, 16, 16))
        y1, aux1 = grouped_moe_mlp_block(h, w, Cfg())
        with jax.sharding.set_mesh(self._ep_mesh(eight_devices)):
            y2, aux2 = jax.jit(grouped_moe_mlp_block, static_argnums=2)(
                h, w, Cfg())
        np.testing.assert_allclose(np.asarray(y2), np.asarray(y1),
                                   rtol=2e-3, atol=1e-5)
        np.testing.assert_allclose(float(aux2), float(aux1), rtol=1e-5)

    def test_ep_dropless_under_total_imbalance(self, eight_devices):
        """All tokens route to the experts of ONE ep shard — the worst-case
        a2a load — and the default capacity still computes every pair."""
        from deepspeed_tpu.moe import grouped_moe_mlp_block

        class Cfg:
            top_k = 2
            moe_ep_capacity_factor = 0.0

        w, hk = self._weights(jax.random.key(1))
        # bias the router so experts 0/1 (both on ep shard 0) win everywhere
        w["router"] = w["router"] * 0.0 + jnp.array(
            [8.0, 7.0] + [-8.0] * 6)[None, :]
        h = jax.random.normal(hk, (4, 16, 16))
        y1, _ = grouped_moe_mlp_block(h, w, Cfg())
        with jax.sharding.set_mesh(self._ep_mesh(eight_devices)):
            y2, _ = jax.jit(grouped_moe_mlp_block, static_argnums=2)(
                h, w, Cfg())
        np.testing.assert_allclose(np.asarray(y2), np.asarray(y1),
                                   rtol=2e-3, atol=1e-5)

    def test_ep_capacity_factor_bounds_payload(self, eight_devices):
        """With a finite moe_ep_capacity_factor the a2a buffer shrinks and
        overflow pairs are dropped (documented trade): output stays finite
        and differs from the dropless result under total imbalance."""
        from deepspeed_tpu.moe import grouped_moe_mlp_block

        class Tight:
            top_k = 2
            moe_ep_capacity_factor = 1.0   # balanced-load capacity only

        w, hk = self._weights(jax.random.key(2))
        w["router"] = w["router"] * 0.0 + jnp.array(
            [8.0, 7.0] + [-8.0] * 6)[None, :]
        h = jax.random.normal(hk, (4, 16, 16))
        y_dropless, _ = grouped_moe_mlp_block(h, w, type(
            "C", (), {"top_k": 2, "moe_ep_capacity_factor": 0.0}))
        with jax.sharding.set_mesh(self._ep_mesh(eight_devices)):
            y_tight, _ = jax.jit(grouped_moe_mlp_block, static_argnums=2)(
                h, w, Tight())
        assert np.isfinite(np.asarray(y_tight)).all()
        assert not np.allclose(np.asarray(y_tight), np.asarray(y_dropless))

    def test_mixtral_serves_under_ep(self, eight_devices, tmp_path):
        """Imported Mixtral generates on an ep=2 mesh with greedy decode
        matching HF exactly — expert parallelism WITH the released routing
        (the round-2 gap: grouped dispatch used to refuse ep>1)."""
        import torch
        from transformers import MixtralConfig, MixtralForCausalLM

        from deepspeed_tpu.inference.engine import InferenceEngine
        from deepspeed_tpu.models.hf import load_hf_checkpoint

        torch.manual_seed(0)
        cfg = MixtralConfig(vocab_size=128, hidden_size=32,
                            intermediate_size=64, num_hidden_layers=2,
                            num_attention_heads=4, num_key_value_heads=2,
                            num_local_experts=4, num_experts_per_tok=2,
                            max_position_embeddings=64)
        hf = MixtralForCausalLM(cfg)
        hf.save_pretrained(str(tmp_path))
        model, params = load_hf_checkpoint(str(tmp_path), dtype="float32")
        eng = InferenceEngine(model, config={"mesh": {"ep": 2, "dp": 4}},
                              params=params)
        ids = np.random.default_rng(0).integers(0, 128, (4, 8))
        out = np.asarray(eng.generate(ids, max_new_tokens=4))
        with torch.no_grad():
            ref = hf.generate(torch.tensor(ids), max_new_tokens=4,
                              do_sample=False).numpy()
        np.testing.assert_array_equal(out, ref)
        # single request: decode steps have S=1 < ep — the pad path
        out1 = np.asarray(eng.generate(ids[:1], max_new_tokens=4))
        np.testing.assert_array_equal(out1[0], ref[0])

    def test_ep_grouped_trains(self, eight_devices):
        """End to end: moe_dispatch='grouped' now composes with ep>1."""
        import dataclasses

        import deepspeed_tpu as ds
        from deepspeed_tpu.models import TransformerLM, get_preset
        from deepspeed_tpu.moe import moe_block_for

        cfg = dataclasses.replace(get_preset("tiny-moe"),
                                  moe_dispatch="grouped")
        model = TransformerLM(cfg, moe_fn=moe_block_for(cfg))
        eng, *_ = ds.initialize(model=model, config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0}, "mesh": {"ep": 4, "dp": 2},
            "steps_per_print": 100})
        b = {"input_ids": np.random.default_rng(0).integers(0, 256, (4, 32))}
        losses = []
        for _ in range(4):
            loss = eng.forward(b)
            eng.backward(loss)
            eng.step()
            losses.append(float(loss))
        assert losses[-1] < losses[0]


def test_capacity_moe_decode_ignores_idle_lanes(eight_devices):
    """A capacity-dispatch MoE model served with a mostly-empty batch must
    match the solo reference: pad/idle lanes are masked out of expert
    capacity competition. The real sequence is placed in a LATE slot so the
    idle lanes (all embedding token 0 — identical router picks) precede it in
    the capacity cumsum; without the valid mask they would fill the experts'
    capacity and evict the real tokens' assignments. A 4-token prompt keeps
    every path inside min_capacity, so any post-fix mismatch is eviction,
    not the (inherent) capacity-vs-batch-shape difference."""
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2

    cfg = get_preset("tiny-moe")  # moe_dispatch='capacity'
    model = TransformerLM(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(9)
    p = rng.integers(0, 256, 4)

    # solo reference through a batch-of-one dense cache
    cache = model.init_kv_cache(1, 32)
    lg, _ = model.forward_with_cache(params, p[None].astype(np.int32), cache)
    ref = np.asarray(lg[0, -1], np.float32)

    eng = InferenceEngineV2(model, params=params, max_sequences=8,
                            max_seq_len=32, block_size=8)
    # burn slots 0-3 then free 0-2: uid 5 lands in slot 4 with four
    # idle-lane slots ahead of it in row order
    for uid in (1, 2, 3, 4):
        eng.put([uid], [rng.integers(0, 256, 4)])
    eng.flush([1, 2, 3])
    r = eng.put([5], [p])
    assert eng.state.sequences[5].slot == 4
    np.testing.assert_allclose(np.asarray(r[5], np.float32), ref,
                               atol=3e-2)


# ---------------------------------------------------------------------------
# dropless grouped kernels: ragged_dot vs the padded one-hot einsum
# ---------------------------------------------------------------------------

class TestDroplessKernels:
    @staticmethod
    def _weights(D, F, E, seed=0):
        rng = jax.random.split(jax.random.key(seed), 4)
        return {"router": jax.random.normal(rng[0], (D, E)) * 0.1,
                "w_gate": jax.random.normal(rng[1], (E, D, F)) / 4,
                "w_up": jax.random.normal(rng[2], (E, D, F)) / 4,
                "w_down": jax.random.normal(rng[3], (E, F, D)) / 6}

    @pytest.mark.parametrize("top_k", [1, 2])
    @pytest.mark.parametrize("shape", [(2, 16), (1, 13), (3, 7)])
    def test_ragged_padded_bit_identity(self, top_k, shape):
        """fp32 outputs of the ragged grouped GEMM and the padded one-hot
        einsum reference agree to the last ulp or two — including odd token
        counts and B=1 decode shapes. (They were bitwise identical under the
        XLA of jax 0.4; jax 0.9's XLA:CPU orders the ragged dot's
        accumulation differently, 1 ulp = 4.8e-7 at these magnitudes.)"""
        from deepspeed_tpu.moe import grouped_moe_mlp_block

        class Cfg:
            pass

        Cfg.top_k = top_k
        w = self._weights(16, 32, 4, seed=top_k)
        h = jax.random.normal(jax.random.key(9), (*shape, 16), jnp.float32)
        jfn = jax.jit(grouped_moe_mlp_block, static_argnums=2,
                      static_argnames=("kernel",))
        yr, ar = jfn(h, w, Cfg, kernel="ragged")
        yp, ap = jfn(h, w, Cfg, kernel="padded")
        np.testing.assert_allclose(np.asarray(yr), np.asarray(yp),
                                   rtol=1e-4, atol=2e-6)
        assert float(ar) == float(ap)

    def test_dropless_beats_capacity_overflow(self):
        """Regression vs the capacity path: route EVERY token to one
        expert — the capacity einsum drops most of them, the grouped path
        drops none (each token keeps its full top-k contribution)."""
        from deepspeed_tpu.moe import grouped_moe_mlp_block, moe_mlp_block
        from deepspeed_tpu.moe.sharded_moe import topk_gating

        class Tight:
            top_k = 1
            capacity_factor = 1.0
            min_capacity = 1

        D, F, E = 16, 32, 4
        w = self._weights(D, F, E, seed=3)
        # a router column so dominant every token picks expert 2
        # (positive activations so the +50 column cannot sign-flip)
        w["router"] = w["router"].at[:, 2].add(50.0)
        h = jax.random.uniform(jax.random.key(5), (1, 32, D), jnp.float32,
                               0.05, 1.0)
        logits = h.reshape(-1, D) @ w["router"]
        _, _, _, stats = topk_gating(logits, k=1, capacity_factor=1.0,
                                     min_capacity=1)
        # capacity cap = S*f/E = 8 of 32 tokens survive the einsum path
        assert float(stats["drop_fraction"]) >= 0.5
        yg, _ = grouped_moe_mlp_block(h, w, Tight)
        yc, _ = moe_mlp_block(h, w, Tight)
        dropped = np.asarray(jnp.sum(jnp.abs(yc), -1) == 0)
        kept_g = np.asarray(jnp.sum(jnp.abs(yg), -1) > 0)
        assert dropped.sum() >= 16          # the einsum really dropped
        assert kept_g.all()                 # the grouped path kept all

    def test_resolve_kernel_and_fallback_warning(self, monkeypatch, caplog):
        """``moe.kernel: ragged`` degrades to padded with exactly ONE
        logged warning when the grouped GEMM cannot lower; bad names are
        rejected; ``padded`` never consults the probe."""
        import logging

        from deepspeed_tpu.moe import sharded_moe as sm

        with pytest.raises(ValueError):
            sm.resolve_moe_kernel("cutlass")
        assert sm.resolve_moe_kernel("padded") == ("padded", "")
        # this host lowers ragged_dot (the probe is memoized)
        assert sm.resolve_moe_kernel("ragged")[0] == "ragged"
        monkeypatch.setattr(sm, "_SUPPORT_MEMO", (None, "forced by test"))
        monkeypatch.setattr(sm, "_FALLBACK_WARNED", False)
        with caplog.at_level(logging.WARNING):
            k1, why1 = sm.resolve_moe_kernel("ragged")
            k2, _ = sm.resolve_moe_kernel("ragged")
        assert (k1, k2) == ("padded", "padded") and why1 == "forced by test"
        warned = [r for r in caplog.records
                  if "falling back" in r.getMessage()]
        assert len(warned) <= 1

    def test_kernel_config_plumbing(self):
        """The knob exists at every layer: MoEConfig validates it, the
        transformer config carries it, the probe reports this backend."""
        from deepspeed_tpu.config.config import MoEConfig
        from deepspeed_tpu.moe import MOE_KERNELS, moe_kernel_support

        assert MoEConfig(kernel="padded").kernel == "padded"
        assert MoEConfig(a2a_bits=8).a2a_bits == 8
        with pytest.raises(Exception):
            MoEConfig(kernel="blocked")
        with pytest.raises(Exception):
            MoEConfig(a2a_bits=3)
        cfg = get_preset("tiny", num_experts=4, moe_kernel="padded",
                         moe_a2a_bits=8, moe_a2a_slice=2)
        assert (cfg.moe_kernel, cfg.moe_a2a_bits, cfg.moe_a2a_slice) == \
            ("padded", 8, 2)
        assert set(MOE_KERNELS) == {"ragged", "padded"}
        mode, why = moe_kernel_support()
        assert mode in (None, "native") and why


# ---------------------------------------------------------------------------
# the grouped dispatch's router: chosen scores, counts and each pair's row
# from compares against arange(experts), held to the gather / bincount /
# scatter forms they replaced (kept here as the references)
# ---------------------------------------------------------------------------

def _placement_by_sort(topk_idx, first, n_held, bound):
    """The placement as it was: ``argsort``, ``bincount``, the rank scatter."""
    S, k = topk_idx.shape
    n = S * k
    local = topk_idx.reshape(-1) - first
    here = (local >= 0) & (local < n_held)
    key = jnp.where(here, local, n_held)
    order = jnp.argsort(key)
    counts = jnp.bincount(key, length=n_held + 1)[:n_held].astype(jnp.int32)
    ends = jnp.minimum(jnp.cumsum(counts), bound)
    group_sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
    n_here = ends[-1]
    rows = order[:bound]
    rank = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    slot = jnp.where(rank < n_here, rank, bound).reshape(S, k)
    return rows, slot, group_sizes, n_here, counts


def _random_picks(seed, S, E, k):
    """[S, k] distinct experts a token, as a top k gives them."""
    scores = jax.random.uniform(jax.random.key(seed), (S, E))
    return jax.lax.top_k(scores, k)[1]


def _softmax_picks_under_a_mask(seed, S, E, k):
    from deepspeed_tpu.moe import sharded_moe as sm

    logits = jax.random.normal(jax.random.key(seed), (S, E))
    valid = jax.random.bernoulli(jax.random.key(seed + 1), 0.6, (S,))
    return sm._route(logits, k, valid=valid)[3]


# name: (picks [S, k], experts, first, held, rows of the buffer)
PLACEMENTS = {
    "every-expert-held": lambda: (_random_picks(0, 64, 8, 2), 8, 0, 8, 128),
    "a-share-from-the-third-on": lambda: (_random_picks(1, 96, 16, 3), 16,
                                          2, 4, 288),
    "the-last-experts-held": lambda: (_random_picks(2, 40, 16, 4), 16, 12,
                                      4, 160),
    "a-buffer-of-twice-the-balanced-load": lambda: (
        _random_picks(3, 256, 16, 3), 16, 4, 4, 384),
    "a-buffer-smaller-than-the-pairs-here": lambda: (
        _random_picks(4, 64, 4, 2), 4, 1, 2, 24),
    "every-pair-to-one-held-expert": lambda: (
        jnp.tile(jnp.array([[5, 9]], jnp.int32), (48, 1)), 16, 4, 4, 96),
    "every-token-to-one-expert-and-a-small-buffer": lambda: (
        jnp.tile(jnp.array([[5, 9]], jnp.int32), (48, 1)), 16, 4, 4, 20),
    "no-pair-here": lambda: (
        jnp.tile(jnp.array([[0, 1, 2]], jnp.int32), (32, 1)), 16, 8, 8, 96),
    "rows-that-fill-no-chunk": lambda: (_random_picks(5, 200, 8, 2), 8, 0,
                                        8, 400),
    "several-chunks-and-a-share": lambda: (_random_picks(6, 520, 32, 6), 32,
                                           8, 8, 1000),
    "four-decode-rows": lambda: (_random_picks(7, 4, 8, 2), 8, 0, 8, 8),
    "one-pick-a-token": lambda: (_random_picks(8, 130, 4, 1), 4, 0, 4, 130),
    "softmax-router-under-a-valid-mask": lambda: (
        _softmax_picks_under_a_mask(9, 72, 8, 2), 8, 0, 8, 144),
    "softmax-router-under-a-mask-a-share-and-a-small-buffer": lambda: (
        _softmax_picks_under_a_mask(11, 72, 8, 2), 8, 2, 4, 60),
}


def _placed(name):
    """A case's picks, weights of a pair each, what :func:`_placement` makes
    of them (jitted) and the buffer's rows."""
    from deepspeed_tpu.moe import sharded_moe as sm

    idx, E, first, held, bound = PLACEMENTS[name]()
    idx = idx.astype(jnp.int32)
    assert idx.max() < E
    weights = jax.random.uniform(jax.random.key(12), idx.shape, jnp.float32,
                                 0.05, 1.0)
    got = jax.jit(lambda idx, weights: sm._placement(
        idx, weights, first, held, bound))(idx, weights)
    return idx, weights, first, held, bound, got


@pytest.mark.parametrize("name", sorted(PLACEMENTS))
def test_placement_by_compares_is_the_sorts(name):
    """``rows`` (the first ``bound`` of ``order``) up to the last pair,
    ``slot``, ``group_sizes``, ``n_here`` and ``counts`` of
    :func:`sharded_moe._placement` (compares against ``arange(held)``, a
    running count) equal the ``argsort`` / ``bincount`` / rank-scatter form's,
    element for element."""
    idx, _, first, held, bound, got = _placed(name)
    order, row_weight, *got = got
    want = _placement_by_sort(idx, first, held, bound)
    n_here = int(want[3])
    assert int(got[2]) == n_here
    assert order.shape == (idx.size,) and row_weight.shape == (bound,)
    np.testing.assert_array_equal(np.sort(order), np.arange(idx.size))
    np.testing.assert_array_equal(order[:n_here], want[0][:n_here])
    for g, w_ in zip(got, want[1:]):
        assert g.dtype == w_.dtype and g.shape == w_.shape
        np.testing.assert_array_equal(g, w_)
    dropped = int(want[4].sum()) - n_here
    assert (dropped > 0) == ("small" in name)
    if name == "no-pair-here":
        assert n_here == 0 and (np.asarray(got[0]) == bound).all()


@pytest.mark.parametrize("one_key", [True, False],
                         ids=["expert-and-pair-one-key", "a-stable-sort"])
@pytest.mark.parametrize("name", sorted(PLACEMENTS))
def test_a_rows_weight_rides_the_sort(name, one_key, monkeypatch):
    """``row_weight`` of :func:`sharded_moe._placement`, the pairs' weights
    carried by the sort that makes ``rows``, is the gather
    ``weights.reshape(-1)[rows]`` to the bit (on every row of the buffer: a
    row past the last pair carries the pair the sort left there), and no
    gradient goes through it; ``order`` is ``argsort``'s, whether expert and
    pair fit one int32 key or, past ``_MOST_KEYS``, the sort is stable on
    the expert."""
    from deepspeed_tpu.moe import sharded_moe as sm

    if not one_key:
        monkeypatch.setattr(sm, "_MOST_KEYS", 0)
    idx, weights, first, held, bound, got = _placed(name)
    order, row_weight = got[:2]
    local = idx.reshape(-1) - first
    np.testing.assert_array_equal(order, jnp.argsort(
        jnp.where((local >= 0) & (local < held), local, held)))
    assert row_weight.dtype == weights.dtype
    np.testing.assert_array_equal(
        row_weight, weights.reshape(-1)[order[:bound]])
    through = jax.grad(lambda w: sm._placement(idx, w, first, held, bound)[1]
                       .sum())(weights)
    assert not np.asarray(through).any()


@pytest.mark.parametrize("name", sorted(PLACEMENTS))
def test_a_scalar_a_row_comes_back_to_its_pair_without_a_gather(name):
    """:func:`sharded_moe._pairs_of_rows` on the placement's own ``order``
    and ``slot`` is ``jnp.take(dot, slot, mode="fill", fill_value=0)``
    element for element, NaN planted in every row past the last pair (the
    rows kernel leaves the tiles past it unwritten); its jaxpr holds a sort
    and no gather."""
    from deepspeed_tpu.moe import sharded_moe as sm

    *_, bound, got = _placed(name)
    order, _, slot, _, n_here, _ = got
    dot = jax.random.normal(jax.random.key(13), (bound,))
    dot = jnp.where(jnp.arange(bound) < n_here, dot, jnp.nan)
    back = jax.jit(sm._pairs_of_rows)(dot, order, slot)
    want = jnp.take(dot, slot, mode="fill", fill_value=0)
    assert back.dtype == want.dtype and np.isfinite(np.asarray(back)).all()
    np.testing.assert_array_equal(back, want)
    assert int((np.asarray(back) != 0).sum()) == int(n_here)
    names = {e.primitive.name for e in jax.make_jaxpr(sm._pairs_of_rows)(
        dot, order, slot).eqns}
    assert "sort" in names and not names & {"gather", "scatter",
                                            "scatter-add", "scatter_add"}


def test_the_running_count_is_exact_at_a_cells_length():
    """16,384 tokens of which every one chose the same expert: the count
    before the last is 16,383 (bf16 operands, float32 sums)."""
    from deepspeed_tpu.moe import sharded_moe as sm

    hot = jnp.stack([jnp.ones((16384,), jnp.int32),
                     jnp.arange(16384, dtype=jnp.int32) % 3 == 0], axis=1)
    got = np.asarray(sm._count_before(hot.astype(jnp.int32)))
    want = np.cumsum(np.asarray(hot, np.int64), axis=0) - np.asarray(hot)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("ties", [False, True])
def test_sigmoid_routers_pick_is_the_gathers_to_the_bit(ties, monkeypatch):
    """Weights, experts, counts, the balance term and ``jax.grad`` with
    respect to the logits of :func:`sharded_moe._route_sigmoid` equal those
    of the router as it was, its chosen scores by ``take_along_axis`` (whose
    transpose is a scatter-add), bit for bit, ties in ``s + bias`` included
    (logits and biases on a coarse grid: many equal scores a token)."""
    from deepspeed_tpu.moe import sharded_moe as sm

    B, T, E, k = 2, 24, 16, 5
    logits = jax.random.normal(jax.random.key(0), (B, T, E))
    bias = 0.1 * jax.random.normal(jax.random.key(1), (E,))
    if ties:
        logits = jnp.round(logits)
        bias = jnp.zeros_like(bias)
    r = jax.random.normal(jax.random.key(2), (B * T, k))

    def objective(logits):
        aux, weights, idx, counts, _ = sm._route_sigmoid(logits, bias, k, 2.5)
        return (weights * r).sum() + 3.0 * aux, (weights, idx, counts, aux)

    got = jax.value_and_grad(objective, has_aux=True)(logits)
    monkeypatch.setattr(sm, "_pick", lambda s, idx, E: jnp.take_along_axis(
        s, idx, axis=-1))
    want = jax.value_and_grad(objective, has_aux=True)(logits)
    if ties:    # the grid makes equal scores inside a token's top k + 1
        s = np.asarray(jax.nn.sigmoid(logits))
        assert any(len(np.unique(row)) < E - k for row in s.reshape(-1, E))
    for g, w_ in zip(jax.tree_util.tree_leaves(got),
                     jax.tree_util.tree_leaves(want)):
        assert g.dtype == w_.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w_))
    assert np.abs(np.asarray(got[1])).sum() > 0


@pytest.mark.parametrize("lowering", ["xla", "kernel"])
def test_softmax_routers_experts_are_lax_top_ks(lowering, monkeypatch):
    """:func:`sharded_moe._route`'s experts and renormalised weights are
    those of ``lax.top_k`` over the gates, bit for bit, through the picker's
    answer off a TPU and through the selection kernel, interpreted (logits
    on a grid: equal gates a token)."""
    import functools

    from deepspeed_tpu.moe import sharded_moe as sm
    from deepspeed_tpu.ops.topk_select import topk_select

    S, E, k = 256, 128, 8
    logits = jnp.round(2 * jax.random.normal(jax.random.key(0), (S, E))) / 2
    if lowering == "kernel":
        monkeypatch.setattr(sm, "topk_select", functools.partial(
            topk_select, interpret=True))
    gates, _, vals, idx = sm._route(logits, k)
    want_vals, want_idx = jax.lax.top_k(gates, k)
    assert any(len(np.unique(row)) < E - k for row in np.asarray(gates))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(
        want_vals / jnp.maximum(want_vals.sum(-1, keepdims=True), 1e-9)))


def test_the_pick_keeps_the_experts_and_not_the_one_hot():
    """What the pick's derivative keeps from the forward is ``idx``: no
    array of tokens x k x experts elements is a residual (92 MB a layer as
    booleans at the Nemotron cell's size, under no recomputation)."""
    from deepspeed_tpu.moe import sharded_moe as sm

    T, E, k = 32, 16, 4
    s = jax.random.uniform(jax.random.key(0), (T, E))
    idx = _random_picks(1, T, E, k)
    _, vjp = jax.vjp(lambda s: sm._pick(s, idx, E), s)
    kept = [x.shape for x in jax.tree_util.tree_leaves(vjp)
            if hasattr(x, "shape")]
    assert all(np.prod(shape) < T * k * E for shape in kept), kept


@pytest.mark.parametrize("masked", [False, True])
def test_softmax_routers_chosen_gates_are_top_ks_to_the_bit(masked):
    """The renormalised weights of :func:`sharded_moe._route` and their
    gradient with respect to the logits equal those taken from
    ``lax.top_k``'s own values (whose transpose is a scatter-add of a scalar
    a pair), bit for bit, with and without a ``valid`` mask."""
    from deepspeed_tpu.moe import sharded_moe as sm

    S, E, k = 40, 8, 3
    logits = jax.random.normal(jax.random.key(0), (S, E))
    valid = jax.random.bernoulli(jax.random.key(1), 0.7, (S,)) \
        if masked else None
    r = jax.random.normal(jax.random.key(2), (S, k))

    def by_top_k(logits):
        gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        vals, idx = jax.lax.top_k(gates, k)
        vals = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)
        if valid is not None:
            vals = vals * valid[:, None].astype(vals.dtype)
        return (vals * r).sum(), (vals, idx)

    def by_compares(logits):
        _, _, vals, idx = sm._route(logits, k, valid=valid)
        return (vals * r).sum(), (vals, idx)

    got, want = (jax.value_and_grad(f, has_aux=True)(logits)
                 for f in (by_compares, by_top_k))
    for g, w_ in zip(jax.tree_util.tree_leaves(got),
                     jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w_))
    assert np.abs(np.asarray(got[1])).sum() > 0
