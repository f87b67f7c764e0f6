"""Fused Pallas paged-decode kernel: parity, fallback, and fused-fence
tests (``inference.decode_kernel``).

The contract under test is EXACTNESS plus dispatch accounting: greedy
decode tokens must be bit-identical between ``decode_kernel='pallas'``
(the fused work-list flash-decode kernel, interpret mode on this CPU
suite) and ``decode_kernel='xla'`` (the dense-gather reference twin) in
fp32 — across ragged lengths, block-boundary prompts, an int8 KV pool,
and speculative verify rounds — and a backend with no Pallas lowering
must fall back to the xla path with ONE logged warning and no behavior
change. fp32 for the same reason as ``test_prefix_cache.py``: a
random-init model's near-tied bf16 logits flip argmax between
numerically-equivalent kernels, which is a test-model artifact.

The fused promote-fence prologue rides along: with the pallas kernel
active, pending tier promotions land inside the next step's dispatch
instead of a standalone donated scatter, counted in ``tier_report()``.
``tools/decode_kernel_drill.py`` is the invariant authority for the
hardware claims; its slow wrappers are at the bottom under the
``pallas`` marker.
"""

import logging
import os

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngineV2
from deepspeed_tpu.models import TransformerLM, get_preset

_TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "tools")


@pytest.fixture(scope="module")
def f32_lm():
    model = TransformerLM(get_preset("tiny", dtype="float32"))
    params = model.init(jax.random.key(0))
    return model, params


def _engine(model, params, **kw):
    base = dict(max_sequences=8, max_seq_len=64, block_size=8)
    base.update(kw)
    return InferenceEngineV2(model, params=params, **base)


def _pair(f32_lm, **kw):
    model, params = f32_lm
    return {kern: _engine(model, params, decode_kernel=kern, **kw)
            for kern in ("pallas", "xla")}


# ---------------------------------------------------------------------------
# selector plumbing: config field, ctor validation, backend probe
# ---------------------------------------------------------------------------

class TestKernelSelection:
    def test_inference_config_field(self):
        from deepspeed_tpu.config.config import InferenceConfig

        assert InferenceConfig().decode_kernel == "pallas"
        assert InferenceConfig(decode_kernel="xla").decode_kernel == "xla"
        with pytest.raises(ValueError, match="decode_kernel"):
            InferenceConfig(decode_kernel="cuda")

    def test_engine_rejects_unknown_kernel(self, f32_lm):
        model, params = f32_lm
        with pytest.raises(ValueError, match="decode_kernel"):
            _engine(model, params, decode_kernel="triton")

    def test_support_probe_on_cpu(self):
        from deepspeed_tpu.ops.paged_attention import decode_kernel_support

        mode, reason = decode_kernel_support()
        assert mode == "interpret" and "CPU" in reason

    def test_ops_reject_unknown_kernel(self):
        from deepspeed_tpu.ops.paged_attention import _check_kernel

        assert _check_kernel("xla") is True
        assert _check_kernel("pallas") is False
        with pytest.raises(ValueError, match="kernel"):
            _check_kernel("cuda")

    def test_engine_resolves_interpret_mode(self, f32_lm):
        model, params = f32_lm
        eng = _engine(model, params, decode_kernel="pallas")
        assert eng.decode_kernel == "pallas"
        assert eng.decode_kernel_mode == "interpret"
        assert eng.spec_stats["fused"] == 1
        eng2 = _engine(model, params, decode_kernel="xla")
        assert eng2.decode_kernel == "xla"
        assert eng2.decode_kernel_mode == "xla"
        assert eng2.spec_stats["fused"] == 0


# ---------------------------------------------------------------------------
# fp32 greedy-token parity: pallas (interpret) vs the xla reference twin
# ---------------------------------------------------------------------------

class TestGreedyParity:
    def test_ragged_and_block_boundary_prompts(self, f32_lm):
        """Ragged prompt lengths including exact block multiples (8, 16 at
        block_size=8): identical greedy tokens through prefill + the fused
        decode scan."""
        engines = _pair(f32_lm)
        rng = np.random.default_rng(3)
        lens = [3, 8, 11, 16, 21]
        prompts = [rng.integers(1, 256, n).astype(np.int32) for n in lens]
        toks = {}
        for kern, eng in engines.items():
            uids = list(range(len(prompts)))
            first = eng.put(uids, prompts)
            starts = [int(np.argmax(first[u])) for u in uids]
            out = eng.decode_batch(uids, starts, steps=6)
            toks[kern] = np.stack([out[u] for u in uids])
            eng.flush(uids)
        np.testing.assert_array_equal(toks["pallas"], toks["xla"])

    def test_single_token_put_steps(self, f32_lm):
        """The 1-token-atom packed put path (latency serving mode) stays
        identical too — it reads the pool through the same kernel."""
        engines = _pair(f32_lm)
        rng = np.random.default_rng(4)
        prompt = rng.integers(1, 256, 11).astype(np.int32)
        logits = {}
        for kern, eng in engines.items():
            r = eng.put([0], [prompt])
            cur = int(np.argmax(r[0]))
            seq = []
            for _ in range(5):
                r = eng.put([0], [np.array([cur], np.int32)])
                cur = int(np.argmax(r[0]))
                seq.append(cur)
            logits[kern] = seq
            eng.flush([0])
        assert logits["pallas"] == logits["xla"]

    def test_int8_kv_pool(self, f32_lm):
        engines = _pair(f32_lm, kv_dtype="int8")
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, 256, 11).astype(np.int32),
                   rng.integers(1, 256, 21).astype(np.int32)]
        toks = {}
        for kern, eng in engines.items():
            first = eng.put([0, 1], prompts)
            starts = [int(np.argmax(first[0])), int(np.argmax(first[1]))]
            out = eng.decode_batch([0, 1], starts, steps=6)
            toks[kern] = np.stack([out[0], out[1]])
            eng.flush([0, 1])
        np.testing.assert_array_equal(toks["pallas"], toks["xla"])

    def test_spec_verify_wide_shape(self, f32_lm):
        """Speculative verify (logits gathered at every draft position —
        the wide-decode shape) through the shared packed step: identical
        emitted tokens, and the verify rounds really ran."""
        spec = {"enabled": True, "ngram": 2, "max_draft": 3,
                "fallback_steps": 2}
        engines = _pair(f32_lm, speculative=spec)
        rng = np.random.default_rng(6)
        rep = np.tile(rng.integers(1, 256, 3), 7).astype(np.int32)
        toks = {}
        for kern, eng in engines.items():
            first = eng.put([0], [rep])
            out = eng.decode_batch([0], [int(np.argmax(first[0]))],
                                   steps=8, speculative=True)
            toks[kern] = [int(t) for t in out[0]]
            assert eng.spec_stats["rounds"] > 0
            eng.flush([0])
        assert toks["pallas"] == toks["xla"]


# ---------------------------------------------------------------------------
# fused promote-fence prologue (tiers demote -> promote -> decode)
# ---------------------------------------------------------------------------

class TestFusedPromoteFence:
    TIERS = {"enabled": True,
             "tiers": {"enabled": True, "host_mb": 8.0}}

    def _roundtrip(self, eng, seed=7):
        """Publish a 3-block shared prefix, demote it, re-attach it on a
        fresh uid (promotions pending), then decode — returns the greedy
        tokens that crossed the promote fence."""
        rng = np.random.default_rng(seed)
        shared = rng.integers(1, 256, 24).astype(np.int32)
        sfx = rng.integers(1, 256, 4).astype(np.int32)
        eng.put([0], [np.concatenate([shared, sfx])])
        eng.flush([0])
        pc = eng.prefix_cache
        pc.evict(pc.evictable_blocks())
        first = eng.put([1], [np.concatenate([shared, sfx])])
        out = eng.decode_batch([1], [int(np.argmax(first[1]))], steps=6)
        eng.flush([1])
        return [int(t) for t in out[1]]

    def test_demote_promote_identical_and_dispatches_saved(self, f32_lm):
        model, params = f32_lm
        toks, reports = {}, {}
        for kern in ("pallas", "xla"):
            eng = _engine(model, params, max_sequences=4, max_seq_len=96,
                          decode_kernel=kern, prefix_cache=self.TIERS)
            toks[kern] = self._roundtrip(eng)
            reports[kern] = eng.tier_report()
            eng.close()
        assert toks["pallas"] == toks["xla"]
        # pallas: the promotions rode a step prologue (>= 1 standalone
        # scatter dispatch saved); xla: the standalone fence ran as before
        assert reports["pallas"]["fused_prologue_dispatches_saved"] >= 1
        assert reports["xla"]["fused_prologue_dispatches_saved"] == 0

    def test_fence_leaves_no_pending_state(self, f32_lm):
        model, params = f32_lm
        eng = _engine(model, params, max_sequences=4, max_seq_len=96,
                      decode_kernel="pallas", prefix_cache=self.TIERS)
        self._roundtrip(eng)
        rep = eng.tier_report()
        assert rep["pending_promotes"] == 0
        assert rep["pending_resumes"] == 0
        alloc = eng.state.allocator
        eng.prefix_cache.clear()
        assert alloc.free_blocks == alloc.num_blocks  # no leaked refs
        eng.close()

    def test_pause_resume_through_fused_prologue(self, f32_lm):
        """A PAUSED request resumed while prefix promotions are pending:
        the resume upload flushes standalone (unwind semantics) and the
        prefix promotions still fuse — tokens identical to the xla path."""
        model, params = f32_lm
        toks = {}
        for kern in ("pallas", "xla"):
            eng = _engine(model, params, max_sequences=4, max_seq_len=96,
                          decode_kernel=kern, prefix_cache=self.TIERS)
            rng = np.random.default_rng(11)
            prompt = rng.integers(1, 256, 19).astype(np.int32)
            r = eng.put([5], [prompt])
            cur = int(np.argmax(r[5]))
            assert eng.pause_request(5)
            assert eng.resume_request(5)
            assert eng.flush_resumes() == []
            out = eng.decode_batch([5], [cur], steps=6)
            toks[kern] = [int(t) for t in out[5]]
            eng.flush([5])
            eng.close()
        assert toks["pallas"] == toks["xla"]


# ---------------------------------------------------------------------------
# fallback: Pallas unavailable -> xla path, one warning, same behavior
# ---------------------------------------------------------------------------

class TestFallback:
    def test_unavailable_backend_falls_back_with_one_warning(
            self, f32_lm, monkeypatch):
        from deepspeed_tpu.ops import paged_attention as pa
        from deepspeed_tpu.utils.logging import logger

        monkeypatch.setattr(
            pa, "decode_kernel_support",
            lambda: (None, "backend 'rocm' has no Pallas TPU lowering"))
        records = []

        class _Cap(logging.Handler):
            def emit(self, r):
                records.append(r)

        cap = _Cap(level=logging.WARNING)
        logger.addHandler(cap)
        try:
            model, params = f32_lm
            eng = _engine(model, params, decode_kernel="pallas")
        finally:
            logger.removeHandler(cap)
        assert eng.decode_kernel == "xla"
        assert eng.decode_kernel_mode == "xla"
        assert "rocm" in eng.decode_kernel_reason
        assert eng.spec_stats["fused"] == 0
        warns = [r for r in records
                 if "decode_kernel" in r.getMessage()]
        assert len(warns) == 1 and warns[0].levelno == logging.WARNING

        # no behavior change: tokens identical to an explicit-xla engine
        rng = np.random.default_rng(8)
        prompt = rng.integers(1, 256, 13).astype(np.int32)
        xeng = _engine(model, params, decode_kernel="xla")
        toks = {}
        for name, e in (("fallback", eng), ("explicit", xeng)):
            first = e.put([0], [prompt])
            out = e.decode_batch([0], [int(np.argmax(first[0]))], steps=6)
            toks[name] = [int(t) for t in out[0]]
            e.flush([0])
        assert toks["fallback"] == toks["explicit"]

    def test_explicit_xla_engine_logs_no_warning(self, f32_lm):
        from deepspeed_tpu.utils.logging import logger

        records = []

        class _Cap(logging.Handler):
            def emit(self, r):
                records.append(r)

        cap = _Cap(level=logging.WARNING)
        logger.addHandler(cap)
        try:
            model, params = f32_lm
            _engine(model, params, decode_kernel="xla")
        finally:
            logger.removeHandler(cap)
        assert not [r for r in records
                    if "decode_kernel" in r.getMessage()]


# ---------------------------------------------------------------------------
# drill wrappers (slow; tools/decode_kernel_drill.py is the authority)
# ---------------------------------------------------------------------------

@pytest.mark.pallas
@pytest.mark.slow
@pytest.mark.parametrize("scenario", ["parity", "fused-fence"])
def test_decode_kernel_drill(scenario):
    import sys

    sys.path.insert(0, _TOOLS)
    from decode_kernel_drill import run_scenario

    verdict = run_scenario(scenario)
    assert verdict["ok"], verdict
