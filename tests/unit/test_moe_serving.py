"""Expert-parallel MoE serving: engine wiring of the dropless grouped
path, AutoEP load balancing (tracker -> planner -> placement swap), the
quantized a2a byte accounting, and the ``moe_a2a_error`` fault site.

The bit-identity contracts asserted here are the PR's acceptance
criteria: greedy decode output is invariant to (a) the grouped kernel
choice, (b) expert-parallel width, and (c) an applied rebalance.

Slow wrappers at the bottom delegate to ``tools/serve_drill.py
--scenario moe-storm`` and ``tools/comm_drill.py --scenario moe-a2a``
(markers: ``moe`` + ``slow``).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import TransformerLM, get_preset

pytestmark = pytest.mark.moe

_TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "tools")

_FP32 = {"dtype": "float32", "param_dtype": "float32"}


def _engine(E=4, top_k=2, mesh=None, decode_kernel="xla", **kw):
    """What these cases test is the experts' dispatch, so attention is the
    XLA twin's; the first case's sharded engine keeps the kernel
    (interpreted here)."""
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2

    return InferenceEngineV2(
        TransformerLM(get_preset("tiny", num_experts=E, top_k=top_k,
                                 moe_dispatch="grouped", **_FP32)),
        max_sequences=8, max_seq_len=128, block_size=16, mesh=mesh,
        decode_kernel=decode_kernel, **kw)


def _greedy(eng, prompt, n=8):
    r = eng.put([7], [np.asarray(prompt, np.int32)])
    first = int(np.argmax(np.asarray(r[7], np.float32)))
    out = eng.decode_batch([7], [first], steps=n)
    eng.flush([7])
    return [first] + [int(t) for t in out[7]]


class TestEngineExpertParallel:
    def test_ep_decode_matches_single_device(self, eight_devices):
        """fp32 greedy decode through the ep=4 sharded a2a dispatch is
        IDENTICAL to the unsharded engine — dropless means expert
        parallelism is a pure layout choice."""
        prompt = np.random.default_rng(0).integers(0, 250, 16)
        ref = _greedy(_engine(), prompt)
        ep = _engine(mesh={"ep": 4, "dp": 2}, decode_kernel="pallas")
        assert ep._moe_ep and ep.moe_kernel in ("ragged", "padded")
        assert _greedy(ep, prompt) == ref

    def test_ep_kernel_choice_is_invisible(self, eight_devices):
        """ragged vs padded under ep>1: same greedy tokens."""
        prompt = np.random.default_rng(1).integers(0, 250, 16)
        a = _greedy(_engine(mesh={"ep": 4, "dp": 2},
                            moe_kernel="ragged"), prompt)
        b = _greedy(_engine(mesh={"ep": 4, "dp": 2},
                            moe_kernel="padded"), prompt)
        assert a == b

    def test_rebalance_preserves_greedy(self, eight_devices):
        """An applied AutoEP rebalance (hot expert replicated onto spare
        slots, experts moved between shards) leaves greedy decode output
        bit-identical, and the planner's LPT bound holds."""
        from deepspeed_tpu.observability import MetricsRegistry

        eng = _engine(mesh={"ep": 4, "dp": 2}, moe_replica_slots=1)
        eng.enable_metrics(registry=MetricsRegistry())
        prompt = np.random.default_rng(2).integers(0, 250, 16)
        before = _greedy(eng, prompt)
        plan = eng.rebalance_moe(counts=[1000, 10, 10, 10])
        assert plan is not None and plan.moved_slots > 0
        assert plan.nrep[0] > 1                       # hot expert replicated
        assert plan.imbalance_after <= plan.bound + 1e-9
        assert plan.imbalance_after < plan.imbalance_before
        assert _greedy(eng, prompt) == before
        # second swap (back toward uniform) keeps the contract too
        eng.rebalance_moe(counts=[10, 10, 1000, 10])
        assert _greedy(eng, prompt) == before

    def test_expert_metrics_prometheus(self, eight_devices):
        """Per-expert token counters and the imbalance gauge land in the
        Prometheus exposition under the ``moe/`` namespace and the shard
        counts sum to tokens * top_k."""
        from deepspeed_tpu.moe import set_expert_tracker
        from deepspeed_tpu.observability import MetricsRegistry

        reg = MetricsRegistry()
        eng = _engine(mesh={"ep": 4, "dp": 2})
        eng.enable_metrics(registry=reg)
        try:
            prompt = np.random.default_rng(3).integers(0, 250, 16)
            _greedy(eng, prompt, n=4)
            counts = eng._moe_tracker.snapshot()
            # prefill 16 + 4 decode steps, top_k=2 (>= — retraces replay)
            assert counts.sum() >= (16 + 4) * 2
            text = reg.render_prometheus()
            assert 'moe_expert_tokens_total{expert="0"}' in text
            assert "moe_imbalance" in text
            assert eng._moe_tracker.imbalance() >= 1.0
        finally:
            set_expert_tracker(None)


class TestBalancerUnits:
    def test_plan_properties(self):
        from deepspeed_tpu.moe import plan_rebalance

        plan = plan_rebalance([900, 50, 30, 20], ep=4, slots_per_shard=2)
        assert sum(plan.nrep) == 8 and len(plan.assign) == 8
        assert plan.nrep[0] == 5                      # hot expert replicated
        assert set(plan.assign) == {0, 1, 2, 3}       # nobody evicted
        assert plan.imbalance_after <= plan.bound + 1e-9
        # replanning from the SAME counts and placement is a no-op
        again = plan_rebalance([900, 50, 30, 20], ep=4, slots_per_shard=2,
                               prev_assign=plan.assign)
        assert again.moved_slots == 0
        # uniform load wants no replication
        flat = plan_rebalance([100] * 8, ep=4, slots_per_shard=2)
        assert flat.nrep == [1] * 8 and flat.imbalance_after == 1.0

    def test_placement_tables_and_apply(self):
        from deepspeed_tpu.moe import apply_placement, placement_tables

        assign = [0, 1, 0, 2]                         # expert 0 on both shards
        t = placement_tables(assign, num_experts=3, ep=2)
        assert t["place_nrep"].tolist() == [2, 1, 1]
        # expert 0's replicas live at (shard 0, slot 0) and (shard 1, slot 0)
        assert sorted(zip(t["place_dest"][0].tolist()[:2],
                          t["place_slot"][0].tolist()[:2])) == [(0, 0), (1, 0)]
        w = {"router": jnp.arange(6.0).reshape(2, 3),
             "w_up": jnp.arange(12.0).reshape(3, 4)}
        out = apply_placement(w, assign, num_experts=3, ep=2)
        # slot layout [0, 1, 0, 2]: expert 0 duplicated, router untouched
        np.testing.assert_array_equal(np.asarray(out["w_up"]),
                                      np.asarray(w["w_up"])[[0, 1, 0, 2]])
        np.testing.assert_array_equal(np.asarray(out["router"]),
                                      np.asarray(w["router"]))
        assert out["place_nrep"].tolist() == [2, 1, 1]

    def test_tracker_window(self):
        from deepspeed_tpu.moe import ExpertLoadTracker

        tr = ExpertLoadTracker(4)
        tr.observe(np.array([8, 0, 0, 0]))
        tr.observe(np.array([0, 8, 0, 0]))
        assert tr.snapshot().tolist() == [8, 8, 0, 0]
        assert tr.imbalance() == pytest.approx(2.0)
        tr.reset()
        assert tr.snapshot().sum() == 0 and tr.imbalance() == 1.0


class TestA2ABytes:
    def test_wire_bytes_formula(self):
        import sys

        from deepspeed_tpu.comm import quantized as cq

        del sys  # idiom guard
        # dense single-hop bf16: ep chunks of chunk_elems * 2 bytes
        assert cq.moe_a2a_wire_bytes(8, 512)["all_to_all"] == 8 * 512 * 2
        # int8 shrinks the payload; the scale lanes keep it > 1/2
        q = cq.moe_a2a_wire_bytes(8, 512, bits=8, block_size=128)
        assert 8 * 512 * 1 <= q["all_to_all"] < 8 * 512 * 2
        # two-hop (slice_size=2): cross hop carries m=4 super-chunks,
        # intra hop stays dense bf16
        t = cq.moe_a2a_wire_bytes(8, 512, bits=8, block_size=128,
                                  slice_size=2)
        assert t["all_to_all_intra"] == 8 * 512 * 2
        # the quantized payload crossing slices is byte-for-byte the same
        # volume, carried in m=4 larger messages instead of ep=8 small ones
        assert t["all_to_all"] == q["all_to_all"]

    def test_cost_model_moe_a2a(self):
        from deepspeed_tpu.parallel.cost_model import moe_a2a_bytes

        # whole group inside one slice -> pure ICI
        ici = moe_a2a_bytes(128, 64, 2, ep=8, ici_size=8)
        assert ici["dcn"] == 0 and ici["ici"] > 0
        # group spans slices -> the cross hop rides DCN
        spl = moe_a2a_bytes(128, 64, 2, ep=8, ici_size=2)
        assert spl["dcn"] > 0 and spl["ici"] > 0
        # int8 wire cuts the DCN share, never the intra-slice hop
        q = moe_a2a_bytes(128, 64, 2, ep=8, ici_size=2, quant_bits=8)
        assert q["dcn"] < spl["dcn"] and q["ici"] == spl["ici"]

    def test_enumerate_meshes_ranks_ep(self):
        """The mesh enumerator prices the a2a for expert-sharded shapes
        (an ep axis must not be free — or autotuning would always pick
        it)."""
        from deepspeed_tpu.parallel.cost_model import (ModelProfile,
                                                       collective_volumes)

        prof = ModelProfile(n_params=int(1e8), n_layers=2, n_heads=4,
                            n_kv_heads=4, hidden=64, vocab=256, seq=128,
                            n_experts=8, top_k=2)
        vol = collective_volumes(prof, {"ep": 8}, tokens=1024)
        assert vol["per_axis"].get("ep", 0) > 0


class TestFaultSite:
    def test_on_moe_dispatch_site_pinning(self):
        from deepspeed_tpu.resilience.faults import (FaultInjector,
                                                     InjectedIOError)

        inj = FaultInjector([{"kind": "moe_a2a_error", "times": 1,
                              "site": "decode"}])
        inj.on_moe_dispatch("prefill")                # pinned: no fire
        with pytest.raises(InjectedIOError):
            inj.on_moe_dispatch("decode")
        inj.on_moe_dispatch("decode")                 # budget spent
        assert inj.fired == ["moe_a2a_error@moe_a2a:decode:step=-1"]


# ---------------------------------------------------------------------------
# drill wrappers (slow): the scenario CLIs are the authority
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_moe_storm_drill(tmp_path):
    """serve_drill moe-storm: skewed-router storm + mid-dispatch a2a
    faults -> zero token loss, bounded rebalance, identical greedy across
    the swap, pool restored."""
    import sys

    sys.path.insert(0, _TOOLS)
    from serve_drill import run_scenario

    verdict = run_scenario("moe-storm", workdir=str(tmp_path))
    assert verdict["ok"], verdict


@pytest.mark.slow
def test_moe_a2a_comm_drill(eight_devices):
    """comm_drill moe-a2a: traced wire bytes of the (quantized,
    hierarchical) expert a2a match the analytic payload exactly."""
    import sys

    sys.path.insert(0, _TOOLS)
    from comm_drill import run_scenario

    verdict = run_scenario("moe-a2a")
    assert verdict["ok"], verdict
