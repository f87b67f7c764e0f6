"""Mesh cost model + autotuner v2 + scaling-harness tests.

Fast tier: enumeration legality/determinism, calibration round-trip,
winner-store semantics, the ``mesh: "auto"`` config path and the Autotuner
engine-lifecycle regression. The ``scaling``+``slow`` wrapper runs a
real tiny 2-world sweep through the harness (the drill CLI
``tools/scaling_drill.py`` is the full-loop authority).
"""

import json
import os
import sys

import numpy as np
import pytest

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "tools")
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)


def _profile(**over):
    from deepspeed_tpu.parallel.cost_model import ModelProfile

    base = dict(n_params=148032, n_layers=2, n_heads=8, n_kv_heads=8,
                hidden=64, vocab=256, seq=64, n_experts=1, top_k=2,
                sp_capable=False)
    base.update(over)
    return ModelProfile(**base)


# ---------------------------------------------------------------------------
# mesh enumeration
# ---------------------------------------------------------------------------
class TestMeshEnumeration:
    def test_factorizations_are_exact_and_legal(self):
        from deepspeed_tpu.parallel.cost_model import enumerate_meshes

        p = _profile()
        for world in (1, 2, 4, 8, 12):
            for m in enumerate_meshes(world, p):
                assert int(np.prod(list(m.values()) or [1])) == world, m
                assert all(v > 1 for v in m.values()), m  # size-1 axes omitted

    def test_divisibility_pruning(self):
        from deepspeed_tpu.parallel.cost_model import enumerate_meshes

        # 8 heads, 2 layers, dense, no sp: tp>8 / pp>2 / ep / sp never appear
        p = _profile()
        meshes = enumerate_meshes(8, p)
        assert {"tp": 8} in meshes and {"fsdp": 8} in meshes
        assert all(m.get("pp", 1) <= 2 for m in meshes)
        assert all("ep" not in m and "sp" not in m for m in meshes)

        # 6 heads: tp must divide 6 AND the device count → tp in {2} at w=8
        p6 = _profile(n_heads=6, n_kv_heads=6, hidden=96)
        assert all(m.get("tp", 1) in (1, 2)
                   for m in enumerate_meshes(8, p6))

        # moe: ep divides the expert count only
        pm = _profile(n_experts=4)
        assert any(m.get("ep") == 4 for m in enumerate_meshes(8, pm))
        assert all(m.get("ep", 1) <= 4 for m in enumerate_meshes(8, pm))

        # sp only for sp-capable models, and it must divide seq and heads
        ps = _profile(sp_capable=True)
        with_sp = [m for m in enumerate_meshes(8, ps) if "sp" in m]
        assert with_sp and all(ps.seq % m["sp"] == 0
                               and ps.n_heads % m["sp"] == 0
                               for m in with_sp)

    def test_deterministic_ordering(self):
        from deepspeed_tpu.parallel.cost_model import enumerate_meshes

        p = _profile(sp_capable=True, n_experts=4)
        a = enumerate_meshes(8, p)
        b = enumerate_meshes(8, p)
        assert a == b
        # canonical MESH_AXES-order sort: stable across processes/hosts
        keys = [tuple(m.get(ax, 1) for ax in
                      ("pp", "dp", "fsdp", "ep", "sp", "tp")) for m in a]
        assert keys == sorted(keys)

    def test_axes_restriction(self):
        from deepspeed_tpu.parallel.cost_model import enumerate_meshes

        p = _profile()
        only = enumerate_meshes(8, p, axes=("dp", "fsdp"))
        assert {"dp": 8} in only and {"fsdp": 8} in only
        assert all(set(m) <= {"dp", "fsdp"} for m in only)


# ---------------------------------------------------------------------------
# cost model: prediction + calibration round-trip
# ---------------------------------------------------------------------------
class TestCostModel:
    def test_volumes_shape_sensitivity(self):
        from deepspeed_tpu.parallel.cost_model import collective_volumes

        p = _profile()
        dp = collective_volumes(p, {"dp": 8}, tokens=1024)
        fsdp = collective_volumes(p, {"fsdp": 8}, zero_stage=3, tokens=1024)
        tp = collective_volumes(p, {"tp": 8}, tokens=128)
        assert dp["ici_bytes"] > 0 and fsdp["ici_bytes"] > 0
        # stage-3 fsdp pays the param gather on top of the grad scatter
        fsdp1 = collective_volumes(p, {"fsdp": 8}, zero_stage=1, tokens=1024)
        assert fsdp["ici_bytes"] > fsdp1["ici_bytes"]
        # tp moves per-layer activations; flops split over the tp group
        assert tp["flops"] == pytest.approx(dp["flops"] * 128 / 1024)
        # pipeline bubble follows (p-1)/(m+p-1)
        pp = collective_volumes(p, {"pp": 2, "fsdp": 4}, zero_stage=3,
                                tokens=1024, micro_batches=2)
        assert pp["bubble_frac"] == pytest.approx(1 / 3)

    def test_quantized_wire_shrinks_fsdp_bytes(self):
        from deepspeed_tpu.parallel.cost_model import collective_volumes

        p = _profile()
        dense = collective_volumes(p, {"fsdp": 8}, zero_stage=3, tokens=512)
        quant = collective_volumes(
            p, {"fsdp": 8}, zero_stage=3, tokens=512,
            zero_pp={"enabled": True, "qwz": True, "qgz": True,
                     "weight_bits": 4, "grad_bits": 8})
        assert quant["ici_bytes"] < 0.5 * dense["ici_bytes"]

    def test_dcn_link_class_from_ici_sizes(self):
        from deepspeed_tpu.parallel.cost_model import collective_volumes

        p = _profile()
        flat = collective_volumes(p, {"fsdp": 8}, zero_stage=3, tokens=512)
        sliced = collective_volumes(p, {"fsdp": 8}, zero_stage=3, tokens=512,
                                    ici_sizes={"fsdp": 4})
        assert flat["dcn_bytes"] == 0
        assert sliced["dcn_bytes"] == flat["ici_bytes"]
        assert sliced["ici_bytes"] == 0

    def test_calibration_round_trip(self):
        """Fit on synthetic curves generated from known link rates →
        recover the rates (the satellite acceptance check)."""
        from deepspeed_tpu.parallel.cost_model import (CostModel,
                                                       LinkBandwidths,
                                                       enumerate_meshes,
                                                       fit_bandwidths)

        p = _profile(sp_capable=True)
        true = LinkBandwidths(flops_per_s=2e11, ici_bytes_per_s=5e9,
                              dcn_bytes_per_s=1e9, overhead_s=2e-3)
        gen = CostModel(true)
        samples = []
        for w in (1, 2, 4, 8):
            for m in enumerate_meshes(w, p):
                # the harness batch law: tokens scale with the dp axes
                tokens = 128 * m.get("dp", 1) * m.get("fsdp", 1)
                for ici in (None, {"fsdp": max(1, m.get("fsdp", 1) // 2)}):
                    pred = gen.predict(p, m, zero_stage=3, tokens=tokens,
                                       ici_sizes=ici)
                    samples.append({
                        "step_s": pred["step_s"], "flops": pred["flops"],
                        "ici_bytes": pred["ici_bytes"],
                        "dcn_bytes": pred["dcn_bytes"],
                        "bubble_frac": pred["bubble_frac"]})
        fit = fit_bandwidths(samples)
        assert fit.calibrated_from == len(samples)
        assert fit.flops_per_s == pytest.approx(true.flops_per_s, rel=0.05)
        assert fit.ici_bytes_per_s == pytest.approx(true.ici_bytes_per_s,
                                                    rel=0.05)
        assert fit.dcn_bytes_per_s == pytest.approx(true.dcn_bytes_per_s,
                                                    rel=0.05)
        assert fit.overhead_s == pytest.approx(true.overhead_s, rel=0.05)

    def test_calibration_degrades_gracefully(self):
        from deepspeed_tpu.parallel.cost_model import (LinkBandwidths,
                                                       fit_bandwidths)

        prior = LinkBandwidths()
        # too little data → the prior comes back untouched
        assert fit_bandwidths([]) == prior
        assert fit_bandwidths([{"step_s": 1.0, "flops": 1.0}]) == prior
        # no DCN variation → DCN keeps the prior, never a fitted zero
        fit = fit_bandwidths([
            {"step_s": 0.1, "flops": 1e10, "ici_bytes": 1e8,
             "dcn_bytes": 0.0, "bubble_frac": 0.0},
            {"step_s": 0.2, "flops": 2e10, "ici_bytes": 3e8,
             "dcn_bytes": 0.0, "bubble_frac": 0.0},
            {"step_s": 0.4, "flops": 4e10, "ici_bytes": 9e8,
             "dcn_bytes": 0.0, "bubble_frac": 0.0},
        ])
        assert fit.dcn_bytes_per_s == prior.dcn_bytes_per_s
        assert fit.ici_bytes_per_s > 0 and fit.flops_per_s > 0

    def test_throughput_ranking_amortizes_overhead(self):
        """Per-step overhead hits a 1-token shape harder than a dp shape
        that amortizes it over 8x tokens — ranking must be by tokens/s,
        not raw step time."""
        from deepspeed_tpu.parallel.cost_model import (CostModel,
                                                       LinkBandwidths)

        p = _profile()
        cm = CostModel(LinkBandwidths(flops_per_s=1e12,
                                      ici_bytes_per_s=1e11,
                                      overhead_s=5e-3))
        tp = cm.predict_throughput(p, {"tp": 8}, micro_batch=2)
        dp = cm.predict_throughput(p, {"dp": 8}, micro_batch=2)
        assert tp["step_s"] < dp["step_s"]          # fewer tokens per step
        assert dp["tokens_per_sec"] > tp["tokens_per_sec"]
        ranked = cm.rank_by_throughput(p, [{"tp": 8}, {"dp": 8}],
                                       micro_batch=2)
        assert ranked[0][0] == {"dp": 8}

    def test_mesh_auto_ranks_by_the_default_rates_and_opens_no_file(
            self, tmp_path, monkeypatch, eight_devices):
        """With no cost model given, ``mesh: "auto"``'s resolution and the
        autotuner's mesh axis rank by ``CostModel()``, and read nothing
        outside the winner store they were pointed at."""
        import builtins

        from deepspeed_tpu.autotuning import Autotuner
        from deepspeed_tpu.autotuning.mesh_store import resolve_auto_axis_sizes
        from deepspeed_tpu.models import TransformerLM, get_preset
        from deepspeed_tpu.parallel.cost_model import (CostModel, ModelProfile,
                                                       enumerate_meshes)

        real_open = builtins.open
        # its own directory, and the run's compile cache (tests/conftest.py)
        mine = (str(tmp_path), os.environ.get("JAX_COMPILATION_CACHE_DIR", str(tmp_path)))

        def fenced(path, *a, **kw):
            if not str(path).startswith(mine):
                raise AssertionError(f"opened {path}")
            return real_open(path, *a, **kw)

        monkeypatch.setattr(builtins, "open", fenced)
        p = _profile()
        want = CostModel().rank_by_throughput(
            p, enumerate_meshes(8, p), zero_stage=3, micro_batch=2)
        got = resolve_auto_axis_sizes(8, p, zero_stage=3, micro_batch=2,
                                      winner_cache=str(tmp_path / "w.json"))
        assert got == (want[0][0] or {"dp": 8})
        assert CostModel().bw.calibrated_from == 0

        tuner = Autotuner(lambda: TransformerLM(get_preset("tiny")), {},
                          micro_batch_candidates=(2,),
                          zero_stage_candidates=(3,), mesh_candidates="auto",
                          mesh_top_k=3, make_batch=lambda n: None)
        prof = ModelProfile.from_model(TransformerLM(get_preset("tiny")))
        want = CostModel().rank_by_throughput(
            prof, enumerate_meshes(8, prof), zero_stage=3, micro_batch=2)
        assert tuner._resolved_mesh_candidates() == [m for m, _ in want[:3]]


# ---------------------------------------------------------------------------
# winner store + mesh:"auto" resolution
# ---------------------------------------------------------------------------
class TestWinnerStore:
    def test_round_trip_and_atomicity(self, tmp_path):
        from deepspeed_tpu.autotuning.mesh_store import WinnerStore

        store = WinnerStore(str(tmp_path / "w.json"))
        assert store.get("sig", 8, "cpu") is None
        store.put("sig", 8, "cpu", {"fsdp": 4, "dp": 2, "tp": 1}, 99.5)
        rec = store.get("sig", 8, "cpu")
        assert rec["mesh"] == {"fsdp": 4, "dp": 2}   # size-1 axes dropped
        assert rec["metric"] == 99.5
        # other keys stay distinct — including the zero stage: a shape
        # tuned under stage-3 fsdp gathers must not leak into stage 0
        assert store.get("sig", 4, "cpu") is None
        assert store.get("sig", 8, "tpu v5e") is None
        assert store.get("sig", 8, "cpu", zero_stage=3) is None
        # corrupt store file → treated as empty, not a crash
        (tmp_path / "w.json").write_text("{not json")
        assert store.get("sig", 8, "cpu") is None
        store.put("sig", 8, "cpu", {"tp": 2}, 1.0)
        assert store.get("sig", 8, "cpu")["mesh"] == {"tp": 2}

    def test_resolution_ladder(self, tmp_path, eight_devices):
        from deepspeed_tpu.autotuning.mesh_store import (
            WinnerStore, device_kind, resolve_auto_axis_sizes)
        from deepspeed_tpu.parallel.cost_model import model_signature

        path = str(tmp_path / "w.json")
        p = _profile()
        # miss → cost-model prediction (a legal factorization of 8)
        got = resolve_auto_axis_sizes(8, p, winner_cache=path)
        assert int(np.prod(list(got.values()))) == 8
        # hit → the measured winner verbatim
        WinnerStore(path).put(model_signature(p), 8, device_kind(),
                              {"fsdp": 8}, 50.0)
        assert resolve_auto_axis_sizes(8, p, winner_cache=path) == \
            {"fsdp": 8}
        # no profile → all-dp fallback
        assert resolve_auto_axis_sizes(8, None, winner_cache=path) == \
            {"dp": 8}
        assert resolve_auto_axis_sizes(1, p) == {"dp": 1}


class TestMeshAutoConfig:
    def test_mesh_auto_spelling(self):
        from deepspeed_tpu.config import from_config

        cfg = from_config({"train_micro_batch_size_per_gpu": 1,
                           "mesh": "auto"})
        assert cfg.mesh.auto is True
        cfg2 = from_config({"train_micro_batch_size_per_gpu": 1,
                            "mesh": {"auto": True}})
        assert cfg2.mesh.auto is True
        assert from_config({"train_micro_batch_size_per_gpu": 1}) \
            .mesh.auto is False

    def test_auto_rejects_explicit_sizes(self):
        from deepspeed_tpu.config import from_config

        with pytest.raises(Exception, match="mutually exclusive"):
            from_config({"train_micro_batch_size_per_gpu": 1,
                         "mesh": {"auto": True, "fsdp": 4}})

    def test_auto_rejects_multi_slice(self):
        # auto resolution returns flat axis sizes; silently dropping the
        # DCN slice factoring must be a loud error, not a slow run
        from deepspeed_tpu.config import from_config

        with pytest.raises(Exception, match="multi-slice"):
            from_config({"train_micro_batch_size_per_gpu": 1,
                         "mesh": {"auto": True, "num_slices": 2}})

    def test_autotuning_section_validation(self):
        from deepspeed_tpu.config import from_config

        cfg = from_config({"train_micro_batch_size_per_gpu": 1,
                           "autotuning": {"top_k": 3,
                                          "winner_cache": "/tmp/x.json"}})
        assert cfg.autotuning.top_k == 3
        with pytest.raises(Exception):
            from_config({"train_micro_batch_size_per_gpu": 1,
                         "autotuning": {"top_k": 0}})
        with pytest.raises(Exception):
            from_config({"train_micro_batch_size_per_gpu": 1,
                         "autotuning": {"mesh_axes": ["dp", "bogus"]}})

    def test_engine_adopts_winner(self, tmp_path, eight_devices):
        """mesh:'auto' + a persisted winner → the engine builds that mesh
        (the build_mesh wiring, end to end on a real engine)."""
        import deepspeed_tpu as ds
        from deepspeed_tpu.autotuning.mesh_store import (WinnerStore,
                                                         device_kind)
        from deepspeed_tpu.models import TransformerLM, get_preset
        from deepspeed_tpu.parallel.cost_model import (ModelProfile,
                                                       model_signature)

        path = str(tmp_path / "w.json")
        model = TransformerLM(get_preset("tiny"))
        sig = model_signature(ModelProfile.from_model(model))
        WinnerStore(path).put(sig, 8, device_kind(), {"fsdp": 4, "dp": 2},
                              10.0, zero_stage=3)
        eng = None
        try:
            eng, *_ = ds.initialize(model=model, config={
                "train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3,
                                      "param_persistence_threshold": 0},
                "mesh": "auto", "autotuning": {"winner_cache": path},
                "steps_per_print": 10 ** 9})
            assert eng.topology.axis_sizes["fsdp"] == 4
            assert eng.topology.axis_sizes["dp"] == 2
            loss = eng.fused_train_step(
                {"input_ids": np.zeros((8, 16), np.int32)})
            assert np.isfinite(float(loss))
        finally:
            if eng is not None:
                eng.shutdown()


# ---------------------------------------------------------------------------
# autotuner v2: engine lifecycle + mesh axis
# ---------------------------------------------------------------------------
class _FakeLoss:
    def block_until_ready(self):
        return self


class _FakeEngine:
    def __init__(self, fail, shutdowns):
        self._fail = fail
        self._shutdowns = shutdowns

    @property
    def topology(self):
        return type("T", (), {"dp_world_size": 1})()

    def fused_train_step(self, batch):
        if self._fail:
            raise RuntimeError("simulated OOM")
        return _FakeLoss()

    def train_batch_size(self):
        return 2

    def shutdown(self):
        self._shutdowns.append(self._fail)


class TestAutotunerLifecycle:
    def test_every_trial_engine_is_shut_down(self, monkeypatch):
        """Regression: _run_trial leaked engines on BOTH paths — worker
        threads and buffers accumulated across grid trials and skewed
        later timings. Success and failure must both shut down."""
        import deepspeed_tpu as ds
        from deepspeed_tpu.autotuning import Autotuner

        shutdowns = []
        calls = {"n": 0}

        def fake_initialize(model=None, config=None, **kw):
            calls["n"] += 1
            # second trial's step fails (stage 1 in the grid below)
            return (_FakeEngine(fail=config["zero_optimization"]["stage"] == 1,
                                shutdowns=shutdowns), None, None, None)

        monkeypatch.setattr(ds, "initialize", fake_initialize)
        tuner = Autotuner(lambda: object(), {},
                          micro_batch_candidates=(2,),
                          zero_stage_candidates=(0, 1), steps=1,
                          make_batch=lambda n: {"x": np.zeros((n, 4))})
        best = tuner.tune()
        assert best is not None and best.ok
        assert calls["n"] == 2
        # one shutdown per built engine, including the failed trial
        assert sorted(shutdowns) == [False, True]
        failed = [r for r in tuner.results if not r.ok]
        assert len(failed) == 1 and "simulated OOM" in failed[0].error

    def test_mesh_axis_rides_the_grid(self, monkeypatch):
        import deepspeed_tpu as ds
        from deepspeed_tpu.autotuning import Autotuner

        seen = []

        def fake_initialize(model=None, config=None, **kw):
            seen.append(config.get("mesh"))
            return (_FakeEngine(False, []), None, None, None)

        monkeypatch.setattr(ds, "initialize", fake_initialize)
        tuner = Autotuner(lambda: object(), {},
                          micro_batch_candidates=(1,),
                          zero_stage_candidates=(3,),
                          mesh_candidates=[{"fsdp": 8}, {"dp": 8}], steps=1,
                          make_batch=lambda n: {"x": np.zeros((n, 4))})
        best = tuner.tune()
        assert best is not None and best.config["mesh"] in (
            {"fsdp": 8}, {"dp": 8})
        assert seen == [{"fsdp": 8}, {"dp": 8}]

    def test_search_shape_defaults_from_autotuning_config(self):
        from deepspeed_tpu.autotuning import Autotuner

        tuner = Autotuner(lambda: object(), {
            "autotuning": {"top_k": 5, "measure_steps": 7,
                           "mesh_axes": ["dp", "tp"],
                           "winner_cache": "/tmp/wc.json"}},
            make_batch=lambda n: None)
        assert tuner.mesh_top_k == 5 and tuner.steps == 7
        assert tuner.mesh_axes == ("dp", "tp")
        assert tuner._winner_cache == "/tmp/wc.json"
        # explicit constructor args still win
        t2 = Autotuner(lambda: object(),
                       {"autotuning": {"top_k": 5, "measure_steps": 7}},
                       mesh_top_k=1, steps=2, make_batch=lambda n: None)
        assert t2.mesh_top_k == 1 and t2.steps == 2

    def test_winner_persisted_for_mesh_trials(self, monkeypatch, tmp_path):
        import deepspeed_tpu as ds
        from deepspeed_tpu.autotuning import Autotuner, WinnerStore
        from deepspeed_tpu.models import TransformerLM, get_preset

        def fake_initialize(model=None, config=None, **kw):
            return (_FakeEngine(False, []), None, None, None)

        monkeypatch.setattr(ds, "initialize", fake_initialize)
        store = WinnerStore(str(tmp_path / "w.json"))
        tuner = Autotuner(lambda **kw: TransformerLM(get_preset("tiny")),
                          {}, micro_batch_candidates=(1,),
                          zero_stage_candidates=(3,),
                          mesh_candidates=[{"fsdp": 8}], steps=1,
                          winner_store=store,
                          make_batch=lambda n: {"x": np.zeros((n, 4))})
        best = tuner.tune()
        assert best is not None
        data = json.loads((tmp_path / "w.json").read_text())
        recs = list(data["winners"].values())
        assert len(recs) == 1 and recs[0]["mesh"] == {"fsdp": 8}


# ---------------------------------------------------------------------------
# scheduler best-config write-back (satellite coverage)
# ---------------------------------------------------------------------------
class TestSchedulerWriteback:
    def test_best_file_schema_and_failed_runs_excluded(self, tmp_path):
        from deepspeed_tpu.autotuning import ExperimentScheduler

        def runner(exp, exp_dir):
            if exp.config["mesh"] == {"tp": 8}:
                raise RuntimeError("compile failed")
            return 10.0 * exp.config["mesh"].get("fsdp", 1)

        sched = ExperimentScheduler(
            [{"mesh": {"fsdp": 8}}, {"mesh": {"tp": 8}},
             {"mesh": {"dp": 8}}],
            hosts=["h0"], results_dir=str(tmp_path), runner=runner)
        best = sched.run()
        assert best is not None and best.config == {"mesh": {"fsdp": 8}}
        with open(tmp_path / "best_config.json") as f:
            doc = json.load(f)
        assert doc["config"] == {"mesh": {"fsdp": 8}}
        assert doc["metric"] == 80.0 and doc["exp_id"] == best.exp_id

    def test_no_writeback_when_everything_fails(self, tmp_path):
        from deepspeed_tpu.autotuning import ExperimentScheduler

        def runner(exp, exp_dir):
            raise RuntimeError("boom")

        sched = ExperimentScheduler([{"i": 0}, {"i": 1}], hosts=["h0"],
                                    results_dir=str(tmp_path), runner=runner)
        assert sched.run() is None
        assert not (tmp_path / "best_config.json").exists()


# ---------------------------------------------------------------------------
# the real thing (slow): a tiny 2-world sweep through the harness
# ---------------------------------------------------------------------------
@pytest.mark.scaling
@pytest.mark.slow
def test_tiny_two_world_sweep(eight_devices):
    from deepspeed_tpu.autotuning.scaling import run_sweep

    res = run_sweep(worlds=(1, 2), shapes=("dp", "fsdp"), steps=2)
    assert not res["failures"], res["failures"]
    curves = res["curves"][res["device"]]     # device-scoped series
    assert set(curves) == {"dp", "fsdp"}
    for name, pts in curves.items():
        pt = pts["w2"]
        assert pt["tokens_per_sec_per_chip"] > 0
        assert 0 < pt["parallel_efficiency"] < 10
    # the explicit-collective fsdp shape logged real wire bytes
    assert curves["fsdp"]["w2"]["comm_bytes_per_step"].get(
        "reduce_scatter", 0) > 0
    assert res["calibration"]["calibrated_from"] >= 3


@pytest.mark.scaling
@pytest.mark.slow
def test_drill_store_scenario(eight_devices):
    sys.path.insert(0, TOOLS)
    import scaling_drill

    verdict = scaling_drill.run_scenario("store")
    assert verdict["ok"], verdict
