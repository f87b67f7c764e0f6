"""Aux-subsystem unit tests (pattern: reference ``tests/unit/launcher``,
``tests/unit/elasticity``, ``unit/autotuning``, ``unit/profiling`` — pure-unit,
no device work)."""

import os

import numpy as np
import pytest

from deepspeed_tpu.elasticity import compute_elastic_config, get_compatible_chip_counts
from deepspeed_tpu.launcher.runner import filter_hosts, parse_hostfile


class TestLauncher:
    def test_parse_hostfile(self, tmp_path):
        hf = tmp_path / "hosts"
        hf.write_text("# comment\nworker-0 slots=4\nworker-1 slots=4\n\n")
        hosts = parse_hostfile(str(hf))
        assert hosts == {"worker-0": 4, "worker-1": 4}

    def test_parse_hostfile_duplicate(self, tmp_path):
        hf = tmp_path / "hosts"
        hf.write_text("a slots=2\na slots=4\n")
        with pytest.raises(ValueError, match="duplicate"):
            parse_hostfile(str(hf))

    def test_parse_hostfile_empty(self, tmp_path):
        hf = tmp_path / "hosts"
        hf.write_text("# nothing\n")
        with pytest.raises(ValueError, match="empty"):
            parse_hostfile(str(hf))

    def test_filters(self):
        hosts = {"a": 4, "b": 4, "c": 4}
        assert filter_hosts(hosts, include="a,b") == {"a": 4, "b": 4}
        assert filter_hosts(hosts, exclude="c") == {"a": 4, "b": 4}
        with pytest.raises(ValueError):
            filter_hosts(hosts, include="zzz")

    @pytest.mark.parametrize("name", ["pdsh", "openmpi", "slurm", "mpich",
                                      "impi"])
    def test_multinode_runner_cmds(self, name):
        """Reference multinode_runner.py parity: each transport builds one
        command that fans the script out with the rendezvous env (pure-unit,
        same as tests/unit/launcher's multinode cmd tests)."""
        import argparse

        from deepspeed_tpu.launcher.multinode_runner import RUNNERS

        args = argparse.Namespace(script="train.py", script_args=["--x", "1"],
                                  master_port=29500, slurm_comment="")
        hosts = {"h0": 1, "h1": 1}
        runner = RUNNERS[name](args)
        base_env = {"PYTHONPATH": "/repo", "HOME": "/root"}
        cmd = runner.get_cmd(base_env, hosts)
        joined = " ".join(cmd)
        assert cmd[0] in ("pdsh", "mpirun", "srun", "mpiexec")
        assert "train.py" in joined and "--x" in joined
        if name == "slurm":
            # slurm forwards rendezvous via the srun process env (inline
            # --export K=V cannot carry comma-valued DSTPU_HOSTS) and pins
            # the coordinator to the sorted-first host (= SLURM task 0)
            env = runner.get_env(base_env, hosts)
            assert env["DSTPU_COORDINATOR"] == "h0:29500"
            assert env["DSTPU_HOSTS"] == "h0,h1"
            assert "--ntasks-per-node" in cmd and "--export" in cmd
            assert "ALL" in cmd and "DSTPU_HOSTS" not in joined
        else:
            assert "DSTPU_COORDINATOR" in joined and "h0:29500" in joined
            assert "DSTPU_WORLD_SIZE" in joined
            assert "PYTHONPATH" in joined     # exported prefix forwarded
            assert "HOME" not in joined       # non-exported env NOT forwarded
        if name in ("openmpi", "mpich", "impi"):
            assert "2" in cmd  # one rank per host

    def test_scheduler_rank_discovery(self, monkeypatch):
        """init_distributed reads scheduler-native rank envs (SLURM/OMPI/PMI)
        when the launcher's DSTPU_RANK is absent."""
        import deepspeed_tpu.comm.comm as c

        captured = {}

        def fake_init(**kw):
            captured.update(kw)

        monkeypatch.setattr(c.jax.distributed, "initialize", fake_init)
        monkeypatch.setattr(c, "_initialized", False)
        monkeypatch.setenv("DSTPU_COORDINATOR", "h0:29500")
        monkeypatch.setenv("DSTPU_WORLD_SIZE", "4")
        monkeypatch.setenv("SLURM_PROCID", "3")
        monkeypatch.delenv("DSTPU_RANK", raising=False)
        c.init_distributed()
        assert captured == {"coordinator_address": "h0:29500",
                            "process_id": 3, "num_processes": 4}
        monkeypatch.setattr(c, "_initialized", True)


class TestElasticity:
    def test_compatible_chips(self):
        chips = get_compatible_chip_counts(64, [1, 2, 4], min_chips=1, max_chips=16)
        assert 8 in chips and 16 in chips
        assert all(any(64 % (n * mb) == 0 for mb in [1, 2, 4]) for n in chips)

    def test_elastic_config(self):
        batch, chips, micro = compute_elastic_config({
            "max_train_batch_size": 64,
            "micro_batch_sizes": [1, 2, 4],
            "min_gpus": 1, "max_gpus": 16,
        })
        assert batch <= 64 and len(chips) >= 8
        for n, mb in micro.items():
            assert batch % (n * mb) == 0

    def test_incompatible_world_raises(self):
        with pytest.raises(ValueError, match="not elastic-compatible"):
            compute_elastic_config({
                "max_train_batch_size": 8,
                "micro_batch_sizes": [8],
                "min_gpus": 1, "max_gpus": 4,
            }, target_chips=3)


class TestCompression:
    def test_magnitude_pruning(self):
        import jax

        from deepspeed_tpu.compression import prune_magnitude

        params = {"w": jax.random.normal(jax.random.key(0), (32, 32))}
        pruned = prune_magnitude(params, sparsity=0.5)
        frac = float((np.asarray(pruned["w"]) == 0).mean())
        assert 0.45 <= frac <= 0.55

    def test_ste_quantize_grad_passthrough(self):
        import jax
        import jax.numpy as jnp

        from deepspeed_tpu.compression import ste_quantize

        x = jnp.linspace(-1, 1, 256)
        g = jax.grad(lambda x: (ste_quantize(x) ** 2).sum())(x)
        # straight-through: grad ≈ 2*xq, nonzero, finite
        assert bool(jnp.all(jnp.isfinite(g)))
        assert float(jnp.abs(g).sum()) > 0

    def test_ptq_roundtrip_close(self):
        import jax

        from deepspeed_tpu.compression import quantize_weights_ptq

        params = {"w": jax.random.normal(jax.random.key(1), (64, 64))}
        q = quantize_weights_ptq(params, bits=8)
        err = np.abs(np.asarray(q["w"]) - np.asarray(params["w"])).max()
        assert err < 0.05


class TestEnvReport:
    def test_report_runs(self):
        from deepspeed_tpu.env_report import report

        text = report()
        assert "deepspeed_tpu" in text and "op compatibility" in text


class TestProfiler:
    def test_profile_fn_flops(self):
        import jax.numpy as jnp

        from deepspeed_tpu.profiling import profile_fn

        def f(a, b):
            return a @ b

        stats = profile_fn(f, jnp.ones((64, 64)), jnp.ones((64, 64)))
        # 2*64^3 flops expected (cost analysis may fold, allow wide band)
        assert stats["flops"] > 1e4


class TestAutotuner:
    def test_grid_sweeps_all_axes(self, eight_devices):
        """The tuner enumerates micro-batch x stage x remat x offload (the
        reference tuner's full axis set) and returns the fastest OK trial.
        The smallest grid that shows it (one micro-batch, six engines where
        two made twelve): offload only from stage 1 up, both recomputation
        values at either stage."""
        from deepspeed_tpu.autotuning import Autotuner
        from deepspeed_tpu.models import TransformerLM, TransformerConfig

        def factory(remat_policy="none"):
            return TransformerLM(TransformerConfig(
                vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
                max_seq_len=32, remat_policy=remat_policy))

        tuner = Autotuner(
            factory,
            {"optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
             "mesh": {"dp": 8}, "steps_per_print": 1000},
            micro_batch_candidates=(1,),
            zero_stage_candidates=(0, 1),
            remat_candidates=("none", "full"),
            offload_candidates=(None, "cpu"),
            steps=1,
            make_batch=lambda n: {"input_ids": np.zeros((n, 16), np.int32)})
        best = tuner.tune()
        assert best is not None and best.ok
        axes = {(r.config["micro_batch"], r.config["stage"],
                 r.config["remat"], r.config["offload"])
                for r in tuner.results}
        # offload trials only run at stage >= 1
        assert (1, 1, "none", "cpu") in axes
        assert all(off is None or stage >= 1
                   for (_, stage, _, off) in axes)
        assert {r.config["remat"] for r in tuner.results} == {"none", "full"}
        assert len(axes) == len(tuner.results) == 2 + 4     # stage 0, stage 1


class TestAIOBench:
    def test_sweep(self, tmp_path):
        from deepspeed_tpu.ops.aio_bench import sweep

        res = sweep(str(tmp_path), sizes_mb=[1], threads=[1, 2], repeats=2)
        assert len(res) == 2
        for r in res:
            assert r["write_MBps"] > 0 and r["read_MBps"] > 0


class TestActivationOffload:
    def test_offload_attn_policy(self):
        """FPDT-style host offload: saved attention outputs round-trip through
        pinned host memory; gradients match the no-remat baseline. (Under
        SPMD meshes this policy is TPU-only — the CPU partitioner rejects
        device-placement annotations; single-device covers the math here.)"""
        import jax
        import jax.numpy as jnp
        from jax.ad_checkpoint import checkpoint_name

        from deepspeed_tpu.runtime.activation_checkpointing import (
            POLICIES, checkpoint_wrapper, resolve_policy)

        assert "offload_attn" in POLICIES
        assert resolve_policy("offload_attn") is not None

        def f(w, x):
            h = checkpoint_name(jnp.tanh(x @ w), "flash_attn_out")
            return (h @ w.T).sum()

        w = jax.random.normal(jax.random.key(0), (8, 8))
        x = jax.random.normal(jax.random.key(1), (4, 8))
        g_off = jax.grad(checkpoint_wrapper(f, policy="offload_attn"))(w, x)
        g_ref = jax.grad(f)(w, x)
        np.testing.assert_allclose(np.asarray(g_off), np.asarray(g_ref),
                                   atol=1e-5)


class TestSanityChecks:
    """SURVEY §5.2: the engine-level sanity pass (reference sanity_checks
    config engine.py:1346 + cross-rank asserts zero/utils)."""

    def _engine(self, eight_devices, **extra):
        import deepspeed_tpu as ds
        from deepspeed_tpu.models import TransformerLM, get_preset

        cfg = {"train_micro_batch_size_per_gpu": 2,
               "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
               "zero_optimization": {"stage": 3,
                                     "param_persistence_threshold": 0},
               "mesh": {"fsdp": 8}, "steps_per_print": 100, **extra}
        return ds.initialize(model=TransformerLM(get_preset("tiny")),
                             config=cfg)[0]

    def test_startup_and_first_batch_pass(self, eight_devices):
        eng = self._engine(eight_devices, sanity_checks=True)
        b = {"input_ids": np.random.default_rng(0).integers(0, 256, (16, 16))}
        loss = eng.forward(b)
        eng.backward(loss)
        eng.step()
        assert eng._first_batch_checked
        assert np.isfinite(float(loss))

    def test_param_integrity_catches_nan(self, eight_devices):
        import jax
        import jax.numpy as jnp

        from deepspeed_tpu.runtime.sanity import check_param_integrity

        eng = self._engine(eight_devices)
        # poison one leaf
        eng.params["final_norm"]["scale"] = eng.params["final_norm"][
            "scale"].at[0].set(jnp.nan)
        with pytest.raises(RuntimeError, match="non-finite"):
            check_param_integrity(eng)

    def test_param_placement_catches_mismatch(self, eight_devices):
        import jax

        from deepspeed_tpu.runtime.sanity import check_param_placement

        eng = self._engine(eight_devices)
        check_param_placement(eng)  # sane engine passes
        # replicate a leaf that the engine declared sharded
        from jax.sharding import NamedSharding, PartitionSpec as P

        eng.params["embed"]["tokens"] = jax.device_put(
            eng.params["embed"]["tokens"], NamedSharding(eng.mesh, P()))
        with pytest.raises(RuntimeError, match="placed as"):
            check_param_placement(eng)

    def test_integrity_ignores_integer_leaves(self, eight_devices):
        from deepspeed_tpu.runtime.sanity import check_param_integrity

        eng = self._engine(eight_devices)
        import jax.numpy as jnp

        eng.params["counter"] = jnp.zeros((4,), jnp.int32)
        check_param_integrity(eng)  # must not raise on integer leaves


def test_per_module_profile_attributes_blocks(eight_devices):
    """Round-2 weak #9: the profiler now breaks cost down per named module
    (the reference profiler's 'top modules' view) instead of whole-program
    totals only."""
    import jax

    from deepspeed_tpu.models import TransformerLM, get_preset
    from deepspeed_tpu.profiling import per_module_profile

    model = TransformerLM(get_preset("tiny"))
    params = model.init(jax.random.key(0))
    ids = np.random.default_rng(0).integers(0, 256, (2, 16))
    mods = per_module_profile(lambda p: model.logits(p, ids), params)
    scopes = set(mods)
    assert any(s.startswith("mlp") for s in scopes), scopes
    assert any(s.startswith("attn") for s in scopes), scopes
    assert any("lm_head" in s for s in scopes), scopes
    # the mlp is the FLOPs-heaviest block of a dense decoder layer
    top = next(iter(mods))
    assert top.startswith("mlp"), mods
    assert all(v["gflops"] >= 0 and v["ops"] > 0 for v in mods.values())


class TestExperimentScheduler:
    """Multi-host autotuning scheduler (reference autotuning/scheduler.py):
    experiments fan out over a host pool, failures are recorded not raised,
    and the best config is written back."""

    def test_parallel_scheduling_and_best_writeback(self, tmp_path):
        import json
        import threading

        from deepspeed_tpu.autotuning import ExperimentScheduler

        in_flight, peak = [0], [0]
        lock = threading.Lock()

        def runner(exp, exp_dir):
            with lock:
                in_flight[0] += 1
                peak[0] = max(peak[0], in_flight[0])
            try:
                import time
                time.sleep(0.05)
                if exp.config["mb"] == 3:
                    raise RuntimeError("simulated OOM")
                return float(exp.config["mb"] * 10)
            finally:
                with lock:
                    in_flight[0] -= 1

        sched = ExperimentScheduler(
            [{"mb": m} for m in (1, 2, 3, 4)],
            hosts=["host-a", "host-b"], results_dir=str(tmp_path),
            runner=runner)
        best = sched.run()
        assert best is not None and best.config == {"mb": 4}
        assert peak[0] == 2              # both hosts were busy concurrently
        statuses = {e.config["mb"]: e.status for e in sched.experiments}
        assert statuses[3] == "failed" and statuses[4] == "done"
        with open(tmp_path / "best_config.json") as f:
            assert json.load(f)["config"] == {"mb": 4}

    def test_multi_host_reservations(self, tmp_path):
        from deepspeed_tpu.autotuning import ExperimentScheduler

        seen = []

        def runner(exp, exp_dir):
            seen.append(tuple(sorted(exp.hosts)))
            return 1.0

        sched = ExperimentScheduler(
            [{"i": 0}, {"i": 1}], hosts=["h0", "h1", "h2", "h3"],
            results_dir=str(tmp_path), runner=runner, hosts_per_exp=2)
        assert sched.run() is not None
        assert all(len(h) == 2 for h in seen)
        assert len(set(sum(map(list, seen), []))) == 4  # disjoint host sets
