"""``chip_smoke.py`` off the chip: a CPU run is never a pass, the phase
functions' control flow runs to the end at toy size, the parent stays off JAX;
and the compile-cache helper places the cache from outside or at one fixed
path. Every case is a child process (``JAX_PLATFORMS=cpu``, persistent cache
off) so nothing here shares JAX state with the rest of the suite."""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")


def _run(args, cwd=_REPO, timeout=600, **env):
    full = {**os.environ, "JAX_PLATFORMS": "cpu",
            "JAX_ENABLE_COMPILATION_CACHE": "false"}
    full.pop("XLA_FLAGS", None)      # one CPU device, like one chip
    for k, v in env.items():         # None = unset
        full.pop(k, None) if v is None else full.update({k: v})
    return subprocess.run([sys.executable] + args, cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=timeout)


def test_cpu_run_is_never_a_pass():
    r = _run([_SMOKE, "--toy"])
    assert r.returncode != 0
    last = r.stdout.strip().splitlines()[-1]
    assert json.loads(last).get("ok") is not True
    assert "no TPU" in last


@pytest.mark.parametrize("phase,devices", [("train", 1), ("serve", 1),
                                           ("sharded", 4)])
def test_phase_runs_to_the_end_at_toy_size(phase, devices):
    """The phase function itself (not the entry that refuses a CPU), with
    Pallas in interpret mode: loss falls; every request is answered."""
    code = (
        "import json, sys; sys.path.insert(0, %r); import chip_smoke; "
        "print(json.dumps(chip_smoke.PHASES[%r](seed=1, toy=True)))"
        % (_REPO, phase))
    r = _run(["-c", code], XLA_FLAGS=(
        f"--xla_force_host_platform_device_count={devices}"))
    assert r.returncode == 0, r.stderr[-2000:]
    facts = json.loads(r.stdout.strip().splitlines()[-1])
    assert facts["ok"], facts["problems"]
    assert facts["device"] == {"platform": "cpu", "kind": "cpu",
                               "count": devices}
    if phase == "train":
        assert facts["losses"][-1] < facts["losses"][0]
        assert facts["recompiles_after_first_step"] == 0
    elif phase == "serve":
        assert all(q["state"] == "completed" for q in facts["requests"])
        assert facts["max_in_flight"] >= 2
        assert facts["decode_kernel_mode"] == "interpret"
    else:
        assert facts["losses_full_depth"][-1] < facts["losses_full_depth"][0]
        assert max(facts["loss_abs_diffs"]) < 0.02


def test_parent_never_imports_jax():
    code = (
        "import sys; sys.path.insert(0, %r); import chip_smoke; "
        "rc = chip_smoke.main(['--toy']); "
        "bad = [m for m in ('jax', 'jaxlib', 'deepspeed_tpu', 'numpy') "
        "if m in sys.modules]; print('RC', rc, 'BAD', bad)" % _REPO)
    r = _run(["-c", code])
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == "RC 1 BAD []"


def test_compile_cache_is_placed_from_outside_or_at_one_fixed_path(tmp_path):
    probe = (
        "import json, sys; sys.path.insert(0, %r); import jax; "
        "from deepspeed_tpu.utils.compile_cache import place_compile_cache; "
        "before = jax.config.jax_compilation_cache_dir; "
        "p = place_compile_cache(); "
        "print(json.dumps([before, p, jax.config.jax_compilation_cache_dir]))"
        % _REPO)
    outside = str(tmp_path / "elsewhere")
    r = _run(["-c", probe], cwd=str(tmp_path),
             JAX_COMPILATION_CACHE_DIR=outside)
    assert r.returncode == 0, r.stderr[-2000:]
    before, placed, after = json.loads(r.stdout.strip().splitlines()[-1])
    # the variable is set: JAX read it, our code set no directory
    assert before == outside and placed == outside and after == outside
    seen = []
    for cwd in (str(tmp_path), _REPO):
        r = _run(["-c", probe], cwd=cwd, JAX_COMPILATION_CACHE_DIR=None)
        assert r.returncode == 0, r.stderr[-2000:]
        before, placed, after = json.loads(r.stdout.strip().splitlines()[-1])
        assert before is None and placed == after
        seen.append(placed)
    assert seen[0] == seen[1] == os.path.join(_REPO, ".jax_cache")
    with open(os.path.join(_REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
