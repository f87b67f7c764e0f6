"""Test configuration: run the whole suite on a virtual 8-device CPU mesh.

Mirrors the reference's multi-process-on-one-host distributed test harness
(``tests/unit/common.py:139``): instead of forking processes with NCCL over localhost,
JAX gives us N virtual devices in-process via ``--xla_force_host_platform_device_count``,
and every mesh/sharding/collective path exercises the same SPMD partitioner used on a
real pod. Set ``DSTPU_TEST_TPU=1`` to run against real TPU hardware instead.
"""

import atexit
import gc
import os
import shutil
import sys
import tempfile
import time

import pytest

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def _one_compile_cache_a_run() -> None:
    """Hundreds of cases build engines whose programs are the same HLO; with
    no persistent cache each compiles them anew. The run keeps one cache, in
    a temporary directory of its own (nothing is written into the checkout:
    the entry points would place it there, ``utils/compile_cache.py``), cold
    at the start, shared by the xdist workers and the cases' child processes
    through the variable JAX itself reads, and removed when the run ends. A
    directory the caller named is left as it is."""
    if os.environ.get(CACHE_ENV) or "PYTEST_XDIST_WORKER" in os.environ:
        return      # the caller's, or the controller's, inherited
    path = tempfile.mkdtemp(prefix="dstpu_tests_jax_cache_")
    os.environ[CACHE_ENV] = path
    atexit.register(shutil.rmtree, path, ignore_errors=True)
    # Python's bytecode too: where the installation keeps none (no
    # ``__pycache__`` beside jax, ``PYTHONDONTWRITEBYTECODE`` set), every
    # worker and every child process a case starts compiled the source of
    # all it imports, 4.1 s for ``import jax, deepspeed_tpu`` against 1.5
    sys.pycache_prefix = os.environ["PYTHONPYCACHEPREFIX"] = os.path.join(path, "pycache")
    os.environ.pop("PYTHONDONTWRITEBYTECODE", None)
    sys.dont_write_bytecode = False


# Tracing and lowering a program allocates containers by the million, and
# the collector's defaults start a pass every 700 of them: on a case that
# lowers interpreted kernels a fifth of its time was the collector's
# (29.7 s against 23.1 s with it off, test_inference.py's int8 pool case). A
# young pass every 50,000 allocations still frees every cycle, later.
gc.set_threshold(50_000, 20, 100)

#: XLA:CPU builds the tests' programs without LLVM's optimiser: each runs once
#: or twice on a few hundred rows, and the optimiser costs more than it gives
#: back. Five files under six workers: 174 s of wall and 1,043 s of CPU at
#: level 0, 227 and 1,456 at level 1, and the whole run 994 s for 1,254 to
#: 1,266; ``test_granite.py`` alone 128 s, 132 at level 1, 181 as XLA comes.
#: At level 0 two programs that are one function no longer agree to the bit
#: where they compute in float16, which is LLVM's to legalise on a CPU:
#: ``test_engine.py``'s float16 carried-copy case compares its two in a
#: process that compiles as XLA comes. The chip's compiler reads ``XLA_FLAGS``
#: too and takes as long either way (the Granite cell's step alone: 21.9 s
#: with the two flags, 21.6 without).
LLVM_O0 = " --xla_backend_optimization_level=0 --xla_llvm_disable_expensive_passes=true"

if os.environ.get("DSTPU_TEST_TPU") != "1":
    # Six xdist workers each hold eight virtual devices (a thread a device,
    # which must all meet at every collective) on a machine of eight cores.
    # With XLA:CPU's intra-op pool on as well, every matmul of every device
    # of every worker fans out over a pool sized to the machine: collectives
    # waited tens of seconds for their threads to be scheduled together
    # (ROADMAP.md, D0). One thread a device is what the cores can run.
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
        " --xla_cpu_multi_thread_eigen=false" + LLVM_O0
    )
    os.environ["JAX_PLATFORMS"] = "cpu"
    _one_compile_cache_a_run()
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_compilation_cache_dir", os.environ[CACHE_ENV])
    # every program is kept, however quickly it compiled: a case that calls
    # anything op by op builds a hundred one-op programs at 70 ms each, the
    # same ones in every case and every worker (two of test_kanana.py's
    # cases, cache warm: 15.4 s, for 21.6 when only programs of 0.3 s or more
    # were kept). XLA:CPU executables read back warn about machine features;
    # the tests that count compiles and cache misses turn the cache off
    # around themselves: ``no_compile_cache``
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


@pytest.fixture
def no_compile_cache():
    """The persistent compile cache off around a test that counts compiles
    or cache misses, or compiles what cannot be read back."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="session")
def eight_devices():
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    return jax.devices()[:8]


@pytest.fixture(scope="session")
def run_memo():
    """``run_memo(name, build)``: see :func:`_run_memo`."""
    return _run_memo


def _run_memo(name, build):
    """What ``build()`` returns (a pytree of arrays): computed by whichever
    xdist worker asks first, pickled under the run's cache directory by an
    atomic rename, and read by the others (one that asks while it is being
    built waits up to two minutes, then builds its own). A reference's
    outputs that a file's module-scoped fixture holds were computed once in
    every worker that drew one of the file's cases. In a directory the caller
    named (which outlives the run, and the code) nothing is kept."""
    import pickle

    import jax

    root = os.environ.get(CACHE_ENV, "")
    if "dstpu_tests_jax_cache_" not in root:
        return jax.device_get(build())
    path = os.path.join(root, f"memo_{name}.pkl")
    try:        # the first to ask builds; who asks meanwhile waits for it, a while
        os.close(os.open(f"{path}.claim", os.O_CREAT | os.O_EXCL))
    except FileExistsError:
        for _ in range(600):
            if os.path.exists(path):
                break
            time.sleep(0.2)
    try:
        with open(path, "rb") as f:
            return pickle.load(f)
    except OSError:
        value = jax.device_get(build())
    with open(f"{path}.{os.getpid()}", "wb") as f:
        pickle.dump(value, f)
    os.replace(f"{path}.{os.getpid()}", path)
    return value


CELL_COMPILE = "_cells_step_program_compiles_for_v5e"
#: the cells whose whole step test_chip_compile.py compiles, dearest first
#: (133 to 51 s alone: ``tools/tier1_cost.py`` prints them); a cell not
#: listed yet goes before them
DEAREST_FIRST = ("ling3", "nemotron", "olmo_hybrid", "keye_vl2", "lfm2", "granite")


def pytest_collection_modifyitems(items):
    """The whole-cell compiles (50 to 130 s each, and up to 2.4 times that
    when six run side by side, which ``--dist load`` arranged by handing out
    one file's consecutive cases) at even distances through the first two
    thirds of the order, the dearest first: they do not meet, and none starts
    in the run's last minutes, where it would be all tail."""
    cells = [i for i in items if i.name.endswith(CELL_COMPILE)]
    if len(cells) < 2 or len(items) < 3 * len(cells):
        return
    cells.sort(key=lambda i: next(
        (k for k, cell in enumerate(DEAREST_FIRST) if f"_the_{cell}_cells" in i.name), -1))
    rest = [i for i in items if not i.name.endswith(CELL_COMPILE)]
    stride = 2 * len(rest) // (3 * len(cells))
    for k, cell in enumerate(cells):
        rest.insert(k * (stride + 1), cell)
    items[:] = rest
