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
import tempfile

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


# Tracing and lowering a program allocates containers by the million, and
# the collector's defaults start a pass every 700 of them: on a case that
# lowers interpreted kernels a fifth of its time was the collector's
# (29.7 s against 23.1 s with it off, test_inference.py's int8 pool case). A
# young pass every 50,000 allocations still frees every cycle, later.
gc.set_threshold(50_000, 20, 100)

if os.environ.get("DSTPU_TEST_TPU") != "1":
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    )
    os.environ["JAX_PLATFORMS"] = "cpu"
    _one_compile_cache_a_run()
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_compilation_cache_dir", os.environ[CACHE_ENV])
    # programs that took a third of a second or more to compile are kept
    # (XLA:CPU executables read back warn about machine features; the tests
    # that count compiles and cache misses turn the cache off around
    # themselves: ``no_compile_cache``)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
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
