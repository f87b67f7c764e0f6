"""Test configuration: run the whole suite on a virtual 8-device CPU mesh.

Mirrors the reference's multi-process-on-one-host distributed test harness
(``tests/unit/common.py:139``): instead of forking processes with NCCL over localhost,
JAX gives us N virtual devices in-process via ``--xla_force_host_platform_device_count``,
and every mesh/sharding/collective path exercises the same SPMD partitioner used on a
real pod. Set ``DSTPU_TEST_TPU=1`` to run against real TPU hardware instead.
"""

import os

import pytest

if os.environ.get("DSTPU_TEST_TPU") != "1":
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    )
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    # The entry points place the persistent compile cache in the checkout
    # (utils/compile_cache.py). Tests leave nothing there: XLA:CPU executables
    # read back from it warn about machine features on every load.
    jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(scope="session")
def eight_devices():
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    return jax.devices()[:8]
