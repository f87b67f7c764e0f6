"""The main path's kernels, compiled by the chip's own compiler at the two
``chip_smoke.py`` models' widths — for a v5e that is described, not attached.

Interpret mode cannot see what Mosaic refuses (unaligned slices, VMEM over the
scoped limit, a kernel that cannot be partitioned); these compiles can, at no
chip time. Nothing runs: a compile that passes is not a chip run.

The topology is described inside the module-scoped fixture below and nowhere
else — never at import, in a ``skipif`` or in ``parametrize`` arguments — so
every xdist worker collects the same tests and only the worker that runs this
file loads the TPU compiler. Keep these cases in this one file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# llama3-1b (train phase) and llama3-8b (serve phase) widths
H, K = 32, 8
POOL = dict(layers=16, blocks=513, bs=128)


@pytest.fixture(scope="module")
def one_chip():
    """SingleDeviceSharding on the first device of a described v5e 2x2, with
    the persistent compile cache off around the module's tests (a described
    compile is written to it but cannot be read back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _flash(d, grad):
    from deepspeed_tpu.ops.flash_attention import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    q = ((1, 2048, H, d), jnp.bfloat16)
    kv = ((1, 2048, K, d), jnp.bfloat16)
    return fn, [q, kv, kv]


def _paged(tq, kv_int8, rows=64):
    from deepspeed_tpu.ops.paged_attention import ragged_paged_attention

    d, L, nbp1, bs = 128, POOL["layers"], POOL["blocks"], POOL["bs"]
    n = rows if tq == 1 else tq          # tq > 1: one atom of that width
    atoms = n // tq

    def fn(q, ks, vs, kp, vp, bt, slot, pos0, alen, layer, *scale):
        return ragged_paged_attention(
            q, ks, vs, kp, vp, bt, slot, pos0, alen, tq, layer=layer,
            kv_scale=scale[0] if scale else None, kv_bits=8,
            interpret=False)

    pool = ((L, nbp1, bs, K * d), jnp.int8 if kv_int8 else jnp.bfloat16)
    args = [((n, H, d), jnp.bfloat16), ((n, K, d), jnp.bfloat16),
            ((n, K, d), jnp.bfloat16), pool, pool,
            ((8, 16), jnp.int32), ((atoms,), jnp.int32),
            ((atoms,), jnp.int32), ((atoms,), jnp.int32), ((), jnp.int32)]
    if kv_int8:
        args.append(((L, nbp1, 1, 2 * bs), jnp.float32))
    return fn, args


def _qmm(bits, din, f, rows):
    from deepspeed_tpu.ops.quant_matmul import quantized_matmul

    L, group = 4, 128
    prow = din // 2 if bits == 4 else din

    def fn(x, packed, scales, layer):
        return quantized_matmul(x, packed, scales, bits=bits, layer=layer,
                                interpret=False)

    return fn, [((rows, din), jnp.bfloat16), ((L, prow, f), jnp.int8),
                ((L, din // group, f), jnp.bfloat16), ((), jnp.int32)]


def _rms():
    from deepspeed_tpu.ops.rms_norm import _rms_pallas

    def fn(x, w):
        return _rms_pallas(x, w, 1e-5, 256, False)

    return fn, [((4096, 2048), jnp.bfloat16), ((2048,), jnp.float32)]


def _ragged_dot():
    def fn(x, w, sizes):
        return jax.lax.ragged_dot(x, w, sizes)

    return fn, [((512, 4096), jnp.bfloat16), ((8, 4096, 14336), jnp.bfloat16),
                ((8,), jnp.int32)]


# (builder, kwargs, must the compiled program hold a Mosaic kernel?)
CASES = {
    "flash-fwd-d64": (_flash, dict(d=64, grad=False), True),
    "flash-grad-d64": (_flash, dict(d=64, grad=True), True),
    "flash-fwd-d128": (_flash, dict(d=128, grad=False), True),
    "flash-grad-d128": (_flash, dict(d=128, grad=True), True),
    "paged-decode-bf16": (_paged, dict(tq=1, kv_int8=False), True),
    "paged-decode-128rows": (_paged, dict(tq=1, kv_int8=False, rows=128),
                             True),
    "paged-decode-int8kv": (_paged, dict(tq=1, kv_int8=True), True),
    "paged-prefill128-bf16": (_paged, dict(tq=128, kv_int8=False), True),
    "paged-prefill128-int8kv": (_paged, dict(tq=128, kv_int8=True), True),
    # tile_tq=256 (TransformerLM.MAX_ATOM, which the engine schedules for
    # 129..256-token continuation chunks) needs 18.3 MiB of VMEM at
    # H=32/K=8/d=128: over Mosaic's 16 MiB default, inside the limit the
    # past kernel sets for itself (ops/paged_attention._PAST_VMEM_LIMIT)
    "paged-prefill256-bf16": (_paged, dict(tq=256, kv_int8=False), True),
    "paged-prefill256-int8kv": (_paged, dict(tq=256, kv_int8=True), True),
    "qmm-int8-up": (_qmm, dict(bits=8, din=4096, f=14336, rows=64), True),
    "qmm-int4-up": (_qmm, dict(bits=4, din=4096, f=14336, rows=256), True),
    "qmm-int8-down": (_qmm, dict(bits=8, din=14336, f=4096, rows=64), True),
    "qmm-int4-down": (_qmm, dict(bits=4, din=14336, f=4096, rows=64), True),
    "qmm-int8-qkv": (_qmm, dict(bits=8, din=4096, f=6144, rows=64), True),
    "qmm-int4-1b-up": (_qmm, dict(bits=4, din=2048, f=8192, rows=256), True),
    # 256 rows x 14336 blow the kernel's VMEM budget: the XLA dequant-matmul
    # twin runs, and quant_matmul_path says so
    "qmm-int8-down-256rows-twin": (
        _qmm, dict(bits=8, din=14336, f=4096, rows=256), False),
    "rms-pallas": (_rms, {}, True),
    "ragged-dot": (_ragged_dot, {}, True),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_compiles_for_v5e(one_chip, name):
    build, kwargs, mosaic = CASES[name]
    fn, shapes = build(**kwargs)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert ("tpu_custom_call" in text) == mosaic, name


def test_kernel_path_rules_match_what_compiled():
    """The rules the engine's ``kernel_paths`` record reads say what the
    compiles above found — no topology needed."""
    from deepspeed_tpu.ops.paged_attention import attention_kernel_path
    from deepspeed_tpu.ops.quant_matmul import quant_matmul_path

    assert attention_kernel_path(128, 128, 1, interpret=False)[0] == "pallas"
    assert attention_kernel_path(128, 128, 256, interpret=False)[0] == "pallas"
    for d in (64, 96):              # llama3-1b/gpt2/pythia/opt, phi3-mini
        path, why = attention_kernel_path(d, 128, 1, interpret=False)
        assert path == "xla" and str(d) in why
    assert attention_kernel_path(128, 64, 128, interpret=False)[0] == "xla"
    assert attention_kernel_path(64, 16, 1, interpret=True)[0] == "pallas"
    assert attention_kernel_path(128, 128, 1, kernel="xla")[0] == "xla"
    assert quant_matmul_path(64, 14336, 4096, 128)[0] > 0
    bf, why = quant_matmul_path(256, 14336, 4096, 128)
    assert bf == 0 and "VMEM" in why
    assert quant_matmul_path(512, 4096, 14336, 128)[0] == 0
