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

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.observability import steplog
from deepspeed_tpu.ops import lowerings

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


def _flash(d, grad, T=2048, heads=H, kv_heads=K, window=None):
    from deepspeed_tpu.ops.flash_attention import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    q = ((1, T, heads, d), jnp.bfloat16)
    kv = ((1, T, kv_heads, d), jnp.bfloat16)
    return fn, [q, kv, kv]


def _flash_lse_pair(window):
    """FPDT's chunk pair: a q-chunk against a longer run of keys at a static
    rel_offset, gradients through both results (out and the log-sum-exp)."""
    from deepspeed_tpu.ops.flash_attention import flash_attention_lse

    def loss(q, k, v):
        out, lse = flash_attention_lse(q, k, v, causal=True, window=window,
                                       rel_offset=2048, interpret=False)
        return out.astype(jnp.float32).sum() + lse.sum()

    q = ((1, 2048, H, 128), jnp.bfloat16)
    kv = ((1, 4096, K, 128), jnp.bfloat16)
    return jax.grad(loss, argnums=(0, 1, 2)), [q, kv, kv]


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


def _rope_rows(grad):
    """The rope on latent attention's q at the kanana-2 cell's shape: 2 x
    32 heads x 8192 positions of 64 rope columns, heads first."""
    from deepspeed_tpu.models.transformer import rope_frequencies
    from deepspeed_tpu.ops.rope import rope_heads_first

    def fwd(x):
        return rope_heads_first(x, rope_frequencies(64, 8192, 1e6),
                                interpret=False)

    def loss(x):    # (not linear in x: the backward call reads a value)
        return jnp.square(fwd(x).astype(jnp.float32)).sum()

    return (jax.grad(loss) if grad else fwd), [((2, 32, 8192, 64),
                                                jnp.bfloat16)]


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


def _grouped(product, C=2304, O=896, rows=65536, experts=16):
    """The experts' products at the Mellum2 cell's shapes (BENCHMARK.json:
    65,536 buffer rows, 16 held experts, widths 2304 and 896), each kernel
    alone, under the VMEM limit the calls set for themselves."""
    from deepspeed_tpu.ops import grouped_matmul as gm

    bf = jnp.bfloat16
    xs, dys, sizes = ((rows, C), bf), ((rows, O), bf), ((experts,), jnp.int32)
    if product == "tgmm":
        return gm.tgmm, [xs, dys, sizes]
    if product == "gmm-transposed":
        return (lambda d, w, g: gm.gmm(d, w, g, transpose_w=True),
                [dys, ((experts, C, O), bf), sizes])
    if product == "gmm-transposed-pair":        # the gate's and the up's, summed
        return (lambda d1, d2, w1, w2, g: gm.gmm((d1, d2), (w1, w2), g,
                                                 transpose_w=True),
                [dys, dys, ((experts, C, O), bf), ((experts, C, O), bf), sizes])
    # by the rule's answer for the shape, as if on the chip
    took, _ = gm.grouped_lowering(rows, C, O, experts, bf, tpu=True)
    return (lambda x, w, g: gm.grouped_matmul(x, w, g, lowering=took),
            [xs, ((experts, C, O), bf), sizes])


def _ssd(grad, H=64, P=64, G=1, N=128, T=4096, chunk=256):
    """The state-space scan at the Granite cell's shapes (BENCHMARK.json: 1 x
    4096 tokens, 64 heads of 64, one group, a state of 128, chunks of 256),
    by the picker's answer for the shape as if on the chip: the forward
    kernel alone, and the differentiated forward with the backward kernel."""
    from deepspeed_tpu.ops import ssd_scan as ss

    took, _ = ss.scan_lowering(chunk, H, P, G, N, jnp.bfloat16, tpu=True)

    def fwd(x, dt, A, B, C, D):
        if took == "xla":
            return ss.scan_einsum(x, dt, A, B, C, D, chunk)
        return ss._scan_pallas(x, dt, A, B, C, D, chunk, False)

    def loss(*a):
        return fwd(*a).astype(jnp.float32).sum()

    bc = ((1, T, G, N), jnp.bfloat16)
    return (jax.grad(loss, argnums=tuple(range(6))) if grad else fwd), [
        ((1, T, H, P), jnp.bfloat16), ((1, T, H), jnp.float32),
        ((H,), jnp.float32), bc, bc, ((H,), jnp.float32)]


def _delta(grad, H=15, dk=96, dv=192, T=4096, einsum=False, unit=True):
    """The chunked delta rule at the Olmo-Hybrid cell's shapes
    (BENCHMARK.json: 1 x 4096 tokens, 15 heads held, keys of 96, values of
    192, chunks of 64; ``unit``: q and k in float32 as the convolutions leave
    them, the norms the rule's), by the picker's answer for the shape as if
    on the chip (``einsum``: the einsum form whatever it says): the forward
    kernel alone, and the differentiated forward with the backward kernel,
    each under the VMEM limit it sets for itself."""
    from deepspeed_tpu.ops import delta_rule as dr

    took, _ = dr.rule_lowering(T, H, dk, dv, jnp.bfloat16, tpu=True)
    norms = (dk ** -0.5, 1e-6) if unit else None

    def fwd(q, k, v, g, beta):
        if einsum or took == "xla":
            if unit:
                q, k = (dr.unit_heads(x, s, norms[1], v.dtype)
                        for x, s in ((q, norms[0]), (k, 1.0)))
            return dr.rule_einsum(q, k, v, g, beta)
        return dr._rule_pallas(q, k, v, g, beta, norms, False)

    def loss(*a):
        return fwd(*a).astype(jnp.float32).sum()

    qk = ((1, T, H, dk), jnp.float32 if unit else jnp.bfloat16)
    gb = ((1, T, H), jnp.float32)
    return (jax.grad(loss, argnums=tuple(range(5))) if grad else fwd), [
        qk, qk, ((1, T, H, dv), jnp.bfloat16), gb, gb]


def _conv(grad, C=4352, bias=True, out=jnp.bfloat16, splits=(), T=4096, K=4,
          act="silu"):
    """The mixers' convolution with its silu at the Granite cell's call
    (BENCHMARK.json: 1 x 4096 tokens, xBC 4352 wide, a bias, bf16 out, x, B
    and C as arrays of their own) and the Olmo-Hybrid cell's three (q and k
    1440 wide to float32, v 2880 wide to bf16, no bias; rows that end inside
    a lane tile), and without an activation at the LFM2 cell's (2 x 8192
    tokens a step, a row a call here; 2048 wide, 3 taps, no bias), by the
    picker's answer for the shape as if on the chip:
    the forward kernel alone, and the differentiated forward with the
    backward kernel."""
    from deepspeed_tpu.ops import causal_conv as cc

    took, _ = cc.conv_lowering(T, C, K, jnp.bfloat16, out, splits, tpu=True)

    def fwd(x, w, b=None):
        if took == "xla":
            return (jax.nn.silu(cc.causal_conv(x, w, b)).astype(out),)
        return cc._conv_pallas(x, w, b, jnp.dtype(out).name,
                               cc._widths(C, splits), False, act)

    def loss(*a):
        return sum((part.astype(jnp.float32) ** 2).sum() for part in fwd(*a))

    shapes = [((1, T, C), jnp.bfloat16), ((K, C), jnp.bfloat16)] \
        + [((C,), jnp.bfloat16)] * bias
    return (jax.grad(loss, argnums=tuple(range(len(shapes)))) if grad
            else fwd), shapes


def _head_gate(grad, T=8192, H=16, dv=128):
    """KDA's output norm and head gate at the Ling cell's call
    (BENCHMARK.json: 1 x 8192 tokens, 16 heads held, values of 128, a head
    one lane tile of ``[1, 8192, 2048]``) and at Olmo-Hybrid's 192-wide
    heads, by the picker's answer for the shape as if on the chip: the
    forward kernel alone, and the differentiated forward with the backward
    kernel."""
    from deepspeed_tpu.ops import head_norm_gate as hg

    took, _ = hg.gate_lowering(T, H, dv, jnp.bfloat16, tpu=True)

    def fwd(o, z, scale):
        if took == "xla":
            return hg.head_norm_gate_xla(o, z, scale, 1e-6)
        return hg._gate_pallas(o, z, scale, 1e-6, False)

    def loss(*a):
        return (fwd(*a).astype(jnp.float32) ** 2).sum()

    return (jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd), [
        ((1, T, H * dv), jnp.bfloat16), ((1, T, H), jnp.bfloat16),
        ((dv,), jnp.float32)]


# (builder, kwargs, must the compiled program hold a Mosaic kernel?)
CASES = {
    "flash-fwd-d64": (_flash, dict(d=64, grad=False), True),
    "flash-grad-d64": (_flash, dict(d=64, grad=True), True),
    "flash-fwd-d128": (_flash, dict(d=128, grad=False), True),
    "flash-grad-d128": (_flash, dict(d=128, grad=True), True),
    # the forward alone at the two training cells' shapes (BENCHMARK.json),
    # at T 16,384, and at a T whose block_q (8 of 3000) is no legal
    # [1, block_q] row, so that the log-sum-exp leaves as the column
    "flash-fwd-mistral-cell": (
        _flash, dict(d=128, grad=False, T=4096, heads=32, kv_heads=8,
                     window=4096), True),
    "flash-fwd-ouro-cell": (
        _flash, dict(d=128, grad=False, T=4096, heads=16, kv_heads=16), True),
    "flash-fwd-16k": (
        _flash, dict(d=128, grad=False, T=16384, heads=4, kv_heads=4), True),
    "flash-fwd-d64-window": (
        _flash, dict(d=64, grad=False, T=4096, heads=8, kv_heads=2,
                     window=1000), True),
    "flash-fwd-column-T3000": (
        _flash, dict(d=128, grad=False, T=3000, heads=4, kv_heads=4), True),
    "flash-grad-column-T3000": (
        _flash, dict(d=128, grad=True, T=3000, heads=4, kv_heads=4), True),
    "flash-lse-chunk-pair-grad": (_flash_lse_pair, dict(window=None), True),
    "flash-lse-chunk-pair-window-grad": (_flash_lse_pair, dict(window=3000),
                                         True),
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
    "rope-heads-first-fwd": (_rope_rows, dict(grad=False), True),
    "rope-heads-first-grad": (_rope_rows, dict(grad=True), True),
    "ragged-dot": (_ragged_dot, {}, True),
    "grouped-gmm-gate-up": (_grouped, dict(product="gmm"), True),
    "grouped-gmm-down": (_grouped, dict(product="gmm", C=896, O=2304), True),
    "grouped-gmm-transposed-gate-up": (
        _grouped, dict(product="gmm-transposed"), True),
    "grouped-gmm-transposed-down": (
        _grouped, dict(product="gmm-transposed", C=896, O=2304), True),
    "grouped-gmm-transposed-gate-and-up": (
        _grouped, dict(product="gmm-transposed-pair"), True),
    "grouped-tgmm-gate-up": (_grouped, dict(product="tgmm"), True),
    "grouped-tgmm-down": (_grouped, dict(product="tgmm", C=896, O=2304), True),
    "ssd-scan-fwd-granite-cell": (_ssd, dict(grad=False), True),
    "ssd-scan-grad-granite-cell": (_ssd, dict(grad=True), True),
    # two groups, heads of 128 channels, a T that is padded to whole chunks
    "ssd-scan-grad-2groups-p128": (
        _ssd, dict(grad=True, H=16, P=128, G=2, T=1000, chunk=128), True),
    # heads of 32 channels: the picker gives the einsum form
    "ssd-scan-grad-p32-einsum": (
        _ssd, dict(grad=True, H=16, P=32, T=1024), False),
    # the chunked delta rule at the Olmo-Hybrid cell's shapes (15 heads held,
    # keys of 96, values of 192, chunks of 64) and at a T that is padded: the
    # two kernels (with q and k as the convolutions leave them, and bf16 q
    # and k normed before); the einsum form (the triangular inverse's loop
    # and the chunk loop) at the same shapes; keys of 128, which the picker
    # refuses
    "delta-rule-fwd-olmo-cell": (_delta, dict(grad=False), True),
    "delta-rule-grad-olmo-cell": (_delta, dict(grad=True), True),
    "delta-rule-grad-T1000-normed-before": (
        _delta, dict(grad=True, H=4, T=1000, unit=False), True),
    "delta-rule-grad-olmo-cell-einsum": (
        _delta, dict(grad=True, einsum=True), False),
    "delta-rule-grad-T1000-einsum": (
        _delta, dict(grad=True, H=4, T=1000, einsum=True), False),
    # (keys and values of 128 take the kernels since PR 66: the Qwen3-Next
    # cell's whole step below; a width off the list still takes the einsums)
    "delta-rule-grad-k48-refused": (
        _delta, dict(grad=True, H=4, dk=48, dv=96, T=1024), False),
    # the mixers' convolution with its silu at both cells' calls, a T that
    # is no whole sublane tiles (the picker gives the jax.numpy form)
    "conv-silu-fwd-granite-cell": (
        _conv, dict(grad=False, splits=(4096, 4224)), True),
    "conv-silu-grad-granite-cell": (
        _conv, dict(grad=True, splits=(4096, 4224)), True),
    "conv-silu-fwd-olmo-cell-q-k": (
        _conv, dict(grad=False, C=1440, bias=False, out=jnp.float32), True),
    "conv-silu-grad-olmo-cell-q-k": (
        _conv, dict(grad=True, C=1440, bias=False, out=jnp.float32), True),
    "conv-silu-grad-olmo-cell-v": (
        _conv, dict(grad=True, C=2880, bias=False), True),
    "conv-silu-grad-T4090-numpy-form": (
        _conv, dict(grad=True, C=256, T=4090), False),
    "conv-plain-fwd-lfm2-cell": (
        _conv, dict(grad=False, C=2048, bias=False, T=8192, K=3, act=None),
        True),
    "conv-plain-grad-lfm2-cell": (
        _conv, dict(grad=True, C=2048, bias=False, T=8192, K=3, act=None),
        True),
    # KDA's output norm and head gate at the Ling cell's call; heads of 192
    # (a lane tile and a half: the picker gives the jax.numpy lines)
    "head-gate-fwd-ling-cell": (_head_gate, dict(grad=False), True),
    "head-gate-grad-ling-cell": (_head_gate, dict(grad=True), True),
    "head-gate-grad-dv192-numpy-lines": (
        _head_gate, dict(grad=True, T=4096, H=15, dv=192), False),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_compiles_for_v5e(one_chip, name):
    build, kwargs, mosaic = CASES[name]
    fn, shapes = build(**kwargs)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert ("tpu_custom_call" in text) == mosaic, name


# The flash backward at the two training cells' shapes (BENCHMARK.json:
# 1 x 4096 tokens; Mistral 32 query heads over 8 KV heads with window 4096,
# Ouro 16 over 16), where a head's dq fits the fused kernel's VMEM budget, and
# at lengths over it, where the same call works the head in segments of
# q-tiles that fit (that it compiles under the budget as its
# ``vmem_limit_bytes`` is the proof of ``_fused_bwd_vmem_bytes``): (T, H, K,
# window, which kernel the shape takes, its segments[, rows, head width])
FLASH_BWD = {
    "mistral-cell": (4096, 32, 8, 4096, "fused", 1),
    "ouro-cell": (4096, 16, 16, None, "fused", 1),
    "16k-largest-fused": (16384, 4, 4, None, "fused", 1),
    "32k-over-budget": (32768, 4, 4, None, "fused", 2),
    # the window layers of the Laguna cell (one row, 36 heads held over 4)
    # and of the Mellum2 cell (two rows)
    "laguna-window-cell": (8192, 36, 4, 512, "fused", 1),
    "mellum2-window-cell": (8192, 32, 4, 1024, "fused", 1, 2),
    # the full layer of the Qwen3-Next cell: one row of 16,384 at d 256,
    # 16 query heads over 2
    "qwen3-next-cell": (16384, 16, 2, None, "fused", 2, 1, 256),
}
# ``flash_bwd_tiles`` a head at the cells' shapes: the tiles by arm and, of
# the masked tiles' 512 x 512 sub-blocks, those worked, skipped and unmasked
FLASH_BWD_TILES = {
    "mistral-cell": dict(masked=4, unmasked=6, dead=6, sub_live=12,
                         sub_dead=4, sub_inside=4),
    "ouro-cell": dict(masked=4, unmasked=6, dead=6, sub_live=12, sub_dead=4,
                      sub_inside=4),
    "laguna-window-cell": dict(masked=15, unmasked=0, dead=49, sub_live=31,
                               sub_dead=29, sub_inside=0),
    "mellum2-window-cell": dict(masked=15, unmasked=0, dead=49, sub_live=45,
                                sub_dead=15, sub_inside=15),
}


def _in_parts(rows, T, heads, kv_whole, one_chip):
    """The loss and the arguments of the flash kernels with q and k in the
    parts latent attention's products write (128 + 64 rope columns over
    values of 128; ``kv_whole``: keys and values side by side in one array),
    traced under the scope the block calls them in."""
    from deepspeed_tpu.ops import flash_attention as fa

    def loss(q, q_rope, k_rope, k, v=None):
        with jax.named_scope("attn"), jax.named_scope("attn_mla"):
            out = fa.flash_attention(q, k, v, q_rope=q_rope, k_rope=k_rope,
                                     causal=True, interpret=False)
        return out.astype(jnp.float32).sum()

    def arg(h, w):
        return jax.ShapeDtypeStruct((rows, T, h, w), jnp.bfloat16,
                                    sharding=one_chip)

    args = [arg(heads, 128), arg(heads, 64), arg(1, 64)] + (
        [arg(heads, 256)] if kv_whole else [arg(heads, 128), arg(heads, 128)])
    return jax.grad(loss, argnums=tuple(range(len(args)))), args


def _mla_roofline_patterns():
    """(forward, backward): how the benchmark's ``flash_*_roofline.mla``
    find the kernels among a program's instructions."""
    import json
    import os

    metrics = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "benchmarks", "metrics")
    found = []
    for name in ("flash_fwd_roofline.mla", "flash_bwd_roofline.mla"):
        with open(os.path.join(metrics, name + ".json")) as f:
            found.append(re.compile(json.load(f)["args"]["pattern"]))
    return found


# The flash kernels at a key width and a value width (latent attention: keys
# of 128 + 64 rope dimensions over values of 128), forward and backward, at the
# kanana-2 cell's shape (BENCHMARK.json: 2 x 8192 tokens, 32 heads), where the
# fused backward fits its budget with dk and dv as a result each, and at a
# length over it, which it works in segments: (rows, T, heads, which backward
# the shape takes, its segments)
FLASH_TWO_WIDTHS = {
    "kanana2-cell": (2, 8192, 32, "fused", 1),
    "32k-over-budget": (1, 32768, 2, "fused", 4),
}

# The same shapes with q and k in the parts the products write, the rope
# columns and the one rope key as operands of their own: (rows, T, heads,
# which backward, keys and values in one array, the backward's segments)
FLASH_IN_PARTS = {
    "kanana2-cell": (2, 8192, 32, "fused", True, 1),
    "kanana2-cell-k-and-v-apart": (2, 8192, 32, "fused", False, 1),
    "32k-over-budget": (1, 32768, 2, "fused", True, 4),
}


@pytest.mark.parametrize("name", sorted(FLASH_TWO_WIDTHS))
def test_flash_at_a_key_and_a_value_width_compiles_for_v5e(one_chip, name):
    from deepspeed_tpu.ops import flash_attention as fa

    rows, T, heads, took, segments = FLASH_TWO_WIDTHS[name]
    assert fa._bwd_takes_fused(T, 192, fa.DEFAULT_BLOCK_Q,
                               fa.DEFAULT_BLOCK_K, 2, 128) \
        == (took == "fused")

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True,
                                  interpret=False).astype(jnp.float32).sum()

    qk = jax.ShapeDtypeStruct((rows, T, heads, 192), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((rows, T, heads, 128), jnp.bfloat16,
                             sharding=one_chip)
    before = lowerings.snapshot()
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        qk, qk, v).compile().as_text()
    said = lowerings.since(before)
    assert said["flash_bwd"] == {took: 1}
    assert said["flash_bwd_segments"] == {192: segments}
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
             and " custom-call(" in ln]
    # the forward writes values' width; the fused backward dq by q-tile at
    # the keys' width and dk, dv each at its own (three results: what
    # metrics/flash_bwd_roofline.mla.json looks for), a partial a segment
    # where the head is worked in segments
    assert any(f"= (bf16[{rows},{heads},{T},128]" in ln and "f32[" in ln
               for ln in calls)
    part = f"{segments}," if segments > 1 else ""
    assert len(calls) == 2
    assert any(f"bf16[{rows},{heads},{T // 1024},1024,192]" in ln
               and f"bf16[{part}{rows},{heads},{T},192]" in ln
               and f"bf16[{part}{rows},{heads},{T},128]" in ln for ln in calls)


@pytest.mark.parametrize("name", sorted(FLASH_IN_PARTS))
def test_flash_with_the_rope_columns_as_operands_compiles_for_v5e(one_chip,
                                                                  name):
    """At a described v5e, T 8192 and 1024-wide tiles the fused backward
    holds the new operands within its budget; the calls keep the name of the
    scope they are traced under and the results the benchmark's
    ``flash_fwd_roofline.mla`` / ``flash_bwd_roofline.mla`` find them by: the
    forward (bf16, f32), the backward at most three bf16 results (dq by
    q-tile at 192; dk and dv side by side at 256, or stacked; the rope key's
    share a head)."""
    from deepspeed_tpu.ops import flash_attention as fa

    rows, T, heads, took, kv_whole, segments = FLASH_IN_PARTS[name]
    assert fa._bwd_takes_fused(T, 128, fa.DEFAULT_BLOCK_Q,
                               fa.DEFAULT_BLOCK_K, 2, 128, 64) \
        == (took == "fused")
    fn, args = _in_parts(rows, T, heads, kv_whole, one_chip)
    before = lowerings.snapshot()
    text = jax.jit(fn).lower(*args).compile().as_text()
    said = lowerings.since(before)
    assert said["flash_bwd"] == {took: 1}
    assert said["flash_bwd_segments"] == {128: segments}
    assert list(said["flash_rope_operand"]) == ["operand"]
    calls = [ln.strip() for ln in text.splitlines()
             if "tpu_custom_call" in ln and " custom-call(" in ln]
    forward, backward = _mla_roofline_patterns()
    fwd = [ln for ln in calls if forward.search(ln)]
    bwd = [ln for ln in calls if backward.search(ln)]
    # every Mosaic call is found by one of the two, none by both
    assert len(fwd) == 1 and len(fwd) + len(bwd) == len(calls)
    assert f"= (bf16[{rows},{heads},{T},128]" in fwd[0] \
        and f"f32[{rows},{heads},1,{T}]" in fwd[0]
    # (a partial a segment where the head is worked in segments)
    part = f"{segments}," if segments > 1 else ""
    dkv = (f"bf16[{part}{rows},{heads},{T},256]" if kv_whole
           else f"bf16[2,{part}{rows},{heads},{T},128]")
    dk_rope = f"bf16[{part}{rows},{heads},{T},64]"
    assert len(bwd) == 1
    assert f"= (bf16[{rows},{heads},{T // 1024},1024,192]" in bwd[0]
    assert dkv in bwd[0].split(" custom-call(")[0]
    assert dk_rope in bwd[0].split(" custom-call(")[0]
    # nothing 192 wide is written on the way in: the operands are the parts
    operands = [ln.split(" custom-call(")[1] for ln in calls]
    assert not any("192]" in o.split("custom_call_target")[0]
                   for o in operands if "1024,192]" not in o)


def _gradient_program(model, one_chip):
    """The optimized text of ``model``'s gradient program at 2 x 1024 tokens,
    compiled for the chip."""
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(model.init, jax.random.key(0)))
    batch = {"input_ids": jax.ShapeDtypeStruct((2, 1024), jnp.int32,
                                               sharding=one_chip)}
    return jax.jit(jax.grad(model.loss_fn)).lower(
        params, batch).compile().as_text()


# (results, operands) of the flash calls in the gradient program of the dense
# model below, as the tree before PR 40 compiles it for a v5e: the forward
# (out, log-sum-exp rows) from q, k, v; the fused backward (dq by q-tile, dk
# and dv stacked) from q, k, v, do and the two rows of statistics
DENSE_FLASH_CALLS = [
    ("(bf16[2,2,1,1024,128], bf16[2,2,2,1024,128])",
     "bf16[2,2,1024,128], bf16[2,1,1024,128], bf16[2,1,1024,128], "
     "bf16[2,2,1024,128], f32[2,2,1,1024], f32[2,2,1,1024]"),
    ("(bf16[2,2,1024,128], f32[2,2,1,1024])",
     "bf16[2,2,1024,128], bf16[2,1,1024,128], bf16[2,1,1024,128]"),
]


def test_a_dense_models_flash_calls_are_the_parents(one_chip, monkeypatch):
    """A dense model's gradient program compiled for the chip: its flash
    calls take the operands and give the results they did before the kernels
    learned to take q and k in parts (no operand more, no result more). The
    text below is what the parent commit's tree compiles to."""
    import deepspeed_tpu.ops as ops
    from deepspeed_tpu.models import TransformerConfig, TransformerLM
    from deepspeed_tpu.ops import flash_attention as fa

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    model = TransformerLM(TransformerConfig(
        vocab_size=512, hidden_size=256, num_layers=2, num_heads=2,
        num_kv_heads=1, intermediate_size=512, max_seq_len=1024,
        dtype="bfloat16", attention_impl="flash", remat_policy="full"))
    before = lowerings.snapshot()
    text = _gradient_program(model, one_chip)
    assert list(lowerings.since(before)["flash_rope_operand"]) == ["none"]
    calls = []
    for line in text.splitlines():
        if "tpu_custom_call" not in line or " custom-call(" not in line:
            continue
        results = line.split(" = ", 1)[1].split(" custom-call(")[0]
        operands = re.search(r"operand_layout_constraints=\{(.*?\})\}",
                             line).group(1)
        calls.append((re.sub(r"\{[^}]*\}", "", results),
                      re.sub(r"\{[^}]*\}", "", operands)))
    assert sorted(set(calls)) == sorted(DENSE_FLASH_CALLS)


def test_a_latent_attention_models_calls_keep_their_names(one_chip,
                                                          monkeypatch):
    """The gradient program of a small model with latent attention at the
    published head widths, compiled for the chip under recomputation: the
    flash calls are named for the scope ``attn_mla`` and found by the
    benchmark's two patterns (two forwards and a backward a stack), every one
    with the rope columns as operands; the rope on q is a call of its own
    name, which neither pattern finds; no other Mosaic call is there."""
    import deepspeed_tpu.ops as ops
    from deepspeed_tpu.models import TransformerConfig, TransformerLM
    from deepspeed_tpu.ops import flash_attention as fa
    from deepspeed_tpu.ops import rope

    for module, name in ((ops, "on_tpu"), (fa, "_on_tpu"), (rope, "on_tpu")):
        monkeypatch.setattr(module, name, lambda: True)
    model = TransformerLM(TransformerConfig(
        vocab_size=256, hidden_size=128, num_layers=2, num_heads=2,
        intermediate_size=256, max_seq_len=1024, dtype="bfloat16",
        attention_impl="flash", remat_policy="full", tie_embeddings=False,
        kv_lora_rank=64, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, rope_interleave=True))
    before = lowerings.snapshot()
    text = _gradient_program(model, one_chip)
    assert lowerings.since(before)["flash_rope_operand"] == {"operand": 3}
    calls = [ln.strip() for ln in text.splitlines()
             if "tpu_custom_call" in ln and " custom-call(" in ln]
    forward, backward = _mla_roofline_patterns()
    fwd = [ln for ln in calls if forward.search(ln)]
    bwd = [ln for ln in calls if backward.search(ln)]
    rest = [ln for ln in calls if ln not in fwd + bwd]
    assert len(fwd) == 2 and len(bwd) == 1
    assert all(ln.startswith("%attn_mla.") for ln in fwd + bwd)
    assert len(rest) == 3 and all(ln.startswith("%_rotate.") for ln in rest)
    assert not set(fwd) & set(bwd)
    # the parts: q and its rope columns, keys and values in one array (twice:
    # a column block each), the one rope key
    assert ("bf16[2,2,1024,128], bf16[2,2,1024,256], bf16[2,2,1024,256], "
            "bf16[2,2,1024,64], bf16[2,1,1024,64]") in re.sub(
        r"\{[^}]*\}", "", fwd[0].split("operand_layout_constraints={")[1])


@pytest.mark.parametrize("name", sorted(FLASH_BWD))
def test_flash_backward_compiles_for_v5e(one_chip, name):
    import re

    from deepspeed_tpu.ops import flash_attention as fa

    T, heads, kv_heads, window, took, segments, *rest = FLASH_BWD[name]
    B, d = (rest + [1, 128][len(rest):])
    assert fa._bwd_takes_fused(T, d, fa.DEFAULT_BLOCK_Q,
                               fa.DEFAULT_BLOCK_K, 2) == (took == "fused")

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=window,
                                  interpret=False).astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct((B, T, heads, d), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, T, kv_heads, d), jnp.bfloat16,
                              sharding=one_chip)
    before = lowerings.snapshot()
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    said = lowerings.since(before)
    assert said["flash_bwd"] == {took: 1}
    assert said["flash_bwd_segments"] == {d: segments}
    # the fused kernel is one Mosaic call with two bf16 results, both of five
    # dimensions (dq by q-tile; dk and dv stacked): what the benchmark's
    # metrics/flash_bwd_fused_roofline.json looks for. In segments it is
    # still the one call, and the stack has a sixth extent, the segments'
    # partials: metrics/flash_bwd_roofline.gdn.json takes either. The split
    # pair's results have four dimensions
    five = r"bf16\[\d+,\d+,\d+,\d+,\d+\]\{[^}]*\}"
    stack = (five if segments == 1 else
             rf"bf16\[2,{segments},\d+,\d+,\d+,\d+\]\{{[^}}]*\}}")
    fused = re.findall(rf"= \({five}, {stack}\) custom-call\(.*"
                       r"tpu_custom_call", text)
    assert len(fused) == int(took == "fused"), name
    assert len(re.findall(r" custom-call\(.*tpu_custom_call", text)) == 2
    # a crossed tile in sub-blocks is still that one call, and the counter
    # says how many of them it works
    assert ("flash_bwd_tiles" in said) == (took == "fused")
    if name in FLASH_BWD_TILES:
        assert said["flash_bwd_tiles"] == {
            window or "causal": FLASH_BWD_TILES[name]}
    # the forward is one Mosaic call with the results (bf16 out, f32 lse),
    # what metrics/flash_fwd_roofline.json looks for, and its log-sum-exp
    # leaves as the [B, H, 1, T] rows the fused kernel reads
    fwd = re.findall(r"= \(bf16\[[\d,]*\]\{[^}]*\}, (f32\[[\d,]*\])\{[^}]*\}\) "
                     r"custom-call\(.*tpu_custom_call", text)
    assert fwd == [f"f32[{B},{heads},1,{T}]"], (name, fwd)
    tiles = said["flash_fwd_tiles"]
    assert tiles["rows"]
    if T == 4096:       # the cells: 4 x 4 tiles of 1024 a head, window inert
        assert tiles == {"masked": 4, "unmasked": 6, "dead": 6, "rows": True}


@pytest.mark.parametrize("width,took", [(2304, "pallas"), (2300, "xla")])
def test_the_gated_ffns_gradient_compiles_for_v5e(one_chip, width, took):
    """The experts' FFN of the Mellum2 cell, forward and backward: nine
    grouped products, every one a Mosaic call and none a ragged-dot; with a
    width that is no multiple of 128, ``lax.ragged_dot`` as before."""
    from deepspeed_tpu.moe import sharded_moe as sm
    from deepspeed_tpu.ops import grouped_matmul as gm

    rows, E, F = 65536, 16, 896
    live = (jnp.arange(rows) < 32768)[:, None]

    def loss(xs, w, sizes):
        ys = sm._grouped_ffn(xs, sizes, w, jnp.bfloat16, "ragged",
                             interpret=False)
        return (jnp.where(live, ys, 0).astype(jnp.float32) ** 2).sum()

    def arg(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    w = {"w_gate": arg((E, width, F)), "w_up": arg((E, width, F)),
         "w_down": arg((E, F, width))}
    before = lowerings.snapshot()
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        arg((rows, width), jnp.bfloat16), w, arg((E,), jnp.int32)
    ).compile().as_text()
    said = lowerings.since(before)["moe_grouped"]
    calls = text.count("custom_call_target=\"tpu_custom_call\"")
    if took == "pallas":
        # nine products in eight calls: the gate's and the up projection's
        # cotangents of the rows are one gmm over both stacks
        assert said == {"pallas": 9} and calls == 8
        assert "ragged-dot" not in text
    else:
        assert said == {"xla": 3} and "ragged-dot" in text


def _moe_rows_case(name):
    """(function, argument shapes) of one row kernel of the expert layer at
    the Mellum2 cell's shapes: 16,384 tokens, eight pairs each, a buffer of
    65,536 rows, 2304 columns."""
    from deepspeed_tpu.ops import moe_rows as mr

    S, k, bound, D, G = 16384, 8, 65536, 2304, 16
    w = mr.packed_width(D)
    bf16, f32, i32, u32 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.uint32
    runs = ((G * (S // 256) + 1,), i32)
    return {
        "pack-tokens": (mr.pack_rows, [((S, D), bf16)]),
        "pack-live-rows": (mr.pack_rows, [((bound, D), bf16), ((), i32)]),
        "dispatch": (
            lambda xp, tok, n: mr.rows_of_tokens(xp, tok, n, D=D),
            [((S, 1, w), u32), ((bound,), i32), ((), i32)]),
        "combine-backward": (
            lambda gp, tok, n, wt, ys: mr.rows_of_tokens(
                gp, tok, n, D=D, weight=wt, ys=ys),
            [((S, 1, w), u32), ((bound,), i32), ((), i32), ((bound,), f32),
             ((bound, D), bf16)]),
        "combine": (
            lambda yp, slot, line, runs, wt: mr.sum_of_rows(
                yp, slot, line, runs, wt, D=D),
            [((bound, 1, w), u32), ((S, k), i32), ((bound,), i32), runs,
             ((S, k), f32)]),
        "dispatch-backward": (
            lambda gp, slot, line, runs: mr.sum_of_rows(
                gp, slot, line, runs, None, D=D),
            [((bound, 1, w), u32), ((S, k), i32), ((bound,), i32), runs]),
        "runs-of-a-token-tile": (
            lambda rows, sizes: mr.token_tile_runs(rows, sizes, S=S, k=k),
            [((bound,), i32), ((G,), i32)]),
    }[name]


@pytest.mark.parametrize("name", [
    "pack-tokens", "pack-live-rows", "dispatch", "combine-backward",
    "combine", "dispatch-backward", "runs-of-a-token-tile"])
def test_the_expert_layers_row_kernels_compile_for_v5e(one_chip, name):
    """A one-row copy out of the packed ``[n, 1, w]`` words, a load with a
    stride of a row, 18.9 MB of rows held at once: what interpret mode cannot
    refuse and the chip's compiler can."""
    fn, shapes = _moe_rows_case(name)
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = text.count("custom_call_target=\"tpu_custom_call\"")
    assert calls == (0 if name == "runs-of-a-token-tile" else 1)
    if name.startswith("pack"):
        # a packed row is laid out whole, one after another
        assert "u32[%d,1,1152]{2,1,0:T(1,128)}" % args[0].shape[0] in text


def _index_ops_under(text, scope):
    """The ``gather`` and ``scatter`` instructions of a compiled program that
    belong to ``scope``: by their own ``op_name`` or, where they carry none
    (the transpose of a gather keeps its scope only on its operands'
    reshapes), by that of any instruction of their fused computation or of
    the fusion that calls it."""
    comps = re.split(r"\n(?=(?:ENTRY )?%?[\w.\-]+ \([^\n]*\) -> [^\n]*\{\n)",
                     text)
    found = []
    for comp in comps:
        ops = re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = \S+ "
                         r"(?:gather|scatter)\((.*)$", comp, re.M)
        if not ops:
            continue
        name = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(", comp).group(1)
        around = re.findall(r'op_name="([^"]*)"', comp)
        around += re.findall(r"calls=%?" + re.escape(name)
                             + r'\b[^\n]*op_name="([^"]*)"', text)
        for op, rest in ops:
            own = re.findall(r'op_name="([^"]*)"', rest)
            if any(scope in path for path in own or around):
                found.append(op)
    return found


def _sorts_under(text, scope):
    """``op_name`` of the ``sort`` instructions of a compiled program under
    ``/<scope>/``."""
    return [n for n in re.findall(r'^.* sort\(.*op_name="([^"]*)"', text,
                                  re.M) if f"/{scope}/" in n]


def _picks_without_a_sort(text, calls):
    """Under ``moe_router`` the selection kernel ``calls`` times, and no sort
    but ``_placement``'s, one a call of the router; under ``moe_dispatch``
    only ``_pairs_of_rows``', in the backward."""
    select = _kernel_calls(text, "moe_router")
    assert len(select) == calls
    assert all("/moe/moe_router/jit(select_rounds)/" in n for n in select)
    assert len(_sorts_under(text, "moe_router")) == calls
    assert all("transpose(" in n for n in _sorts_under(text, "moe_dispatch"))


@pytest.mark.parametrize("k, groups", [(8, (8, 4)), (22, (1, 1))])
def test_the_selection_kernel_compiles_for_v5e(one_chip, k, groups):
    """``ops/topk_select.py`` alone at the Ling and Nemotron routers' shapes
    ([8192, 512]: 8 a token under the group limit, 22 without): one Mosaic
    call, no sort beside it."""
    from deepspeed_tpu.ops import topk_select as ts

    assert ts.topk_lowering(8192, 512, k, groups, jnp.float32,
                            tpu=True) == ("pallas", "")
    x = jax.ShapeDtypeStruct((8192, 512), jnp.float32, sharding=one_chip)
    text = jax.jit(lambda x: ts.select_rounds(x, k=k, groups=groups)).lower(
        x).compile().as_text()
    assert len(re.findall(r"custom-call\(.*tpu_custom_call", text)) == 1
    assert not re.findall(r" sort\(", text)


# an expert layer at a cell's shapes: tokens (2 x 8192), width, experts'
# width, experts, held, a token's picks, the buffer's factor, sigmoid scores
CELL_LAYERS = {
    "mellum2": (16384, 2304, 896, 64, 16, 8, 2.0, False),
    "kanana2": (16384, 2048, 768, 128, 16, 6, 2.0, True),
}


@pytest.mark.parametrize("cell", sorted(CELL_LAYERS))
def test_the_layers_gradient_takes_the_row_kernels_on_v5e(one_chip,
                                                         monkeypatch, cell):
    """A cell-shaped expert layer, forward and backward, as if on the chip:
    dispatch, combine and their backwards are row kernels (with the backward's
    second fetch of the rows, five of them, and four packings) and no gather
    of rows is left; and the router (softmax in Mellum2, sigmoid scores with a
    selection bias in Kanana-2) holds no gather and no scatter: the chosen
    scores, the counts and each pair's row are compares and sums; nor does
    the combine's backward, whose two scalars a row ride sorts."""
    from deepspeed_tpu.moe import sharded_moe as sm
    from deepspeed_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    S, D, F, E, held, k, factor, sigmoid = CELL_LAYERS[cell]

    class Share:
        top_k = k
        moe_kernel = "ragged"
        moe_experts_held = held
        moe_first_expert = 0
        moe_ep_capacity_factor = factor
        moe_scoring = "sigmoid" if sigmoid else "softmax"
        moe_routed_scale = 2.448

    def loss(h, w):
        out, aux = sm.grouped_moe_mlp_block(h, w, Share, kernel="ragged")
        lb = aux["lb"] if isinstance(aux, dict) else aux
        return (out.astype(jnp.float32) ** 2).sum() + lb

    def arg(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    w = {"router": arg((D, E)), "w_gate": arg((held, D, F)),
         "w_up": arg((held, D, F)), "w_down": arg((held, F, D))}
    if sigmoid:
        w["router_bias"] = arg((E,))
    before = lowerings.snapshot()
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        arg((2, S // 2, D), jnp.bfloat16), w).compile().as_text()
    assert lowerings.since(before)["moe_dispatch"] == {"pallas": 4}
    by = {name: len(re.findall(r"custom-call\(.*tpu_custom_call.*"
                               rf"jit\({name}\)/", text))
          for name in ("pack_rows", "rows_of_tokens", "sum_of_rows", "gmm",
                       "tgmm")}
    assert by == {"pack_rows": 4, "rows_of_tokens": 3, "sum_of_rows": 2,
                  "gmm": 5, "tgmm": 3}
    assert not re.search(r"bf16\[(65536|16384),%d\]\S* gather\(" % D, text)
    assert "moe_router" in text
    assert _index_ops_under(text, "moe_router") == []
    # nor does the combine's backward: a row's weight rode the router's sort
    # and the rows' dots come back to pair order by a sort of their own
    assert _index_ops_under(text, "moe_dispatch") == []
    assert len(re.findall(r" sort\(", text)) >= 2


def test_an_expert_layer_keeps_no_one_hot_of_the_picks():
    """What a Nemotron-sized expert layer (8192 tokens, 22 of 512 experts a
    token, 8 held, sigmoid scores) keeps from its forward for its backward
    holds no array of tokens x picks x experts elements: the pick's
    derivative compares ``idx`` again (92 MB a layer as booleans otherwise,
    under the cell's ``none``). Traced only: nothing runs."""
    from deepspeed_tpu.moe import sharded_moe as sm

    S, D, Z, F, E, held, k = 8192, 4096, 1024, 2688, 512, 8, 22

    class Share:
        top_k = k
        moe_kernel = "ragged"
        moe_experts_held = held
        moe_first_expert = 0
        moe_ep_capacity_factor = 8.0
        moe_scoring = "sigmoid"
        moe_routed_scale = 5.0
        activation = "relu2"

    def layer(h, w):
        out, aux = sm.grouped_moe_mlp_block(h, w, Share, kernel="ragged")
        return out, aux["lb"]

    def arg(*shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt)

    w = {"router": arg(D, E), "router_bias": arg(E),
         "latent_down": arg(D, Z), "latent_up": arg(Z, D),
         "w_up": arg(held, Z, F), "w_down": arg(held, F, Z)}
    kept = jax.tree_util.tree_leaves(jax.eval_shape(
        lambda h, w: jax.vjp(layer, h, w)[1], arg(1, S, D, dt=jnp.bfloat16),
        w))
    assert kept and max(x.size for x in kept) < S * k * E
    assert any(x.size == S * E for x in kept)        # the scores are there


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


def _cell_step_program(one_chip, monkeypatch, config: str, modelcfg: str,
                       parameters: int, seq: int = 4096, rows: int = 1,
                       position_axes: int = 0, noised: bool = False,
                       **overrides):
    """A benchmark cell's whole step (``benchmarks/configs/<config>.json``
    at ``rows`` x ``seq`` tokens, the file's recomputation policy): gradient and
    AdamW over fp32 master weights, compiled for the chip, every picker
    answering as on a TPU. With ``position_axes`` the batch also holds
    ``position_ids`` [axes, rows, seq], with ``noised`` a block-diffusion
    batch's ``noised_ids`` and ``loss_weights``; ``overrides`` go to the
    file's mapping (another recomputation policy)."""
    import importlib
    import json
    import os

    import optax

    import deepspeed_tpu.ops as ops
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.ops import (causal_conv, delta_rule, grouped_matmul,
                                   ssd_scan, topk_select)
    from deepspeed_tpu.ops import flash_attention as fa
    from deepspeed_tpu.runtime.optimizers import build_optimizer

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    for module in (fa, delta_rule, ssd_scan, causal_conv, grouped_matmul,
                   topk_select):
        monkeypatch.setattr(module, "_on_tpu", lambda: True)
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmarks", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    model = TransformerLM(importlib.import_module(
        "benchmarks." + modelcfg).transformer_config(
            cfg, max_seq_len=seq, param_dtype="float32", **overrides))
    tx = build_optimizer("adamw", {"lr": 1e-6}, lr_schedule=None,
                         gradient_clipping=0.0)

    def step(params, opt, batch):
        (loss, parts), grads = jax.value_and_grad(
            model.loss_and_parts, has_aux=True)(params, batch)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, loss, parts

    def described(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    params = jax.eval_shape(model.init, jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) \
        == parameters
    batch = {"input_ids": jax.ShapeDtypeStruct((rows, seq), jnp.int32,
                                               sharding=one_chip)}
    if position_axes:
        batch["position_ids"] = jax.ShapeDtypeStruct(
            (position_axes, rows, seq), jnp.int32, sharding=one_chip)
    if noised:
        batch["noised_ids"] = batch["input_ids"]
        batch["loss_weights"] = jax.ShapeDtypeStruct(
            (rows, seq), jnp.float32, sharding=one_chip)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        described(params), described(jax.eval_shape(tx.init, params)),
        batch).compile()
    mem = compiled.memory_analysis()
    # fp32 weights, Adam m and v: 12 B a parameter as arguments
    assert mem.argument_size_in_bytes == pytest.approx(12 * parameters,
                                                       rel=1e-3)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.0e9
    return compiled.as_text(), mem


#: ``op_name`` of the Mosaic calls under ``/<scope>/``
_kernel_calls = steplog.kernel_calls


def _conv_kernels_alone_under(text, scope, forwards, backwards):
    """Under ``attn/<scope>``: ``forwards`` forward kernels, half of them in
    the backward's recomputed region, ``backwards`` backward kernels, and
    beside them no padded copy and no shifted multiply-add."""
    calls = _kernel_calls(text, scope)
    assert calls and all(f"/attn/{scope}/" in n for n in calls)
    fwd = [n for n in calls if "jit(conv_fwd)" in n]
    assert len(fwd) == forwards and len(calls) == forwards + backwards
    assert sum("rematted_computation" in n for n in fwd) == forwards // 2
    assert all("jit(conv_bwd)" in n and "transpose(" in n
               for n in calls if n not in fwd)
    beside = {n.rsplit("/", 1)[-1] for n in re.findall(
        r'op_name="([^"]*)"', text) if f"/{scope}/" in n}
    assert not beside & {"pad", "mul", "add", "logistic", "reduce_sum"}, beside


def test_the_granite_cells_step_program_compiles_for_v5e(one_chip,
                                                         monkeypatch):
    """The whole step at the benchmark cell's size (``benchmarks/configs/
    granite4_h_micro_train_d10v8.json``: one period at the published widths,
    12544 rows, ``full`` recomputation). It fits beside what a chip
    reserves with no more temporaries than before the convolution's kernels
    (4,497,313,280), and each of the two runs of state-space layers holds
    the convolution's forward kernel twice (once recomputed) and its
    backward once under ``attn/ssm_conv``, beside the scan's under
    ``attn/ssm_scan``; under ``full`` nothing a kernel named is kept, so the
    attention layer's flash forward is there twice, once recomputed, as each
    scan's and each convolution's is (what ROADMAP A18 is for)."""
    text, mem = _cell_step_program(
        one_chip, monkeypatch, "granite4_h_micro_train_d10v8",
        "modelcfg_granite4h", 772_160_448)
    assert mem.temp_size_in_bytes < 4.5e9
    flash = _kernel_calls(text, "attn_full")
    assert len(flash) == 3
    assert steplog.recomputed_kernels(text) == {
        "attn_full": 1, "ssm_conv": 2, "ssm_scan": 2}
    _conv_kernels_alone_under(text, "ssm_conv", forwards=4, backwards=2)
    scan = _kernel_calls(text, "ssm_scan")
    assert sum("jit(ssd_fwd)" in n for n in scan) == 4
    assert sum("jit(ssd_bwd)" in n for n in scan) == 2
    assert not any("conv_" in n for n in scan)


def test_the_olmo_hybrid_cells_step_program_compiles_for_v5e(one_chip,
                                                             monkeypatch):
    """The whole step at the benchmark cell's size (``benchmarks/configs/
    olmo_hybrid_7b_train_d4h15v8.json``: one period at the published widths,
    15 of 30 heads, 12544 rows, ``dots_saveable``). It fits beside what a
    chip reserves, the full layer runs the flash kernels (the forward once:
    the policy keeps what its rule named), the delta layers' rule is there
    under its scope and is not run again in the backward, and
    each delta layer's three convolutions are kernels under
    ``attn/delta_conv`` (run again there: a kernel is no dot), none of them
    under ``delta_scan``, where the benchmark's reader would take one for a
    second forward of the rule."""
    text, mem = _cell_step_program(
        one_chip, monkeypatch, "olmo_hybrid_7b_train_d4h15v8",
        "modelcfg_olmo_hybrid", 766_241_946)
    # the full layer's flash kernels, the forward once; what is still run
    # again is the convolutions' forward, three a delta layer
    flash = _kernel_calls(text, "attn_full")
    assert len(flash) == 2 and sum("transpose(" in n for n in flash) == 1
    assert steplog.recomputed_kernels(text) == {"delta_conv": 9}
    assert mem.temp_size_in_bytes < 4.07e9
    calls = _kernel_calls(text, "delta_scan")
    assert sum("jit(rule_fwd)" in n for n in calls) == 3
    assert sum("jit(rule_bwd)" in n for n in calls) == 3
    assert not any("rematted_computation" in n for n in calls)
    assert not any("conv_" in n for n in calls)
    _conv_kernels_alone_under(text, "delta_conv", forwards=18, backwards=9)


def test_the_nemotron_cells_step_program_compiles_for_v5e(one_chip,
                                                          monkeypatch):
    """The whole step at the benchmark cell's size (``benchmarks/configs/
    nemotron3_super_120b_train_d11h16e8v8.json``: one period of 11 one-branch
    layers at the published widths, 1 x 8192 tokens, no recomputation). It
    fits beside what a chip reserves; each of the five expert layers runs
    the grouped products (two forward, two ``gmm`` and two ``tgmm``
    backward: an expert is two products) and the row kernels at a latent of
    1024 and 22 pairs a token under ``moe``, each of the five Mamba layers
    the scan's and the convolution's kernels at chunk 128 and one group
    under ``attn``, and the attention layer the flash kernels under
    ``attn/attn_full``, whose instructions keep the names the benchmark's
    patterns look for; under ``moe_router`` and ``moe_dispatch`` nothing
    is gathered or scattered."""
    snap = lowerings.snapshot()
    text, mem = _cell_step_program(
        one_chip, monkeypatch, "nemotron3_super_120b_train_d11h16e8v8",
        "modelcfg_nemotron_h", 700_865_520, seq=8192)
    counted = lowerings.since(snap)
    assert mem.temp_size_in_bytes < 6.4e9
    experts = _kernel_calls(text, "moe_experts")
    assert sum("jit(gmm)" in n for n in experts) == 5 * 4
    assert sum("jit(tgmm)" in n for n in experts) == 5 * 2
    moves = _kernel_calls(text, "moe_dispatch")
    assert sum("jit(sum_of_rows)" in n for n in moves) == 5 * 2
    assert sum("jit(rows_of_tokens)" in n for n in moves) == 5 * 3
    assert all("/moe/" in n for n in experts + moves)
    scan = _kernel_calls(text, "ssm_scan")
    assert sum("jit(ssd_fwd)" in n for n in scan) == 5
    assert sum("jit(ssd_bwd)" in n for n in scan) == 5
    conv = _kernel_calls(text, "ssm_conv")
    assert sum("jit(conv_fwd)" in n for n in conv) == 5
    assert sum("jit(conv_bwd)" in n for n in conv) == 5
    assert len(_kernel_calls(text, "attn_full")) == 2
    assert len(re.findall(r"^\s*%attn_full[.\d]* = .*custom-call\(.*"
                          r"tpu_custom_call", text, re.M)) == 2
    assert "/moe/moe_latent/" in text and "ragged-dot" not in text
    # the router's chosen scores, counts and rows: no gather, no scatter;
    # nor the combine's backward (a row's weight, a pair's dot)
    assert "/moe/moe_router/" in text
    assert _index_ops_under(text, "moe_router") == []
    assert _index_ops_under(text, "moe_dispatch") == []
    # 22 of 512 a token by the selection kernel, once a layer (no
    # recomputation): no sort of the scores
    assert counted["moe_topk"] == {"pallas": 1}
    _picks_without_a_sort(text, calls=5)


def test_the_lfm2_cells_step_program_compiles_for_v5e(one_chip, monkeypatch):
    """The whole step at the benchmark cell's size (``benchmarks/configs/
    lfm2_24b_train_d5e8v8.json``: a dense conv layer and one period, full,
    conv, conv, conv, at the published widths, 2 x 8192 tokens,
    ``dots_saveable``). It fits beside what a chip reserves; each of the two
    runs of conv layers holds the convolution's forward kernel twice (once
    in the backward's recomputed region: a kernel is no dot) and its
    backward once under ``attn/sconv_conv``, **without an activation and
    not as the padded float32 form**, with the two gates' products beside
    them and the projections under ``attn/sconv_proj``; the attention layer
    runs the flash kernels at 64-wide heads under ``attn/attn_full``, whose
    instructions keep the names the benchmark's patterns look for, behind
    the per-head norm and the rope, the forward once (its rule names what
    its backward reads and the policy keeps the names: 7.054 GB of
    temporaries became 7.189, one ``[2, 32, 8192, 64]`` output whose 64
    columns take a 128-lane tile in HBM, 134 MB, and its rows); the four
    routed layers run the grouped products and the row kernels under
    ``moe``, and under ``moe_router`` and ``moe_dispatch`` nothing is
    gathered or scattered."""
    text, mem = _cell_step_program(
        one_chip, monkeypatch, "lfm2_24b_train_d5e8v8", "modelcfg_lfm2",
        469_285_248, seq=8192, rows=2)
    assert mem.temp_size_in_bytes < 7.25e9
    calls = _kernel_calls(text, "sconv_conv")
    assert calls and all("/attn/sconv_conv/" in n for n in calls)
    fwd = [n for n in calls if "jit(conv_fwd)" in n]
    bwd = [n for n in calls if "jit(conv_bwd)" in n]
    assert len(fwd) == 4 and len(bwd) == 2 and len(calls) == 6
    assert sum("rematted_computation" in n for n in fwd) == 2
    assert all("transpose(" in n for n in bwd)
    beside = {n.rsplit("/", 1)[-1] for n in re.findall(
        r'op_name="([^"]*)"', text) if "/sconv_conv/" in n}
    assert "mul" in beside                      # the two gates
    assert not beside & {"pad", "logistic", "reduce_sum"}, beside
    assert "/attn/sconv_proj/" in text
    assert not _kernel_calls(text, "sconv_proj")
    # one forward and one backward; what the policy still pays for twice
    # names no flash kernel
    flash = _kernel_calls(text, "attn_full")
    assert len(flash) == 2 and sum("transpose(" in n for n in flash) == 1
    again = steplog.recomputed_kernels(text)
    assert set(again) == {"sconv_conv", "moe_dispatch", "moe_experts"}
    assert again["sconv_conv"] == 2
    assert len(re.findall(r"^\s*%attn_full[.\d]* = .*custom-call\(.*"
                          r"tpu_custom_call", text, re.M)) == 2
    experts = _kernel_calls(text, "moe_experts")
    assert any("jit(gmm)" in n for n in experts)
    assert any("jit(tgmm)" in n for n in experts)
    moves = _kernel_calls(text, "moe_dispatch")
    assert any("jit(rows_of_tokens)" in n for n in moves)
    assert any("jit(sum_of_rows)" in n for n in moves)
    assert all("/moe/" in n for n in experts + moves)
    assert "ragged-dot" not in text and "/mlp/" in text
    assert "/moe/moe_router/" in text
    assert _index_ops_under(text, "moe_router") == []
    assert _index_ops_under(text, "moe_dispatch") == []


def test_the_ling3_cells_step_program_compiles_for_v5e(one_chip, monkeypatch):
    """The whole step at the benchmark cell's size (``benchmarks/configs/
    ling3_flash_train_d7h16e8v8.json``: a dense KDA layer and one period,
    three KDA layers, latent attention, two KDA layers, 16 of 32 heads, at
    the published widths, 1 x 8192 tokens, ``attn_saveable``). It fits beside what a
    chip reserves; the rule with a decay a key channel is its two Mosaic
    kernels under ``attn/kda_scan`` (``ops/kda_rule.py``), counted twice a
    traced body (the rule and the kernels' own backward), and each of the
    three bodies holds the forward kernel once (``attn_saveable`` keeps the
    output and the chunks' states its forward rule named, so the backward's
    region holds no second run and ``recomputed_kernels`` does not list
    ``kda_scan``) and the backward kernel once; the output norm and head
    gate's two row kernels under ``attn/kda_gate``
    (``ops/head_norm_gate.py``) still run the forward twice a body: the gate
    names nothing, its ``y`` is a pass over the kept ``o`` at the bytes'
    time, and keeping it would cost what ``o`` costs again; no array tiled
    over the heads (``[1, 8192, 16, 128]``) and no ``copy``, ``transpose`` or
    ``reshape`` of a whole array under either scope; each of the
    three runs of KDA layers holds the convolution's forward kernel for q,
    k and v twice (once recomputed) and its backward once under
    ``attn/kda_conv``; the latent-attention layer
    runs the flash kernels on 16 heads under ``attn/attn_mla``, the forward
    once (the policy keeps what its rule named), whose
    instructions keep the names the benchmark's patterns look for; the six
    routed layers run the grouped products and the row kernels under
    ``moe``, and under ``moe_router``, group selection and all, and
    ``moe_dispatch`` nothing is gathered or scattered."""
    from deepspeed_tpu.ops import head_norm_gate, kda_rule

    for module in (kda_rule, head_norm_gate):
        monkeypatch.setattr(module, "_on_tpu", lambda: True)
    snap = lowerings.snapshot()
    text, mem = _cell_step_program(
        one_chip, monkeypatch, "ling3_flash_train_d7h16e8v8",
        "modelcfg_ling3", 648_853_344, seq=8192)
    # 6.00 GB as compiled here, beside 7.79 GB of arguments (4.81 before the
    # policy kept the rule's output and states, 168 MB a KDA layer and what
    # the schedule holds beside them: PR 67; 6.22 with the rule's einsum
    # form: PR 54)
    assert mem.temp_size_in_bytes < 6.06e9
    counted = lowerings.since(snap)
    # a dense run, two routed runs: three bodies of a KDA layer, each a rule
    # and the kernels' own backward
    assert counted["kda_scan"] == {"pallas": 3 * 2}
    assert counted["conv"] == {"pallas": 3 * 3 * 2}
    rule = _kernel_calls(text, "kda_scan")
    assert rule and all("/attn/kda_scan/" in n for n in rule)
    assert sum("jit(kda_fwd)" in n for n in rule) == 3
    assert sum("jit(kda_bwd)" in n for n in rule) == 3
    assert not any("rematted_computation" in n for n in rule)
    assert "kda_scan" not in steplog.recomputed_kernels(text)
    # the output norm and the head's gate: the row kernels on o as the rule's
    # kernels wrote it, the forward twice a body (y is not kept) and the
    # backward once, and no array tiled over the heads between the rule's
    # kernels and ``wo``'s product, in either direction
    assert counted["kda_gate"] == {"pallas": 3 * 2}
    gate = _kernel_calls(text, "kda_gate")
    assert gate and all("/attn/kda_gate/" in n for n in gate)
    assert sum("jit(gate_fwd)" in n for n in gate) == 3 * 2
    assert sum("jit(gate_bwd)" in n and "transpose(" in n
               for n in gate) == 3 and len(gate) == 9
    assert sum("rematted_computation" in n for n in gate) == 3
    assert steplog.recomputed_kernels(text)["kda_gate"] == 3
    # nothing under either scope is tiled over the heads, and no array is
    # laid out anew (on a TPU a ``reshape`` that is no bitcast is a copy)
    moved = [ln.strip()[:160] for ln in text.splitlines()
             if re.search(r"/attn/kda_(gate|scan)/", ln)
             and re.search(r"= \S*\[1,8192,16,128\]| (copy|transpose|reshape)"
                           r"\(%\S+\), metadata", ln)
             and not re.search(r"= \w+\[\d*\]", ln)]
    assert not moved, moved[:3]
    conv = _kernel_calls(text, "kda_conv")
    assert conv and all("/attn/kda_conv/" in n for n in conv)
    assert sum("jit(conv_fwd)" in n for n in conv) == 3 * 3 * 2
    assert sum("jit(conv_bwd)" in n for n in conv) == 3 * 3
    assert not _kernel_calls(text, "kda_proj")
    flash = _kernel_calls(text, "attn_mla")
    # one forward and the fused backward: the policy keeps the forward's
    # output and log-sum-exp
    assert len(flash) == 2 and sum("transpose(" in n for n in flash) == 1
    assert "attn_mla" not in steplog.recomputed_kernels(text)
    assert len(re.findall(r"^\s*%attn_mla[.\d]* = .*custom-call\(.*"
                          r"tpu_custom_call", text, re.M)) == 2
    experts = _kernel_calls(text, "moe_experts")
    assert any("jit(gmm)" in n for n in experts)
    assert any("jit(tgmm)" in n for n in experts)
    moves = _kernel_calls(text, "moe_dispatch")
    assert any("jit(rows_of_tokens)" in n for n in moves)
    assert any("jit(sum_of_rows)" in n for n in moves)
    assert all("/moe/" in n for n in experts + moves)
    assert "/moe/moe_shared/" in text and "ragged-dot" not in text
    assert "/moe/moe_router/" in text and "/mlp/" in text
    assert _index_ops_under(text, "moe_router") == []
    assert _index_ops_under(text, "moe_dispatch") == []
    # the group limit and the 8 of 512 as one selection kernel, once a
    # routed body and once more in its recomputed region: no sort of the
    # scores, of a group's or of the groups'
    assert counted["moe_topk"] == {"pallas": 3}
    _picks_without_a_sort(text, calls=3 * 2)
    assert steplog.recomputed_kernels(text)["moe_router"] == 3


def test_the_keye_vl2_cells_step_program_compiles_for_v5e(one_chip,
                                                          monkeypatch):
    """The whole step at the benchmark cell's size (``benchmarks/configs/
    keye_vl2_30b_train_d5e16v8.json``: five layers of grouped-query attention
    over the 2,048 keys a 16-head indexer picks for each query, 16 of 128
    experts, at the published widths, one row of 16,384 positions with its
    positions over three axes, under the file's policy). It fits beside what
    a chip reserves; the indexer's weighted head-score sum and the gradient
    of its loss are the two Mosaic kernels of ``ops/dsa.py`` under
    ``dsa_indexer`` and ``dsa_loss``, one call for each of the four runs'
    key lengths, and **no array of the program holds a query tile's head
    scores** (``[512, 16, S]``: what the einsum form wrote and read four
    times a tile); the selection and the attention over the set are the
    ``jax.numpy`` form, traced once for the scanned layer body and once for
    what the policy leaves to the backward's region; no array of the program
    holds ``[T, T]`` or is as large as two heads' ``[T, T]`` scores, let
    alone 32; the sets are found with no sort; and the five routed layers
    run the grouped products and the row kernels under ``moe``."""
    from deepspeed_tpu.ops import dsa

    T = 16384
    monkeypatch.setattr(dsa, "_on_tpu", lambda: True)
    snap = lowerings.snapshot()
    text, mem = _cell_step_program(
        one_chip, monkeypatch, "keye_vl2_30b_train_d5e16v8",
        "modelcfg_keye_vl2", 562_290_560, seq=T, position_axes=3)
    # 7.405 GB of temporaries as compiled here under the file's
    # "attn_saveable" (7.41 with the einsum form, PR 56: the peak is not in
    # the indexer's tiles), beside 6.75 GB of arguments
    assert mem.temp_size_in_bytes < 7.45e9
    counted = lowerings.since(snap)
    # the scanned layer body and the custom_vjp's own forward rule
    assert counted["dsa"] == {"pallas": 2}
    scores = _kernel_calls(text, "dsa_indexer")
    grads = _kernel_calls(text, "dsa_loss")
    assert scores and all("jit(dsa_index_fwd)" in n for n in scores)
    assert grads and all("jit(dsa_index_bwd)" in n for n in grads)
    assert len(scores) == len(grads) == len(dsa.runs(T, 512))
    assert not re.search(r"\[512,16,\d{4,}\]", text)
    largest = max(
        int(np_prod) for np_prod in (
            eval("*".join(dims.split(",")))  # noqa: S307 (digits and commas)
            for dims in re.findall(r"[a-z]+\d*\[([\d,]+)\]", text)))
    # the five layers' kept outputs [5, 1, T, 32, 128] are the largest, 336M
    # elements, then the logits [T, 18992], 311M; two heads' [T, T] would
    # be 537M
    assert largest == 5 * T * 32 * 128 < 2 * T * T
    assert f"{T},{T}" not in text
    under = lambda scope: [n for n in re.findall(  # noqa: E731
        r'op_name="([^"]*)"', text) if f"/{scope}/" in n]
    assert not any(n.rsplit("/", 1)[-1] in ("sort", "top_k", "approx_top_k")
                   for n in under("attn_dsa"))
    for scope in ("dsa_indexer", "dsa_select", "dsa_attend", "dsa_loss"):
        assert under(scope), scope
    assert any("while" in n for n in under("dsa_select"))
    experts = _kernel_calls(text, "moe_experts")
    assert any("jit(gmm)" in n for n in experts)
    assert any("jit(tgmm)" in n for n in experts)
    moves = _kernel_calls(text, "moe_dispatch")
    assert any("jit(rows_of_tokens)" in n for n in moves)
    assert all("/moe/" in n for n in experts + moves)


def test_the_laguna_cells_step_program_compiles_for_v5e(one_chip,
                                                        monkeypatch):
    """The whole step at the benchmark cell's size (``benchmarks/configs/
    laguna_s21_train_d5h24e8v8.json``: a full dense layer, three window
    layers and a full routed one at the published widths, 24 of 48 and 36 of
    72 query heads over 4 of 8 key-value heads, 8 of 256 experts, one row of
    8,192 positions, under the file's policy). It fits beside what a chip
    reserves; **one program holds the flash kernels at two shapes**, window
    512 at 36 heads in groups of 9 and full at 24 heads in groups of 6,
    forward and fused backward, under ``attn/attn_window`` and
    ``attn/attn_full`` with the names the benchmark's patterns look for; the
    head gate's operations lie under ``attn_gate`` inside either kind's
    scope and are no kernel; the dense layer's FFN stands under ``mlp`` and
    the four routed layers run the grouped products, the row kernels and
    the shared expert under ``moe``. The fused backward's counter reads both
    kinds: a window head works 31 of its crossed tiles' 60 sub-blocks, a full
    head 24 of 32."""
    before = lowerings.snapshot()
    text, mem = _cell_step_program(
        one_chip, monkeypatch, "laguna_s21_train_d5h24e8v8",
        "modelcfg_laguna", 672_126_976, seq=8192)
    assert lowerings.since(before)["flash_bwd_tiles"] == {
        512: FLASH_BWD_TILES["laguna-window-cell"],
        "causal": dict(masked=8, unmasked=28, dead=28, sub_live=24,
                       sub_dead=8, sub_inside=8)}
    # 6.33 GB of temporaries as compiled here under the file's
    # "dots_saveable" (this plain step casts its weights inside: the
    # engine's own, with the carried copy, compiles to 4.84 beside 9.41 GB
    # of arguments), beside 8.07 GB of arguments
    assert mem.temp_size_in_bytes < 6.5e9
    # the policy keeps the forward kernels' named results: neither kind's
    # forward runs again in the backward's region
    assert not {"attn_window", "attn_full"} & set(
        steplog.recomputed_kernels(text))
    shapes = {"attn_window": 36, "attn_full": 24}
    for scope, H in shapes.items():
        calls = _kernel_calls(text, scope)
        assert calls and all(f"/attn/{scope}/" in n for n in calls)
        assert any("transpose(" in n for n in calls)
        fwd = re.findall(
            rf"^\s*%{scope}[.\d]* = \(bf16\[1,{H},8192,128\]\S*, "
            rf"f32\[1,{H},1,8192\]\S*\) custom-call\(.*tpu_custom_call",
            text, re.M)
        bwd = re.findall(
            rf"^\s*%{scope}[.\d]* = \(bf16\[1,{H},8,1024,128\]\S*, "
            rf"bf16\[2,1,{H},8192,128\]\S*\) custom-call\(.*tpu_custom_call",
            text, re.M)
        assert fwd and bwd, scope
        # nothing of the other kind's width under this kind's scope
        other = next(h for s, h in shapes.items() if s != scope)
        assert not re.search(rf"^\s*%{scope}[.\d]* = \(bf16\[1,{other},",
                             text, re.M)
    gate = [n for n in re.findall(r'op_name="([^"]*)"', text)
            if "/attn_gate/" in n]
    assert gate and all("/attn/attn_window/attn_gate/" in n
                        or "/attn/attn_full/attn_gate/" in n for n in gate)
    assert not _kernel_calls(text, "attn_gate")
    experts = _kernel_calls(text, "moe_experts")
    assert any("jit(gmm)" in n for n in experts)
    assert any("jit(tgmm)" in n for n in experts)
    moves = _kernel_calls(text, "moe_dispatch")
    assert any("jit(rows_of_tokens)" in n for n in moves)
    assert all("/moe/" in n for n in experts + moves)
    assert "/moe/moe_shared/" in text and "ragged-dot" not in text
    assert "/moe/moe_router/" in text and "/mlp/" in text


def test_the_qwen3_next_cells_step_program_compiles_for_v5e(one_chip,
                                                            monkeypatch):
    """The whole step at the benchmark cell's size (``benchmarks/configs/
    qwen3_next_80b_train_d4e32v8.json``: one period at the published widths,
    three gated delta-rule layers whose 16 key heads serve 32 value heads at
    128 / 128 and one gated full-attention layer of 256-wide heads, 32 of 512
    experts beside the gated shared one, one row of 16,384 positions, under
    the file's policy). It fits beside what a chip reserves; **the delta
    layers take the rule's Mosaic kernels, the forward once a layer**
    (``attn_saveable`` keeps the output and the chunks' states the forward
    rule named), whose q and k operands are the ``[1, T, 16 x 128]`` arrays
    the convolutions wrote (float32, handed over
    by the convolution kernels with no copy between), read once a key head,
    and no ``[1, T, 32, 128]`` array stands under ``delta_scan`` (a repeat of
    q or k to the value heads would be one); the full layer runs the flash
    kernels at d 256, its backward the fused kernel in two segments of
    8,192 rows (a head's dq of 16,384 x 256 is past its budget, a
    segment's fits); the gate a channel lies under
    ``attn_gate``; the router picks 10 of 512 in the selection kernel and the
    four routed layers run the grouped products and the row kernels under
    ``moe``."""
    T = 16384
    snap = lowerings.snapshot()
    text, mem = _cell_step_program(
        one_chip, monkeypatch, "qwen3_next_80b_train_d4e32v8",
        "modelcfg_qwen3_next", 625_667_136, seq=T)
    # 5.36 GB of temporaries as compiled here under the file's
    # "attn_saveable", beside 7.51 GB of arguments (4.71 before the policy
    # kept the rule's output and states, 671 MB a delta layer: PR 67; 4.18
    # under "full"; 7.21 under "dots_saveable" then)
    assert mem.temp_size_in_bytes < 5.42e9
    counted = lowerings.since(snap)
    assert counted["delta_scan"] == {"pallas": 6}     # three rules, and back
    assert counted["delta_qk_rows"] == {"pallas": 2 * T * 16 * 3}
    assert counted["flash_bwd"] == {"fused": 1}
    assert counted["flash_bwd_arm"] == {256: "fused"}
    assert counted["flash_bwd_segments"] == {256: 2}
    assert counted["moe_topk"] == {"pallas": 4}
    assert set(counted["conv"]) == set(counted["moe_grouped"]) == {"pallas"}
    rules = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "/delta_scan/" in line]
    fwd = [r for r in rules if "jit(rule_fwd)" in r]
    bwd = [r for r in rules if "jit(rule_bwd)" in r]
    assert len(bwd) == len(fwd) == 3
    assert not any("rematted_computation" in r for r in fwd)
    assert steplog.recomputed_kernels(text) == {
        "delta_conv": 9, "moe_router": 4, "moe_dispatch": 12,
        "moe_experts": 12}
    # the kept output is held tiled over the heads, the form the gated
    # norm's ``jax.numpy`` lines read it in, not the kernel's (the heads in
    # lanes): with the output named as the kernel wrote it the step kept
    # [1, T, 4096] and ``delta_gate`` read 40.5 ms a step on the chip where
    # it reads 20.0 with this (``PERF.md`` section 6, PR 67)
    assert len(re.findall(r"= bf16\[2048,8,32,128\]\S* copy\(", text)) == 3
    for call in rules:
        # q and k as the convolutions left them, v a value head's columns
        assert call.count(f"f32[1,{T},2048]{{2,1,0}}") >= 2, call[:200]
        assert f"bf16[1,{T},4096]{{2,1,0}}" in call
    assert all(re.search(r"custom-call\(%conv_fwd[.\d]*, %conv_fwd", r)
               for r in fwd)
    for line in text.splitlines():
        if "/delta_scan/" in line:
            assert f"[1,{T},32,128]" not in line.split(" = ")[1][:60], line
    flash = _kernel_calls(text, "attn_full")
    # the backward is one call: dq by q-tile, and dk and dv stacked, a
    # partial a segment (what metrics/flash_bwd_roofline.gdn.json finds)
    assert len([n for n in flash if "transpose(" in n]) == 1
    assert re.search(rf"^\s*%attn_full[.\d]* = \(bf16\[1,16,{T // 1024},1024,"
                     rf"256\]\S*, bf16\[2,2,1,16,{T},256\]\S*\) custom-call\(",
                     text, re.M)
    assert re.search(rf"bf16\[1,16,{T},256\]", text)
    gate = [n for n in re.findall(r'op_name="([^"]*)"', text)
            if "/attn_full/attn_gate/" in n]
    assert gate and not any(n.endswith("dot_general") for n in gate)
    experts = _kernel_calls(text, "moe_experts")
    assert any("jit(gmm)" in n for n in experts)
    assert any("jit(tgmm)" in n for n in experts)
    moves = _kernel_calls(text, "moe_dispatch")
    assert any("jit(rows_of_tokens)" in n for n in moves)
    assert all("/moe/" in n for n in experts + moves)



#: temporaries of the SDAR cell's step as compiled here, by policy (bytes,
#: upper bounds a hundredth above what was read: 7.209 and 5.878 GB, beside
#: 6.61 GB of arguments; 8.048 and 6.270 before PR 65, with a third flash
#: call, a merge and the halves joined at the heads' width). The test
#: compiles the file's policy; the other is ``remat_policy="full"`` passed to
#: the same helper (70 s more)
SDAR_TEMP = {"attn_saveable": 7.3e9, "full": 5.95e9}


def test_the_sdar_cells_step_program_compiles_for_v5e(one_chip, monkeypatch,
                                                      policy="attn_saveable"):
    """The whole step at the benchmark cell's size (``benchmarks/configs/
    sdar_30b_a3b_train_d5e16v8.json``: five layers of grouped-query attention
    trained by block diffusion, 16 of 128 experts, at the published widths,
    one row of 8,192 tokens as 16,384 positions, under the file's policy).
    It fits beside what a chip reserves; each layer's attention is
    **two calls of the flash kernels over the clean keys under the rounded
    diagonal**, forward and fused backward, under ``attn/attn_full/bd_cross``
    with the names the benchmark's patterns look for, each over 8,192 query
    rows and 8,192 clean keys; the noised half's takes its own noised block
    as a second key source (two more operands of 8,192 rows, the own tile a
    q-tile on a grid step the call has, the four of its sixteen 256-wide
    sub-blocks that keep a pair worked; its gradients two more slabs of the
    backward's stacked result), and nothing stands under a scope ``bd_own``;
    **no array of the program is as
    large as three heads' ``[L, L]`` scores and none has a query and a key
    dimension, let alone ``[2L, 2L]``**; no
    gather or scatter stands under the mixer's scopes (the repeated positions
    read the rope's table once a layer, a position); the head's product runs
    over 8,192 rows, not 16,384; and the five routed layers run the grouped
    products and the row kernels under ``moe`` over both halves."""
    import json
    import os

    L = 8192
    with open(os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "benchmarks", "configs",
            "sdar_30b_a3b_train_d5e16v8.json")) as f:
        assert json.load(f)["deployment"]["remat_policy"] == policy
    before = lowerings.snapshot()
    text, mem = _cell_step_program(
        one_chip, monkeypatch, "sdar_30b_a3b_train_d5e16v8", "modelcfg_sdar",
        550_984_960, seq=L, noised=True, remat_policy=policy)
    print(f"sdar temp {policy}: {mem.temp_size_in_bytes / 1e9:.3f} GB, "
          f"arguments {mem.argument_size_in_bytes / 1e9:.3f} GB")
    assert mem.temp_size_in_bytes < SDAR_TEMP[policy]
    counted = lowerings.since(before)
    arms = dict(masked=8, unmasked=28, dead=28, sub_live=24, sub_dead=8,
                sub_inside=8)
    assert counted["flash_bwd_tiles"] == {
        "diag4": arms, "diag4_strict": arms,
        # (a head's eight own tiles of 1,024, a q-tile each: of a tile's
        # sixteen sub-blocks of 256 the four on its diagonal)
        "diag4_own": dict(masked=8, unmasked=0, dead=0, sub_live=32,
                          sub_dead=96, sub_inside=0)}
    assert counted["flash_diag_fwd_tiles"] == counted["flash_bwd_tiles"]
    assert counted["flash_bwd"] == {"fused": 2}
    # a layer's noised call, and as often its clean call (each lowering,
    # forward or backward, counts)
    own = counted["flash_own_keys"]
    assert set(own) == {"operand", "none"} and own["operand"] == own["none"]
    calls = _kernel_calls(text, "bd_cross")
    assert calls and all("/attn/attn_full/bd_cross/" in n for n in calls)
    assert any("transpose(" in n for n in calls)
    assert "bd_own" not in text
    fwd = re.findall(
        r"^\s*%bd_cross[.\d]* = \(bf16\[1,32,8192,128\]\S*, "
        r"f32\[1,32,1,8192\]\S*\) custom-call\(.*tpu_custom_call", text, re.M)
    # dq, and the keys' and values' gradients stacked: the clean call's two,
    # the noised call's two and after them its own keys' two
    bwd = re.findall(
        r"^\s*%bd_cross[.\d]* = \(bf16\[1,32,8,1024,128\]\S*, "
        r"bf16\[[24],1,32,8192,128\]\S*\) custom-call\(.*tpu_custom_call",
        text, re.M)
    assert sorted(re.search(r"bf16\[([24]),1,32,8192", b).group(1)
                  for b in bwd) == ["2", "4"]
    # a layer's two halves forward (the body of the layer scan; once more in
    # the backward's region where the policy keeps nothing of them) and
    # backward
    again = "bd_cross" in steplog.recomputed_kernels(text)
    assert again == (policy == "full")
    assert len(fwd) == (4 if again else 2) and len(bwd) == 2
    # no Mosaic call of the mixer takes an operand of 16,384 rows
    for line in text.splitlines():
        if "tpu_custom_call" in line and "%bd_cross" in line:
            assert ",16384," not in line.split("custom-call(")[1] \
                .split("custom_call_target")[0]
    sizes = [eval("*".join(dims.split(",")))  # noqa: S307 (digits and commas)
             for dims in re.findall(r"[a-z]+\d*\[([\d,]+)\]", text)]
    # the largest array is the five layers' kept inputs [5, 1, 2L, 2048];
    # one head's [L, L] scores would be two fifths of it, 32 heads' thirteen
    # times it; no array has a query and a key dimension
    assert max(sizes) == 5 * 2 * L * 2048 < 3 * L * L
    for dims in (f"{2 * L},{2 * L}", f"{L},{L}", f"{2 * L},{L}",
                 f"{L},{2 * L}"):
        assert dims not in text
    names = re.findall(r'op_name="([^"]*)"', text)
    mine = [n for n in names if "/bd_cross/" in n]
    assert mine
    assert not any(n.rsplit("/", 1)[-1].startswith(("gather", "scatter"))
                   for n in mine)
    # the head reads the noised half alone
    assert re.search(r"bf16\[1,8192,18992\]", text)
    assert not re.search(r"\[1,16384,18992\]", text)
    experts = _kernel_calls(text, "moe_experts")
    assert any("jit(gmm)" in n for n in experts)
    assert any("jit(tgmm)" in n for n in experts)
    moves = _kernel_calls(text, "moe_dispatch")
    assert any("jit(rows_of_tokens)" in n for n in moves)
    assert all("/moe/" in n for n in experts + moves)


def _sharded_step_text(monkeypatch, mesh_axes, stage, cfg_kw, rows, seq):
    """The engine's own ``ds_train_step`` over a described v5e 2x2, compiled
    and never run: the engine's state and batch are stubbed with
    ``ShapeDtypeStruct``s of its own shardings and the dispatch hands the
    jitted program back (``.claude/skills/verify/SKILL.md``, PR 53)."""
    import time

    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    import deepspeed_tpu as ds
    import deepspeed_tpu.ops as ops
    from deepspeed_tpu.models import TransformerConfig, TransformerLM
    from deepspeed_tpu.ops import flash_attention as fa
    from deepspeed_tpu.parallel import build_mesh
    from deepspeed_tpu.parallel import sharding as shd
    from deepspeed_tpu.runtime.engine import DeepSpeedTpuEngine

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")

    def described(tree, shardings):
        return jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, shardings)

    def init_state(self, config, init_rng, schedule_fn):
        self._offload = None
        self._params = described(self._param_shapes, self.param_sharding)
        self._work = described(
            jax.eval_shape(self._copy_of, self._param_shapes),
            self.work_sharding)
        self.opt_state = described(
            jax.eval_shape(self.tx.init, self._param_shapes),
            self.opt_sharding)
        rep = NamedSharding(self.mesh, P())
        self.scaler_state = {
            "scale": jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
            "good_steps": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)}
        self._grad_acc = self._pending = None
        self._grad_acc_count = 0

    def put_batch(self, batch):
        spec = shd.batch_spec(self.topology)
        self._t_put = time.perf_counter()
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(
                    self.mesh, P(*list(spec)[:x.ndim]))), batch)

    class Captured(Exception):
        pass

    got = {}

    def dispatch(self, key, *args):
        got["fn"], got["args"] = self._fused_step_cache[key], args
        raise Captured()

    monkeypatch.setattr(DeepSpeedTpuEngine, "_init_state", init_state)
    monkeypatch.setattr(DeepSpeedTpuEngine, "_put_batch", put_batch)
    monkeypatch.setattr(DeepSpeedTpuEngine, "_dispatch_fused", dispatch)
    engine, *_ = ds.initialize(
        model=TransformerLM(TransformerConfig(**cfg_kw)),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": stage},
                "steps_per_print": 10 ** 9},
        mesh=build_mesh(axis_sizes=mesh_axes, devices=list(topo.devices)))
    with pytest.raises(Captured):
        engine.fused_train_step(
            {"input_ids": np.zeros((rows, seq), np.int32)})
    with jax.sharding.set_mesh(engine.mesh):
        return got["fn"].lower(*got["args"]).compile().as_text()


def test_the_zero3_steps_collectives_as_the_v5e_compiler_writes_them(
        one_chip, monkeypatch):
    """ZeRO-3 over ``fsdp=4`` of a described v5e 2x2, the engine's own step
    at narrow widths. What the four-chip cell's readers rest on
    (``benchmarks/readers/COLLECTIVES.md``): the compiler writes no trip count
    and the record reads the layer loop's from its condition; the exchanges
    in the loops are asynchronous; every one of them has an owner among the
    step's scopes; and the flash kernels, one a shard inside
    ``_flash_on_mesh``'s ``shard_map``, keep the name of the model's
    innermost scope there, ``attn``, which is what the benchmark's roofline
    readers know them by."""
    from deepspeed_tpu.models.transformer import STEP_SCOPES

    layers = 3
    text = _sharded_step_text(
        monkeypatch, {"fsdp": 4}, 3,
        dict(vocab_size=2048, hidden_size=512, num_layers=layers,
             num_heads=4, num_kv_heads=2, intermediate_size=1024,
             max_seq_len=1024, arch="llama", dtype="bfloat16",
             param_dtype="float32", attention_impl="auto"),
        rows=4, seq=1024)
    assert "known_trip_count" not in text
    rows = steplog.collectives(text)
    assert rows and all(r["unknown_trips"] == 0 for r in rows)
    in_loop = [r for r in rows if r["in_layer_loop"]]
    assert in_loop and {r["trips"] for r in in_loop} == {layers}
    assert {r["trips"] for r in rows if not r["in_layer_loop"]} == {1}
    assert any(r["async"] for r in in_loop)
    assert all(r["scope"] in STEP_SCOPES for r in rows), \
        [r["name"] for r in rows if r["scope"] not in STEP_SCOPES]
    assert {r["scope"] for r in in_loop} <= {"attn", "mlp", "layers"}
    assert all(r["group"] == 4 for r in rows)
    sums = steplog.collective_sums(rows)
    # at least the bf16 weights of the layers thrice over (gathered for the
    # forward, again for the backward, the gradients reduced), 3/4 of each
    layer = 2 * 512 * 512 + 2 * 512 * 256 + 3 * 512 * 1024
    assert sums["collective_bytes_in_layer_loop"] \
        >= 3 * layers * layer * 2 * 3 // 4
    assert set(sums["collective_calls_by_kind"]) \
        <= set(steplog.COLLECTIVE_KINDS)
    # the kernels under the mesh: named for the scope, forward and backward
    kernels = re.findall(r"^\s*%([\w.\-]+) = .* custom-call\(.*"
                         r"tpu_custom_call", text, re.M)
    assert len(kernels) == 2 and all(
        re.fullmatch(r"attn[.\d]*", k) for k in kernels), kernels


def test_the_flash_kernels_keep_each_kinds_scope_on_a_mesh(one_chip,
                                                           monkeypatch):
    """A model whose layers are of two kinds, under the same mesh: the
    kernels inside ``_flash_on_mesh``'s ``shard_map`` are named for the
    scope the model had open at the call, ``attn_window`` and ``attn_full``
    (what the readers of a cell with both tell them apart by), not for one
    name the ops layer chose."""
    text = _sharded_step_text(
        monkeypatch, {"fsdp": 4}, 3,
        dict(vocab_size=2048, hidden_size=512, num_layers=2, num_heads=4,
             num_kv_heads=2, intermediate_size=1024, max_seq_len=1024,
             arch="llama", dtype="bfloat16", param_dtype="float32",
             attention_impl="auto", sliding_window=256,
             attn_pattern=("window", "full")),
        rows=4, seq=1024)
    kernels = re.findall(r"^\s*%([\w\-]+)[.\d]* = .* custom-call\(.*"
                         r"tpu_custom_call", text, re.M)
    assert sorted(kernels) == ["attn_full"] * 2 + ["attn_window"] * 2, kernels

