"""Op numeric-parity tests (pattern: reference ``tests/unit/ops/`` — each custom
kernel vs a plain reference implementation). Pallas kernels run in interpret mode on
the CPU mesh; real-TPU parity is exercised by the verify drive."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.transformer import xla_attention
from deepspeed_tpu.ops import flash_attention as fa, lowerings
from deepspeed_tpu.ops.flash_attention import (flash_attention,
                                               flash_attention_lse)
from deepspeed_tpu.ops.quantization import (
    dequantize_blockwise, dequantize_fp8, quantize_blockwise, quantize_fp8,
)
from deepspeed_tpu.ops.rms_norm import fused_rms_norm


def _qkv(T=64, S=64, H=4, K=4, d=16, dtype=jnp.float32):
    q = jax.random.normal(jax.random.key(1), (1, T, H, d), dtype)
    k = jax.random.normal(jax.random.key(2), (1, S, K, d), dtype)
    v = jax.random.normal(jax.random.key(3), (1, S, K, d), dtype)
    return q, k, v


class TestFlashAttention:
    def test_forward_parity_causal(self):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, causal=True, interpret=True)
        ref = xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_forward_gqa(self):
        q, k, v = _qkv(H=8, K=2)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        ref = xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_noncausal(self):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, causal=False, interpret=True)
        ref = xla_attention(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_backward_parity(self):
        q, k, v = _qkv(T=32, S=32)

        def f_flash(q, k, v):
            return flash_attention(q, k, v, causal=True, interpret=True).sum()

        def f_ref(q, k, v):
            return xla_attention(q, k, v, causal=True).sum()

        g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)

    def test_backward_gqa(self):
        q, k, v = _qkv(T=32, S=32, H=4, K=2)
        g1 = jax.grad(lambda k: flash_attention(q, k, v, interpret=True).sum())(k)
        g2 = jax.grad(lambda k: xla_attention(q, k, v).sum())(k)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=5e-4)

    def test_uneven_block_sizes(self):
        # T=48 not divisible by default blocks → _pick_block must adapt
        q, k, v = _qkv(T=48, S=48)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        ref = xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    @pytest.mark.parametrize("window", [1, 8, 24, 1000])
    def test_sliding_window_forward(self, window):
        """Window masking (mistral/qwen2): parity with the masked XLA path,
        incl. window=1 (self-only), window crossing block boundaries (small
        blocks force multi-block), and window > T (plain causal)."""
        q, k, v = _qkv(T=64, S=64)
        out = flash_attention(q, k, v, causal=True, window=window,
                              block_q=16, block_k=16, interpret=True)
        ref = xla_attention(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_sliding_window_backward(self):
        q, k, v = _qkv(T=32, S=32, H=4, K=2)

        def f_flash(q, k, v):
            return flash_attention(q, k, v, causal=True, window=8,
                                   block_q=8, block_k=8,
                                   interpret=True).sum()

        def f_ref(q, k, v):
            return xla_attention(q, k, v, causal=True, window=8).sum()

        g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


# The fused flash backward against the two split kernels and XLA's autodiff.
# 128-wide tiles over 512 keys: 4 x 4 tile pairs, a [1, 128] row is a legal
# block, and a head's dq fits the VMEM budget, so the shape takes the fused
# kernel in one segment. T != S puts query row t at position t + S - T
# (rel_offset, xla_attention's end-aligned mask); "dlse" sends a cotangent
# into the log-sum-exp output. "sub" stands in for the kernel's 512
# (``fa._SUB``), so that a tile a boundary crosses is worked in sub-blocks, of
# which some are dead, some masked and some wholly inside the band; "tiles"
# pins the counter where the pattern is a training cell's, elsewhere the dense
# mask says what to expect.
#
# "segments": the budget is set to what the fused kernel holds with the dq of
# that share of the head's rows, so the head is worked in that many segments
# of q-tiles by the one call (Qwen3-Next's 16,384 rows at d 256 are two of
# these at the real budget); each segment's dq rows and dk/dv partial are
# held to the split pair run on the segment's rows alone. "long-split" is
# past the real budget: two segments (four in float32), and the split pair
# is still compared through ``_bwd_split_call``; "budget" 1 fits no segment
# and takes the split pair. "dv": values of another width than keys; "dr": the rope columns of q
# and the one rope key as operands, "kv_whole" then keys and values side by
# side in one array; "own": the keys and values at the queries' own positions
# as a second source, in blocks of that length under ``DIAG_BEFORE``.
FUSED_BWD_CASES = {
    "causal-gqa4": dict(H=4, K=1, causal=True),
    "full-mha": dict(H=2, K=2, causal=False),
    "window8-gqa4": dict(H=4, K=1, causal=True, window=8),
    "window200-mha": dict(H=2, K=2, causal=True, window=200, dlse=True),
    "rect-rel-offset-dlse": dict(H=4, K=2, T=256, causal=True, dlse=True),
    "rect-window-gqa4-dlse": dict(H=4, K=1, T=256, causal=True, window=300,
                                  dlse=True),
    "dlse-mha": dict(H=2, K=2, causal=True, dlse=True),
    "long-split": dict(H=1, K=1, T=65536, S=128, causal=False, block=1024,
                       segments=2, segments_f32=4, budget=None),
    "no-segment-fits-split": dict(H=2, K=1, causal=True, budget=1,
                                  took="split"),
    # the causal cells' diagonal tiles (T 4096 in 1,024-wide tiles with an
    # edge of 512 is these 4 x 4 with one of 64)
    "sub-diagonal-the-cells-pattern-gqa4": dict(
        H=4, K=1, causal=True, sub=64,
        tiles=dict(masked=4, unmasked=6, dead=6, sub_live=12, sub_dead=4,
                   sub_inside=4)),
    "sub-diagonal-quarter-edge-mha-dlse": dict(H=2, K=2, causal=True, sub=32,
                                               dlse=True),
    # Laguna's window layers: half a tile, so no sub-block is inside the band
    "sub-window-half-a-tile-gqa4": dict(
        H=4, K=1, causal=True, window=64, sub=64,
        tiles=dict(masked=7, unmasked=0, dead=9, sub_live=15, sub_dead=13,
                   sub_inside=0)),
    # Mellum2's: a tile, on the tiles' edge
    "sub-window-on-a-tile-edge-gqa2-dlse": dict(
        H=4, K=2, causal=True, window=128, sub=64, dlse=True,
        tiles=dict(masked=7, unmasked=0, dead=9, sub_live=21, sub_dead=7,
                   sub_inside=7)),
    "sub-window-ends-inside-a-tile-mha-dlse": dict(
        H=2, K=2, causal=True, window=200, sub=32, dlse=True),
    "sub-window-of-one-key-gqa2": dict(H=4, K=2, causal=True, window=1,
                                       sub=64),
    "sub-rect-rel-offset-gqa2-dlse": dict(H=4, K=2, T=256, causal=True,
                                          sub=32, dlse=True),
    "sub-rect-window-rel-offset-gqa4-dlse": dict(
        H=4, K=1, T=256, causal=True, window=300, sub=32, dlse=True),
    "sub-full-mha": dict(H=2, K=2, causal=False, sub=32),
    # Qwen3-Next's pattern: two segments under the diagonal, the first one's
    # last two kv-tiles dead for every row of it
    "seg2-causal-the-cells-pattern-gqa4": dict(
        H=4, K=1, causal=True, segments=2, sub=64,
        tiles=dict(masked=4, unmasked=6, dead=6, sub_live=12, sub_dead=4,
                   sub_inside=4)),
    "seg4-causal-a-tile-a-segment-gqa2-dlse": dict(H=4, K=2, causal=True,
                                                   segments=4, dlse=True),
    "seg2-full-mha": dict(H=2, K=2, causal=False, segments=2),
    # the second segment's rows keep no key of the first kv-tile, the first
    # segment's none of the last two: dead kv-tiles lead and trail
    "seg2-window100-leading-kv-tiles-dead-gqa4": dict(
        H=4, K=1, causal=True, window=100, segments=2),
    "seg4-window200-mha-dlse": dict(H=2, K=2, causal=True, window=200,
                                    segments=4, dlse=True, sub=32),
    "seg2-rect-rel-offset-gqa2-dlse": dict(H=4, K=2, T=256, causal=True,
                                           segments=2, dlse=True),
    "seg2-rect-window-rel-offset-gqa4-dlse": dict(
        H=4, K=1, T=256, causal=True, window=300, segments=2, dlse=True,
        sub=32),
    "two-widths-gqa2": dict(H=4, K=2, causal=True, d=32, dv=16),
    "seg2-two-widths-gqa2-dlse": dict(H=4, K=2, causal=True, d=32, dv=16,
                                      segments=2, dlse=True),
    "seg2-rope-operands-mha": dict(H=2, K=2, causal=True, dr=8, segments=2),
    "seg4-rope-operands-kv-whole-dlse": dict(
        H=2, K=2, causal=True, dr=8, kv_whole=True, segments=4, dlse=True),
    "own-keys-gqa2": dict(H=4, K=2, causal=True, own=4),
    "seg2-own-keys-gqa2": dict(H=4, K=2, causal=True, own=4, segments=2),
    "seg4-own-keys-mha-sub": dict(H=2, K=2, causal=True, own=8, segments=4,
                                  sub=32),
}


def _ref_lse(q, k, causal, window):
    """Row log-sum-exp of the masked scores, [B, H, T, 1] (the kernel's
    second output), query row t at position t + S - T."""
    (_, T, H, d), S = q.shape, k.shape[1]
    k = jnp.repeat(k, H // k.shape[2], axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(d)
    gap = (jnp.arange(T)[:, None] + S - T) - jnp.arange(S)[None, :]
    keep = jnp.ones((T, S), bool)
    if causal:
        keep &= gap >= 0
    if window is not None:
        keep &= gap < window
    s = jnp.where(keep, s, -jnp.inf)
    return jax.scipy.special.logsumexp(s, axis=-1)[..., None]


def _ref_own(q, k, v, k_own, v_own, n):
    """One softmax over the keys before a query's block of ``n`` and the own
    keys of its block, model layout."""
    (_, T, H, d), K = q.shape, k.shape[2]
    k, v, k_own, v_own = (jnp.repeat(x, H // K, axis=2)
                          for x in (k, v, k_own, v_own))
    qb, kb = jnp.arange(T)[:, None] // n, jnp.arange(T)[None, :] // n
    s = jnp.concatenate([
        jnp.where(m, jnp.einsum("bthd,bshd->bhts", q, x) / np.sqrt(d),
                  -jnp.inf) for m, x in ((kb < qb, k), (kb == qb, k_own))],
        axis=-1)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p,
                      jnp.concatenate([v, v_own], axis=1))


def _dense_keep(T, S, causal, window):
    """[T, S] of the pairs attention keeps, query row t at position
    t + S - T."""
    gap = (np.arange(T)[:, None] + S - T) - np.arange(S)[None, :]
    keep = np.ones((T, S), bool)
    if causal:
        keep &= gap >= 0
    if window is not None:
        keep &= gap < window
    return keep


def _some_every(keep, bq, bk):
    """Per ``[bq, bk]`` tile of a dense mask: keeps a pair, keeps them all."""
    per_tile = keep.reshape(keep.shape[0] // bq, bq, keep.shape[1] // bk, bk)
    return per_tile.any((1, 3)), per_tile.all((1, 3))


def _held_to(a, b):
    """Two kernels' bf16 results. Bit for bit on the chip (PERF.md section
    6, PR 29). The CPU sums a transposed operand's products in another
    order, which now and then moves the bf16 rounding of one P or dS and
    with it an element by 2^-8 of one of its terms: all but a few in a
    thousand equal, and none further off than that."""
    assert a.dtype == b.dtype == jnp.bfloat16 and a.shape == b.shape
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert np.mean(a != b) < 0.01
    np.testing.assert_allclose(a, b, rtol=2.0 ** -7, atol=2e-3)


@pytest.mark.parametrize("name", sorted(FUSED_BWD_CASES))
def test_fused_backward(name, monkeypatch):
    case = dict(FUSED_BWD_CASES[name])
    if "sub" in case:
        monkeypatch.setattr(fa, "_SUB", case["sub"])
        monkeypatch.setattr(fa, "_OWN_SUB", case["sub"])
    H, K, causal = case["H"], case["K"], case["causal"]
    S = case.get("S", 512)
    T = case.get("T", S)
    window, dlse = case.get("window"), case.get("dlse", False)
    block = case.get("block", 128)
    bq, bk, rel, d = min(block, T), min(block, S), S - T, case.get("d", 16)
    dv, dr, own = case.get("dv", d), case.get("dr", 0), case.get("own")
    kv_whole = case.get("kv_whole", False)
    diag = (own, fa.DIAG_BEFORE) if own else None
    took, segments = case.get("took", "fused"), case.get("segments", 1)

    def budget_for(itemsize):
        """What that many segments of the head hold is the budget (or the
        case's own; None: the real one)."""
        shape = (T, d, bq, bk, itemsize, dv, dr, bool(own))
        n = case.get("segments_f32", segments) if itemsize == 4 else segments
        budget = case.get("budget", fa._fused_bwd_vmem_bytes(
            T // n, *shape[1:]))
        if budget is not None:
            monkeypatch.setattr(fa, "_FUSED_VMEM_BUDGET", budget)
        assert fa._bwd_takes_fused(*shape) == (took == "fused")
        assert fa._bwd_segments(*shape) == (n if took == "fused" else 0)
        return n

    budget_for(2)

    # (1) the kernels themselves, bf16 as in training: same dq, dk, dv (per
    # query head, before the GQA sum)
    keys = list(jax.random.split(jax.random.key(7), 10))
    qt, kt, vt, do, qr, kr, ko, vo = (
        jax.random.normal(key, (1, h, t, w), jnp.bfloat16)
        for key, (h, t, w) in zip(keys[:4] + keys[6:], (
            (H, T, d), (K, S, d), (K, S, dv), (H, T, dv), (H, T, dr),
            (1, S, dr), (K, S, d), (K, S, dv))))
    more = (qr, kr) if dr else (None, None)
    more += (ko, vo) if own else ()
    if kv_whole:
        kt, vt = jnp.concatenate([kt, vt], axis=-1), None
    kernel_kw = dict(scale=(d + dr) ** -0.5, causal=causal, window=window,
                     block_q=bq, block_k=bk, rel_offset=rel, diag=diag)
    out, lse = fa._fwd_pallas(
        qt, kt, vt, interpret=True, **kernel_kw,
        **(dict(q_rope=qr, k_rope=kr) if dr else {}),
        **(dict(k_own=ko, v_own=vo) if own else {}))
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), -1)
    if dlse:
        delta = delta - jax.random.normal(keys[4], delta.shape, jnp.float32)
    # the fused kernel reads its statistics as rows, the split pair as columns
    row, col = (1, H, 1, T), (1, H, T, 1)
    before = lowerings.snapshot()
    fused = fa._bwd_fused_call(qt, kt, vt, do, lse.reshape(row),
                               delta.reshape(row), *more, interpret=True,
                               **kernel_kw)
    said = lowerings.since(before)
    assert said["flash_bwd_segments"] == {d: segments}
    want = None
    if not own:
        # the counter: a head's tiles by arm and the sub-blocks of its
        # crossed tiles, as the dense mask has them
        hq, w = (min(case.get("sub", 512), b) for b in (bq, bk))
        keep = _dense_keep(T, S, causal, window)
        some, every = _some_every(keep, bq, bk)
        sub_some, sub_every = _some_every(keep, hq, w)
        masked = np.repeat(np.repeat(some & ~every, bq // hq, 0), bk // w, 1)
        want = dict(masked=int((some & ~every).sum()),
                    unmasked=int(every.sum()), dead=int((~some).sum()),
                    sub_live=int((masked & sub_some).sum()),
                    sub_dead=int((masked & ~sub_some).sum()),
                    sub_inside=int((masked & sub_every).sum()))
        assert want == case.get("tiles", want)
        # (kept under the mask's name: a model's window and full layers both
        # read)
        want = {window or ("causal" if causal else "none"): want}
        assert said["flash_bwd_tiles"] == want

    # the kv-tiles a segment's index maps keep (the others name the nearest
    # of them: dead steps fetch nothing) are those some row of it keeps a
    # key of, by the kernels' own predicate
    live, _ = fa._tiles(T, S, bq, bk, causal, window, rel, diag)
    for s in range(segments):
        first, last = (T // segments * x + rel for x in (s, s + 1))
        lo, hi = fa._live_kv_tiles(causal, window, first, last - 1, bk, diag)
        kept = [lo <= ik <= (S // bk if hi is None else hi)
                for ik in range(S // bk)]
        seg_nq = T // bq // segments
        assert live[s * seg_nq:(s + 1) * seg_nq].any(0).tolist() == kept

    def split_pair(rows, rel):
        stats = (x.reshape(col)[:, :, rows] for x in (lse, delta))
        return fa._bwd_split_call(
            qt[:, :, rows], kt, vt, do[:, :, rows], *stats,
            more[0] if more[0] is None else more[0][:, :, rows], *more[1:],
            interpret=True, **dict(kernel_kw, rel_offset=rel))

    if segments == 1:
        for a, b in zip(fused, split_pair(slice(None), rel)):
            _held_to(a, b)
    elif not own:
        # a segment's rows of dq and its partials of the others: the split
        # pair's over those rows alone
        assert all(x.shape[0] == segments for x in fused[1:] if x is not None)
        for s in range(segments):
            rows = slice(s * T // segments, (s + 1) * T // segments)
            fdq, *fkv = fused
            sdq, *skv = split_pair(rows, rel + rows.start)
            _held_to(fdq[:, :, rows], sdq)
            for a, b in zip(fkv, skv):
                assert (a is None) == (b is None)
                if a is not None:
                    _held_to(a[s], b)
    else:
        # (the own keys lie at every query's position: no split pair over a
        # segment's rows alone.) dq and the own keys' gradients, which one
        # segment holds and the others leave 0, as the split pair's; dk and
        # dv to the bf16 rounding of a partial a segment
        fdq, *fkv = fused
        sdq, *skv = split_pair(slice(None), rel)
        _held_to(fdq, sdq)
        for a, b, whole in zip(fkv, skv, (False, False, True, True)):
            assert a.shape == (segments,) + b.shape
            if whole:
                assert np.all(np.asarray(a != 0).any((1, 2, 4)).sum(0) <= 1)
                _held_to(a.astype(jnp.float32).sum(0).astype(jnp.bfloat16),
                         b)
            else:
                a = np.asarray(a, np.float32)
                np.testing.assert_allclose(
                    a.sum(0), np.asarray(b, np.float32), rtol=2.0 ** -7,
                    atol=2.0 ** -8 * segments * np.abs(a).max())

    # (2) through the public entry in float32, against XLA's autodiff, and
    # (3) the lowering counter says which kernel the shape took
    args = [x.astype(jnp.float32).transpose(0, 2, 1, 3)
            for x in (qt, kt, vt, qr, kr, ko, vo) if x is not None]
    if not dr:
        del args[2 + (vt is not None):4 + (vt is not None)]
    if not own:
        del args[-2:]
    w_out = jax.random.normal(keys[5], (1, T, H, dv), jnp.float32)
    w_lse = jax.random.normal(keys[4], (1, H, T, 1), jnp.float32)

    def operands(args):
        """(q, k, v, {the optional operands by name}) of ``args``."""
        q, k, *rest = args
        v = None if kv_whole else rest.pop(0)
        named = {}
        if dr:
            named.update(q_rope=rest.pop(0), k_rope=rest.pop(0))
        if own:
            named.update(k_own=rest.pop(0), v_own=rest.pop(0))
        return q, k, v, named

    def f_flash(*args):
        q, k, v, named = operands(args)
        kw = dict(causal=causal, window=window, block_q=bq, block_k=bk,
                  interpret=True, diag=diag, **named)
        if not dlse and T == S:
            return (flash_attention(q, k, v, **kw) * w_out).sum()
        o, l = flash_attention_lse(q, k, v, rel_offset=rel, **kw)
        return (o * w_out).sum() + ((l * w_lse).sum() if dlse else 0.0)

    def f_ref(*args):
        q, k, v, named = operands(args)
        if own:
            return (_ref_own(q, k, v, named["k_own"], named["v_own"], own)
                    * w_out).sum()
        q, k, v = fa.assembled(q, k, v, named.get("q_rope"),
                               named.get("k_rope"))
        o = xla_attention(q, k, v, causal=causal, window=window)
        l = _ref_lse(q, k, causal, window) if dlse else 0.0
        return (o * w_out).sum() + (l * w_lse).sum()

    segments = budget_for(4)
    before = lowerings.snapshot()
    g1 = jax.grad(f_flash, argnums=tuple(range(len(args))))(*args)
    said = lowerings.since(before)
    assert said["flash_bwd"] == {took: 1}
    assert said.get("flash_bwd_segments") == (
        {d: segments} if took == "fused" else None)
    if not own:
        assert said.get("flash_bwd_tiles") == (
            want if took == "fused" else None)
    g2 = jax.grad(f_ref, argnums=tuple(range(len(args))))(*args)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


# The flash forward, tile by tile (interpreter). Small tiles, and sub-blocks
# smaller still ("sub" stands in for the kernel's 512), so that tiles a
# boundary crosses, tiles none does and dead tiles all occur, and on a crossed
# tile dead sub-blocks: "tiles" pins the counter where the pattern is the
# training cells' (T 4096 in 1024-wide tiles is these 4 x 4), elsewhere the
# dense mask says what to expect. T != S puts query row t at position
# t + S - T (rel_offset); "rows" is whether [1, block_q] is a legal block.
FWD_TILE_CASES = {
    "causal-mha-the-cells-pattern": dict(
        H=2, K=2, tiles=dict(masked=4, unmasked=6, dead=6, rows=False)),
    "causal-rows-128-wide": dict(
        H=2, K=1, T=512, block=128, sub=64,
        tiles=dict(masked=4, unmasked=6, dead=6, rows=True)),
    "full-gqa2": dict(H=4, K=2, causal=False,
                      tiles=dict(masked=0, unmasked=16, dead=0, rows=False)),
    "window-ends-inside-a-tile-gqa4": dict(H=4, K=1, window=24),
    "window-on-a-tile-edge-mha": dict(H=2, K=2, window=32),
    "window-beyond-S-gqa2": dict(H=4, K=2, window=1000),
    "window-of-one": dict(H=2, K=2, window=1),
    "rect-rel-offset-gqa2": dict(H=4, K=2, T=32, S=64),
    "rect-window-gqa4": dict(H=4, K=1, T=32, S=64, window=40),
    "tall-tiles": dict(H=2, K=2, block_q=32, block_k=16),
    "wide-tiles": dict(H=2, K=1, block_q=16, block_k=32),
    "d64-rows-whole-sub-block": dict(H=2, K=1, T=256, block=128, d=64,
                                     sub=None),
    "one-tile-whole-T": dict(H=2, K=2, block=1024, sub=None,
                             tiles=dict(masked=1, unmasked=0, dead=0,
                                        rows=True)),
}


@pytest.mark.parametrize("name", sorted(FWD_TILE_CASES))
def test_flash_forward_tiles(name, monkeypatch):
    case = dict(FWD_TILE_CASES[name])
    H, K, d = case["H"], case["K"], case.get("d", 16)
    S = case.get("S", case.get("T", 64))
    T = case.get("T", S)
    causal, window = case.get("causal", True), case.get("window")
    bq = min(case.get("block_q", case.get("block", 16)), T)
    bk = min(case.get("block_k", case.get("block", 16)), S)
    if case.get("sub", 8) is not None:
        monkeypatch.setattr(fa, "_SUB", case.get("sub", 8))
    rel = S - T
    q, k, v = _qkv(T=T, S=S, H=H, K=K, d=d)
    kw = dict(causal=causal, window=window, block_q=bq, block_k=bk,
              interpret=True)

    # (1) out and lse: against XLA's attention and the log-sum-exp of the
    # masked scores
    out, lse = flash_attention_lse(q, k, v, rel_offset=rel, **kw)
    ref = xla_attention(q, k, v, causal=causal, window=window)
    ref_lse = _ref_lse(q, k, causal, window)
    assert lse.shape == (1, H, T, 1)            # the documented column
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               atol=2e-5)
    if T == S:
        np.testing.assert_allclose(
            np.asarray(flash_attention(q, k, v, **kw)), np.asarray(ref),
            atol=2e-5)

    # (2) the counter: tiles by arm as the dense mask has them, and the
    # layout the kernel's own lse left in
    some, every = _some_every(_dense_keep(T, S, causal, window), bq, bk)
    rows = bq == T or bq % 128 == 0
    want = dict(masked=int((some & ~every).sum()), unmasked=int(every.sum()),
                dead=int((~some).sum()), rows=rows)
    assert want == case.get("tiles", want)
    before = lowerings.snapshot()
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    _, raw = fa._fwd_pallas(qt, kt, vt, scale=d ** -0.5, causal=causal,
                            window=window, block_q=bq, block_k=bk,
                            interpret=True, rel_offset=rel)
    assert lowerings.since(before)["flash_fwd_tiles"] == want
    assert raw.shape == ((1, H, 1, T) if rows else (1, H, T, 1))
    np.testing.assert_array_equal(np.asarray(raw).reshape(-1),
                                  np.asarray(lse).reshape(-1))

    # (3) gradients as XLA's autodiff has them: through flash_attention, and
    # through flash_attention_lse with a cotangent into the log-sum-exp
    w_out = jax.random.normal(jax.random.key(5), q.shape, jnp.float32)
    w_lse = jax.random.normal(jax.random.key(6), (1, H, T, 1), jnp.float32)

    def f_lse(q, k, v):
        o, l = flash_attention_lse(q, k, v, rel_offset=rel, **kw)
        return (o * w_out).sum() + (l * w_lse).sum()

    def f_ref(q, k, v, with_lse=True):
        o = xla_attention(q, k, v, causal=causal, window=window)
        l = _ref_lse(q, k, causal, window) if with_lse else 0.0
        return (o * w_out).sum() + (l * w_lse).sum()

    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(jax.grad(f_lse, argnums=(0, 1, 2))(q, k, v), g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)
    if T == S:
        g = jax.grad(lambda q, k, v: (flash_attention(q, k, v, **kw)
                                      * w_out).sum(), argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(lambda q, k, v: f_ref(q, k, v, with_lse=False),
                         argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4)


class TestRMSNorm:
    def test_parity(self):
        x = jax.random.normal(jax.random.key(4), (4, 32, 64))
        w = jax.random.normal(jax.random.key(5), (64,)) + 1.0
        ref = np.asarray(x) / np.sqrt(
            (np.asarray(x) ** 2).mean(-1, keepdims=True) + 1e-5) * np.asarray(w)
        np.testing.assert_allclose(np.asarray(fused_rms_norm(x, w)), ref, atol=2e-5)

    def test_grad_parity(self):
        x = jax.random.normal(jax.random.key(6), (8, 64))
        w = jax.random.normal(jax.random.key(7), (64,)) + 1.0

        def ref_fn(x, w):
            inv = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-5)
            return (x * inv * w).sum()

        g1 = jax.grad(lambda x, w: fused_rms_norm(x, w).sum(), argnums=(0, 1))(x, w)
        g2 = jax.grad(ref_fn, argnums=(0, 1))(x, w)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


class TestQuantization:
    @pytest.mark.parametrize("bits,tol", [(8, 0.02), (4, 0.35)])
    def test_roundtrip(self, bits, tol):
        x = np.random.default_rng(0).normal(size=(4096,)).astype(np.float32)
        q, s = quantize_blockwise(x, bits=bits, group_size=512)
        d = np.asarray(dequantize_blockwise(q, s, bits=bits, shape=x.shape,
                                            dtype=jnp.float32))
        assert np.abs(d - x).max() < tol
        if bits == 8:
            assert q.dtype == jnp.int8 and q.size == x.size
        else:
            assert q.size == x.size // 2  # packed nibbles

    def test_fp8_roundtrip(self):
        x = np.random.default_rng(1).normal(size=(1024,)).astype(np.float32) * 10
        q, s = quantize_fp8(jnp.asarray(x))
        d = np.asarray(dequantize_fp8(q, s, dtype=jnp.float32))
        rel = np.abs(d - x) / (np.abs(x) + 1e-3)
        assert np.median(rel) < 0.05


def test_op_registry():
    from deepspeed_tpu.ops import ALL_OPS, get_op_builder, op_report

    assert "flash_attn" in ALL_OPS
    fn = get_op_builder("flash_attn").load()
    assert callable(fn)
    assert all(isinstance(ok, bool) for _, ok in op_report())


def test_attention_registry_has_flash():
    from deepspeed_tpu.models.transformer import _ATTENTION_IMPLS

    import deepspeed_tpu  # noqa: F401  (import registers)

    assert "flash" in _ATTENTION_IMPLS


class TestQuantizedMatmul:
    """Fused dequant-GEMM (reference cutlass_ops/mixed_gemm W4A16/W8A16).

    On-chip measurements (v5e, D=4096 F=14336): XLA fuses the blockwise
    dequant into the matmul — int4-base decode throughput measured 0.95-3.9x
    the bf16 GEMM depending on batch — and this Pallas kernel keeps the
    packed weights compressed all the way into VMEM for the cases XLA
    declines to fuse."""

    @pytest.mark.parametrize("bits", [4, 8])
    def test_kernel_matches_dense_reference(self, bits):
        from deepspeed_tpu.ops.quant_matmul import (
            dequantize_matmul_weight, quantize_matmul_weight,
            quantized_matmul)

        rng = np.random.default_rng(0)
        D, F = 512, 768
        w = jnp.asarray(rng.normal(size=(D, F)).astype(np.float32) / 30)
        packed, scales = quantize_matmul_weight(w, bits=bits, group=128)
        wd = dequantize_matmul_weight(packed, scales, bits, D)
        # quantization error bounded by the group scale
        assert float(jnp.abs(wd.astype(jnp.float32) - w).max()) < 0.02
        for B in (8, 64):
            x = jnp.asarray(rng.normal(size=(B, D)).astype(np.float32)
                            ).astype(jnp.bfloat16)
            ref = np.asarray(x @ wd, np.float32)
            out = np.asarray(quantized_matmul(x, packed, scales, bits=bits),
                             np.float32)
            np.testing.assert_allclose(out, ref, atol=2e-1, rtol=2e-2)

    def test_off_sweet_spot_falls_back(self):
        from deepspeed_tpu.ops.quant_matmul import (
            quantize_matmul_weight, quantized_matmul)

        rng = np.random.default_rng(1)
        D, F = 192, 160        # not 128-aligned → XLA fallback path
        w = jnp.asarray(rng.normal(size=(D, F)).astype(np.float32) / 30)
        packed, scales = quantize_matmul_weight(w, bits=8, group=96)
        x = jnp.asarray(rng.normal(size=(4, D)).astype(np.float32)
                        ).astype(jnp.bfloat16)
        out = quantized_matmul(x, packed, scales, bits=8)
        assert out.shape == (4, F) and np.isfinite(np.asarray(
            out, np.float32)).all()
