"""The Pallas grouped matmul (``ops/grouped_matmul.py``), interpreted on the
CPU: the product, its transposed-weight form and the weights' transpose
against a per-group dense reference in f32 and against ``lax.ragged_dot``; the
gradient of the experts' FFN against the ``ragged_dot`` path; rows past the
groups; and the rule that picks the lowering."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import sharded_moe as sm
from deepspeed_tpu.ops import grouped_matmul as gm, lowerings

# (rows, row tile, group sizes): what the groups hold may end before the rows
LAYOUTS = {
    "uneven": (1024, 128, [300, 411, 100, 213]),
    "an-empty-group": (1024, 128, [200, 0, 500, 324]),
    "no-multiple-of-the-tile": (768, 256, [77, 301, 5, 130]),
    "rows-past-the-groups": (1024, 128, [130, 120, 0, 70]),
    "aligned": (1024, 256, [256, 256, 256, 256]),
    "one-group-holds-all": (512, 128, [0, 512, 0, 0]),
    "no-rows-at-all": (512, 128, [0, 0, 0, 0]),
}
WIDTHS = {"128x256": (128, 256), "896x128": (896, 128), "2304x128": (2304, 128)}


def operands(rows, C, O, E=4, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    xs = jax.random.normal(k1, (rows, C), jnp.bfloat16)
    w = (jax.random.normal(k2, (E, C, O), jnp.float32) * 0.05
         ).astype(jnp.bfloat16)
    dys = jax.random.normal(k3, (rows, O), jnp.bfloat16)
    return xs, w, dys


def by_group(sizes):
    lo = 0
    for e, n in enumerate(sizes):
        yield e, slice(lo, lo + n)
        lo += n


def f32(x):
    return np.asarray(x, np.float32)


@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_product_and_its_transposed_weight_form(layout, widths):
    rows, tm, sizes = LAYOUTS[layout]
    C, O = WIDTHS[widths]
    xs, w, _ = operands(rows, C, O)
    g = jnp.asarray(sizes, jnp.int32)
    out = f32(gm.gmm(xs, w, g, interpret=True, tm=tm))
    out_t = f32(gm.gmm(xs, jnp.swapaxes(w, 1, 2), g, transpose_w=True,
                       interpret=True, tm=tm))
    ragged = f32(jax.lax.ragged_dot(xs, w, g))
    held = sum(sizes)
    for e, rows_e in by_group(sizes):
        want = f32(xs[rows_e]) @ f32(w[e])
        np.testing.assert_allclose(out[rows_e], want, atol=0.05, rtol=0.02)
    # (on the chip the three agree to the bit: tools/grouped_matmul_bench.py)
    np.testing.assert_allclose(out[:held], ragged[:held], atol=0.02, rtol=0.01)
    np.testing.assert_allclose(out_t[:held], out[:held], atol=0.02, rtol=0.01)
    # a visited tile's rows that no group holds are zero, not what was there
    live_end = -(-held // tm) * tm
    assert not out[held:live_end].any() and not out_t[held:live_end].any()


@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_weights_transpose(layout, widths):
    rows, tm, sizes = LAYOUTS[layout]
    C, O = WIDTHS[widths]
    xs, _, dys = operands(rows, C, O)
    # rows the groups do not hold must not count, whatever they are
    held = sum(sizes)
    xs = xs.at[held:].set(jnp.nan)
    dys = dys.at[held:].set(jnp.inf)
    got = f32(gm.tgmm(xs, dys, jnp.asarray(sizes, jnp.int32), interpret=True,
                      tm=tm))
    assert got.shape == (len(sizes), C, O)
    for e, rows_e in by_group(sizes):
        want = f32(xs[rows_e]).T @ f32(dys[rows_e])
        np.testing.assert_allclose(got[e], want, atol=0.3, rtol=0.02)


def test_widths_cut_to_tiles_give_the_same_products(monkeypatch):
    """A budget that holds no whole width: the contraction is accumulated
    over tiles, the output and the weights' transpose are written by tile."""
    rows, C, O, sizes = 512, 384, 256, [100, 0, 290, 60]
    monkeypatch.setattr(gm, "_VMEM_BUDGET", 2 * 2 * 3 * 128 * 128 + 2 * 128 * 128 * 4)
    assert gm._gmm_tiles(128, C, O, 2) == (128, 128)
    assert gm._tgmm_tiles(128, C, O, 2) == (128, 128)
    xs, w, dys = operands(rows, C, O, seed=5)
    g = jnp.asarray(sizes, jnp.int32)
    out = f32(gm.gmm(xs, w, g, interpret=True, tm=128))
    out_t = f32(gm.gmm(dys, w, g, transpose_w=True, interpret=True, tm=128))
    dw = f32(gm.tgmm(xs, dys, g, interpret=True, tm=128))
    for e, rows_e in by_group(sizes):
        np.testing.assert_allclose(out[rows_e], f32(xs[rows_e]) @ f32(w[e]),
                                   atol=0.05, rtol=0.02)
        np.testing.assert_allclose(out_t[rows_e],
                                   f32(dys[rows_e]) @ f32(w[e]).T,
                                   atol=0.05, rtol=0.02)
        np.testing.assert_allclose(dw[e], f32(xs[rows_e]).T @ f32(dys[rows_e]),
                                   atol=0.3, rtol=0.02)
    assert not out[450:].any() and not out_t[450:].any()


def test_products_of_several_stacks_are_summed_in_one_call():
    """``gmm`` over a tuple of (rows, stack) pairs: the rows' cotangent when
    two stacks read them, against the two products added."""
    rows, C, O, sizes = 768, 128, 256, [100, 300, 0, 250]
    _, w1, d1 = operands(rows, C, O, seed=7)
    _, w2, d2 = operands(rows, C, O, seed=8)
    g = jnp.asarray(sizes, jnp.int32)
    both = f32(gm.gmm((d1, d2), (w1, w2), g, transpose_w=True, interpret=True,
                      tm=128))
    for e, rows_e in by_group(sizes):
        want = f32(d1[rows_e]) @ f32(w1[e]).T + f32(d2[rows_e]) @ f32(w2[e]).T
        np.testing.assert_allclose(both[rows_e], want, atol=0.08, rtol=0.02)
    assert not both[650:].any()
    assert gm._gmm_tiles(256, 896, 2304, 2, pairs=2) == (896, 2304)


def stacks(E, D, F, gated, seed=1):
    ks = jax.random.split(jax.random.key(seed), 3)
    w = {"w_up": jax.random.normal(ks[0], (E, D, F)) * 0.05,
         "w_down": jax.random.normal(ks[1], (E, F, D)) * 0.05}
    if gated:
        w["w_gate"] = jax.random.normal(ks[2], (E, D, F)) * 0.05
    return w


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
def test_the_ffns_gradient_is_the_ragged_dot_paths(gated):
    rows, E, D, F = 768, 4, 256, 128
    sizes = jnp.asarray([200, 0, 391, 100], jnp.int32)
    xs = jax.random.normal(jax.random.key(0), (rows, D), jnp.bfloat16)
    w = stacks(E, D, F, gated)
    keep = (jnp.arange(rows) < 691)[:, None]

    def loss(xs, w, interpret):
        ys = sm._grouped_ffn(xs, sizes, w, jnp.bfloat16, "ragged",
                             interpret=interpret)
        ys = jnp.where(keep, ys, 0).astype(jnp.float32)
        return (ys * jnp.cos(jnp.arange(D, dtype=jnp.float32))).sum()

    before = lowerings.snapshot()
    got = jax.value_and_grad(loss, argnums=(0, 1))(xs, w, True)
    products = 3 if gated else 2
    assert lowerings.since(before)["moe_grouped"] == {"pallas": 3 * products}
    want = jax.value_and_grad(loss, argnums=(0, 1))(xs, w, None)
    assert lowerings.since(before)["moe_grouped"]["xla"] == products
    np.testing.assert_allclose(got[0], want[0], rtol=2e-3)
    np.testing.assert_allclose(f32(got[1][0])[:691], f32(want[1][0])[:691],
                               atol=2e-2, rtol=2e-2)
    for name in w:
        scale = float(jnp.abs(want[1][1][name]).max())
        np.testing.assert_allclose(f32(got[1][1][name]),
                                   f32(want[1][1][name]), atol=0.02 * scale)


class HeldShare:
    """Four of eight routed experts held: about half the pairs are absent,
    so the buffer's rows run past the groups."""
    top_k = 2
    moe_kernel = "ragged"
    moe_experts_held = 4
    moe_first_expert = 2
    moe_ep_capacity_factor = 2.0


def test_a_row_past_the_groups_never_reaches_the_output_or_the_gradients(
        monkeypatch):
    D, F, S = 128, 128, 256
    h = jax.random.normal(jax.random.key(3), (1, S, D), jnp.bfloat16)
    w = dict(stacks(4, D, F, True),
             router=jax.random.normal(jax.random.key(4), (D, 8)) * 0.5)
    ffn = sm._grouped_ffn
    seen = {}

    def run(poison):
        def poisoned(xs, group_sizes, w, dt, kernel, **kw):
            past = (jnp.arange(xs.shape[0]) >= group_sizes.sum())[:, None]
            seen["rows"], seen["held"] = xs.shape[0], group_sizes.sum()
            # NaN in the rows past the groups, going in and coming out (what
            # uninitialised memory may hold), added so that the cotangents
            # of those rows pass as they come
            nan = jax.lax.stop_gradient(
                jnp.where(past & poison, jnp.nan, 0).astype(xs.dtype))
            assert {k: v for k, v in kw.items() if k != "act"} \
                == {"rows_past_groups": True}
            return ffn(xs + nan, group_sizes, w, dt, kernel, interpret=True,
                       **kw) + nan

        monkeypatch.setattr(sm, "_grouped_ffn", poisoned)

        def loss(h, w):
            out, parts = sm.grouped_moe_mlp_block(h, w, HeldShare)
            return (out.astype(jnp.float32) ** 2).sum(), (out, parts)

        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(h, w)

    before = lowerings.snapshot()
    (_, (out, parts)), grads = run(poison=True)
    assert lowerings.since(before)["moe_grouped"] == {"pallas": 9}
    assert int(parts["pairs_dropped"]) == 0
    assert 0 < int(seen["held"]) < seen["rows"] == 512
    (_, (clean, _)), clean_grads = run(poison=False)
    for got, want in zip(jax.tree_util.tree_leaves((out, grads)),
                         jax.tree_util.tree_leaves((clean, clean_grads))):
        assert np.isfinite(f32(got)).all()
        np.testing.assert_array_equal(f32(got), f32(want))


# what the call can see -> the lowering it takes; the Mellum2 cell's products
# are the first two
RULE = {
    "cell-gate-up": (dict(rows=65536, C=2304, O=896, groups=16,
                          dtype=jnp.bfloat16, tpu=True), "pallas"),
    "cell-down": (dict(rows=65536, C=896, O=2304, groups=16,
                       dtype=jnp.bfloat16, tpu=True), "pallas"),
    "int8-leaves": (dict(rows=65536, C=2304, O=896, groups=16,
                         dtype=jnp.bfloat16, tpu=True, dense=False), "xla"),
    "a-width-of-100": (dict(rows=65536, C=100, O=896, groups=16,
                            dtype=jnp.bfloat16, tpu=True), "xla"),
    "an-output-of-100": (dict(rows=65536, C=896, O=100, groups=16,
                              dtype=jnp.bfloat16, tpu=True), "xla"),
    "four-rows": (dict(rows=4, C=2304, O=896, groups=16,
                       dtype=jnp.bfloat16, tpu=True), "xla"),
    "under-a-tile-a-group": (dict(rows=1024, C=2304, O=896, groups=16,
                                  dtype=jnp.bfloat16, tpu=True), "xla"),
    "rows-no-tile-divides": (dict(rows=65536 + 8, C=2304, O=896, groups=16,
                                  dtype=jnp.bfloat16, tpu=True), "xla"),
    "f32-operands": (dict(rows=65536, C=2304, O=896, groups=16,
                          dtype=jnp.float32, tpu=True), "xla"),
    "a-cpu-backend": (dict(rows=65536, C=2304, O=896, groups=16,
                           dtype=jnp.bfloat16, tpu=None), "xla"),
}


@pytest.mark.parametrize("case", sorted(RULE))
def test_the_rule_that_picks_the_lowering(case):
    facts, want = RULE[case]
    took, why = gm.grouped_lowering(**facts)
    assert took == want and bool(why) == (want == "xla")


def test_int8_stacks_and_a_decode_step_take_ragged_dot():
    """Through ``_grouped_ffn`` itself, as if on a TPU (``interpret=True``):
    the int8 serving leaves and a few rows stay with ``lax.ragged_dot``."""
    E, D, F = 4, 128, 128
    w = {k: v.astype(jnp.bfloat16) for k, v in stacks(E, D, F, True).items()}
    q = {}
    for name, v in w.items():       # one scale a column, as _expert_weight reads
        scale = jnp.abs(v.astype(jnp.float32)).max(axis=1, keepdims=True) / 127
        q[name + "_q"] = jnp.round(v.astype(jnp.float32) / scale).astype(
            jnp.int8)
        q[name + "_s"] = scale.astype(jnp.bfloat16)
    sizes = jnp.asarray([100, 200, 112, 100], jnp.int32)
    xs = jax.random.normal(jax.random.key(0), (512, D), jnp.bfloat16)
    for stacks_, rows, want in ((q, xs, {"xla": 3}),
                                (w, xs[:8], {"xla": 3}),
                                (w, xs, {"pallas": 3})):
        before = lowerings.snapshot()
        sm._grouped_ffn(rows, jnp.minimum(sizes, rows.shape[0] // 4), stacks_,
                        jnp.bfloat16, "ragged", interpret=True)
        assert lowerings.since(before)["moe_grouped"] == want


def test_tiles_come_from_the_shapes_and_fit_the_budget():
    for C, O in ((2304, 896), (896, 2304)):
        assert gm._row_tile(65536, 16) == 256
        assert gm._gmm_tiles(256, C, O, 2) == (C, O)        # nothing is cut
        assert gm._tgmm_tiles(256, C, O, 2) == (C, O)
    assert gm._row_tile(16 * 512 * 16, 16) == 512
    assert gm._row_tile(2048, 16) == 128 and gm._row_tile(2047, 16) is None
    # a Mixtral-sized expert does not fit whole: the widths are cut to whole
    # lanes that divide them, under the budget
    tc, to = gm._gmm_tiles(512, 4096, 14336, 2)
    assert 4096 % tc == 0 and 14336 % to == 0 and tc % 128 == to % 128 == 0
    assert (tc, to) != (4096, 14336)
    assert gm._VMEM_BUDGET < gm._VMEM_LIMIT
