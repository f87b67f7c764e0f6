"""The expert layer's row kernels (``ops/moe_rows.py``) against the ``jnp.take``
lowering of the same moves, interpreted on the CPU: dispatch, combine, the
combine's backward and the dispatch's backward, the rule that picks the
lowering, and the gradient of ``grouped_moe_mlp_block`` through them.

To the bit, with one proviso the CPU forces: its compiler contracts a
multiply and the add after it into one rounding where it sees both in one
loop (the interpreted kernel), and a v5e has no such instruction. So the
weights here have eight significant bits: a bf16 row times such a weight is
exact in f32, and both orders of rounding give the same sum. With weights of
full precision the chip's results are equal to the bit
(``tools/moe_rows_bench.py`` checks it there) and the CPU's within one bf16
rounding.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import sharded_moe as sm
from deepspeed_tpu.ops import lowerings, moe_rows as mr


def f32(a):
    return np.asarray(a, np.float32)


def routing(expert, k, held, first=0, bound=None):
    """``order`` (its first ``bound`` are ``rows``), ``slot``,
    ``group_sizes``, ``n_here``, ``bound`` as ``grouped_moe_mlp_block`` makes
    them from each pair's expert."""
    expert = np.asarray(expert).reshape(-1)
    n = expert.size
    bound = n if bound is None else bound
    local = expert - first
    key = np.where((local >= 0) & (local < held), local, held)
    order = np.argsort(key, kind="stable")
    counts = np.bincount(key, minlength=held + 1)[:held]
    ends = np.minimum(np.cumsum(counts), bound)
    n_here = int(ends[-1])
    rank = np.empty(n, np.int64)
    rank[order] = np.arange(n)
    slot = np.where(rank < n_here, rank, bound).reshape(-1, k)
    return dict(rows=jnp.asarray(order[:bound], jnp.int32),
                order=jnp.asarray(order, jnp.int32),
                slot=jnp.asarray(slot, jnp.int32),
                sizes=jnp.asarray(np.diff(ends, prepend=0), jnp.int32),
                n_here=n_here, bound=bound, k=k, S=n // k)


def choices(S, k, E, seed):
    rng = np.random.default_rng(seed)
    return np.argsort(rng.random((S, E)), axis=1)[:, :k]


def _all_and_none():
    e = choices(64, 4, 8, 1)
    e[0] = [2, 3, 4, 5]             # every pair of token 0 is here
    e[1] = [0, 1, 6, 7]             # none of token 1's
    return routing(e, 4, held=4, first=2)


def _empty_groups():
    e = choices(64, 4, 8, 2)
    e[e == 3] = 7                   # experts 3 and 5 get nothing
    e[e == 5] = 6
    return routing(e, 4, held=4, first=2)


#: the buffers the kernels meet
ROUTINGS = {
    "a-held-share": lambda: routing(choices(64, 4, 8, 0), 4, held=4, first=2),
    "one-token-with-every-pair-here-one-with-none": _all_and_none,
    "no-pair-here": lambda: routing(np.full((64, 4), 7), 4, held=4, first=2,
                                    bound=128),
    "more-pairs-than-the-buffer-has-rows": lambda: routing(
        choices(64, 4, 8, 3), 4, held=6, bound=128),
    "uneven-and-empty-groups": _empty_groups,
    "every-expert-here": lambda: routing(choices(32, 2, 4, 4), 2, held=4),
    # 96 tokens are three tiles of 32, 384 rows three of 128: a tile's copies
    # start while the one before is worked
    "three-tiles-of-tokens": lambda: routing(choices(96, 4, 8, 5), 4, held=5,
                                             first=1),
}


def operands(r, D, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(ks[0], (r["S"], D), jnp.bfloat16)
    ys = jax.random.normal(ks[1], (r["bound"], D), jnp.bfloat16)
    # rows that carry no pair hold NaN, as uninitialised memory may
    ys = jnp.where((jnp.arange(r["bound"]) < r["n_here"])[:, None], ys,
                   jnp.nan)
    # eight significant bits: see the module's docstring
    weights = jax.random.uniform(ks[2], (r["S"], r["k"]), jnp.bfloat16,
                                 0.05, 1.0).astype(jnp.float32)
    g = jax.random.normal(ks[3], (r["S"], D), jnp.bfloat16)
    return x, ys, weights, g


def live_tiles(a, r):
    """The rows of the tiles the kernels write: up to the last that holds a
    pair."""
    tile = mr._tile(r["bound"])
    return a[:-(-r["n_here"] // tile) * tile]


CASES = [(name, D) for name in ROUTINGS for D in (128, 2304)] \
    + [("a-held-share", 384)]


@pytest.mark.parametrize("name,D", CASES)
def test_dispatch_is_the_take_to_the_bit(name, D):
    r = ROUTINGS[name]()
    x, *_ = operands(r, D)
    tok = r["rows"] // r["k"]
    got = mr.rows_of_tokens(mr.pack_rows(x, interpret=True), tok,
                            jnp.int32(r["n_here"]), D=D, interpret=True)
    assert got.shape == (r["bound"], D) and got.dtype == x.dtype
    np.testing.assert_array_equal(f32(live_tiles(got, r)),
                                  f32(live_tiles(x[tok], r)))


@pytest.mark.parametrize("weighted", [True, False],
                         ids=["combine", "dispatch-backward"])
@pytest.mark.parametrize("name,D", CASES)
def test_the_sum_of_rows_is_the_takes_to_the_bit(name, D, weighted):
    """The combine (weights) and the tokens' cotangent of the dispatch (none):
    f32, j ascending, one rounding; the NaN in the rows that carry no pair
    reaches nothing."""
    r = ROUTINGS[name]()
    _, ys, weights, _ = operands(r, D)
    weights = weights if weighted else None
    line, runs = mr.token_tile_runs(r["rows"], r["sizes"], S=r["S"], k=r["k"])
    packed = mr.pack_rows(ys, jnp.int32(r["n_here"]), interpret=True)
    got = mr.sum_of_rows(packed, r["slot"], line, runs, weights, D=D,
                         interpret=True)
    want = sm._sum_of_rows(ys, r["slot"], weights).astype(ys.dtype)
    assert np.isfinite(f32(got)).all()
    np.testing.assert_array_equal(f32(got), f32(want))


def planted(dot, r):
    """``dot`` [bound] with NaN past the last tile the rows kernel writes."""
    tile = mr._tile(r["bound"])
    live = -(-r["n_here"] // tile) * tile
    return jnp.where(jnp.arange(r["bound"]) < live, dot, jnp.nan)


def the_takes_backward(ys, weights, rows, slot, g):
    """The combine's backward as gathers: a row's weight out of the pairs',
    each pair's row of ``ys`` for its dot with the token's cotangent."""
    k = slot.shape[1]
    dys = (g[rows // k].astype(jnp.float32)
           * weights.reshape(-1)[rows][:, None]).astype(ys.dtype)
    gf = g.astype(jnp.float32)
    dw = jnp.stack([
        (gf * jnp.take(ys, slot[:, j], axis=0, mode="fill", fill_value=0)
         .astype(jnp.float32)).sum(axis=-1) for j in range(k)], axis=1)
    return dys, dw


@pytest.mark.parametrize("lowering", ["pallas", "xla"])
@pytest.mark.parametrize("name,D", CASES)
def test_the_combines_backward_is_the_takes(name, D, lowering):
    """``dys`` to the bit; the weights' gradient, the same dots summed in
    another order, within 1e-5 of the largest: by the row kernels and by
    ``jnp.take`` of rows, a row's weight being the one the sort carried and
    the dots coming back to pair order by :func:`sharded_moe._pairs_of_rows`."""
    r = ROUTINGS[name]()
    _, ys, weights, g = operands(r, D)
    k = r["k"]
    how = (lowering, lowering == "pallas")
    moves = sm._row_moves(r["rows"], r["sizes"], jnp.int32(r["n_here"]),
                          r["S"], k, how)
    row_weight = weights.reshape(-1)[r["rows"]]
    dys, dw, *rest = sm._wsum_bwd(
        how, (ys, row_weight, r["order"], r["slot"], moves), g)
    assert rest == [None] * 4
    want_dys, want_dw = the_takes_backward(ys, weights, r["rows"], r["slot"],
                                           g)
    if lowering == "pallas":
        dys = live_tiles(dys, r)
    np.testing.assert_array_equal(f32(dys)[:r["n_here"]],
                                  f32(want_dys)[:r["n_here"]])
    assert np.isfinite(f32(dw)).all() and dw.dtype == weights.dtype
    scale = max(float(jnp.abs(want_dw).max()), 1.0)
    np.testing.assert_allclose(f32(dw), f32(want_dw), atol=1e-5 * scale)


@pytest.mark.parametrize("name", sorted(ROUTINGS))
def test_the_rows_dots_reach_their_pairs_as_the_gather_brings_them(name):
    """:func:`sharded_moe._pairs_of_rows` is ``jnp.take(dot, slot,
    mode="fill", fill_value=0)`` element for element, NaN standing in the
    tiles of ``dot`` the rows kernel never writes: a pair without a row gets
    its zero by a select."""
    r = ROUTINGS[name]()
    dot = planted(jax.random.normal(jax.random.key(7), (r["bound"],)), r)
    got = jax.jit(sm._pairs_of_rows)(dot, r["order"], r["slot"])
    want = jnp.take(dot, r["slot"], mode="fill", fill_value=0)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.isfinite(f32(got)).all()
    np.testing.assert_array_equal(f32(got), f32(want))
    assert (f32(got) != 0).sum() == r["n_here"]


def test_weights_of_full_precision_are_within_one_rounding():
    r = ROUTINGS["a-held-share"]()
    _, ys, _, _ = operands(r, 256)
    weights = jax.random.uniform(jax.random.key(9), (r["S"], r["k"]))
    line, runs = mr.token_tile_runs(r["rows"], r["sizes"], S=r["S"], k=r["k"])
    got = mr.sum_of_rows(mr.pack_rows(ys, interpret=True), r["slot"], line,
                         runs, weights, D=256, interpret=True)
    want = sm._sum_of_rows(ys, r["slot"], weights).astype(ys.dtype)
    np.testing.assert_allclose(f32(got), f32(want),
                               atol=2 ** -7 * float(jnp.abs(f32(want)).max()))


@pytest.mark.parametrize("name", sorted(ROUTINGS))
def test_the_runs_of_a_token_tile_hold_its_rows(name):
    r = ROUTINGS[name]()
    S, k = r["S"], r["k"]
    line, runs = mr.token_tile_runs(r["rows"], r["sizes"], S=S, k=k)
    tt = mr._tile(S)
    tiles, G = S // tt, r["sizes"].shape[0]
    runs, rows = np.asarray(runs), np.asarray(r["rows"])
    assert runs.shape == (G * tiles + 1,) and runs[-1] == r["n_here"]
    offsets = np.concatenate([[0], np.cumsum(np.asarray(r["sizes"]))])
    seen = []
    for g in range(G):
        for i in range(tiles):
            lo, hi = runs[g * tiles + i], runs[g * tiles + i + 1]
            assert offsets[g] <= lo <= hi <= offsets[g + 1]
            assert ((rows[lo:hi] // k) // tt == i).all()
            seen.extend(range(lo, hi))
    assert seen == list(range(r["n_here"]))
    pair = rows[:r["n_here"]]
    np.testing.assert_array_equal(np.asarray(line)[:r["n_here"]],
                                  (pair % k) * tt + (pair // k) % tt)


@pytest.mark.parametrize("D", [128, 384, 2304])
def test_a_packed_row_holds_two_columns_a_word(D):
    a = jax.random.normal(jax.random.key(D), (64, D), jnp.bfloat16)
    w = mr.packed_width(D)
    assert w % 128 == 0 and D <= 2 * w < D + 256
    packed = np.asarray(mr.pack_rows(a, interpret=True))
    assert packed.shape == (64, 1, w) and packed.dtype == np.uint32
    bits = np.asarray(jax.lax.bitcast_convert_type(a, jnp.uint16),
                      np.uint32)
    high = np.zeros((64, w), np.uint32)
    high[:, :D - w] = bits[:, w:]
    np.testing.assert_array_equal(packed[:, 0], bits[:, :w] | (high << 16))
    # tiles of rows past ``n`` are left alone
    some = np.asarray(mr.pack_rows(a, jnp.int32(9), interpret=True))
    tile = mr._tile(64)
    np.testing.assert_array_equal(some[:tile], packed[:tile])


# ---- through the layer ------------------------------------------------------

class HeldShare:
    """Four of eight routed experts held: about half the pairs are absent,
    so the buffer's rows run past the groups."""
    top_k = 2
    moe_kernel = "ragged"
    moe_experts_held = 4
    moe_first_expert = 2
    moe_ep_capacity_factor = 2.0


def layer(D=128, F=128, S=1024, seed=3):
    ks = jax.random.split(jax.random.key(seed), 5)
    h = jax.random.normal(ks[0], (1, S, D), jnp.bfloat16)
    w = {"w_gate": jax.random.normal(ks[1], (4, D, F)) * 0.05,
         "w_up": jax.random.normal(ks[2], (4, D, F)) * 0.05,
         "w_down": jax.random.normal(ks[3], (4, F, D)) * 0.05,
         "router": jax.random.normal(ks[4], (D, 8)) * 0.5}
    return h, w


def test_the_layers_gradient_through_the_kernels_is_the_take_paths(
        monkeypatch):
    """A held share, a gated FFN, the products by the Pallas kernels both
    times; dispatch and combine by the row kernels, then by ``jnp.take``."""
    h, w = layer()

    def loss(h, w):
        out, parts = sm.grouped_moe_mlp_block(h, w, HeldShare, interpret=True)
        return (out.astype(jnp.float32) ** 2).sum(), (out, parts)

    grad = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
    before = lowerings.snapshot()
    (_, (out, parts)), grads = grad(h, w)
    assert lowerings.since(before)["moe_dispatch"] == {"pallas": 4}
    assert int(parts["pairs_dropped"]) == 0
    monkeypatch.setattr(sm, "_moves_lowering",
                        lambda *a, **kw: ("xla", False))
    before = lowerings.snapshot()
    (_, (want, _)), want_grads = grad(h, w)
    assert lowerings.since(before)["moe_dispatch"] == {"xla": 4}
    # the router's weights are f32 with all their bits: one bf16 rounding of
    # a sum's larger term (the CPU's contraction, see the module's docstring)
    np.testing.assert_allclose(f32(out), f32(want),
                               atol=2 ** -7 * float(jnp.abs(f32(want)).max()))
    for name in w:
        scale = float(jnp.abs(want_grads[1][name]).max())
        np.testing.assert_allclose(f32(grads[1][name]),
                                   f32(want_grads[1][name]),
                                   atol=0.02 * scale)
    scale = float(jnp.abs(f32(want_grads[0])).max())
    np.testing.assert_allclose(f32(grads[0]), f32(want_grads[0]),
                               atol=0.02 * scale)


def every_equation(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from every_equation(sub)


def test_the_layers_backward_moves_no_scalar_a_pair_or_a_row_by_a_gather():
    """The ``"xla"`` lowering (what a CPU gets: no kernel's body in the
    jaxpr) of a held share's layer, 64 tokens of 3 picks in a buffer of 96
    rows: no ``gather`` and no ``scatter`` of the backward reads or writes
    [S k], [S, k] or [bound] scalars (the takes of whole rows stay: [bound,
    D] out of [S, D] and back); and what the forward keeps for it holds the
    rows' weights, [bound] float32, and no one-hot of the picks over the
    held experts."""
    S, D, F, E, held, k, bound = 64, 128, 256, 16, 4, 3, 96

    class Share(HeldShare):
        top_k = k
        moe_experts_held = held

    ks = jax.random.split(jax.random.key(5), 5)
    h = jax.random.normal(ks[0], (1, S, D), jnp.float32)
    w = {"w_gate": jax.random.normal(ks[1], (held, D, F)) * 0.05,
         "w_up": jax.random.normal(ks[2], (held, D, F)) * 0.05,
         "w_down": jax.random.normal(ks[3], (held, F, D)) * 0.05,
         "router": jax.random.normal(ks[4], (D, E)) * 0.5}
    before = lowerings.snapshot()
    out, back = jax.vjp(
        lambda h, w: sm.grouped_moe_mlp_block(h, w, Share)[0], h, w)
    kept = [x for x in jax.tree_util.tree_leaves(back) if hasattr(x, "shape")]
    assert any(x.shape == (bound,) and x.dtype == jnp.float32 for x in kept)
    assert not any(x.shape == (S, k, held) for x in kept), \
        [x.shape for x in kept]
    scalars = {(S * k,), (S, k), (bound,)}
    moved = [(eqn.primitive.name, [v.aval.shape for v in ends])
             for eqn in every_equation(jax.make_jaxpr(back)(out).jaxpr)
             if eqn.primitive.name.startswith(("gather", "scatter"))
             for ends in [[eqn.invars[0], *eqn.invars[2:], *eqn.outvars]]
             if scalars & {v.aval.shape for v in ends}]
    assert moved == []
    assert lowerings.since(before)["moe_dispatch"] == {"xla": 4}


def test_a_row_past_the_pairs_never_reaches_the_output_or_the_gradients(
        monkeypatch):
    """NaN in the buffer's rows that carry no pair, going into the FFN and
    coming out of it: the row kernels never fetch one."""
    h, w = layer(S=256)
    ffn = sm._grouped_ffn

    def run(poison):
        def poisoned(xs, group_sizes, w, dt, kernel, fetch=None, **kw):
            def fetched(x):
                rows = fetch(x)
                past = (jnp.arange(rows.shape[0]) >= group_sizes.sum())
                return rows + jax.lax.stop_gradient(jnp.where(
                    past[:, None] & poison, jnp.nan, 0).astype(rows.dtype))
            ys = ffn(xs, group_sizes, w, dt, kernel, fetch=fetched, **kw)
            past = (jnp.arange(ys.shape[0]) >= group_sizes.sum())[:, None]
            return ys + jax.lax.stop_gradient(
                jnp.where(past & poison, jnp.nan, 0).astype(ys.dtype))

        monkeypatch.setattr(sm, "_grouped_ffn", poisoned)

        def loss(h, w):
            out, _ = sm.grouped_moe_mlp_block(h, w, HeldShare,
                                              interpret=True)
            return (out.astype(jnp.float32) ** 2).sum(), out

        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(h, w)

    (_, out), grads = run(poison=True)
    (_, clean), clean_grads = run(poison=False)
    for got, want in zip(jax.tree_util.tree_leaves((out, grads)),
                         jax.tree_util.tree_leaves((clean, clean_grads))):
        assert np.isfinite(f32(got)).all()
        np.testing.assert_array_equal(f32(got), f32(want))


def stacks(E, D, F, dtype=jnp.float32):
    return {"w_gate": jnp.zeros((E, D, F), dtype),
            "w_up": jnp.zeros((E, D, F), dtype),
            "w_down": jnp.zeros((E, F, D), dtype)}


def int8_stacks(E, D, F):
    w = {}
    for name, v in stacks(E, D, F).items():
        w[name + "_q"] = v.astype(jnp.int8)
        w[name + "_s"] = jnp.ones((E, 1, v.shape[2]), jnp.float32)
    return w


# what the layer can see -> the lowering of its dispatch and combine: the
# answer of ``grouped_lowering`` for its FFN; the Mellum2 cell is the first
RULE = {
    "the-cell": (dict(S=16384, bound=65536, w=stacks(16, 2304, 896),
                      dt=jnp.bfloat16, interpret=True), "pallas"),
    "a-decode-steps-rows": (dict(S=8, bound=64, w=stacks(16, 2304, 896),
                                 dt=jnp.bfloat16, interpret=True), "xla"),
    "int8-stacks": (dict(S=16384, bound=65536, w=int8_stacks(16, 2304, 896),
                         dt=jnp.bfloat16, interpret=True), "xla"),
    "float32": (dict(S=16384, bound=65536, w=stacks(16, 2304, 896),
                     dt=jnp.float32, interpret=True), "xla"),
    "a-width-of-2300": (dict(S=16384, bound=65536, w=stacks(16, 2300, 896),
                             dt=jnp.bfloat16, interpret=True), "xla"),
    "the-cpu": (dict(S=16384, bound=65536, w=stacks(16, 2304, 896),
                     dt=jnp.bfloat16, interpret=None), "xla"),
    "the-padded-twin": (dict(S=16384, bound=65536, w=stacks(16, 2304, 896),
                             dt=jnp.bfloat16, interpret=True,
                             kernel="padded"), "xla"),
    "tokens-no-tile-divides": (dict(S=16380, bound=65536,
                                    w=stacks(16, 2304, 896),
                                    dt=jnp.bfloat16, interpret=True), "xla"),
    "more-rows-than-scalar-memory-holds": (
        dict(S=262144, bound=1048576, w=stacks(16, 2304, 896),
             dt=jnp.bfloat16, interpret=True), "xla"),
}


@pytest.mark.parametrize("case", sorted(RULE))
def test_the_rule_that_picks_the_moves_lowering(case):
    facts, want = RULE[case]
    facts = dict({"kernel": "ragged"}, **facts)
    shapes = jax.eval_shape(lambda: facts.pop("w"))
    took, interpret = sm._moves_lowering(w=shapes, **facts)
    assert took == want
    assert interpret == (want == "pallas")


def test_only_a_layer_that_takes_the_kernels_loads_them():
    """A model without experts loads neither the grouped products nor the row
    kernels, and an expert layer whose moves are ``jnp.take`` (the CPU) does
    not load the row kernels (``setup_s`` of the cells that are there)."""
    import os
    import subprocess
    import sys

    code = (
        "import sys, jax, jax.numpy as jnp\n"
        "import deepspeed_tpu\n"
        "from deepspeed_tpu.models import TransformerConfig, TransformerLM\n"
        "def run(**kw):\n"
        "    m = TransformerLM(TransformerConfig(hidden_size=64, num_heads=4,"
        " num_layers=2, vocab_size=64, **kw))\n"
        "    p = m.init(jax.random.key(0))\n"
        "    jax.grad(lambda p, b: m.loss_fn(p, b))(p, {'input_ids':"
        " jnp.zeros((1, 8), 'int32')})\n"
        "    return sorted(k.rsplit('.', 1)[1] for k in sys.modules"
        " if k.endswith(('ops.moe_rows', 'ops.grouped_matmul')))\n"
        "print(run())\n"
        "print(run(num_experts=4, top_k=2, moe_dispatch='grouped',"
        " moe_intermediate_size=32))\n")
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    out = subprocess.run([sys.executable, "-c", code], cwd=root, text=True,
                         capture_output=True,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-2:] == [
        "[]", "['grouped_matmul']"]
