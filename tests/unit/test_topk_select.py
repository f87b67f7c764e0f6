"""The router's selection kernel (``ops/topk_select.py``), interpreted on the
CPU: its indices are ``lax.top_k``'s element for element, order and ties
included; the group limit is the ``jax.numpy`` lines'; the picker says why."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from deepspeed_tpu.ops import lowerings
from deepspeed_tpu.ops import topk_select as ts

# the routers the benchmark's cells run: (experts, experts a token)
ROUTERS = [(512, 8), (512, 22), (256, 10), (128, 8), (128, 6)]
ROWS = 128


def _scores(E, seed=0, rows=ROWS):
    rng = np.random.default_rng(seed)
    s = 1.0 / (1.0 + np.exp(-rng.standard_normal((rows, E))))
    return (s + 0.01 * rng.standard_normal((E,))).astype(np.float32)


def _tied(E, k, seed=1):
    """Scores with equal values planted: a grid of a few values in every
    fifth lane, a row's maximum at several lanes, one on each side of a
    lane tile's edge, and a tie of four at the k-th place."""
    x = _scores(E, seed)
    x[:, ::5] = np.round(x[:, ::5], 1)
    x[0, [3, 77, E - 1]] = 9.0                    # the maximum, three times
    x[1, [E - 129, E - 128]] = 9.0                # across a lane tile's edge
    x[1, [E - 127, 2]] = 8.0
    at = np.random.default_rng(seed).permutation(E)
    x[2, at[:k - 1]] = 5.0 + np.arange(k - 1)     # k - 1 clear winners, then
    x[2, at[k - 1:k + 3]] = 4.0                   # four at the k-th place
    x[3] = 0.25                                   # a row of one value
    return x


@pytest.mark.parametrize("planted", ["random", "ties"])
@pytest.mark.parametrize("E, k", ROUTERS)
def test_the_kernels_indices_are_lax_top_ks(E, k, planted):
    x = jnp.asarray(_scores(E) if planted == "random" else _tied(E, k))
    idx, keep = ts.topk_select(x, k, interpret=True)
    assert keep is None and idx.dtype == jnp.int32
    np.testing.assert_array_equal(idx, lax.top_k(x, k)[1])


@pytest.mark.parametrize("E, k", [(128, 8), (512, 22)])
def test_a_row_with_fewer_than_k_finite_scores(E, k):
    """``-inf`` everywhere but a few lanes, both zeros, an ``inf``: the
    rounds take what is left by index, as the sort does (a taken lane is
    marked apart from ``-inf``)."""
    x = np.full((ROWS, E), -np.inf, np.float32)
    x[:, 5], x[:, 100], x[:, 101] = 0.5, -0.0, 0.0
    x[1] = -np.inf
    x[2, :] = 0.0
    x[2, 7::9] = -0.0
    x[3, 64] = np.inf
    x = jnp.asarray(x)
    np.testing.assert_array_equal(ts.topk_select(x, k, interpret=True)[0],
                                  lax.top_k(x, k)[1])


def _kept_groups_as_it_was(sb, n_group, topk_group):
    """``moe/sharded_moe.py:_kept_groups`` before the op took it."""
    B, T, E = sb.shape
    grouped = sb.reshape(B, T, n_group, E // n_group)
    score = lax.top_k(grouped, 2)[0].sum(-1)
    _, best = lax.top_k(score, topk_group)
    keep = (best[..., None] == jnp.arange(n_group)).any(axis=-2)
    return (jnp.where(keep[..., None], grouped, -jnp.inf).reshape(B, T, E),
            keep, keep.sum(axis=(0, 1), dtype=jnp.int32))


@pytest.mark.parametrize("E, k, groups", [(512, 8, (8, 4)), (128, 6, (4, 2)),
                                          (256, 10, (8, 3))])
def test_the_group_limit_is_the_lines_it_replaced(E, k, groups):
    """``keep``, the count of tokens a group and the final indices, with two
    groups' scores tied (rows 0 and 1: the lower group is kept), a group
    whose maximum stands twice (row 2) and a tie inside the kept groups."""
    n, size = groups[0], E // groups[0]
    x = _scores(E, seed=3, rows=2 * ROWS)
    x[:, ::7] = np.round(x[:, ::7], 1)
    for row, (a, b) in enumerate([(1, 2), (n - 1, 0)]):
        x[row] = np.linspace(0.1, 0.3, E)
        x[row, [a * size + 3, a * size + 9]] = 2.0, 1.0
        x[row, [b * size + 1, b * size + size - 1]] = 1.5, 1.5
    x[2, 5 * size % E + 2] = x[2, 5 * size % E + 4] = 3.0
    x = jnp.asarray(x).reshape(2, ROWS, E)
    masked, keep_was, kept_was = _kept_groups_as_it_was(x, *groups)
    idx, keep = ts.topk_select(x, k, groups, interpret=True)
    np.testing.assert_array_equal(keep, keep_was)
    np.testing.assert_array_equal(keep.sum(axis=(0, 1), dtype=jnp.int32),
                                  kept_was)
    np.testing.assert_array_equal(idx, lax.top_k(masked, k)[1])
    assert int(kept_was.sum()) == groups[1] * 2 * ROWS
    twin = ts.topk_select_xla(x, k, groups)
    np.testing.assert_array_equal(twin[0], idx)
    np.testing.assert_array_equal(twin[1], keep)


def test_rows_in_several_tiles_and_leading_axes():
    x = jnp.asarray(_scores(128, seed=5, rows=3 * 256)).reshape(3, 256, 128)
    idx, _ = ts.topk_select(x, 4, interpret=True)
    assert idx.shape == (3, 256, 4)
    np.testing.assert_array_equal(idx, lax.top_k(x, 4)[1])


@pytest.mark.parametrize("T, E, k, groups, dtype, tpu, lowering, why", [
    (8192, 512, 8, (8, 4), jnp.float32, True, "pallas", ""),
    (8192, 512, 22, (1, 1), jnp.float32, True, "pallas", ""),
    (8192, 512, 8, (8, 4), jnp.float32, False, "xla", "not a TPU"),
    (8192, 64, 8, (1, 1), jnp.float32, True, "xla", "64 experts"),
    (8192, 512, 8, (1, 1), jnp.bfloat16, True, "xla", "bfloat16 scores"),
    (100, 128, 8, (1, 1), jnp.float32, True, "xla", "100 rows"),
    (8192, 128, 64, (1, 1), jnp.float32, True, "xla", "k = 64"),
    (8192, 128, 8, (32, 4), jnp.float32, True, "xla", "groups (32, 4)"),
])
def test_the_picker_says_which_and_why(T, E, k, groups, dtype, tpu, lowering,
                                       why):
    got, said = ts.topk_lowering(T, E, k, groups, dtype, tpu=tpu)
    assert got == lowering and why in said and bool(said) == bool(why)


def test_off_a_tpu_the_op_is_lax_top_k_counted_and_without_gradient():
    x = jnp.asarray(_scores(512, seed=7))
    snap = lowerings.snapshot()
    idx, keep = ts.topk_select(x, 8, (8, 4))
    assert lowerings.since(snap) == {"moe_topk": {"xla": 1}}
    want = ts.topk_select_xla(x, 8, (8, 4))
    np.testing.assert_array_equal(idx, want[0])
    np.testing.assert_array_equal(keep, want[1])
    snap = lowerings.snapshot()
    g = jax.grad(lambda x: jnp.take_along_axis(
        x, ts.topk_select(x, 8, interpret=True)[0], axis=-1).sum())(x)
    assert lowerings.since(snap) == {"moe_topk": {"pallas": 1}}
    assert float(g.sum()) == 8 * ROWS            # the picks' own, no more
    with pytest.raises(ValueError, match="64 experts"):
        ts.topk_select(x[:, :64], 8, interpret=True)
