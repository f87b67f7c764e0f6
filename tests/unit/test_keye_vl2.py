"""Attention over the keys a learned indexer picks for each query, the indexer
trained beside the model by a loss of its own, under a rope over three
position axes, over a held share of softmax-routed experts (Keye-VL-2.0's
language model): the program against the plain reference
(``benchmarks/reference_keye_vl2.py``: the tests import it from there, a
reference is held once) on seeded random weights with every scale and bias
drawn; the exact selection against ``lax.top_k``, ties and all; which loss
trains which leaves; the rope; the expert shares adding up to the whole
layer; the readings the benchmark cell's check has to tell from the model;
the step programs of the models that do not load the kind; what refuses the
model; and the published config's mapping."""

import dataclasses
import hashlib
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import modelcfg_keye_vl2 as modelcfg
from benchmarks import opcount_keye_vl2 as opcount
from benchmarks import reference_keye_vl2 as ref
from deepspeed_tpu.models import (TransformerConfig, TransformerLM,
                                  get_preset)
from deepspeed_tpu.models import transformer as tr
from deepspeed_tpu.ops import dsa

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL_CONFIG = os.path.join(ROOT, "benchmarks", "configs",
                           "keye_vl2_30b_train_d5e16v8.json")
ALPHA, T = 1e-2, 64


def hf_config(**over):
    """A small file of the cell's keys: hidden 32, 4 query heads of 16 on 2
    key-value heads, a 4-head indexer of 8 channels keeping 12 keys a query
    in tiles of 16, 4 of 8 experts held from the 2nd on, 2 a token."""
    hf = {"model_type": "KeyeVL2", "vocab_size": 64, "hidden_size": 32,
          "num_hidden_layers": 2, "num_attention_heads": 4,
          "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 64,
          "max_position_embeddings": 128, "rope_theta": 1e4,
          "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
          "rope_scaling": {"mrope_section": [2, 2, 4],
                           "rope_type": "default", "type": "default"},
          "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                        "indexer_num_kv_heads": 1, "kv_chunk_size": 16,
                        "q_chunk_size": 16, "topk": 12},
          "num_experts": 4, "router_width": 8, "first_expert": 2,
          "num_experts_per_tok": 2, "moe_intermediate_size": 16,
          "norm_topk_prob": True,
          "deployment": {"local_pairs_factor": 4.0,
                         "load_balance_coef": ALPHA,
                         "indexer_loss_coef": 1.0, "remat_policy": "full",
                         "embed_init_std": 1.0}}
    hf.update(over)
    return hf


def model_for(hf, dtype="float32", **over):
    return TransformerLM(modelcfg.transformer_config(
        hf, max_seq_len=T, param_dtype="float32", dtype=dtype, **over))


def init(model, seed=0):
    """Seeded weights with nothing left at 1 or 0: every norm's scale and
    the LayerNorm's bias drawn."""
    params = jax.jit(model.init)(jax.random.key(seed))     # one program, not an op at a time
    key = jax.random.key(seed + 1)

    def jig(path, a):
        name = jax.tree_util.keystr(path)
        if re.search(r"norm|ln\d|bias", name):
            return a + 0.3 * jax.random.normal(
                jax.random.fold_in(key, name_hash(name)), a.shape)
        return a

    return jax.tree_util.tree_map_with_path(jig, params)


def name_hash(name: str) -> int:
    return int(hashlib.sha256(name.encode()).hexdigest()[:6], 16)


def a_batch(seed=0, rows=2):
    """Ids and positions whose three axes differ (sorted draws: positions
    do not run backwards, as a row with image spans has them)."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 64, (rows, T)).astype(np.int32)
    pos = np.stack([np.sort(rng.integers(0, T, (rows, T)), axis=-1)
                    for _ in range(3)]).astype(np.int32)
    return {"input_ids": ids, "position_ids": pos}


def reference_of(hf, params, batch, **kw):
    pos = batch.get("position_ids")
    return ref.batch_loss_and_grads(
        hf, modelcfg.weights_getter(params), list(batch["input_ids"]), ALPHA,
        positions=None if pos is None else [pos[:, b]
                                            for b in range(pos.shape[1])],
        **kw)


@pytest.fixture(scope="module")
def small(run_memo):
    old, ref._QUERY_BLOCK = ref._QUERY_BLOCK, 32
    hf = hf_config()
    model = model_for(hf)
    params = init(model)
    batch = a_batch()
    ((loss, parts), grads), (want, ref_grads) = run_memo("keye_vl2_small", lambda: (
        jax.jit(jax.value_and_grad(model.loss_and_parts, has_aux=True))(
            params, batch), reference_of(hf, params, batch)))
    yield hf, model, params, batch, loss, parts, grads, want, ref_grads
    ref._QUERY_BLOCK = old


# ---- the program against the reference ------------------------------------

def test_loss_parts_sets_and_every_gradient_match_the_reference(small):
    hf, model, params, batch, loss, parts, grads, want, ref_grads = small
    assert float(loss) == pytest.approx(float(want["loss"]), abs=2e-6)
    for name, tol in (("lb_loss", 1e-6), ("indexer_loss", 1e-6),
                      ("mix_out_ms", 1e-5)):
        np.testing.assert_allclose(parts[name], want[name], rtol=tol,
                                   atol=tol)
    np.testing.assert_array_equal(parts["expert_pairs"],
                                  want["expert_pairs"])
    sets = np.unpackbits(np.asarray(parts["dsa_probe_sets"]), axis=-1,
                         bitorder="little").astype(bool)
    np.testing.assert_array_equal(sets, want["probe_sets"])
    # (a probe query past the topk-th keeps topk keys, an earlier one all)
    assert sets.sum(-1).max() == 12 and sets.sum(-1).min() == 8
    got = modelcfg.weights_getter(grads)
    assert len(ref_grads) == 3 + 2 * len(ref.LAYER_TENSORS)
    for (name, layer), g in ref_grads.items():
        mine = np.asarray(got(name, layer))
        assert np.linalg.norm(mine - g) <= 2e-5 * np.linalg.norm(g), \
            (name, layer)


def test_the_layer_by_layer_gradient_is_the_whole_losss(small):
    hf, _, params, batch, *_, ref_grads = small
    get = modelcfg.weights_getter(params)
    weights = {(n, None): get(n) for n in ("embed", "final_norm", "lm_head")}
    weights.update({(n, i): get(n, i) for i in range(2)
                    for n in ref.LAYER_TENSORS})
    pos = batch["position_ids"]
    _, whole = ref.loss_and_grads(
        hf, weights, list(batch["input_ids"]), ALPHA,
        positions=[pos[:, b] for b in range(2)])
    for key, g in ref_grads.items():
        assert np.linalg.norm(whole[key] - g) <= 1e-5 * np.linalg.norm(g)


def test_in_bf16_the_program_stays_near_the_reference(small):
    hf, _, params, batch, *_, want, _ = small
    _, parts = jax.jit(model_for(hf, dtype="bfloat16").loss_and_parts)(
        params, batch)
    assert float(parts["indexer_loss"]) == pytest.approx(
        float(want["indexer_loss"]), rel=0.05)
    np.testing.assert_allclose(parts["mix_out_ms"], want["mix_out_ms"],
                               rtol=0.05)


def test_rows_no_longer_than_topk_are_plain_causal_attention():
    """Every key a query may see is in its set: the model is the one whose
    layers are of kind "full", weight for weight."""
    hf = hf_config(sa_config={**hf_config()["sa_config"], "topk": T})
    model = model_for(hf)
    params = init(model)
    plain = TransformerLM(dataclasses.replace(
        model.cfg, attn_pattern=None, dsa_index_heads=0,
        attention_impl="xla"))
    layers = {k: v for k, v in params["layers"].items() if k != "indexer"}
    batch = a_batch(1)
    a = model.logits(params, batch["input_ids"])
    b = plain.logits({**params, "layers": layers}, batch["input_ids"])
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


# ---- the selection ---------------------------------------------------------

def _top_k_mask(scores, q_pos, k):
    S = scores.shape[1]
    causal = np.arange(S)[None] <= np.asarray(q_pos)[:, None]
    s = jnp.where(scores == 0, 0.0, scores)
    _, idx = jax.lax.top_k(jnp.where(causal, s, -jnp.inf), min(k, S))
    mask = np.zeros(scores.shape, bool)
    mask[np.arange(scores.shape[0])[:, None], np.asarray(idx)] = True
    return mask & causal


@pytest.mark.parametrize("S, k, levels", [
    (64, 12, 0), (64, 12, 5), (256, 40, 3), (256, 255, 7), (128, 128, 4),
    (128, 200, 2), (512, 1, 2)])
def test_the_set_is_top_ks_with_ties_to_the_lower_position(S, k, levels):
    """``levels`` > 0 plants ties: the scores take that many values only
    (zeros of both signs and negatives among them)."""
    rng = np.random.default_rng(S + k + levels)
    n = 32
    if levels:
        values = np.concatenate([[0.0, -0.0], rng.normal(size=levels)])
        scores = values[rng.integers(0, len(values), (n, S))]
    else:
        scores = rng.normal(size=(n, S))
    scores = jnp.asarray(scores, jnp.float32)
    q_pos = jnp.asarray(np.sort(rng.integers(0, S, n)), jnp.int32
                        ).at[-1].set(S - 1)
    got = np.asarray(jax.jit(dsa.select, static_argnums=2)(scores, q_pos, k))
    np.testing.assert_array_equal(got, _top_k_mask(scores, q_pos, k))
    assert (got.sum(-1) == np.minimum(np.asarray(q_pos) + 1, k)).all()


def test_the_threshold_is_the_kth_largest_of_any_floats():
    x = jnp.asarray([[3.5, -1.0, 0.0, -0.0, 1e-30, -1e-30, np.inf, -2.5,
                      7.0, 7.0, -np.inf, 2.0 ** -140]], jnp.float32)
    u = dsa.sortable(x)
    order = np.argsort(np.asarray(u[0]), kind="stable")
    assert (np.diff(np.asarray(x[0])[order]) >= 0).all()
    assert int(u[0, 2]) == int(u[0, 3]) and int(u.min()) > 0
    want = np.sort(np.asarray(u[0]))[::-1]
    for k in range(1, 13):
        assert int(dsa.kth_largest(u, k)[0]) == int(want[k - 1])


def test_packed_sets_unpack_to_the_mask():
    mask = np.random.default_rng(0).random((16, 64)) < 0.3
    packed = dsa._pack(jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(dsa._unpack(packed)), mask)
    np.testing.assert_array_equal(
        np.unpackbits(np.asarray(packed), axis=-1, bitorder="little"), mask)
    assert dsa.selected_share(16384, 2048) == pytest.approx(0.2344, abs=1e-4)
    assert dsa.runs(16384, 512) == [(0, 8), (8, 8), (16, 8), (24, 8)]
    assert dsa.runs(64, 64) == [(0, 1)]


# ---- the indexer's kernels against the jax.numpy lines ---------------------

#: a row of 512 in tiles of 128: four runs of one query tile, up to four key
#: tiles a query tile; 4 heads of 64 are two lane tiles of ``qi``
KT, KTILE, KJ, KC, KTOP = 512, 128, 4, 64, 96


def _indexer_inputs(dtype):
    ks = jax.random.split(jax.random.key(7), 4)
    draw = lambda k, shape: jax.random.normal(  # noqa: E731
        k, shape, jnp.float32).astype(dtype)
    qi, ki = draw(ks[0], (KT, KJ, KC)), draw(ks[1], (KT, KC))
    wi = jax.random.normal(ks[2], (KT, KJ)) / np.sqrt(KJ * KC)
    return qi, ki, wi, ks[3]


def _both_lowerings(dtype, i, keys, beyond=0.0):
    """The kernels (interpreted) and the ``jax.numpy`` lines on query tile
    ``i`` against ``keys`` keys: ``(scores, dqi, dki, dwi)`` of each.
    ``d_scores`` is drawn, a seventh of it kept, and ``beyond`` times the
    draw on the key tiles after the query tile's own (where the program's is
    zero: the set is causal)."""
    qi, ki, wi, key = _indexer_inputs(dtype)
    kept = jax.random.uniform(jax.random.fold_in(key, 1), (KTILE, keys)) < 1 / 7
    d_scores = jax.random.normal(key, (KTILE, keys)) * kept * jnp.where(
        jnp.arange(keys) < (i + 1) * KTILE, 1.0, beyond)
    rows = slice(i * KTILE, (i + 1) * KTILE)
    scores, r = dsa.index_scores(qi[rows], ki[:keys], wi[rows])
    twin = (scores,) + dsa.index_grads(d_scores, r, qi[rows], ki[:keys],
                                       wi[rows])
    at, flat, kk = jnp.int32(i), qi.reshape(KT, -1), dsa.lane_keys(ki)
    dq, dk, dw = dsa.dsa_index_bwd(at, flat, kk, wi, d_scores, tile=KTILE,
                                   interpret=True)
    mine = (dsa.dsa_index_fwd(at, flat, kk, wi, tile=KTILE, keys=keys,
                              interpret=True),
            dq.reshape(KTILE, KJ, KC), dk, dw)
    return [np.asarray(x, np.float64) for x in mine], \
        [np.asarray(x, np.float64) for x in twin], d_scores


def _rel(a, b):
    return np.linalg.norm(a - b) / np.linalg.norm(b)


@pytest.mark.parametrize("case", ["float32", "bfloat16", "dead_key_tiles",
                                  "shapes_that_do_not_fit"])
def test_the_indexers_kernels_are_the_jax_numpy_lines(case):
    """``dsa_index_fwd`` / ``dsa_index_bwd`` interpreted, against
    ``index_scores`` / ``index_grads``, a query tile of each run."""
    from deepspeed_tpu.ops import lowerings

    if case == "float32":
        assert dsa.runs(KT, KTILE) == [(0, 1), (1, 1), (2, 1), (3, 1)]
        for i in (1, 3):            # the second run's tile and the last's
            mine, twin, _ = _both_lowerings(jnp.float32, i, (i + 1) * KTILE)
            for a, b in zip(mine, twin):
                assert _rel(a, b) < 1e-5
    elif case == "bfloat16":
        # the head scores round as the einsum's do: the weighted sums differ
        # by the order of a float32 sum over the heads, but where a product
        # fell within an ulp of a rounding boundary
        (scores, dq, dk, dw), twin, _ = _both_lowerings(jnp.bfloat16, 3, KT)
        off = np.abs(scores - twin[0]) > 4e-7 * np.abs(twin[0]).max()
        assert off.mean() < 1e-3 and _rel(scores, twin[0]) < 1e-4
        for a, b in zip((dq, dk, dw), twin[1:]):
            assert _rel(a, b) < 2e-3
        pos = 3 * KTILE + jnp.arange(KTILE, dtype=jnp.int32)
        sets = [np.asarray(dsa.select(jnp.asarray(x, jnp.float32), pos,
                                      KTOP)) for x in (scores, twin[0])]
        # a set differs only where a flipped product sits at the threshold
        assert (sets[0] != sets[1]).sum() <= 2 * off.sum()
        assert (sets[0].sum(-1) == KTOP).all()
    elif case == "dead_key_tiles":
        # query tile 1 against the whole row: key tiles 2 and 3 come after
        # it, whatever ``d_scores`` holds there
        mine, _, d_scores = _both_lowerings(jnp.float32, 1, KT, beyond=1.0)
        _, twin, _ = _both_lowerings(jnp.float32, 1, KT, beyond=0.0)
        assert np.any(np.asarray(d_scores)[:, 2 * KTILE:])
        assert not mine[0][:, 2 * KTILE:].any()
        assert not mine[2][2 * KTILE:].any()
        assert _rel(mine[0][:, :2 * KTILE], twin[0][:, :2 * KTILE]) < 1e-5
        for a, b in zip(mine[1:], twin[1:]):
            assert _rel(a, b) < 1e-5
    else:
        assert dsa.index_lowering(512, 16, 64, jnp.bfloat16, tpu=True) \
            == ("pallas", "")
        for shape in ((512, 16, 64, jnp.float32), (72, 16, 64, jnp.bfloat16),
                      (512, 3, 64, jnp.bfloat16), (512, 16, 128, jnp.bfloat16),
                      (512, 64, 64, jnp.bfloat16)):
            assert dsa.index_lowering(*shape, tpu=True)[0] == "jnp", shape
        assert dsa.index_lowering(512, 16, 64, jnp.bfloat16)[0] == "jnp"
        # heads of 8 in tiles of 16 (the module's small model): the picker
        # is not asked under ``interpret`` and the kernels refuse; asked, on
        # this backend, it takes the lines and says so
        draw = lambda *shape: jax.random.normal(  # noqa: E731
            jax.random.key(0), shape)
        args = (draw(1, 32, 2, 16), draw(1, 32, 1, 16), draw(1, 32, 1, 16),
                draw(1, 32, 4, 8), draw(1, 32, 8), draw(1, 32, 4))
        with pytest.raises(ValueError, match="do not take tiles of 16"):
            dsa.dsa_attention(*args, 12, 16, True)
        snap = lowerings.snapshot()
        jax.make_jaxpr(lambda *a: dsa.dsa_attention(*a, 12, 16))(*args)
        assert lowerings.since(snap)["dsa"] == {"jnp": 1}


def test_the_op_with_the_kernels_keeps_the_sets_and_the_gradients():
    """The whole op with the indexer's kernels (interpreted) against the
    ``jax.numpy`` lines through its ``custom_vjp``, two rows of two query
    tiles: the probe queries' sets, the loss and every gradient; and the
    count names the kernels."""
    from deepspeed_tpu.ops import lowerings

    ks = jax.random.split(jax.random.key(3), 6)
    n = 2 * KTILE
    draw = lambda k, *shape: jax.random.normal(k, shape)  # noqa: E731
    args = (draw(ks[0], 2, n, 2, 16), draw(ks[1], 2, n, 1, 16),
            draw(ks[2], 2, n, 1, 16), draw(ks[3], 2, n, KJ, KC),
            draw(ks[4], 2, n, KC), draw(ks[5], 2, n, KJ) / np.sqrt(KJ * KC))

    def run(interpret):
        def loss(*a):
            o, kl, probes = dsa.dsa_attention(*a, KTOP, KTILE, interpret)
            return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(kl), probes
        return jax.jit(jax.value_and_grad(loss, argnums=range(6),
                                          has_aux=True))(*args)

    snap = lowerings.snapshot()
    (a, sets_a), grads_a = run(True)
    assert lowerings.since(snap)["dsa"] == {"pallas": 1}
    (b, sets_b), grads_b = run(None)
    assert float(a) == pytest.approx(float(b), rel=1e-5)
    np.testing.assert_array_equal(sets_a, sets_b)
    assert np.unpackbits(np.asarray(sets_a)).sum() > dsa.PROBES * KTOP
    for x, y in zip(grads_a, grads_b):
        assert _rel(np.asarray(x, np.float64), np.asarray(y, np.float64)) \
            < 1e-5


# ---- which loss trains which leaves ---------------------------------------

def _leaves(tree):
    return {jax.tree_util.keystr(p): np.asarray(v) for p, v in
            jax.tree_util.tree_leaves_with_path(tree)}


def test_each_loss_trains_its_own_leaves_and_no_other(small):
    hf, model, params, batch, *_ = small
    lm_only = model_for(hf, indexer_loss_coef=0.0)
    by_lm = _leaves(jax.jit(jax.grad(lm_only.loss_fn))(params, batch))
    by_index = _leaves(jax.jit(jax.grad(
        lambda p: model.loss_and_parts(p, batch)[1]["indexer_loss"]))(params))
    for name in by_lm:
        mine = "'indexer'" in name
        assert (np.any(by_index[name] != 0) and not np.any(by_lm[name])) \
            if mine else (np.any(by_lm[name] != 0)
                          and not np.any(by_index[name])), name


def test_the_indexer_loss_is_a_kl_and_falls_under_its_own_gradient(small):
    _, model, params, batch, *_ = small

    def f(p):
        return model.loss_and_parts(p, batch)[1]["indexer_loss"]

    value, g = jax.jit(jax.value_and_grad(f))(params)
    stepped = jax.tree_util.tree_map(lambda p, d: p - 0.05 * d, params, g)
    assert 0 < float(jax.jit(f)(stepped)) < float(value)


# ---- the rope --------------------------------------------------------------

def test_three_equal_axes_are_the_plain_rope_and_sections_pick_the_axis():
    freqs = tr.rope_frequencies(16, T, 1e4)
    x = jax.random.normal(jax.random.key(0), (2, T, 3, 16))
    pos = jnp.asarray(a_batch(2)["position_ids"])
    same = jnp.broadcast_to(pos[:1], pos.shape)
    np.testing.assert_array_equal(
        tr.apply_rope(x, freqs, same, sections=(2, 2, 4)),
        tr.apply_rope(x, freqs, same[0]))
    got = tr.apply_rope(x, freqs, pos, sections=(2, 2, 4))
    for b in range(2):
        np.testing.assert_allclose(
            got[b], ref.rope(x[b], pos[:, b], 1e4, [2, 2, 4]), rtol=1e-5,
            atol=1e-5)
    # each pair turns by its own axis alone
    for axis, pairs in enumerate((slice(0, 2), slice(2, 4), slice(4, 8))):
        moved = pos.at[axis].add(1)
        diff = np.abs(tr.apply_rope(x, freqs, moved, sections=(2, 2, 4))
                      - got).reshape(-1, 2, 8).max(axis=(0, 1))
        assert (diff[pairs] > 0).all() and not np.delete(
            diff, np.r_[pairs]).any()
    with pytest.raises(ValueError, match="mrope_section"):
        tr.apply_rope(x, freqs, pos, sections=(2, 2, 2))
    # positions [B, T] trace to the program they had
    two = lambda f: str(jax.make_jaxpr(f)(x, freqs, pos[0]))  # noqa: E731
    assert two(lambda x, f, p: tr.apply_rope(x, f, p)) == two(
        lambda x, f, p: tr.apply_rope(x, f, p, sections=(2, 2, 4)))


def test_a_batch_without_positions_reads_the_tokens_index(small):
    _, model, params, batch, *_ = small
    T_ = batch["input_ids"].shape[1]
    index = np.broadcast_to(np.arange(T_, dtype=np.int32), (3, 2, T_))
    a, _ = jax.jit(model.loss_and_parts)(params, {
        "input_ids": batch["input_ids"]})
    b, _ = jax.jit(model.loss_and_parts)(params, {
        "input_ids": batch["input_ids"], "position_ids": index})
    assert float(a) == pytest.approx(float(b), abs=1e-6)
    with pytest.raises(ValueError, match="position_ids"):
        model.loss_and_parts(params, {**batch,
                                      "position_ids": index[:, :, :-1]})


# ---- the share -------------------------------------------------------------

def test_the_eight_expert_shares_add_up_to_the_uncut_layer():
    """One layer's output less its attention branch, summed over the eight
    shares of two experts, is the uncut layer's (the router, the top k and
    the attention are every share's alike)."""
    hf = hf_config(num_experts=16, router_width=16, first_expert=0)
    whole = model_for(hf, moe_ep_capacity_factor=16.0)
    params = init(whole)
    layer = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.key(3), (2, T, 32))
    pos = jnp.asarray(a_batch(3)["position_ids"])

    def run(model, w):
        ck, freqs = model._kinds["dsa"]
        y, aux = tr.transformer_block(x, w, ck, freqs, None, model.moe_fn,
                                      positions=pos, kind="dsa")
        return y, aux

    y, aux = run(whole, layer)
    # the attention branch alone: a layer whose experts give nothing
    dead = {**layer, "mlp": {**layer["mlp"], "w_down": jnp.zeros_like(
        layer["mlp"]["w_down"])}}
    a, _ = run(whole, dead)
    total = 0.0
    for first in range(0, 16, 2):
        share = TransformerLM(dataclasses.replace(
            whole.cfg, moe_experts_held=2, moe_first_expert=first))
        w = {**layer, "mlp": {
            n: (t if n == "router" else t[first:first + 2])
            for n, t in layer["mlp"].items()}}
        part, aux_s = run(share, w)
        total = total + (part - a)
        np.testing.assert_allclose(aux_s["indexer_loss"],
                                   aux["indexer_loss"], rtol=1e-6)
    np.testing.assert_allclose(total, y - a, rtol=2e-4, atol=2e-5)


# ---- the readings the cell's check has to tell from the model --------------

#: fault -> the part that shows it and the least it differs by (the program
#: itself agrees to 1e-5)
FAULTS = {"window": ("probe_sets", 0.2), "rope_one_axis": ("mix_out_ms", 1e-3),
          "no_indexer_loss": ("loss", 0.05)}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_wrong_reading_in_the_references_place_shows(small, fault):
    hf, _, params, batch, *_, want, ref_grads = small
    assert set(FAULTS) == set(ref.FAULTS)
    part, least = FAULTS[fault]
    wrong, grads = reference_of({**hf, "fault": fault}, params, batch)
    a, b = np.asarray(wrong[part], np.float64), np.asarray(want[part],
                                                           np.float64)
    if part == "probe_sets":
        assert (a != b).sum() / (2.0 * b.sum()) > least
    else:
        assert np.max(np.abs(a - b) / np.abs(b)) > least
    if fault == "no_indexer_loss":
        # the indexer's leaves then get no gradient at all
        assert all(not np.any(grads[(n, 0)]) for n in ref.INDEXER_TENSORS)
        assert all(np.any(ref_grads[(n, 0)]) for n in ref.INDEXER_TENSORS)


# ---- the step programs of the models that do not load the kind -------------

#: sha256 (16 digits) of ``str(make_jaxpr(value_and_grad(loss_and_parts)))``
#: at the tiny preset under ``test_step_scopes.CASES`` (addresses blanked),
#: taken at the commit before the kind came in (PR 55's tree): the nine
#: benchmark configurations' families trace to the programs they had. A PR
#: that changes one of these programs on purpose pins it anew (``kda_moe``:
#: PR 59, the output norm and gate as one op and the rule on ``[B, T, H d]``;
#: PR 61, the group limit inside the selection op, ``ops/topk_select.py``:
#: the tokens a group kept are counted after the k are chosen, the same
#: equations in another order; the routers without groups trace as they did)
PARENT_PROGRAMS = {
    "dense": "ca70fe9e6dc06654", "moe": "add2dbd3b8475941",
    "looped": "22d1ece2738da248", "pattern_share": "5f8a663a70b70003",
    "hybrid": "45320f843a00a76c", "mla_moe": "3b6cf076e31ab958",
    "delta_hybrid": "12b6c412bcf1322e", "conv_moe": "bcd0d74b3485ac49",
    "kda_moe": "ad206fc800b9fa8f"}


@pytest.mark.parametrize("case", sorted(PARENT_PROGRAMS))
def test_a_model_without_the_kind_traces_to_the_program_it_had(case):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_step_scope_cases", os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "test_step_scopes.py"))
    cases = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cases)
    CASES = cases.CASES
    model = TransformerLM(get_preset("tiny", **CASES[case][0]))
    params = jax.eval_shape(model.init, jax.random.key(0))
    text = str(jax.make_jaxpr(jax.value_and_grad(
        model.loss_and_parts, has_aux=True))(
            params, {"input_ids": jax.ShapeDtypeStruct((2, 32), np.int32)}))
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT_PROGRAMS[case]


def test_a_model_without_the_kind_does_not_load_it():
    code = ("import sys, jax\n"
            "import deepspeed_tpu\n"
            "from deepspeed_tpu.models import TransformerConfig, "
            "TransformerLM\n"
            "m = TransformerLM(TransformerConfig(vocab_size=64, "
            "hidden_size=32, num_layers=2, num_heads=2, arch='llama'))\n"
            "m.init(jax.random.key(0))\n"
            "assert 'deepspeed_tpu.models.dsa' not in sys.modules\n"
            "assert 'deepspeed_tpu.ops.dsa' not in sys.modules\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]


# ---- what the step says of itself ------------------------------------------

def test_params_plan_specs_facts_and_the_count_of_lowerings(small):
    from deepspeed_tpu.ops import lowerings

    hf, model, params, batch, *_ = small
    cfg = model.cfg
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == cfg.num_params_estimate()
    assert jax.tree_util.tree_structure(model.param_specs()) \
        == jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda a: 0, params))
    assert cfg.layer_kinds == ("dsa", "dsa") and cfg.patterned
    assert model._layer_plan() == [(0, 2, ("dsa",))]
    facts = model.step_program_facts((2, T))
    assert facts["dsa_topk"] == 12 and facts["mrope_axes"] == 3
    assert facts["dsa_selected_share"] == pytest.approx(
        (12 * 13 / 2 + 52 * 12) / (64 * 65 / 2))
    assert "dsa_selected_share" not in model.step_program_facts()
    snap = lowerings.snapshot()
    jax.make_jaxpr(jax.grad(model.loss_fn))(params, batch)
    # one traced body of the layer scan, once more for its recomputation
    assert lowerings.since(snap)["dsa"] == {"jnp": 2}
    assert model.working_copy(params) == {}    # float32 compute: no copy


def test_the_engines_step_takes_positions_over_three_axes():
    import deepspeed_tpu as ds
    from deepspeed_tpu.observability import steplog
    from deepspeed_tpu.parallel import build_mesh

    model = model_for(hf_config(), dtype="bfloat16")
    config = {"train_micro_batch_size_per_gpu": 2,
              "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
              "bf16": {"enabled": True}, "steps_per_print": 10 ** 9,
              "zero_optimization": {"stage": 0}}
    eng, *_ = ds.initialize(model=model, config=config,
                            mesh=build_mesh(devices=jax.devices()[:1]))
    batch = a_batch(5)
    loss = float(eng.fused_train_step(batch))
    assert np.isfinite(loss)
    row = steplog.programs()[-1]
    assert row.dsa_topk == 12 and row.mrope_axes == 3
    assert row.dsa_lowerings == {"jnp": 2}
    assert row.dsa_selected_share == pytest.approx(
        dsa.selected_share(T, 12))
    parts = steplog.get_steplog().parts(last=1)[-1]
    assert parts["indexer_loss"] > 0
    assert parts["dsa_probe_sets"].shape == (2, 2, dsa.PROBES, T // 8)
    # the same row with every axis the index reads another loss
    plain = float(eng.fused_train_step({"input_ids": batch["input_ids"]}))
    assert np.isfinite(plain)
    eng2, *_ = ds.initialize(
        model=model, config={**config, "gradient_accumulation_steps": 2,
                             "train_micro_batch_size_per_gpu": 1},
        mesh=build_mesh(devices=jax.devices()[:1]))
    with pytest.raises(NotImplementedError, match="position_ids"):
        eng2.fused_train_step(batch)


# ---- what refuses ----------------------------------------------------------

BASE = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
            num_kv_heads=2, head_dim_override=16, arch="llama",
            attn_pattern=("dsa",), dsa_index_heads=4, dsa_index_head_dim=8,
            dsa_topk=12, dsa_q_chunk=16, dsa_kv_chunk=16,
            mrope_section=(2, 2, 4), qk_norm="head")


@pytest.mark.parametrize("what, kw, error", [
    ("looped stack", dict(num_passes=2), NotImplementedError),
    ("parallel_block", dict(parallel_block=True), NotImplementedError),
    ("one_branch", dict(one_branch=True,
                        attn_pattern=("dsa", "dense")), NotImplementedError),
    ("heads_held", dict(heads_held=2), NotImplementedError),
    ("sliding_window", dict(sliding_window=8), NotImplementedError),
    ("fpdt", dict(attention_impl="fpdt", qk_norm=None),
     NotImplementedError),
    ("ring", dict(attention_impl="ring"), NotImplementedError),
    ("norm_placement", dict(norm_placement="post"), NotImplementedError),
    ("rope_scaling", dict(rope_scaling={"rope_type": "linear",
                                        "factor": 2.0}),
     NotImplementedError),
    ("dsa_index_kv_heads", dict(dsa_index_kv_heads=2), NotImplementedError),
    ("dsa_kv_chunk", dict(dsa_kv_chunk=32), NotImplementedError),
    ("dsa_index_heads", dict(dsa_index_heads=0), ValueError),
    ("dsa_topk", dict(dsa_topk=0), ValueError),
    ("three sections", dict(mrope_section=(2, 6)), ValueError),
    ("frequency pairs", dict(mrope_section=(2, 2, 2)), ValueError),
    ("does not scale", dict(mrope_section=(1, 3, 4)), ValueError),
    ("latent attention", dict(attn_pattern=None, kv_lora_rank=16,
                              qk_nope_head_dim=8, qk_rope_head_dim=16,
                              v_head_dim=8, qk_norm=None,
                              mrope_section=(2, 2, 4)), NotImplementedError),
])
def test_what_the_model_does_not_run_refuses_at_config_time(what, kw, error):
    with pytest.raises(error, match=what):
        TransformerConfig(**{**BASE, **kw})


def test_serving_the_tiled_loss_and_a_tp_axis_refuse_by_name():
    model = TransformerLM(TransformerConfig(**BASE))
    for call in (lambda: model.init_kv_cache(1),
                 lambda: model.init_paged_kv_cache(4),
                 lambda: model.set_random_ltd(4),
                 lambda: model.set_pld_depth(1)):
        with pytest.raises(NotImplementedError, match="'dsa' layers"):
            call()
    rope_only = TransformerLM(TransformerConfig(**{
        **BASE, "attn_pattern": None, "dsa_index_heads": 0}))
    with pytest.raises(NotImplementedError, match="three position axes"):
        rope_only.init_kv_cache(1)
    for axes in ({"tp": 2}, {"sp": 2}):
        with pytest.raises(NotImplementedError, match="tp or sp axis"):
            model.check_topology(axes)
    model.check_topology({"tp": 1, "fsdp": 4})
    with pytest.raises(ValueError, match="tiles of 16"):
        dsa.tile_for(40, 12, 16)
    with pytest.raises(ValueError, match="multiple of 8"):
        dsa.tile_for(12, 12, 16)
    assert dsa.tile_for(8, 12, 16) == 8


def test_a_plain_attention_model_takes_the_three_axes_too(small):
    """``mrope_section`` without the kind: the layer loop hands the batch's
    positions to the plain attention block."""
    *_, batch, _, _, _, _, _ = small
    model = TransformerLM(TransformerConfig(**{
        **BASE, "attn_pattern": None, "dsa_index_heads": 0,
        "dtype": "float32", "attention_impl": "xla"}))
    params = model.init(jax.random.key(0))
    index = np.broadcast_to(np.arange(T, dtype=np.int32), (3, 2, T))
    loss_fn = jax.jit(model.loss_fn)
    a = float(loss_fn(params, {"input_ids": batch["input_ids"]}))
    b = float(loss_fn(params, {"input_ids": batch["input_ids"],
                               "position_ids": index}))
    c = float(loss_fn(params, batch))
    assert a == pytest.approx(b, abs=1e-6) and abs(c - a) > 1e-4


# ---- the published config ---------------------------------------------------

def test_the_cells_file_maps_onto_the_model_and_counts_as_it_states():
    from deepspeed_tpu.models.hf import _CONFIG_ONLY, config_from_hf

    with open(CELL_CONFIG) as f:
        hf = json.load(f)
    cfg = modelcfg.transformer_config(hf, max_seq_len=16384,
                                      param_dtype="float32")
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.moe_intermediate_size, cfg.num_experts, cfg.top_k) == (
                2048, 32, 4, 128, 768, 128, 8)
    assert (cfg.dsa_index_heads, cfg.dsa_index_head_dim,
            cfg.dsa_index_kv_heads, cfg.dsa_topk, cfg.dsa_q_chunk,
            cfg.dsa_kv_chunk) == (16, 64, 1, 2048, 512, 512)
    assert cfg.mrope_section == (16, 24, 24) and cfg.rope_theta == 1e7
    assert cfg.qk_norm == "head" and cfg.attn_pattern == ("dsa",)
    assert (cfg.moe_experts_held, cfg.vocab_size, cfg.num_layers) == (
        16, 18992, 5)
    model = TransformerLM(cfg)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == cfg.num_params_estimate() == opcount.total_params(hf) \
        == 562_290_560
    assert "KeyeVL2" in _CONFIG_ONLY
    with pytest.raises(ValueError, match="block_size"):
        config_from_hf({**hf, "sa_config": {**hf["sa_config"],
                                            "block_size": 64}})
    with pytest.raises(ValueError, match="rope_scaling"):
        config_from_hf({**hf, "rope_scaling": {"rope_type": "yarn",
                                               "mrope_section": [16, 24, 24],
                                               "factor": 4.0}})
    with pytest.raises(ValueError, match="mlp_only_layers"):
        config_from_hf({**hf, "mlp_only_layers": [0]})
