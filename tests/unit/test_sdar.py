"""Block-diffusion training of a held share of softmax-routed experts
(SDAR-30B-A3B-Chat): the program against the plain reference
(``benchmarks/reference_sdar.py``: the tests import it from there, a
reference is held once) on seeded random weights with every scale drawn, at
blocks of 1, 4 and 8, the kernels interpreted; the ``2L`` row against the
definition, block by block; the flash kernels under the rounded diagonal
against a dense masked softmax, rows without a key and the merge of the
own-block term among them; the expert shares adding up to the whole layer;
the host noising; the readings the benchmark cell's check has to tell from
the model; what refuses the model; that a model without block diffusion
traces the kernels it traced; and the published config's mapping."""

import dataclasses
import hashlib
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import modelcfg_sdar as modelcfg
from benchmarks import opcount_sdar as opcount
from benchmarks import reference_sdar as ref
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.models import block_diffusion as bd
from deepspeed_tpu.models import transformer as tr
from deepspeed_tpu.ops import flash_attention as fa
from deepspeed_tpu.ops import lowerings
from deepspeed_tpu.runtime.data_pipeline import noise_batch

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL_CONFIG = os.path.join(ROOT, "benchmarks", "configs",
                           "sdar_30b_a3b_train_d5e16v8.json")
ALPHA, MASK = 1e-2, 63


def hf_config(block=4, **over):
    """A small file of the cell's keys: hidden 32, 4 query heads of 16 on 2
    key-value heads, 4 of 8 experts held from the 2nd on, 2 a position."""
    hf = {"model_type": "sdar_moe", "vocab_size": 64, "hidden_size": 32,
          "num_hidden_layers": 2, "num_attention_heads": 4,
          "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 64,
          "max_position_embeddings": 128, "rope_theta": 1e4,
          "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
          "rope_scaling": None,
          "num_experts": 4, "router_width": 8, "first_expert": 2,
          "num_experts_per_tok": 2, "moe_intermediate_size": 16,
          "norm_topk_prob": True, "block_length": block,
          "mask_token_id": MASK,
          "deployment": {"local_pairs_factor": 4.0,
                         "load_balance_coef": ALPHA, "remat_policy": "full",
                         "embed_init_std": 1.0}}
    hf.update(over)
    return hf


def model_for(hf, dtype="float32", impl="flash_pallas", **over):
    return TransformerLM(modelcfg.transformer_config(
        hf, max_seq_len=64, param_dtype="float32", dtype=dtype,
        attention_impl=impl, **over))


def name_hash(name: str) -> int:
    return int(hashlib.sha256(name.encode()).hexdigest()[:6], 16)


def init(model, seed=0):
    """Seeded weights with nothing left at 1: every norm's scale drawn."""
    params = jax.jit(model.init)(jax.random.key(seed))
    key = jax.random.key(seed + 1)

    def jig(path, a):
        name = jax.tree_util.keystr(path)
        if re.search(r"norm|ln\d", name):
            return a + 0.3 * jax.random.normal(
                jax.random.fold_in(key, name_hash(name)), a.shape)
        return a

    return jax.tree_util.tree_map_with_path(jig, params)


def a_batch(block, L, seed=0, rows=2, t_min=0.2):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, MASK, (rows, L)).astype(np.int32)
    return noise_batch({"input_ids": ids}, block=block, mask_token_id=MASK,
                       seed=rng, t_min=t_min)


def reference_of(hf, params, batch, **kw):
    return ref.batch_loss_and_grads(hf, modelcfg.weights_getter(params),
                                    batch, ALPHA, **kw)


#: (block, L): L = 24 is no multiple of a tile of 16 or of 128 (the split
#: backward runs: a [1, block_q] row is no legal block there)
CASES = {"b1": (1, 8), "b4": (4, 24), "b8": (8, 32)}


def _small(case, run_memo):
    block, L = CASES[case]
    hf = hf_config(block)
    model = model_for(hf)
    params = init(model)
    batch = a_batch(block, L)
    ((loss, parts), grads), (want, ref_grads) = run_memo(
        f"sdar_small_{case}", lambda: (
            jax.jit(jax.value_and_grad(model.loss_and_parts, has_aux=True))(
                params, batch), reference_of(hf, params, batch)))
    return hf, model, params, batch, loss, parts, grads, want, ref_grads


@pytest.fixture(scope="module", autouse=True)
def _query_blocks_of_8():
    old, ref._QUERY_BLOCK = ref._QUERY_BLOCK, 8
    yield
    ref._QUERY_BLOCK = old


@pytest.fixture(scope="module", params=sorted(CASES))
def small(request, run_memo):
    return _small(request.param, run_memo)


@pytest.fixture(scope="module")
def small_b4(run_memo):
    return _small("b4", run_memo)


# ---- the program against the reference ------------------------------------

def test_loss_parts_and_every_gradient_match_the_reference(small):
    hf, model, params, batch, loss, parts, grads, want, ref_grads = small
    assert float(loss) == pytest.approx(float(want["loss"]), abs=5e-6)
    for name, theirs, tol in (("lb_loss", "lb_loss", 1e-6),
                              ("mix_out_ms", "mix_out_ms", 1e-5),
                              ("bd_early_ms", "early_ms", 1e-5)):
        np.testing.assert_allclose(parts[name], want[theirs], rtol=tol,
                                   atol=tol)
    np.testing.assert_array_equal(parts["expert_pairs"],
                                  want["expert_pairs"])
    w = batch["loss_weights"]
    assert int(parts["bd_masked_targets"]) == int((w > 0).sum()) > 0
    assert float(parts["bd_weight_sum"]) == pytest.approx(float(w.sum()),
                                                          rel=1e-6)
    assert not np.any(parts["pairs_dropped"])
    got = modelcfg.weights_getter(grads)
    assert len(ref_grads) == 3 + 2 * len(ref.LAYER_TENSORS)
    for (name, layer), g in ref_grads.items():
        mine = np.asarray(got(name, layer))
        assert np.linalg.norm(mine - g) <= 3e-5 * np.linalg.norm(g), \
            (name, layer)


def test_the_layer_by_layer_gradient_is_the_whole_losss(small):
    hf, _, params, batch, *_, ref_grads = small
    get = modelcfg.weights_getter(params)
    weights = {(n, None): get(n) for n in ("embed", "final_norm", "lm_head")}
    weights.update({(n, i): get(n, i) for i in range(2)
                    for n in ref.LAYER_TENSORS})
    _, whole = ref.loss_and_grads(hf, weights, batch, ALPHA)
    for key, g in ref_grads.items():
        assert np.linalg.norm(whole[key] - g) <= 1e-5 * np.linalg.norm(g)


def test_the_dense_softmax_and_the_kernels_are_one_model(small):
    hf, _, params, batch, loss, parts, grads, *_ = small
    dense = model_for(hf, impl="xla")
    (l2, p2), g2 = jax.jit(jax.value_and_grad(
        dense.loss_and_parts, has_aux=True))(params, batch)
    assert float(l2) == pytest.approx(float(loss), abs=2e-6)
    np.testing.assert_allclose(p2["mix_out_ms"], parts["mix_out_ms"],
                               rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g2),
                    jax.tree_util.tree_leaves(grads)):
        assert np.linalg.norm(a - b) <= 3e-5 * np.linalg.norm(b) + 1e-9


def test_in_bf16_the_program_stays_near_the_reference(small):
    hf, _, params, batch, *_, want, _ = small
    loss, parts = jax.jit(model_for(hf, dtype="bfloat16").loss_and_parts)(
        params, batch)
    assert float(loss) == pytest.approx(float(want["loss"]), rel=0.02)
    np.testing.assert_allclose(parts["mix_out_ms"], want["mix_out_ms"],
                               rtol=0.05)


# ---- the row against the definition ----------------------------------------

def test_each_noised_block_reads_what_decoding_that_block_would(small):
    """For each block b, a plain forward over ``[x0 blocks < b ; xt block
    b]`` at positions ``0..(b + 1) B - 1`` under the block-causal mask gives,
    at its last block, the logits the 2L row gives at the noised block b:
    ``p(x^b | x_t^b, x^{<b})``."""
    hf, model, params, batch, *_ = small
    B = int(hf["block_length"])
    get = modelcfg.weights_getter(params)
    logits = np.asarray(jax.jit(model.bd_logits)(params, batch))
    row = np.asarray(ref.row_logits(hf, get, batch, 0))
    np.testing.assert_allclose(logits[0], row, rtol=2e-4, atol=2e-5)
    x0, xt = batch["input_ids"][0], batch["noised_ids"][0]
    for b in range(x0.shape[0] // B):
        ids = np.concatenate([x0[:b * B], xt[b * B:(b + 1) * B]])
        plain = np.asarray(ref.plain_logits(hf, get, ids))
        np.testing.assert_allclose(logits[0, b * B:(b + 1) * B],
                                   plain[b * B:], rtol=2e-4, atol=2e-5)


def test_blocks_of_one_with_nothing_masked_are_the_causal_model():
    """At B = 1 the clean half's mask is the causal one: the clean half's
    hidden states are the next-token model's on x0."""
    hf = hf_config(1)
    model = model_for(hf)
    params = init(model)
    ids = np.random.default_rng(4).integers(0, MASK, (2, 16)).astype(np.int32)
    batch = {"input_ids": ids, "noised_ids": ids,
             "loss_weights": np.zeros(ids.shape, np.float32)}
    row = jnp.concatenate([ids, ids], axis=1)
    hs, _ = model._hidden_passes(params, row,
                                 rope_positions=bd.row_positions(2, 16))
    causal = TransformerLM(dataclasses.replace(
        model.cfg, diffusion_block=None, mask_token_id=None,
        attention_impl="xla"))
    np.testing.assert_allclose(hs[-1][:, 16:],
                               causal.hidden_states(params, ids),
                               rtol=2e-4, atol=2e-5)
    assert model.bd_logits(params, batch).shape == (2, 16, 64)


# ---- the kernels under the rounded diagonal ---------------------------------

def _dense(q, k, v, n, mode):
    """(out, lse) of a dense softmax under the rounded diagonal; a row with
    no key: 0 and -inf."""
    H, K, d = q.shape[2], k.shape[2], q.shape[3]
    k, v = jnp.repeat(k, H // K, axis=2), jnp.repeat(v, H // K, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(d)
    qb = jnp.arange(q.shape[1])[:, None] // n
    kb = jnp.arange(k.shape[1])[None, :] // n
    m = (kb <= qb, kb < qb, kb == qb)[mode]
    s = jnp.where(m, s, -jnp.inf)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.where(m, jnp.exp(s - jnp.where(jnp.isfinite(lse), lse,
                                           0.0)[..., None]), 0.0)
    return jnp.einsum("bhts,bshd->bthd", p, v), lse[..., None]


def _qkv(T, H=4, K=2, d=16, seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    return [jax.random.normal(kk, (1, T, h, d), jnp.float32)
            for kk, h in zip(keys, (H, K, K))]


@pytest.mark.parametrize("T, n, bq, bk, took", [
    (24, 4, 1024, 1024, "fused"),       # one tile, the whole row
    (32, 8, 16, 16, "split"),           # tiles of 16: no [1, 16] row
    (64, 4, 32, 16, "split"),
    (16, 1, 16, 8, "fused"),            # blocks of one: the causal mask
    (256, 4, 128, 128, "fused"),        # tiles above the diagonal are dead
])
@pytest.mark.parametrize("mode", [fa.DIAG_UPTO, fa.DIAG_BEFORE, fa.DIAG_OWN])
def test_the_flash_kernels_take_the_rounded_diagonal(T, n, bq, bk, took,
                                                     mode):
    q, k, v = _qkv(T, seed=T + n)
    w = jax.random.normal(jax.random.key(5), q.shape)
    wl = jax.random.normal(jax.random.key(6), (1, q.shape[2], T, 1))

    def flash(q, k, v):
        o, l = fa.flash_attention_lse(q, k, v, diag=(n, mode), block_q=bq,
                                      block_k=bk, interpret=True)
        seen = l > 0.5 * fa.NEG_INF
        return jnp.sum(o * w) + jnp.sum(jnp.where(seen, l * wl, 0.0)), (o, l)

    def dense(q, k, v):
        o, l = _dense(q, k, v, n, mode)
        seen = jnp.isfinite(l)
        return jnp.sum(o * w) + jnp.sum(
            jnp.where(seen, jnp.where(seen, l, 0.0) * wl, 0.0)), (o, l)

    before = lowerings.snapshot()
    (_, (o1, l1)), g1 = jax.value_and_grad(flash, (0, 1, 2), has_aux=True)(
        q, k, v)
    said = lowerings.since(before)
    (_, (o2, l2)), g2 = jax.value_and_grad(dense, (0, 1, 2), has_aux=True)(
        q, k, v)
    seen = np.isfinite(np.asarray(l2))
    # a row with no key of the call: 0 out, a log-sum-exp that merges to
    # nothing, finite gradients
    assert (~seen).sum() == (n * q.shape[2] if mode == fa.DIAG_BEFORE else 0)
    assert np.all(np.asarray(l1)[~seen] == fa.NEG_INF)
    np.testing.assert_allclose(o1, o2, atol=2e-6)
    np.testing.assert_allclose(np.where(seen, l1, 0.0),
                               np.where(seen, l2, 0.0), atol=2e-6)
    for a, b in zip(g1, g2):
        assert np.all(np.isfinite(a))
        np.testing.assert_allclose(a, b, atol=1e-5)
    assert said["flash_bwd"] == {took: 1}
    label = fa.diag_label((n, mode))
    assert label == f"diag{n}" + ("", "_strict", "_own")[mode]
    arms = said["flash_diag_fwd_tiles"][label]
    assert arms == fa._tile_arms(T, T, min(bq, T), min(bk, T), True, None, 0,
                                 (min(bq, T, 512), min(bk, T, 512)),
                                 (n, mode))
    if took == "fused":
        assert said["flash_bwd_tiles"][label] == arms
    if T == 256:
        # the band's tiles are the diagonal's; under it the tile below the
        # diagonal is whole
        assert (arms["dead"], arms["masked"], arms["unmasked"]) == (
            (2, 2, 0) if mode == fa.DIAG_OWN else (1, 2, 1))
    if mode == fa.DIAG_UPTO:
        plain = fa.flash_attention(q, k, v, diag=(n, fa.DIAG_UPTO), block_q=bq,
                                   block_k=bk, interpret=True)
        np.testing.assert_allclose(plain, o2, atol=2e-6)
        if n == 1:      # blocks of one are the causal kernels' mask
            np.testing.assert_allclose(plain, fa.flash_attention(
                q, k, v, block_q=bq, block_k=bk, interpret=True), atol=2e-6)


def test_the_clean_queries_fetch_no_tile_above_the_rounded_diagonal():
    """The index maps' bounds by the kernels' own predicates: every dead
    tile of a row of 4,096 names a live one's block (the clean keys are the
    only operand: a noised key tile is never an argument of the calls)."""
    for mode in (fa.DIAG_UPTO, fa.DIAG_BEFORE):
        live, _ = fa._tiles(4096, 4096, 1024, 1024, True, None, 0, (4, mode))
        assert live.tolist() == np.tril(np.ones((4, 4), bool)).tolist()
    live, crossed = fa._tiles(4096, 4096, 1024, 1024, True, None, 0,
                              (4, fa.DIAG_OWN))
    assert live.tolist() == (live & crossed).tolist() \
        == np.eye(4, dtype=bool).tolist()
    tiles = bd.kernel_tiles(8192, 4)
    assert tiles["pairs_kept"] == 8192 * 8192 + 8192 * 4
    assert tiles["diag4"] == tiles["diag4_strict"] == {
        "masked": 8, "unmasked": 28, "dead": 28, "sub_live": 24,
        "sub_dead": 8, "sub_inside": 8}


def _grids(fn, *args):
    """The grids of the Mosaic calls ``fn(*args)`` traces, in order."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def test_the_own_keys_add_no_grid_step_and_work_their_live_sub_blocks():
    """The noised call with its own block as the second key source runs the
    grids the call over the clean keys alone runs, forward and backward (the
    own tile is an operand block of a step the grid has), and of a head's
    pairs the two calls work 73,400,320 at the own tiles' 256 edge: a 1,024
    own tile's four live sub-blocks of sixteen a q-tile (75,497,472 at 512,
    79,691,776 whole), under the runner's 1.15 x."""
    L = 2048
    q, k, v = (jax.ShapeDtypeStruct((1, L, h, 128), jnp.bfloat16)
               for h in (4, 2, 2))

    def call(own):
        def loss(q, k, v, ko, vo):
            return jnp.sum(fa.flash_attention(
                q, k, v, diag=(4, fa.DIAG_BEFORE), interpret=False,
                **(dict(k_own=ko, v_own=vo) if own else {})
            ).astype(jnp.float32))
        return _grids(jax.grad(loss, (0, 1, 2) + ((3, 4) if own else ())),
                      q, k, v, k, v)

    before = lowerings.snapshot()
    assert call(True) == call(False) == [(1, 4, 2, 2)] * 2
    said = lowerings.since(before)
    assert said["flash_own_keys"] == {"operand": 2, "none": 2}
    assert said["flash_bwd"] == {"fused": 2}
    own = dict(masked=2, unmasked=0, dead=0, sub_live=8, sub_dead=24,
               sub_inside=0)
    assert said["flash_diag_fwd_tiles"]["diag4_own"] == own \
        == said["flash_bwd_tiles"]["diag4_own"]
    tiles = bd.kernel_tiles(8192, 4)
    assert (fa._SUB, fa._OWN_SUB) == (512, 256)
    assert tiles["pairs_worked"] == 73_400_320 <= 1.15 * tiles["pairs_kept"]
    # a head's eight own tiles, a q-tile each: no dead step, none of its own
    assert tiles["diag4_own"] == {
        "masked": 8, "unmasked": 0, "dead": 0, "sub_live": 32,
        "sub_dead": 96, "sub_inside": 0}
    # the fused backward holds the own tiles and their gradients too
    assert fa._bwd_segments(8192, 128, 1024, 1024, 2, own=True) == 1
    assert fa._fused_bwd_vmem_bytes(8192, 128, 1024, 1024, 2, own=True) \
        - fa._fused_bwd_vmem_bytes(8192, 128, 1024, 1024, 2) == 2 * 2 ** 20


@pytest.mark.parametrize("L, B, tile, sub, took", [
    (32, 4, 1024, 512, "fused"),        # a row of one tile
    (64, 8, 16, 512, "split"),          # clean tiles before the own tile
    (64, 4, 32, 16, "split"),
    (256, 4, 128, 64, "fused"),         # an own tile's dead sub-blocks
    (256, 8, 128, 32, "fused"),
    (128, 64, 128, 64, "fused"),        # a block is a sub-block: unmasked
])
def test_the_two_calls_are_a_dense_masked_softmax(L, B, tile, sub, took,
                                                  monkeypatch):
    """``bd.attention``'s two halves (a call of the kernels each, the noised
    half's with its own block as the second key source of one softmax)
    against the mask as a dense softmax: the value and the three
    gradients."""
    monkeypatch.setattr(fa, "_SUB", sub)
    monkeypatch.setattr(fa, "_OWN_SUB", sub)
    q, k, v = _qkv(2 * L, seed=9)

    def by_parts(q, k, v):
        halves = bd.attention(q, k, v, B, block_q=tile, block_k=tile,
                              interpret=True)
        assert [h.shape for h in halves] == [(1, L) + q.shape[2:]] * 2
        return jnp.concatenate(halves, axis=1)

    def whole(q, k, v):
        return bd.dense_attention(q, k, v, B)

    np.testing.assert_allclose(by_parts(q, k, v), whole(q, k, v), atol=2e-6)
    w = jax.random.normal(jax.random.key(2), q.shape)
    before = lowerings.snapshot()
    g1 = jax.grad(lambda *a: jnp.sum(by_parts(*a) * w), (0, 1, 2))(q, k, v)
    said = lowerings.since(before)
    g2 = jax.grad(lambda *a: jnp.sum(whole(*a) * w), (0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert np.all(np.isfinite(a))
        np.testing.assert_allclose(a, b, atol=1e-5)
    assert said["flash_bwd"] == {took: 2}
    assert said["flash_own_keys"] == {"operand": 2, "none": 2}
    # the registry's own tiles are ``kernel_tiles``'
    tiles = bd.kernel_tiles(L, B, tile, tile)
    label = fa.diag_label((B, fa.DIAG_OWN))
    assert said["flash_diag_fwd_tiles"][label] == tiles[label]
    edge = min(tile, L, sub)
    assert tiles[label]["sub_live"] + tiles[label]["unmasked"] \
        == L // edge
    m = np.asarray(bd.mask(L, B))
    assert m.sum() == bd.mask_pairs(L, B) == L * L + L * B
    assert not m[L:, :L].any()              # clean queries, noised keys
    # the mask by the reference's four lines
    np.testing.assert_array_equal(m, ref.mask_rows(jnp.arange(2 * L), 2 * L,
                                                   L, B))


def test_the_own_keys_are_refused_where_the_kernels_cannot_take_them():
    q, k, v = _qkv(32)
    own = dict(k_own=k, v_own=v, interpret=True)
    for kw, error, said in (
            (dict(k_own=k, diag=(4, 1)), ValueError, "together"),
            (dict(own), ValueError, "DIAG_BEFORE"),
            (dict(own, diag=(4, fa.DIAG_UPTO)), ValueError, "DIAG_BEFORE"),
            (dict(own, diag=(4, 1), block_q=16, block_k=32), ValueError,
             "one tile edge"),
            (dict(own, diag=(4, 1), k_own=k[:, :16], v_own=v[:, :16]),
             ValueError, "queries' positions")):
        with pytest.raises(error, match=said):
            fa.flash_attention(q, k, v, **kw)


def test_kernels_refuse_a_diagonal_they_cannot_round():
    q, k, v = _qkv(16)
    for kw, error, said in (
            (dict(diag=(3, 0)), NotImplementedError, "power of two"),
            (dict(diag=(4, 0), causal=False), ValueError, "causal"),
            (dict(diag=(4, 0), segment_ids=jnp.zeros((1, 16), jnp.int32)),
             NotImplementedError, "segment_ids")):
        with pytest.raises(error, match=said):
            fa.flash_attention(q, k, v, interpret=True, **kw)


# ---- the share --------------------------------------------------------------

def test_the_eight_expert_shares_add_up_to_the_uncut_layer():
    """One layer's output less its attention branch, summed over the eight
    shares of two experts, is the uncut layer's (the router, the top k and
    the attention are every share's alike)."""
    hf = hf_config(num_experts=16, router_width=16, first_expert=0)
    whole = model_for(hf, impl="xla", moe_ep_capacity_factor=16.0)
    params = init(whole)
    layer = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.key(3), (2, 48, 32))
    pos = bd.row_positions(2, 24)

    def run(model, w):
        ck, freqs = model._kinds["full"]
        return tr.transformer_block(x, w, ck, freqs, None, model.moe_fn,
                                    positions=pos, kind="full")

    y, _ = run(whole, layer)
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
        total = total + (run(share, w)[0] - a)
    np.testing.assert_allclose(total, y - a, rtol=2e-4, atol=2e-5)


# ---- the noising ------------------------------------------------------------

def test_the_noising_is_the_seeds_and_follows_its_schedule():
    rng = np.random.default_rng(0)
    ids = rng.integers(0, MASK, (4, 4096)).astype(np.int32)
    a = noise_batch({"input_ids": ids}, block=4, mask_token_id=MASK, seed=7)
    b = noise_batch({"input_ids": ids}, block=4, mask_token_id=MASK, seed=7)
    c = noise_batch({"input_ids": ids}, block=4, mask_token_id=MASK, seed=8)
    for key in ("noised_ids", "loss_weights"):
        np.testing.assert_array_equal(a[key], b[key])
        assert np.any(a[key] != c[key])
    assert a["input_ids"] is ids and a["loss_weights"].dtype == np.float32
    masked = a["noised_ids"] == MASK
    np.testing.assert_array_equal(masked, a["loss_weights"] > 0)
    np.testing.assert_array_equal(a["noised_ids"][~masked], ids[~masked])
    # the weights are 1 / t of the block, exactly: the draw is the seed's
    r = np.random.default_rng(7)
    t = (1e-3 + (1.0 - 1e-3) * r.random((4, 1024))).astype(np.float32)
    t_pos = np.repeat(t, 4, axis=1)
    np.testing.assert_array_equal(a["loss_weights"][masked],
                                  (np.float32(1.0) / t_pos)[masked])
    # the masked share of a block tracks its t: by tenths of t
    share = masked.reshape(4, 1024, 4).mean(-1)
    for lo in np.arange(0.0, 1.0, 0.1):
        sel = (t >= lo) & (t < lo + 0.1)
        assert abs(share[sel].mean() - t[sel].mean()) < 0.03
    # E[w] = 1 a position
    assert abs(a["loss_weights"].mean() - 1.0) < 0.2
    one = noise_batch({"input_ids": ids}, block=4, mask_token_id=MASK,
                      seed=7, t_draw="row")
    w = one["loss_weights"]
    assert all(len(np.unique(w[r][w[r] > 0])) == 1 for r in range(4))
    for kw, said in ((dict(block=3), "whole number of blocks"),
                     (dict(mask_token_id=int(ids[0, 0])), "never does"),
                     (dict(t_draw="token"), "t_draw"),
                     (dict(t_min=0.0), "t_min")):
        with pytest.raises(ValueError, match=said):
            noise_batch({"input_ids": ids}, **{
                "block": 4, "mask_token_id": MASK, "seed": 0, **kw})


# ---- the readings the cell's check has to tell from the model ---------------

#: fault -> the part that shows it and the least it differs by, relative
#: (the program itself agrees to 1e-5); "grads": the worst leaf's gradient
FAULTS = {"mask_token_causal": ("early_ms", 0.02),
          "mask_leak": ("early_ms", 0.02),
          "no_own_block": ("early_ms", 0.02),
          "positions_unrepeated": ("grads", 0.05),
          "loss_unweighted": ("loss", 0.05),
          "loss_on_clean_half": ("grads", 0.2)}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_wrong_reading_in_the_references_place_shows(small_b4, fault):
    hf, _, params, batch, *_, want, ref_grads = small_b4
    assert set(FAULTS) == set(ref.FAULTS)
    part, least = FAULTS[fault]
    wrong, grads = reference_of({**hf, "fault": fault}, params, batch)
    if part == "grads":
        worst = max(np.linalg.norm(grads[k] - g) / np.linalg.norm(g)
                    for k, g in ref_grads.items())
        assert worst > least
    else:
        a = np.asarray(wrong[part], np.float64)
        b = np.asarray(want[part], np.float64)
        assert np.max(np.abs(a - b) / np.abs(b)) > least


# ---- what the step says of itself, and the engine's step --------------------

def test_params_plan_specs_facts_and_scopes(small):
    hf, model, params, batch, *_ = small
    cfg = model.cfg
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == cfg.num_params_estimate()
    assert jax.tree_util.tree_structure(model.param_specs()) \
        == jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda a: 0, params))
    assert cfg.has_bd and cfg.patterned and cfg.reports_mixer_outputs
    assert model._layer_plan() == [(0, 2, ("full",))]
    L = batch["input_ids"].shape[1]
    facts = model.step_program_facts((2, L))
    assert facts["diffusion_block"] == hf["block_length"]
    assert facts["positions_per_token"] == 2 and facts["head_rows"] == L
    assert facts["bd_mask_tiles"]["pairs_kept"] == L * L + L * cfg.diffusion_block
    assert "head_rows" not in model.step_program_facts()
    assert "bd_cross" in tr.STEP_SCOPES and "bd_own" not in tr.STEP_SCOPES


def test_the_engines_step_takes_the_three_keys():
    import deepspeed_tpu as ds
    from deepspeed_tpu.observability import steplog
    from deepspeed_tpu.parallel import build_mesh

    model = model_for(hf_config(), dtype="bfloat16", impl="auto")
    config = {"train_micro_batch_size_per_gpu": 2,
              "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
              "bf16": {"enabled": True}, "steps_per_print": 10 ** 9,
              "zero_optimization": {"stage": 0}}
    eng, *_ = ds.initialize(model=model, config=config,
                            mesh=build_mesh(devices=jax.devices()[:1]))
    batch = a_batch(4, 24, seed=5)
    loss = float(eng.fused_train_step(batch))
    assert np.isfinite(loss)
    row = steplog.programs()[-1]
    assert row.diffusion_block == 4 and row.positions_per_token == 2
    assert row.head_rows == 24 and row.layer_pattern == ("full",)
    parts = steplog.get_steplog().parts(last=1)[-1]
    assert parts["bd_masked_targets"] == (batch["loss_weights"] > 0).sum()
    assert parts["bd_early_ms"].shape == (2, 2)
    # with accumulation the three keys are cut alike
    eng2, *_ = ds.initialize(
        model=model, config={**config, "gradient_accumulation_steps": 2,
                             "train_micro_batch_size_per_gpu": 1},
        mesh=build_mesh(devices=jax.devices()[:1]))
    assert np.isfinite(float(eng2.fused_train_step(batch)))
    for missing in ("noised_ids", "loss_weights"):
        with pytest.raises(NotImplementedError, match="noised_ids and "
                           "loss_weights"):
            eng.fused_train_step({k: v for k, v in batch.items()
                                  if k != missing})
    with pytest.raises(NotImplementedError, match="segment_ids"):
        eng.fused_train_step({**batch, "segment_ids": np.zeros(
            batch["input_ids"].shape, np.int32)})


# ---- what refuses -----------------------------------------------------------

BASE = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
            num_kv_heads=2, head_dim_override=16, arch="llama",
            qk_norm="head", diffusion_block=4, mask_token_id=MASK)


@pytest.mark.parametrize("what, kw, error", [
    ("looped stack", dict(num_passes=2), NotImplementedError),
    ("parallel_block", dict(parallel_block=True), NotImplementedError),
    ("tiled loss", dict(loss_tiling=2), NotImplementedError),
    ("attn_pattern", dict(attn_pattern=("full",)), NotImplementedError),
    ("sliding_window", dict(sliding_window=8), NotImplementedError),
    ("'ring'", dict(attention_impl="ring"), NotImplementedError),
    ("'fpdt'", dict(attention_impl="fpdt", qk_norm=None),
     NotImplementedError),
    ("use_rope=False", dict(use_rope=False), NotImplementedError),
    ("mrope_section", dict(mrope_section=(2, 2, 4)), NotImplementedError),
    ("power of two", dict(diffusion_block=3), NotImplementedError),
    ("mask_token_id", dict(mask_token_id=None), ValueError),
    ("mask_token_id", dict(mask_token_id=64), ValueError),
])
def test_what_the_model_does_not_run_refuses_at_config_time(what, kw, error):
    with pytest.raises(error, match=what):
        TransformerConfig(**{**BASE, **kw})


def test_serving_the_pipeline_and_the_other_step_paths_refuse_by_name():
    import deepspeed_tpu as ds
    from deepspeed_tpu.parallel import build_mesh

    model = TransformerLM(TransformerConfig(**BASE))
    ids = jnp.zeros((1, 8), jnp.int32)
    for call in (lambda: model.init_kv_cache(1),
                 lambda: model.init_paged_kv_cache(4),
                 lambda: model.set_random_ltd(4),
                 lambda: model.set_pld_depth(1),
                 lambda: model.logits(None, ids),
                 lambda: model.hidden_states(None, ids)):
        with pytest.raises(NotImplementedError, match="block diffusion"):
            call()
    from deepspeed_tpu.runtime.pipe import PipelineModule
    with pytest.raises(NotImplementedError, match="block diffusion"):
        PipelineModule(model, num_stages=2)
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
    with pytest.raises(NotImplementedError, match="block diffusion"):
        InferenceEngineV2(model, params=None)
    for what, extra in (
            ("zero_optimization", {"zero_optimization": {
                "stage": 0, "offload_optimizer": {"device": "cpu"}}}),
            ("onebit", {"optimizer": {"type": "onebitadam",
                                      "params": {"lr": 1e-3,
                                                 "freeze_step": 2}}})):
        config = {"train_micro_batch_size_per_gpu": 2,
                  "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                  "steps_per_print": 10 ** 9,
                  "zero_optimization": {"stage": 0}, **extra}
        eng, *_ = ds.initialize(
            model=TransformerLM(TransformerConfig(**BASE, dtype="float32")),
            config=config, mesh=build_mesh(devices=jax.devices()[:1]))
        with pytest.raises(NotImplementedError,
                           match="block-diffusion batch"):
            eng.fused_train_step(a_batch(4, 24))


# ---- a model without block diffusion ----------------------------------------

def test_without_a_diagonal_the_kernels_trace_what_they_traced():
    """No new kernel argument: a causal and a window call's jaxpr name no
    ``diag``, the registry no rounded diagonal, and a next-token model's
    loss reads no weights."""
    q, k, v = _qkv(256, d=16)
    for kw in (dict(), dict(window=64)):
        before = lowerings.snapshot()
        text = str(jax.make_jaxpr(jax.grad(
            lambda q: jnp.sum(fa.flash_attention(
                q, k, v, block_q=128, block_k=128, interpret=True, **kw))))(
                    q))
        said = lowerings.since(before)
        assert "diag" not in text
        assert "flash_diag_fwd_tiles" not in said
        assert set(said["flash_bwd_tiles"]) == {kw.get("window", "causal")}
    before = lowerings.snapshot()
    jax.make_jaxpr(jax.grad(lambda q: jnp.sum(fa.flash_attention(
        q, k, v, block_q=128, block_k=128, interpret=True,
        diag=(4, fa.DIAG_UPTO)))))(q)
    assert set(lowerings.since(before)["flash_diag_fwd_tiles"]) == {"diag4"}
    cfg = TransformerConfig(**{**BASE, "diffusion_block": None,
                               "mask_token_id": None})
    assert not cfg.has_bd and not cfg.patterned
    logits = jnp.zeros((1, 8, 64))
    ids = jnp.arange(8)[None]
    assert float(tr.lm_loss(cfg, logits, {"input_ids": ids})) \
        == pytest.approx(math.log(64), rel=1e-6)


# ---- the published config ----------------------------------------------------

def test_the_cells_file_maps_onto_the_model_and_counts_as_it_states():
    from deepspeed_tpu.models.hf import _CONFIG_ONLY, config_from_hf

    with open(CELL_CONFIG) as f:
        hf = json.load(f)
    assert "sdar_moe" in _CONFIG_ONLY
    cfg = modelcfg.transformer_config(hf, max_seq_len=8192,
                                      param_dtype="float32")
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.num_layers, cfg.vocab_size) == (2048, 32, 4, 128, 5, 18992)
    assert (cfg.num_experts, cfg.moe_experts_held, cfg.top_k,
            cfg.moe_intermediate_size) == (128, 16, 8, 768)
    assert cfg.qk_norm == "head" and cfg.rope_theta == 1e6
    assert cfg.rope_scaling is None and not cfg.tie_embeddings
    assert (cfg.diffusion_block, cfg.mask_token_id) == (4, 18991)
    assert cfg.num_params_estimate() == opcount.total_params(hf) \
        == 550_984_960
    assert opcount.layer_params(hf) == 94_638_336
    assert opcount.mask_pairs(hf, 8192) == 8192 * 8192 + 8192 * 4
    # without the two keys of its own the published file is the next-token
    # model of the same block
    plain = config_from_hf({k: v for k, v in hf.items()
                            if k not in modelcfg.OWN_KEYS + ("num_experts",)},
                           num_experts=128)
    assert not plain.has_bd and plain.qk_norm == "head"
    for key, bad in (("rope_scaling", {"rope_type": "yarn", "factor": 2.0}),
                     ("attention_bias", True), ("norm_topk_prob", False)):
        with pytest.raises(ValueError, match="sdar_moe"):
            config_from_hf({**hf, key: bad})
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "block_diffusion_8k_1row.json")) as f:
        traffic = json.load(f)
    assert traffic["block_length"] == hf["block_length"]
