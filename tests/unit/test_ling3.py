"""KDA layers (the delta rule with a decay a key channel) beside a
latent-attention layer in one pattern, both on a held share of the heads and
under a head-wise output gate, over a leading dense FFN and a held share of
sigmoid-routed experts chosen under a group limit (Ling-3.0-flash): the
program against the plain reference (``benchmarks/reference_ling3.py``: the
tests import it from there, a reference is held once) on seeded random
weights with every scale, ``A_log``, ``dt_bias``, bias and tap drawn; the
chunked rule against the recurrence; the shares adding up to the whole
layer; group-limited selection; the faults the benchmark cell's check has to
see; what refuses the model; and the published config's mapping."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import modelcfg_ling3 as modelcfg
from benchmarks import opcount_ling3 as opcount
from benchmarks import reference_ling3 as ref
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.moe import sharded_moe as sm
from deepspeed_tpu.ops.delta_rule import chunked_delta_rule
from deepspeed_tpu.ops.kda_rule import chunked_kda_rule

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL_CONFIG = os.path.join(ROOT, "benchmarks", "configs",
                           "ling3_flash_train_d7h16e8v8.json")
ALPHA, GAMMA = 1e-2, 1e-3
KINDS = ("kda:dense", "kda:moe", "kda:moe", "kda:moe", "mla:moe", "kda:moe",
         "kda:moe")


def hf_config(**over):
    """A small file of the cell's keys: hidden 64, 2 of 4 heads of 16 held,
    published layers 1-7 (one dense; the fifth latent attention), 4 of 16
    experts held from the 4th on, in 4 groups of 4 with 2 kept, 2 a token."""
    hf = {"model_type": "bailing_hybrid", "hidden_size": 64,
          "num_hidden_layers": 7, "first_layer": 1, "layer_group_size": 6,
          "first_k_dense_replace": 1, "vocab_size": 256,
          "num_attention_heads": 2, "heads": 4, "num_key_value_heads": 4,
          "head_dim": 16, "intermediate_size": 96,
          "moe_intermediate_size": 48,
          "moe_shared_expert_intermediate_size": 48, "rms_norm_eps": 1e-6,
          "short_conv_kernel_size": 4, "kda_lower_bound": -5,
          "kda_safe_gate": True, "no_kda_lora": True, "linear_silu": True,
          "use_qk_norm": True,
          "gated_attention_proj_granularity_type": "head_wise",
          "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
          "qk_rope_head_dim": 8, "rotary_dim": 8, "v_head_dim": 16,
          "rope_theta": 6000000, "rope_interleave": True,
          "num_experts": 4, "router_width": 16, "first_expert": 4,
          "num_experts_per_tok": 2, "n_group": 4, "topk_group": 2,
          "routed_scaling_factor": 2.5, "norm_topk_prob": True,
          "score_function": "sigmoid", "moe_router_enable_expert_bias": True,
          "tie_word_embeddings": False,
          "deployment": {"local_pairs_factor": 4.0, "bias_update_rate": GAMMA,
                         "bias_init": 0.1, "balance_coef": ALPHA,
                         "remat_policy": "none", "embed_init_std": 0.02,
                         "kda_chunk": 64}}
    hf.update(over)
    return hf


def model_for(hf, dtype="float32", **over):
    return TransformerLM(modelcfg.transformer_config(
        hf, max_seq_len=128, param_dtype="float32", dtype=dtype,
        attention_impl="xla", **over))


def init(model, seed=0):
    """Seeded weights with nothing left at 1 or 0: the norm scales drawn in
    (0.5, 1.5), a router that prefers some experts (so that the top k is no
    toss-up); ``A_log``, ``dt_bias``, the taps and the selection biases are
    the initialiser's own draws."""
    params = jax.jit(model.init)(jax.random.key(seed))     # one program, not an op at a time
    layers = params["layers"]
    layers["mlp_moe"]["router"] = layers["mlp_moe"]["router"] * 4.0
    scales = [(layers["ln1"], "scale"), (layers["ln2"], "scale"),
              (layers["kda"], "o_norm"), (layers["mla"], "kv_norm"),
              (params["final_norm"], "scale")]
    for i, (tree, name) in enumerate(scales):
        tree[name] = jax.random.uniform(jax.random.key(100 + i),
                                        tree[name].shape, jnp.float32, 0.5,
                                        1.5)
    # decays that matter at 80 positions: the middle of the cell's range
    layers["kda"]["dt_bias"] = layers["kda"]["dt_bias"] * 0.5
    return params


#: 80 positions: a whole chunk of 64 (the state carries) and a padded one
ROWS = np.random.default_rng(0).integers(0, 256, (2, 80)).astype(np.int32)


@pytest.fixture(scope="module")
def small(run_memo):
    hf = hf_config()
    model = model_for(hf)
    params = init(model)
    (want, grads), ((loss, parts), got) = run_memo("ling3_small", lambda: (
        ref.batch_loss_and_grads(
            hf, modelcfg.weights_getter(params, hf), list(ROWS), ALPHA),
        jax.jit(jax.value_and_grad(model.loss_and_parts, has_aux=True))(
            params, {"input_ids": ROWS})))
    return hf, model, params, want, grads, (loss, parts, got)


# ---- the program against the reference ------------------------------------

def test_loss_mixer_outputs_counts_and_every_gradient_match_the_reference(
        small):
    hf, model, params, want, grads, (loss, parts, got) = small
    assert ref.kinds(hf) == model.cfg.layer_kinds == KINDS
    assert parts["mix_out_ms"].shape == (7,)
    assert parts["router_counts"].shape == (6, 16)
    assert abs(float(loss) - float(want["loss"])) <= 5e-5
    assert abs(float(parts["lb_loss"]) - float(want["lb_loss"])) <= 5e-5
    np.testing.assert_allclose(parts["mix_out_ms"], want["mix_out_ms"],
                               rtol=1e-4)
    for name in ("expert_pairs", "router_counts", "groups_kept"):
        np.testing.assert_array_equal(parts[name], want[name])
    # every token keeps 2 of the 4 groups
    assert np.all(np.asarray(parts["groups_kept"]).sum(-1) == 2 * ROWS.size)
    assert int(np.sum(parts["pairs_dropped"])) == 0
    get = modelcfg.weights_getter(got, hf)
    # embed, head, the final norm; two norms a layer; six KDA mixers, one
    # latent-attention mixer, the dense FFN, six routed FFNs without biases
    assert len(grads) == 3 + 2 * 7 + 13 * 6 + 6 + 3 + 7 * 6
    for (name, layer), g in grads.items():
        mine, g = np.asarray(get(name, layer)), np.asarray(g)
        assert np.linalg.norm(mine - g) <= 3e-4 * np.linalg.norm(g), \
            (name, layer)
        assert np.linalg.norm(g) > 0, (name, layer)
    # the selection bias picks and gets no gradient
    assert not np.any(np.asarray(got["layers"]["mlp_moe"]["router_bias"]))


def test_num_params_plan_specs_and_facts_follow_init(small):
    hf, model, params, *_ = small
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert model.cfg.num_params_estimate() == n == opcount.total_params(hf)
    assert model._layer_plan() == [
        (0, 1, ("kda:dense",)), (1, 4, ("kda:moe",)), (4, 5, ("mla:moe",)),
        (5, 7, ("kda:moe",))]
    layers = params["layers"]
    assert sorted(layers) == ["kda", "ln1", "ln2", "mla", "mlp_dense",
                              "mlp_moe"]
    assert layers["kda"]["wf"].shape == (6, 64, 32)
    assert layers["kda"]["dt_bias"].shape == (6, 32)
    assert layers["kda"]["A_log"].shape == layers["kda"]["wg"].shape[::2] \
        == (6, 2)
    assert layers["mla"]["wq"].shape == (1, 64, 2 * 24)
    assert layers["mla"]["wkv_a"].shape == (1, 64, 40)      # whole
    assert layers["mla"]["wg"].shape == (1, 64, 2)
    specs = model.param_specs()
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda x: 0, params)) \
        == jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda x: 0, specs, is_leaf=lambda x: not isinstance(x, dict)))
    facts = model.step_program_facts((2, 80))
    assert facts["layer_applications"] == 7
    assert facts["layer_pattern"] == ("kda:dense", "kda:moe", "mla:moe")
    assert facts["heads_held"] == (2, 4)
    assert facts["experts_held"] == (4, 4, 16)
    assert facts["moe_groups"] == (4, 2)
    assert (facts["kda_chunk"], facts["kda_chunks_per_step"]) == (64, 24)


# ---- the chunked rule ------------------------------------------------------

def _rule_inputs(T, seed, H=3, dk=16, dv=24, lower=-5.0):
    ks = jax.random.split(jax.random.key(seed), 5)
    q, k = (jax.random.normal(ks[i], (2, T, H, dk)) for i in (0, 1))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / 4.0
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (2, T, H, dv))
    # over its whole range: channels that forget inside a block of 16 rows
    # beside channels that carry the chunk's state
    g = lower * jax.random.uniform(ks[3], (2, T, H, dk)) ** 3
    return q, k, v, g, jax.random.uniform(ks[4], (2, T, H))


@pytest.mark.parametrize("T", [64, 100, 200])
def test_the_chunked_rule_is_the_recurrence_forward_and_backward(T):
    args = _rule_inputs(T, T)
    recurrence = jax.vmap(ref.recurrence)

    def both(fn):
        def run(*args):
            out, back = jax.vjp(fn, *args)
            return out, back(jnp.cos(jnp.arange(
                out.size, dtype=jnp.float32)).reshape(out.shape))
        return jax.jit(run)

    with jax.default_matmul_precision("highest"):
        out, back = both(chunked_kda_rule)(*args)
        want, want_back = both(recurrence)(*args)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=2e-6)
    for mine, theirs in zip(back, want_back):
        assert float(jnp.abs(mine - theirs).max()) \
            <= 2e-5 * float(jnp.abs(theirs).max())


def test_with_one_decay_a_head_the_rule_is_the_delta_kinds():
    q, k, v, g, beta = _rule_inputs(150, 7, lower=-0.5)
    g = g[..., 0]
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            jax.jit(chunked_kda_rule)(
                q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta),
            jax.jit(chunked_delta_rule)(q, k, v, g, beta), rtol=1e-5,
            atol=1e-6)


# ---- the shares add up ----------------------------------------------------

def _halves_of(w, names, parts, axis=-1):
    """Head share ``s`` of 2: the leaves ``names`` cut along ``axis``."""
    return [{**w, **{n: jnp.split(w[n], 2, axis=axis if n != "wo" else 0)[s]
                     for n in names}} for s in range(parts)]


def test_two_head_shares_of_each_mixer_are_the_whole_mixer():
    """The two chips that share a mixer by heads: each holds 2 of 4 heads
    (the reference's function on the cut tensors, which is the program's:
    the test above), and the two outputs sum to the whole mixer's."""
    from deepspeed_tpu.models import kda, mla

    hf = hf_config(num_attention_heads=4)
    whole = model_for(hf)
    cut = dataclasses.replace(whole.cfg, heads_held=2, attn_pattern=None)
    params = init(whole, seed=3)
    u = jax.random.normal(jax.random.key(9), (1, 80, 64), jnp.float32)
    w = jax.tree_util.tree_map(lambda a: a[1], params["layers"]["kda"])
    by_head = ("wq", "wk", "wv", "wf", "wb", "wg", "conv_q", "conv_k",
               "conv_v", "A_log", "dt_bias", "wo")
    shares = _halves_of(w, by_head, 2)
    kda_block = jax.jit(kda.kda_block, static_argnums=2)
    want = kda_block(u, w, whole.cfg)
    np.testing.assert_allclose(
        sum(kda_block(u, s, cut) for s in shares), want, rtol=2e-4,
        atol=2e-6)
    np.testing.assert_allclose(want[0], ref.kda_layer(u[0], w, hf),
                               rtol=2e-4, atol=2e-6)
    w = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["mla"])
    ck, freqs = whole._kinds["mla:moe"]
    from deepspeed_tpu.models.transformer import xla_attention
    shares = _halves_of(w, ("wq", "wkv_b", "wg", "wo"), 2)
    mla_block = jax.jit(mla.mla_block, static_argnums=(2, 4))
    want = mla_block(u, w, ck, freqs, xla_attention)
    np.testing.assert_allclose(
        sum(mla_block(u, s, dataclasses.replace(ck, heads_held=2), freqs,
                      xla_attention) for s in shares),
        want, rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(want[0], ref.mla_layer(u[0], w, hf),
                               rtol=2e-4, atol=2e-6)


def test_the_expert_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """Four models that each hold 4 of the 16 experts: their routed parts,
    with the shared expert counted once, add up to the reference's uncut
    layer."""
    hf = hf_config(num_experts=16, first_expert=0)
    whole = model_for(hf)
    w = jax.tree_util.tree_map(lambda a: a[0], init(whole, seed=5)[
        "layers"]["mlp_moe"])
    u = jax.random.normal(jax.random.key(6), (2, 40, 64), jnp.float32)
    rw = {"router": w["router"], "router_bias": w["router_bias"],
          "w_gate": w["w_gate"], "w_up": w["w_up"], "w_down": w["w_down"],
          "shared_gate": w["shared"]["w_gate"],
          "shared_up": w["shared"]["w_up"],
          "shared_down": w["shared"]["w_down"]}
    want = jax.jit(jax.vmap(
        lambda row: ref.experts(row, rw, hf, held=range(16))[0]))(u)
    shared = jnp.stack([ref.swiglu(row, rw["shared_gate"], rw["shared_up"],
                                   rw["shared_down"]) for row in u])
    total = shared
    for s in range(4):
        cut = dataclasses.replace(whole.cfg, moe_experts_held=4,
                                  moe_first_expert=4 * s,
                                  moe_ep_capacity_factor=4.0,
                                  attn_pattern=None, kv_lora_rank=None,
                                  mla_head_gate=False)
        ws = {**w, **{n: w[n][4 * s:4 * s + 4]
                      for n in ("w_gate", "w_up", "w_down")}}
        out, aux = jax.jit(sm.grouped_moe_mlp_block, static_argnums=2)(
            u, ws, cut)
        assert int(aux["pairs_dropped"]) == 0
        assert np.all(np.asarray(aux["groups_kept"]).sum() == 2 * 80)
        total = total + (out - shared)      # every share adds it: once
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


# ---- group-limited selection ----------------------------------------------

def _route_both(logits, bias, k, groups):
    """(the program's chosen experts, weights, counts, groups kept) and the
    reference's, on the same scores."""
    E = logits.shape[-1]
    mine = sm._route_sigmoid(logits, bias, k, 2.5, groups)
    cfg = {"num_experts_per_tok": k, "router_width": E, "num_experts": E,
           "n_group": groups[0], "topk_group": groups[1],
           "routed_scaling_factor": 2.5}
    route = jax.jit(lambda row: ref.route(row, jnp.eye(E), bias, cfg))
    theirs = [route(row) for row in logits]
    return mine, theirs


def test_group_limited_selection_is_the_references_ties_and_all():
    """Scores on a grid of a few values, so that groups and experts tie: the
    program picks what the reference's rule picks on plain arrays
    (``lax.top_k`` breaks ties alike in both)."""
    rng = np.random.default_rng(4)
    logits = jnp.asarray(rng.integers(-2, 3, (2, 50, 32)), jnp.float32)
    bias = jnp.asarray(rng.integers(-1, 2, (32,)) * 0.25, jnp.float32)
    (aux, weights, idx, counts, kept), theirs = _route_both(
        logits, bias, 6, (8, 3))
    want_idx = jnp.concatenate([t[1] for t in theirs])
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_allclose(weights, jnp.concatenate(
        [t[2] for t in theirs]), rtol=1e-6)
    np.testing.assert_array_equal(
        kept, sum(t[3].sum(0) for t in theirs).astype(np.int32))
    assert int(kept.sum()) == 3 * 100 and int(counts.sum()) == 6 * 100
    # every chosen expert lies in a kept group: at most 3 groups a token
    assert int(jnp.max(jax.vmap(lambda r: jnp.unique(
        r // 4, size=6, fill_value=-1).max() >= 0)(idx))) == 1
    assert max(len(set(np.asarray(r) // 4)) for r in np.asarray(idx)) <= 3


def test_a_token_whose_best_experts_lie_in_five_groups_gets_the_rules():
    """8 groups of 4, 4 kept, 8 a token: a token's 8 highest scores lie one
    in each of five groups and three in a sixth; the plain top 8 would take
    all of them, the rule keeps the four groups whose two best sum highest
    and takes the 8 best among their 16."""
    s = np.full((32,), -4.0, np.float32)
    for grp, vals in enumerate([(3.0, 2.9, 2.8), (2.7,), (2.6,), (2.5,),
                                (2.4,), (2.3,)]):
        s[4 * grp:4 * grp + len(vals)] = vals
    # second-best scores that decide the groups: 2.3's group gets a strong
    # second, 2.7's none
    s[4 * 5 + 1] = 2.2
    logits = jnp.asarray(s)[None, None]
    (_, _, idx, _, kept), theirs = _route_both(logits, jnp.zeros((32,)), 8,
                                               (8, 4))
    plain = sm._route_sigmoid(logits, jnp.zeros((32,)), 8, 2.5)[2]
    assert sorted(np.asarray(plain[0]) // 4) == [0, 0, 0, 1, 2, 3, 4, 5]
    np.testing.assert_array_equal(idx, theirs[0][1])
    # groups 0 (3.0 + 2.9), 5 (2.3 + 2.2), 1 (2.7 - 4) and 2 (2.6 - 4)
    np.testing.assert_array_equal(kept, [1, 1, 1, 0, 0, 1, 0, 0])
    assert set(np.asarray(idx[0]) // 4) == {0, 1, 2, 5}


def _route_sigmoid_as_it_was(logits, bias, k, scale, groups=(1, 1)):
    """``sharded_moe._route_sigmoid`` before the selection became an op
    (``ops/topk_select.py``): ``lax.top_k`` and the group limit's lines in
    place."""
    B, T, E = logits.shape
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    chosen_by, kept = s + bias.astype(jnp.float32), None
    if tuple(groups) != (1, 1):
        n_group, topk_group = groups
        grouped = chosen_by.reshape(B, T, n_group, E // n_group)
        score = jax.lax.top_k(grouped, 2)[0].sum(-1)
        _, best = jax.lax.top_k(score, topk_group)
        keep = sm._hot(best, n_group).any(axis=-2)
        chosen_by = jnp.where(keep[..., None], grouped,
                              -jnp.inf).reshape(B, T, E)
        kept = keep.sum(axis=(0, 1), dtype=jnp.int32)
    _, idx = jax.lax.top_k(chosen_by, k)
    picked = sm._pick(s, idx, E)
    weights = scale * picked / picked.sum(-1, keepdims=True)
    by_seq = sm._hot(idx, E).sum(axis=(1, 2), dtype=jnp.int32)
    f = by_seq.astype(jnp.float32) * (E / (k * T))
    p = (s / s.sum(-1, keepdims=True)).mean(axis=1)
    return ((f * p).sum(-1).mean(), weights.reshape(B * T, k),
            idx.reshape(B * T, k), by_seq.sum(axis=0), kept)


@pytest.mark.parametrize("lowering", ["xla", "kernel"])
@pytest.mark.parametrize("groups", [(1, 1), (8, 4)])
def test_the_router_is_what_it_was_through_either_lowering(groups, lowering,
                                                           monkeypatch):
    """Balance term, weights, experts, counts, groups kept and ``jax.grad``
    with respect to the logits, bit for bit: through the picker's answer
    off a TPU (``lax.top_k``'s lines, moved) and through the selection
    kernel, interpreted; scores on a grid, so experts and groups tie."""
    import functools

    from deepspeed_tpu.ops.topk_select import topk_select

    B, T, E, k = 2, 128, 128, 8
    logits = jnp.round(2 * jax.random.normal(jax.random.key(0), (B, T, E))) / 2
    bias = jnp.round(8 * jax.random.normal(jax.random.key(1), (E,))) / 64
    r = jax.random.normal(jax.random.key(2), (B * T, k))
    if lowering == "kernel":
        monkeypatch.setattr(sm, "topk_select", functools.partial(
            topk_select, interpret=True))

    def both(route):
        def objective(logits):
            aux, weights, *rest = route(logits, bias, k, 2.5, groups)
            return (weights * r).sum() + 3.0 * aux, (weights, *rest)
        return jax.value_and_grad(objective, has_aux=True)(logits)

    got, want = both(sm._route_sigmoid), both(_route_sigmoid_as_it_was)
    assert (got[0][1][-1] is None) == (groups == (1, 1))
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_one_group_selects_as_before_bit_for_bit():
    """``n_group`` 1 traces the program the three sigmoid cells have: the
    same jaxpr as a call without groups, and no kept-groups part."""
    logits = jax.random.normal(jax.random.key(1), (2, 24, 16))
    bias = jax.random.uniform(jax.random.key(2), (16,), minval=-0.1,
                              maxval=0.1)
    f = lambda l, b, **kw: sm._route_sigmoid(l, b, 4, 2.5, **kw)[:4]  # noqa
    assert str(jax.make_jaxpr(f)(logits, bias)) == str(jax.make_jaxpr(
        lambda l, b: f(l, b, groups=(1, 1)))(logits, bias))
    assert sm._route_sigmoid(logits, bias, 4, 2.5, (1, 1))[4] is None
    cfg = TransformerConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        num_experts=8, top_k=2, moe_dispatch="grouped",
        moe_scoring="sigmoid", activation="swiglu")
    assert "moe_groups" not in TransformerLM(cfg).step_program_facts()


# ---- the faults the cell's check has to see --------------------------------

#: fault -> (the part that shows it, the least it has to differ by, as a
#: share of the reference's value; the program itself agrees to 1e-4)
FAULTS = {
    "decay_mean": ("mix_out_ms", 0.01),
    "softplus": ("mix_out_ms", 0.05),
    "gate_by_channel": ("mix_out_ms", 0.01),
    "mla_no_gate": ("mix_out_ms", 0.5),
    "group_max": ("router_counts", 0.01),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_wrong_reading_in_the_references_place_fails_by_its_limit(
        small, fault):
    hf, _, params, want, *_ = small
    part, limit = FAULTS[fault]
    wrong = ref.batch_loss(
        {**hf, "fault": fault}, modelcfg.weights_getter(params, hf),
        list(ROWS), ALPHA)
    a, b = np.asarray(wrong[part], np.float64), \
        np.asarray(want[part], np.float64)
    if part == "router_counts":
        assert np.abs(a - b).sum() > limit * b.sum()
    else:
        assert np.max(np.abs(a - b) / b) > limit
    assert abs(float(wrong["loss"]) - float(want["loss"])) > 1e-4 \
        or part == "router_counts"


def test_kda_layers_at_the_latent_positions_fail_the_mixer_outputs(small):
    """The same weights under a pattern whose latent-attention layer stands
    one layer early: the mixer outputs leave the reference's by far more
    than the program's 1e-4."""
    hf, model, params, want, *_ = small
    moved = TransformerLM(dataclasses.replace(
        model.cfg, attn_pattern=("kda", "kda", "kda", "mla", "kda", "kda",
                                 "kda")))
    _, parts = jax.jit(moved.loss_and_parts)(params, {"input_ids": ROWS})
    got, ref_ms = np.asarray(parts["mix_out_ms"]), \
        np.asarray(want["mix_out_ms"])
    assert np.max(np.abs(got - ref_ms) / ref_ms) > 0.5


# ---- loading ---------------------------------------------------------------

def test_a_model_without_the_kind_does_not_load_the_mixer():
    code = ("import sys, jax\n"
            "import deepspeed_tpu\n"
            "from deepspeed_tpu.models import TransformerConfig, "
            "TransformerLM\n"
            "m = TransformerLM(TransformerConfig(vocab_size=64, "
            "hidden_size=32, num_layers=2, num_heads=2, kv_lora_rank=16, "
            "qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, "
            "arch='llama'))\n"
            "m.init(jax.random.key(0))\n"
            "assert 'deepspeed_tpu.models.mla' in sys.modules\n"
            "assert 'deepspeed_tpu.models.kda' not in sys.modules\n"
            "assert 'deepspeed_tpu.ops.kda_rule' not in sys.modules\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]


# ---- what refuses ---------------------------------------------------------

BASE = dict(vocab_size=64, hidden_size=32, num_layers=3, num_heads=2,
            arch="llama", attn_pattern=("kda", "kda", "mla"),
            delta_key_dim=16, delta_value_dim=16, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
            mla_head_gate=True)
ROUTED = dict(num_experts=8, top_k=2, moe_dispatch="grouped",
              moe_scoring="sigmoid")


@pytest.mark.parametrize("what, kw, error", [
    ("looped stack", dict(num_passes=2), NotImplementedError),
    ("parallel_block", dict(parallel_block=True), NotImplementedError),
    ("tiled loss", dict(loss_tiling=2), NotImplementedError),
    ("fpdt", dict(attention_impl="fpdt"), NotImplementedError),
    ("another kind", dict(attn_pattern=("kda", "full", "kda"),
                          kv_lora_rank=None, mla_head_gate=False),
     NotImplementedError),
    ("another attn_pattern", dict(attn_pattern=("kda", "full", "mla")),
     NotImplementedError),
    ("another attn_pattern", dict(attn_pattern=("full", "full", "mla")),
     NotImplementedError),
    ("another attn_pattern", dict(attn_pattern=("kda",), kv_lora_rank=16),
     NotImplementedError),
    ("kda_lower_bound", dict(kda_lower_bound=0.0), ValueError),
    ("mla_head_gate", dict(attn_pattern=("kda",), kv_lora_rank=None),
     ValueError),
    # a delta kind beside routed experts runs the grouped dispatch since
    # PR 66 (tests/unit/test_qwen3_next.py); the capacity form stays refused
    ("delta layer beside routed", dict(
        attn_pattern=("delta",), kv_lora_rank=None, mla_head_gate=False,
        delta_heads=2, num_experts=8, top_k=2), NotImplementedError),
    ("group-limited", dict(moe_n_group=3, moe_topk_group=1, **ROUTED),
     ValueError),
    ("group-limited", dict(moe_n_group=4, moe_topk_group=2, num_experts=8,
                           top_k=2, moe_dispatch="grouped"), ValueError),
    ("group-limited", dict(moe_n_group=4, moe_topk_group=1, **{
        **ROUTED, "top_k": 3}), ValueError),
])
def test_what_the_model_does_not_run_refuses_at_config_time(what, kw, error):
    with pytest.raises(error, match=what):
        TransformerConfig(**{**BASE, **kw})


def test_serving_the_pipeline_and_a_tp_axis_refuse_by_name():
    model = TransformerLM(TransformerConfig(**BASE))
    for call in (lambda: model.init_kv_cache(1),
                 lambda: model.init_paged_kv_cache(4),
                 lambda: model.set_random_ltd(4)):
        with pytest.raises(NotImplementedError, match="KDA"):
            call()
    with pytest.raises(NotImplementedError, match="tp axis"):
        model.check_topology({"tp": 2})
    model.check_topology({"tp": 1, "fsdp": 4})


# ---- the published config -------------------------------------------------

def test_the_cells_file_maps_onto_the_model_and_counts_as_it_states():
    """The cell's configuration at its published widths, as shapes: the
    program's leaves are the 648,853,344 the file states; the whole model's
    keys map to 35 KDA and 7 latent-attention layers; what the mapping does
    not build is refused by name."""
    from deepspeed_tpu.models.hf import config_from_hf

    with open(CELL_CONFIG) as f:
        cfg = json.load(f)
    model = TransformerLM(modelcfg.transformer_config(
        cfg, max_seq_len=8192, param_dtype="float32"))
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == model.cfg.num_params_estimate() == 648_853_344
    assert n == cfg["deployment"]["parameters"] == opcount.total_params(cfg)
    c = model.cfg
    assert c.layer_kinds == KINDS and not c.tie_embeddings
    assert (c.heads_held, c.num_heads, c.moe_experts_held, c.num_experts) \
        == (16, 32, 8, 512)
    assert (c.moe_n_group, c.moe_topk_group, c.top_k) == (8, 4, 8)
    assert (c.kda_lower_bound, c.moe_routed_scale, c.rope_theta) \
        == (-5.0, 2.5, 6e6)
    assert shapes["layers"]["kda"]["wf"].shape == (6, 2560, 2048)
    assert shapes["layers"]["mla"]["wkv_b"].shape == (1, 512, 16 * 256)
    assert shapes["layers"]["mlp_moe"]["router"].shape == (6, 2560, 512)
    assert shapes["layers"]["mlp_moe"]["shared"]["w_up"].shape \
        == (6, 2560, 768)
    whole = opcount.whole(cfg)
    published = config_from_hf({k: v for k, v in whole.items() if k not in (
        "expert_swiglu_limit_list", "share_expert_swiglu_limit_list")})
    assert published.attn_pattern == ("kda",) * 5 + ("mla",)
    assert published.layer_kinds.count("mla:moe") == 7
    assert published.first_k_dense == 2 and published.heads_held is None
    assert published.num_params_estimate() == opcount.total_params(whole)
    for key, bad, match in [
            ("use_nGPT", True, "use_nGPT"), ("value_norm", True, "value_norm"),
            ("up_proj_norm", True, "up_proj_norm"),
            ("scale_router_input", True, "scale_router_input"),
            ("use_kda_lora", True, "use_kda_lora"),
            ("mtp_use_kda", True, "mtp_use_kda"),
            ("num_kv_heads_for_linear_attn", 4, "num_kv_heads_for_linear"),
            ("group_norm_size", 2, "group_norm_size"),
            ("q_lora_rank", 256, "q_lora_rank")]:
        with pytest.raises(ValueError, match=match):
            config_from_hf({**cfg, key: bad})
    # the whole model's lists hold a clamp from layer 35 on
    with pytest.raises(ValueError, match="expert_swiglu_limit_list"):
        config_from_hf(whole)
