"""What the looped cell's correctness check sees.

The check (``runners/train_looped.py:compare``) holds the first step's loss,
its four per-pass losses and its four mean exit probabilities to the float32
reference on the same weights, within the tolerances of
``configs/ouro2_6b_train_d6.json``. Here the reference itself is run at the
cell's vocabulary and sequence length (so each mean is over as many random
targets), a narrower hidden size and two layers, on weights drawn as the
program draws them and rounded to bf16 as the step computes with them, with
one thing wrong at a time. Each fault has to fail at least one of the
committed tolerances; the effects measured here stand in ``check.tol_why``
beside what the chip measured for the program itself.

The faults: one pass instead of four; the first pass's logits used for all
four; the final norm left out between passes; the post-branch norms left
out; the entropy term dropped; beta halved; fp8-rounded weights (the
nearest precision below the bf16 the configuration states)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_ouro as ref
from benchmarks.runners.train_looped import compare

HERE = os.path.dirname(os.path.abspath(__file__))
D, HEADS, HEAD, F, LAYERS, R, T = 256, 4, 64, 1024, 2, 4, 4096


def _published():
    with open(os.path.join(HERE, "..", "configs",
                           "ouro2_6b_train_d6.json")) as f:
        return json.load(f)


def _weights(rng, vocab):
    def n(shape, std):
        return (rng.standard_normal(shape) * std).astype(np.float32)

    w = {"embed": n((vocab, D), 0.02), "final_norm": np.ones(D, np.float32),
         "head": n((D, vocab), D ** -0.5), "gate_w": n((D,), D ** -0.5),
         "gate_b": np.zeros((), np.float32)}
    for i in range(LAYERS):
        for name in ("ln1", "ln1_post", "ln2", "ln2_post"):
            w[name, i] = np.ones(D, np.float32)
        for name in ("wq", "wk", "wv"):
            w[name, i] = n((D, HEADS * HEAD), D ** -0.5)
        w["wo", i] = n((HEADS * HEAD, D), (HEADS * HEAD) ** -0.5)
        w["w_gate", i] = n((D, F), D ** -0.5)
        w["w_up", i] = n((D, F), D ** -0.5)
        w["w_down", i] = n((F, D), F ** -0.5)
    return w


def _bf16(w):
    return jnp.asarray(w).astype(jnp.bfloat16).astype(jnp.float32)


def _fp8(w):
    if w.ndim < 2:
        return w
    return jnp.asarray(w).astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _put_together(nll, p, beta, entropy_term=True):
    """The check's three quantities from per-token arrays [R, T - 1]."""
    h = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0),
                 axis=0)
    per_token = jnp.sum(p * nll, axis=0) - (beta * h if entropy_term else 0.0)
    return {"loss": float(jnp.mean(per_token)),
            "pass_loss": np.asarray(jnp.mean(nll, axis=1)),
            "exit_prob": np.asarray(jnp.mean(p, axis=1))}


def _per_token(cfg, get, toks, hidden_passes=ref.hidden_passes):
    hs = hidden_passes(cfg, get, toks)
    nll = jnp.stack([ref.next_token_nll(ref.pass_logits(get, h), toks)
                     for h in hs])
    p = (ref.exit_distribution(get, hs)[:, :-1] if len(hs) > 1
         else jnp.ones_like(nll))
    return nll, p


def _no_norm_between_passes(cfg, get, toks):
    """The fault: N_f applied for the head and the gate only; the next pass
    reads the stack's raw output."""
    eps = float(cfg["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        toks = jnp.asarray(toks, jnp.int32)
        pos = jnp.arange(toks.shape[0])
        x = ref._f32(jnp.asarray(get("embed"))[toks])
        hs = []
        for t in range(int(cfg["total_ut_steps"])):
            for i in range(int(cfg["num_hidden_layers"])):
                x = ref.block(x, {n: ref._f32(get(n, i, t))
                                  for n in ref.LAYER_TENSORS}, cfg, pos)
            hs.append(ref.rms_norm(x, ref._f32(get("final_norm")), eps))
        return hs


def _block_without_post_norms(x, w, cfg, positions):
    """The fault: ``ref.block`` as a plain pre-norm block, x + Attn(N1(x))
    and a + FFN(N3(a))."""
    H = int(cfg["num_attention_heads"])
    d = int(cfg["head_dim"])
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    T = x.shape[0]
    h = ref.rms_norm(x, w["ln1"], eps)
    q = ref.rope((h @ w["wq"]).reshape(T, H, d), positions, theta)
    k = ref.rope((h @ w["wk"]).reshape(T, H, d), positions, theta)
    v = (h @ w["wv"]).reshape(T, H, d)
    a = x + ref.attention(q, k, v).reshape(T, H * d) @ w["wo"]
    h = ref.rms_norm(a, w["ln2"], eps)
    return a + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


@pytest.fixture(scope="module")
def case():
    published = _published()
    cfg = {**published, "hidden_size": D, "intermediate_size": F,
           "num_attention_heads": HEADS, "num_key_value_heads": HEADS,
           "head_dim": HEAD, "num_hidden_layers": LAYERS}
    beta = float(published["deployment"]["exit_loss_beta"])
    rng = np.random.default_rng(0)
    w = _weights(rng, cfg["vocab_size"])
    toks = rng.integers(0, cfg["vocab_size"], T, dtype=np.int32)

    def getter(convert=lambda t: t):
        def get(name, layer=None, step=None):
            return convert(_bf16(w[name if layer is None else (name, layer)]))
        return get

    nll, p = _per_token(cfg, getter(), toks)
    want = ref.expected_exit_loss(cfg, getter(), toks, beta)
    want = {k: np.asarray(v) for k, v in want.items()}
    return published["check"], cfg, beta, toks, getter, nll, p, want


def _faults(case):
    check, cfg, beta, toks, getter, nll, p, want = case
    yield "one pass instead of four", _put_together(
        *_per_token({**cfg, "total_ut_steps": 1}, getter(), toks), beta)
    yield "the first pass's logits for all four", _put_together(
        jnp.broadcast_to(nll[:1], nll.shape), p, beta)
    yield "no final norm between passes", _put_together(
        *_per_token(cfg, getter(), toks, _no_norm_between_passes), beta)
    whole = ref.block
    try:
        ref.block = _block_without_post_norms
        yield "no post-branch norms", _put_together(
            *_per_token(cfg, getter(), toks), beta)
    finally:
        ref.block = whole
    yield "the entropy term dropped", _put_together(nll, p, beta, False)
    yield "beta halved", _put_together(nll, p, beta / 2)
    yield "fp8-rounded weights", _put_together(
        *_per_token(cfg, getter(_fp8), toks), beta)


def test_the_reference_passes_its_own_check(case):
    check, _, beta, _, _, nll, p, want = case
    lo, hi = check["first_loss_range"]
    assert lo <= float(want["loss"]) <= hi
    problems, _ = compare(_put_together(nll, p, beta), want, check)
    assert problems == []


def test_each_fault_fails_at_least_one_tolerance(case):
    check, want = case[0], case[-1]
    seen = {}
    for name, system in _faults(case):
        problems, facts = compare(system, want, check)
        seen[name] = {k: v["max_abs_diff"] for k, v in facts.items()}
        assert problems, (name, seen[name])
    print(json.dumps(seen, indent=1))
    assert len(seen) == 7
