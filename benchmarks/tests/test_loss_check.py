"""What the training cell's correctness check can and cannot see.

The check compares the first step's mean loss with the float32 reference on
the same weights, within ``check.loss_abs_tol`` of the configuration file.
Here the reference itself is run at the cell's vocabulary and sequence
length (so the mean is over as many random targets) and a narrower hidden
size, on weights drawn as the program draws them, with one thing changed at
a time. At the committed tolerance a wrong rope base and fp8 weights fail;
int8 weights move the logits by 3 % and the mean loss by a sixth of the
tolerance, so the check does not see them (PERF.md, Open questions)."""

import json
import os

import jax.numpy as jnp
import numpy as np

from benchmarks import reference

HERE = os.path.dirname(os.path.abspath(__file__))
D, HEADS, KV, HEAD, LAYERS = 256, 4, 1, 64, 2


def _weights(rng, vocab):
    def n(shape, std):
        return (rng.standard_normal(shape) * std).astype(np.float32)

    w = {"embed": n((vocab, D), 0.02), "final_norm": np.ones(D, np.float32),
         "head": n((D, vocab), D ** -0.5)}
    for i in range(LAYERS):
        w["ln1", i] = w["ln2", i] = np.ones(D, np.float32)
        w["wq", i] = n((D, HEADS * HEAD), D ** -0.5)
        w["wk", i] = n((D, KV * HEAD), D ** -0.5)
        w["wv", i] = n((D, KV * HEAD), D ** -0.5)
        w["wo", i] = n((HEADS * HEAD, D), (HEADS * HEAD) ** -0.5)
        w["w_gate", i] = n((D, 4 * D), D ** -0.5)
        w["w_up", i] = n((D, 4 * D), D ** -0.5)
        w["w_down", i] = n((4 * D, D), (4 * D) ** -0.5)
    return w


def _bf16(w):
    return jnp.asarray(w).astype(jnp.bfloat16).astype(jnp.float32)


def _int8(w):
    if w.ndim < 2:
        return w
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
    return jnp.round(w / scale) * scale


def _fp8(w):
    if w.ndim < 2:
        return w
    return jnp.asarray(w).astype(jnp.float8_e4m3fn).astype(jnp.float32)


def test_what_the_mean_loss_check_sees():
    with open(os.path.join(HERE, "..", "configs",
                           "mistral7b_train_d2.json")) as f:
        published = json.load(f)
    tol = published["check"]["loss_abs_tol"]
    cfg = {**published, "hidden_size": D, "intermediate_size": 4 * D,
           "num_attention_heads": HEADS, "num_key_value_heads": KV,
           "head_dim": HEAD, "num_hidden_layers": LAYERS}
    rng = np.random.default_rng(0)
    w = _weights(rng, cfg["vocab_size"])
    toks = rng.integers(0, cfg["vocab_size"], 4096, dtype=np.int32)

    def loss(convert, cfg=cfg):
        def get(name, layer=None, expert=None):
            return convert(_bf16(w[name if layer is None else (name, layer)]))
        return float(reference.next_token_loss(
            reference.forward(cfg, get, toks), toks))

    base = loss(lambda t: t)
    lo, hi = published["check"]["first_loss_range"]
    assert lo <= base <= hi
    assert abs(loss(lambda t: t, {**cfg, "rope_theta": 1e6}) - base) > 5 * tol
    assert abs(loss(_fp8) - base) > 2 * tol      # 0.0035
    assert abs(loss(_int8) - base) < 0.5 * tol      # the check's blind spot
