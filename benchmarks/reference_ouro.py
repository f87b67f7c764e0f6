"""Plain reference of the looped decoder (Ouro / LoopLM): forward, the
expected-exit loss with its parts, and gradients by ``jax.grad``.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernels, no scan, no cache, no
batching, no recomputation; one sequence at a time. It imports nothing but
JAX. ``tests/unit/looped_reference.py`` is a copy of this file, kept with the
program's tests; ``benchmarks/tests/test_reference_ouro.py`` holds the two
equal.

The model, from the published ``config.json`` (hidden 2048, 16 heads of 128,
SwiGLU 5632, RMSNorm eps 1e-6, ``rope_theta`` 1e6, untied head,
``total_ut_steps`` 4) and the family's description:

* ``h_0 = E[x]``; one *pass* is the stack of L blocks; passes ``t = 1..R``
  run **over the same weights**: ``u_t = stack(h_{t-1})``,
  ``h_t = N_f(u_t)``;
* a block (sandwich norm): ``a = x + N2(Attn(N1(x)))``,
  ``y = a + N4(FFN(N3(a)))``; attention is causal multi-head with rotary
  embeddings in the half-split ("rotate_half") convention, the FFN is
  ``down(silu(gate(x)) * up(x))``;
* per pass: logits ``z_t = h_t W_head`` and, per token, an exit gate
  ``lambda_t = sigmoid(w_g . h_t + b_g)``;
* exit distribution per token: ``p_t = lambda_t prod_{j<t}(1 - lambda_j)``
  for ``t < R``, ``p_R = prod_{j<R}(1 - lambda_j)``;
* loss: the mean over target positions of
  ``sum_t p_t CE(z_t, next token) - beta H(p)``, ``H(p) = -sum_t p_t ln p_t``.

What the published file does not say, and this reading assumes (the program
follows the same reading; ``configs/ouro2_6b_train_d6.json`` lists them under
``assumed``): that the final norm ``N_f`` closes every pass and the next pass
reads its output; that the gate has a bias; that the second norm of each pair
acts on the branch's output before the residual add; ``beta`` (the caller's).
``lambda_R`` is never computed: ``p_R`` takes what is left. Departures from
the mathematics: none; attention is evaluated in blocks of queries so that a
4096-token sequence does not hold 16 full score matrices at once.

Weights are read through ``get(name, layer=None, step=None)``, which returns
one stored tensor of any float type (upcast here, one layer at a time):
``embed`` [V, D], ``final_norm`` [D], ``head`` [D, V], ``gate_w`` [D],
``gate_b`` [], and per layer ``ln1``, ``ln1_post``, ``ln2``, ``ln2_post``
[D], ``wq`` [D, H d], ``wk``, ``wv`` [D, K d], ``wo`` [H d, D], ``w_gate``, ``w_up``
[D, F], ``w_down`` [F, D]. ``step`` is the pass (0-based) that asks: a
getter over shared weights ignores it; one over R untied copies of the stack
does not, which is how the tests show that the shared gradient is the sum
over passes.
"""

from __future__ import annotations

from typing import Callable, Dict, List

import jax
import jax.numpy as jnp

F32 = jnp.float32
_QUERY_BLOCK = 512
LAYER_TENSORS = ("ln1", "ln1_post", "ln2", "ln2_post", "wq", "wk", "wv", "wo",
                 "w_gate", "w_up", "w_down")


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rope(x, positions, theta):
    """x [T, heads, d]; rotate pairs (j, j + d/2) by positions * theta^(-2j/d)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv[None, :]        # [T, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """q [T, H, d], k/v [T, K, d] (K = H as published; K < H repeats each
    key-value head H / K times), positions 0..T-1; causal."""
    T, H, d = q.shape
    k = jnp.repeat(k, H // k.shape[1], axis=1)
    v = jnp.repeat(v, H // v.shape[1], axis=1)
    kpos = jnp.arange(T)
    outs = []
    for lo in range(0, T, _QUERY_BLOCK):
        qb = q[lo:lo + _QUERY_BLOCK]
        qpos = jnp.arange(lo, lo + qb.shape[0])
        s = jnp.einsum("thd,shd->hts", qb, k) / jnp.sqrt(F32(d))
        s = jnp.where((kpos[None, :] <= qpos[:, None])[None], s, -jnp.inf)
        outs.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(outs, axis=0)


def block(x, w: Dict, cfg: Dict, positions):
    """One sandwich-norm block on x [T, D] float32; ``w`` holds the layer's
    tensors in float32."""
    H, K = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d = int(cfg.get("head_dim") or cfg["hidden_size"] // H)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    T = x.shape[0]
    h = rms_norm(x, w["ln1"], eps)
    q = rope((h @ w["wq"]).reshape(T, H, d), positions, theta)
    k = rope((h @ w["wk"]).reshape(T, K, d), positions, theta)
    v = (h @ w["wv"]).reshape(T, K, d)
    branch = attention(q, k, v).reshape(T, H * d) @ w["wo"]
    a = x + rms_norm(branch, w["ln1_post"], eps)
    h = rms_norm(a, w["ln2"], eps)
    branch = (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
    return a + rms_norm(branch, w["ln2_post"], eps)


def _f32(t):
    return jnp.asarray(t).astype(F32)


def hidden_passes(cfg: Dict, get: Callable, tokens) -> List[jax.Array]:
    """``[h_1 .. h_R]``, each [T, D] float32: the output of ``N_f`` after
    every pass over the stack."""
    eps = float(cfg["rms_norm_eps"])
    block_jit = jax.jit(lambda x, w, pos: block(
        x, {n: t.astype(F32) for n, t in w.items()}, cfg, pos))
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        pos = jnp.arange(tokens.shape[0])
        x = _f32(jnp.asarray(get("embed"))[tokens])
        hs = []
        for t in range(int(cfg["total_ut_steps"])):
            for i in range(int(cfg["num_hidden_layers"])):
                x = block_jit(x, {n: jnp.asarray(get(n, i, t))
                                  for n in LAYER_TENSORS}, pos)
            x = rms_norm(x, _f32(get("final_norm", None, t)), eps)
            hs.append(x)
        return hs


def next_token_nll(logits, tokens):
    """Per-position cross-entropy [T - 1] of logits[t] against tokens[t + 1]."""
    lg = logits[:-1].astype(F32)
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(
        lg, jnp.asarray(tokens, jnp.int32)[1:, None], axis=-1)[:, 0]
    return logz - gold


def pass_logits(get: Callable, h):
    """``z_t = h_t W_head`` for one pass's hidden states, float32."""
    with jax.default_matmul_precision("highest"):
        return h @ _f32(get("head"))


def exit_distribution(get: Callable, hs: List[jax.Array]):
    """p [R, T]: the probability of leaving after each pass, per token."""
    w, b = _f32(get("gate_w")), _f32(get("gate_b"))
    with jax.default_matmul_precision("highest"):
        lam = [jax.nn.sigmoid(h @ w + b) for h in hs[:-1]]
    p, stay = [], jnp.ones_like(lam[0])
    for lt in lam:
        p.append(lt * stay)
        stay = stay * (1.0 - lt)
    return jnp.stack(p + [stay])


def expected_exit_loss(cfg: Dict, get: Callable, tokens, beta: float) -> Dict:
    """The loss and its parts for one sequence: ``loss``, ``pass_loss`` [R]
    (each pass's mean cross-entropy), ``exit_prob`` [R] (the mean exit
    distribution), ``exit_entropy``; means over the T - 1 target positions.
    The logits are formed a pass at a time and reduced at once."""
    hs = hidden_passes(cfg, get, tokens)
    nll = jnp.stack([next_token_nll(pass_logits(get, h), tokens) for h in hs])
    p = exit_distribution(get, hs)[:, :-1]        # the last token has no target
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)),
                                 0.0), axis=0)
    per_token = jnp.sum(p * nll, axis=0) - beta * entropy
    return {"loss": jnp.mean(per_token), "pass_loss": jnp.mean(nll, axis=1),
            "exit_prob": jnp.mean(p, axis=1),
            "exit_entropy": jnp.mean(entropy)}


def loss_and_grads(cfg: Dict, weights: Dict, tokens, beta: float,
                   getter: Callable = None):
    """``(loss, d loss / d weights)`` by ``jax.grad``. ``weights`` is a dict
    of float32 arrays keyed ``name`` or ``(name, layer)`` (or whatever
    ``getter(weights)`` reads to build a ``get``)."""
    make = getter or dict_getter

    def loss(w):
        return expected_exit_loss(cfg, make(w), tokens, beta)["loss"]

    return jax.value_and_grad(loss)(weights)


def dict_getter(weights: Dict) -> Callable:
    """``get`` over ``{name | (name, layer): array}``: shared weights, the
    pass that asks is ignored."""
    def get(name, layer=None, step=None):
        return weights[name if layer is None else (name, layer)]

    return get
