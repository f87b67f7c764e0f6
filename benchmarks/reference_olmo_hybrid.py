"""Plain reference of Olmo-Hybrid (``model_type: "olmo_hybrid"``): forward,
the loss over the vocabulary held, each layer's mixer-output mean square,
gradients by ``jax.grad`` / ``jax.vjp``, and the AdamW update they give.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernels, no chunks, no cache, no
batching; one sequence at a time, one layer at a time (so that it fits on
the chip beside the program's state). Nothing is recomputed but, for a
gradient, what memory forces: a layer's forward from its input, and the
recurrence's states 64 positions at a time from the state before them (the
same arithmetic, run again). It imports nothing but JAX. ``benchmarks/reference_olmo_hybrid.py`` is a copy of this file, kept
with the benchmark; ``benchmarks/tests/test_olmo_hybrid.py`` holds the two
equal.

The model, from the published ``config.json`` and the family it names (the
``linear_*`` keys and ``linear_allow_neg_eigval`` are those of the gated
delta rule, Gated DeltaNet, as the ``fla`` library and Qwen3-Next spell it):

* ``x0 = E[ids]``; a layer: ``h = x + RMSNorm_a(Mix_kind(x))``, ``y = h +
  RMSNorm_f(W_down (silu(W_gate h) * W_up h))``: no norm before a branch, one
  after it (the Olmo 2 / Olmo 3 block); ``logits = RMSNorm(x_L) W_head`` (an
  untied head); no bias anywhere; eps ``rms_norm_eps``.
* a ``full_attention`` layer: ``q = RMSNorm_q(x W_q)``, ``k = RMSNorm_k(x
  W_k)``, each norm over the whole projection (every head held) before the
  heads are split, ``v = x W_v``; each key-value head serving H / K query
  heads; **no rope** (``rope_parameters.rope_theta`` is null); causal
  ``softmax(q k^T / sqrt(d)) v``; ``W_o``.
* a ``linear_attention`` layer, per head of ``d_k = linear_key_head_dim``,
  ``d_v = linear_value_head_dim``::

      q~, k~, v~ = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v))
      q_t, k_t   = q~_t / sqrt(sum q~_t^2 + 1e-6), k~_t / sqrt(sum k~_t^2 + 1e-6)
      beta_t     = 2 sigmoid(x_t W_b)          (the 2: linear_allow_neg_eigval)
      g_t        = -exp(A_log) softplus(x_t W_a + dt_bias),  alpha_t = exp(g_t)
      S_t        = alpha_t S_{t-1} + beta_t k_t (v~_t - alpha_t S_{t-1}^T k_t)^T
      o_t        = S_t^T q_t / sqrt(d_k)
      y_t        = RMSNorm_{d_v}(o_t) * w_o_norm * silu(x_t W_z)
      Mix(x)     = concat_heads(y) W_o

  with ``S`` [d_k, d_v] zero at the start, the convolution causal and
  depthwise over the last ``linear_conv_kernel_dim`` positions without bias
  (zeros before the start), the output norm first and the gate after it, per
  head. **The recurrence itself**, a ``lax.scan`` over the positions:
  independent of the program's chunked form by construction.

**The cut.** ``vocab_size`` rows of the table and columns of the head are
held (ids, logits and loss over the slice), ``num_hidden_layers`` layers (the
first of ``layer_types``), and ``num_attention_heads`` /
``num_key_value_heads`` / ``linear_num_*_heads`` heads of each mixer: the
projections have the held heads' columns, ``W_o`` their rows, and a mixer's
output is the partial sum those heads give. A delta layer's heads are
independent and its output norm is per head, so the shares of its heads add
up to the whole layer exactly. The full layer's q/k norm is over the width
held: with a share of the heads that is **not** the whole projection's mean
square (a deployment would all-reduce one scalar a token; nothing here stands
in for it). ``attention_layer(..., norm_shares=n)`` takes the norm over each
of ``n`` equal groups of columns, which is what the shares of an uncut layer
add up to. With every head, the whole table and every layer there is no
departure from the reading above.

What the published file does not say, and this reading assumes (the program
follows the same reading; the configuration file lists them under
``assumed``): the norm placement and the q/k norm (the Olmo 2 / 3
convention), no rope (a null ``rope_theta``), no convolution bias, the L2
norms' 1e-6 inside the root, the output norm's eps ``rms_norm_eps`` and one
scale of ``d_v`` shared by the heads, ``head_dim`` = 3840 / 30 = 128 (the
file carries it as a key of its own beside the heads held); the mean square
reported for a layer is of the mixer's output **before** the branch's norm.

Weights are read through ``get(name, layer=None)``, which returns one stored
tensor of any float type (upcast here): ``embed`` [V, D], ``lm_head`` [D, V],
``final_norm`` [D]; per layer ``ln1_post``, ``ln2_post`` [D], ``w_gate``,
``w_up`` [D, F], ``w_down`` [F, D]; of a full layer ``wq`` [D, H d], ``wk``,
``wv`` [D, K d], ``q_norm`` [H d], ``k_norm`` [K d], ``wo`` [H d, D]; of a
delta layer ``wq``, ``wk`` [D, H d_k], ``wv``, ``wz`` [D, H d_v], ``wb``,
``wa`` [D, H], ``conv_q``, ``conv_k``, ``conv_v`` [taps, width] (tap k meets
position t - (taps - 1) + k), ``A_log``, ``dt_bias`` [H], ``o_norm`` [d_v],
``wo`` [H d_v, D].
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
_QUERY_BLOCK = 512
#: positions whose states a gradient of the recurrence computes again
#: together (memory only; no part of the arithmetic)
_STATE_BLOCK = 64
L2_EPS = 1e-6
COMMON = ("ln1_post", "ln2_post", "w_gate", "w_up", "w_down")
TENSORS = {
    "full_attention": COMMON + ("wq", "wk", "wv", "q_norm", "k_norm", "wo"),
    "linear_attention": COMMON + (
        "wq", "wk", "wv", "wz", "wb", "wa", "conv_q", "conv_k", "conv_v",
        "A_log", "dt_bias", "o_norm", "wo")}


def kinds(cfg: Dict):
    """``layer_types`` of the layers kept."""
    return list(cfg["layer_types"])[:int(cfg["num_hidden_layers"])]


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def attention(q, k, v):
    """q [T, H, d], k/v [T, K, d] (each key-value head repeated H / K times),
    causal, scores over sqrt(d); in blocks of queries."""
    T, H, d = q.shape
    k = jnp.repeat(k, H // k.shape[1], axis=1)
    v = jnp.repeat(v, H // v.shape[1], axis=1)
    kpos = jnp.arange(T)
    outs = []
    for lo in range(0, T, _QUERY_BLOCK):
        qb = q[lo:lo + _QUERY_BLOCK]
        qpos = jnp.arange(lo, lo + qb.shape[0])
        s = jnp.einsum("thd,shd->hts", qb, k) / math.sqrt(d)
        s = jnp.where((kpos[None, :] <= qpos[:, None])[None], s, -jnp.inf)
        outs.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(outs, axis=0)


def attention_layer(u, w: Dict, cfg: Dict, norm_shares: int = 1):
    """The full-attention mixer on u [T, D]."""
    H, K = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d, eps, T = int(cfg["head_dim"]), float(cfg["rms_norm_eps"]), u.shape[0]
    if (cfg.get("rope_parameters") or {}).get("rope_theta") is not None:
        raise ValueError("only a null rope_theta (no rope) is written down "
                         "here")

    def normed(x, scale):
        parts = x.reshape(T, norm_shares, -1)
        return rms_norm(parts, scale.reshape(norm_shares, -1), eps).reshape(
            x.shape)

    q = normed(u @ w["wq"], w["q_norm"]).reshape(T, H, d)
    k = normed(u @ w["wk"], w["k_norm"]).reshape(T, K, d)
    o = attention(q, k, (u @ w["wv"]).reshape(T, K, d))
    return o.reshape(T, H * d) @ w["wo"]


def conv(x, w):
    """The direct sum: x [T, C], w [K, C]; ``y[t] = sum_k w[k] x[t - (K - 1)
    + k]``, positions before 0 read as zero."""
    T, K = x.shape[0], w.shape[0]
    idx = jnp.arange(T)[:, None] - (K - 1) + jnp.arange(K)[None, :]  # [T, K]
    taps = jnp.where((idx >= 0)[..., None], x[jnp.maximum(idx, 0)], 0.0)
    return jnp.einsum("tkc,kc->tc", taps, w)


def l2_norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def recurrence(q, k, v, g, beta):
    """q, k [T, H, dk] (normed, q not yet scaled), v [T, H, dv], g (the
    decay's logarithm) and beta [T, H] -> o [T, H, dv]: position by position
    over the state S [H, dk, dv]. (Where T allows, the positions are walked
    in runs of ``_STATE_BLOCK`` under ``jax.checkpoint``: a gradient then
    keeps one state a run and computes the run's again, and not 4096 states
    of [H, dk, dv]; the forward is the same steps in the same order.)"""
    T, H, dk = q.shape

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[:, None, None] * S            # the decay first
        wrote = b_t[:, None] * (v_t - jnp.einsum("hde,hd->he", S, k_t))
        S = S + k_t[:, :, None] * wrote[:, None, :]
        return S, jnp.einsum("hde,hd->he", S, q_t) / math.sqrt(dk)

    start, xs = jnp.zeros((H, dk, v.shape[2]), F32), (q, k, v, g, beta)
    if T % _STATE_BLOCK:
        return jax.lax.scan(step, start, xs)[1]
    run = jax.checkpoint(lambda S, xs: jax.lax.scan(step, S, xs))
    _, o = jax.lax.scan(run, start, jax.tree_util.tree_map(
        lambda a: a.reshape(T // _STATE_BLOCK, _STATE_BLOCK, *a.shape[1:]),
        xs))
    return o.reshape(T, H, -1)


def delta_layer(u, w: Dict, cfg: Dict):
    """The gated delta-rule mixer on u [T, D]."""
    H = int(cfg["linear_num_value_heads"])
    dk, dv = int(cfg["linear_key_head_dim"]), int(cfg["linear_value_head_dim"])
    T = u.shape[0]
    q, k, v = (jax.nn.silu(conv(u @ w[p], w[c])) for p, c in
               (("wq", "conv_q"), ("wk", "conv_k"), ("wv", "conv_v")))
    beta = jax.nn.sigmoid(u @ w["wb"])
    if cfg["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(u @ w["wa"] + w["dt_bias"])
    o = recurrence(l2_norm(q.reshape(T, H, dk)), l2_norm(k.reshape(T, H, dk)),
                   v.reshape(T, H, dv), g, beta)
    y = rms_norm(o, w["o_norm"], float(cfg["rms_norm_eps"])) \
        * jax.nn.silu(u @ w["wz"]).reshape(T, H, dv)
    return y.reshape(T, H * dv) @ w["wo"]


def block(x, w: Dict, cfg: Dict, kind: str):
    """One layer on x [T, D] float32 -> (y, the mean square of the mixer's
    output before its norm); ``w`` holds the layer's tensors in float32,
    ``kind`` is its entry of ``layer_types``."""
    eps = float(cfg["rms_norm_eps"])
    mix = (delta_layer if kind == "linear_attention" else attention_layer)(
        x, w, cfg)
    h = x + rms_norm(mix, w["ln1_post"], eps)
    ffn = (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
    return h + rms_norm(ffn, w["ln2_post"], eps), jnp.mean(mix * mix)


def head_nll(x, norm, head, tokens, eps):
    """``nll`` [T - 1]: the cross-entropy of each position's logits (the
    final norm, the untied head) against the next token."""
    lg = (rms_norm(x, norm, eps) @ head)[:-1]
    gold = jnp.take_along_axis(lg, tokens[1:, None], axis=-1)[:, 0]
    return jax.scipy.special.logsumexp(lg, axis=-1) - gold


def sequence(cfg: Dict, get: Callable, tokens) -> Dict:
    """One sequence [T] through the model: ``nll`` [T - 1] (cross-entropy of
    each position's logits against the next token, over the rows held) and
    ``mix_out_ms`` [L]."""
    eps = float(cfg["rms_norm_eps"])
    block_jit = jax.jit(lambda x, w, kind: block(
        x, {n: t.astype(F32) for n, t in w.items()}, cfg, kind),
        static_argnums=2)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = jnp.asarray(get("embed")).astype(F32)[tokens]
        ms = []
        for i, kind in enumerate(kinds(cfg)):
            x, m = block_jit(x, {n: jnp.asarray(get(n, i))
                                 for n in TENSORS[kind]}, kind)
            ms.append(m)
        nll = head_nll(x, jnp.asarray(get("final_norm")).astype(F32),
                       jnp.asarray(get("lm_head")).astype(F32), tokens, eps)
    return {"nll": nll, "mix_out_ms": jnp.stack(ms)}


def batch_loss(cfg: Dict, get: Callable, rows) -> Dict:
    """``loss``: the mean cross-entropy over the B x (T - 1) targets of the
    micro-batch ``rows`` [B, T]; ``mix_out_ms`` [L]: each layer's mixer-output
    mean square over all B x T positions."""
    per_row = [sequence(cfg, get, row) for row in rows]
    return {"loss": jnp.mean(jnp.concatenate([r["nll"] for r in per_row])),
            "mix_out_ms": sum(r["mix_out_ms"] for r in per_row)
            / len(per_row)}


def loss_and_grads(cfg: Dict, weights: Dict, rows):
    """``(loss, d loss / d weights)`` by ``jax.grad``; ``weights`` is a dict
    of float32 arrays keyed ``(name, layer)``, ``(name, None)`` for what no
    layer owns."""
    def loss(w):
        return batch_loss(cfg, dict_getter(w), rows)["loss"]

    return jax.value_and_grad(loss)(weights)


def dict_getter(weights: Dict) -> Callable:
    def get(name, layer=None):
        return weights[(name, layer)]

    return get


def batch_loss_and_grads(cfg: Dict, get: Callable, rows,
                         sink: Optional[Callable] = None):
    """:func:`batch_loss`'s ``loss`` and ``mix_out_ms`` and the gradient of
    the loss by every tensor ``get`` returns (float32, taken at the tensor
    upcast to float32), a layer at a time so that it fits beside a program's
    state: the forward keeps each layer's input, the head gives the
    cotangent of the last, and each layer's ``jax.vjp`` in turn, last layer
    first, its weights' gradients and its input's cotangent. The same
    derivative as :func:`loss_and_grads`, which differentiates the whole.

    Returns ``(out, grads)`` with ``grads`` keyed ``(name, layer)``; given a
    ``sink``, each gradient is handed to ``sink(name, layer, grad)`` as soon
    as it is whole (so that the caller may move it off the device) and
    ``grads`` comes back empty."""
    eps, ks = float(cfg["rms_norm_eps"]), kinds(cfg)
    targets = sum(len(row) - 1 for row in rows)
    held: Dict = {}
    if sink is None:
        def sink(name, layer, grad):
            held[(name, layer)] = grad

    def f32(name, layer=None):
        return jnp.asarray(get(name, layer)).astype(F32)

    forward = jax.jit(lambda x, w, kind: block(x, w, cfg, kind),
                      static_argnums=2)

    def back(x, w, dy, kind):
        return jax.vjp(lambda x, w: block(x, w, cfg, kind)[0], x, w)[1](dy)

    back = jax.jit(back, static_argnums=3)
    head = jax.jit(jax.value_and_grad(
        lambda x, norm, head, tokens:
        jnp.sum(head_nll(x, norm, head, tokens, eps)) / targets,
        argnums=(0, 1, 2)))
    partial: Dict = {}
    loss, ms = 0.0, 0.0
    with jax.default_matmul_precision("highest"):
        for r, row in enumerate(rows):
            def give(name, layer, grad):
                grad = partial.pop((name, layer), 0.0) + grad
                if r == len(rows) - 1:
                    sink(name, layer, grad)
                else:
                    partial[(name, layer)] = grad

            tokens = jnp.asarray(row, jnp.int32)
            table = f32("embed")
            xs, row_ms = [table[tokens]], []
            for i, kind in enumerate(ks):
                y, m = forward(xs[-1], {n: f32(n, i) for n in TENSORS[kind]},
                               kind)
                xs.append(y)
                row_ms.append(m)
            part, (dx, d_norm, d_head) = head(
                xs.pop(), f32("final_norm"), f32("lm_head"), tokens)
            give("final_norm", None, d_norm)
            give("lm_head", None, d_head)
            for i in reversed(range(len(ks))):
                dx, dw = back(xs.pop(), {n: f32(n, i) for n in
                                         TENSORS[ks[i]]}, dx, ks[i])
                for n, g in dw.items():
                    give(n, i, g)
            give("embed", None, jnp.zeros_like(table).at[tokens].add(dx))
            loss, ms = loss + part, ms + jnp.stack(row_ms)
    return {"loss": loss, "mix_out_ms": ms / len(rows)}, held


def adamw_first_step(g, w, lr: float, b1: float = 0.9, b2: float = 0.999,
                     eps: float = 1e-8, weight_decay: float = 0.0):
    """The change AdamW's first step makes to ``w`` given the gradient ``g``
    (Loshchilov & Hutter; moments from zero, both bias corrections, the
    decay decoupled)::

        m = (1 - b1) g,  v = (1 - b2) g^2
        -lr ((m / (1 - b1)) / (sqrt(v / (1 - b2)) + eps) + weight_decay w)

    which is ``-lr g / (|g| + eps)`` without decay: each element's sign,
    where it is not within ``eps`` of zero."""
    m, v = (1.0 - b1) * g, (1.0 - b2) * g * g
    return -lr * ((m / (1.0 - b1)) / (jnp.sqrt(v / (1.0 - b2)) + eps)
                  + weight_decay * w)
