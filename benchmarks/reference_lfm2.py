"""Plain reference of LFM2 with routed experts (``model_type: "lfm2_moe"``;
LiquidAI LFM2-24B-A2B): forward, the loss over the vocabulary held with its
balance term, each layer's mixer-output mean square, the router's counts, the
selection bias after a step, gradients by ``jax.grad`` / ``jax.vjp``, and the
AdamW update they give.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernels, no cache, no batching, no
dispatch; one sequence at a time, one layer at a time (so that it fits on the
chip beside the program's state). Nothing is recomputed but, for a gradient,
what memory forces: a layer's forward from its input, and a block of queries'
scores from q, k and v (the same arithmetic, run again). It imports nothing
but JAX.

The model, from the published ``config.json`` (HF ``modeling_lfm2_moe.py``'s
reading), ``N`` an RMSNorm with eps ``norm_eps``:

* ``x0 = E[ids]``; layer i: ``h = x + Mix_i(N_op(x))``, ``y = h +
  FFN_i(N_ffn(h))``; ``logits = N(x_L) E^T`` (one final norm,
  ``embedding_norm``, and the tied head); no bias anywhere.
* ``Mix`` of a ``conv`` layer (``layer_types``), a gated short convolution,
  on ``u`` [T, D]: ``(B, C, z) = split3(u W_in)`` (``W_in`` [D, 3 D], in that
  order); ``v = B * z``; ``c_t = sum_{k=0..K-1} w[k] * v_{t-(K-1)+k}`` with
  ``K = conv_L_cache`` (depthwise, causal, zeros before the sequence's start,
  the last tap on the current position; no bias, **no activation**): **the sum
  of K shifted arrays**; ``Mix = (C * c) W_out``.
* ``Mix`` of a ``full_attention`` layer: ``q = u W_q`` as H heads of d, ``k``,
  ``v`` as K heads of d (d = ``hidden_size`` / ``num_attention_heads``);
  ``q <- rope(N_d(q))``, ``k <- rope(N_d(k))``: an RMSNorm over each head's d
  channels with one scale of d for q and one for k, shared by the heads,
  **before** the rope; the rope by halves (channel j pairs with j + d / 2),
  theta ``rope_parameters.rope_theta``, every channel; each key-value head
  serving H / K query heads; causal ``softmax(q k^T / sqrt(d)) v``; ``W_o``.
* ``FFN`` of the first ``num_dense_layers`` layers: ``W_2 (silu(W_1 x) * W_3
  x)`` at ``intermediate_size``. Of the others: ``s = sigmoid(x W_r)`` over the
  ``router_width`` routed experts; the ``num_experts_per_tok`` with the
  largest ``s + b`` (``b`` the selection bias, ``use_expert_bias``, which only
  picks and gets no gradient); weights ``s_i / (sum_chosen s_j + 1e-6)``
  (``norm_topk_prob``) times ``routed_scaling_factor``; the sum of the chosen
  experts' SwiGLUs at ``moe_intermediate_size``, **one expert at a time over a
  mask**. No shared expert.

**The cut.** ``vocab_size`` rows of the table are held (ids, logits and loss
over the slice), ``num_hidden_layers`` layers from published layer
``first_layer`` on (their kinds ``layer_types[first_layer:]``), of which the
first ``num_dense_layers`` have the dense FFN, and ``num_experts`` experts from
``first_expert`` on of the ``router_width`` the router scores: a routed
layer's FFN output is the partial sum the held experts give, and what the
absent ones would add is left out. The eight shares of a layer's experts add
up to the whole layer. With every expert, row and layer there is no departure
from the reading above.

What the published file does not say, and this reading assumes (the
configuration file lists them under ``assumed``): the head is tied to the
embedding (the LFM2 family's convention; ``tie_word_embeddings`` false here
reads an untied ``lm_head``); the convolution is a cross-correlation whose
last tap meets the current position; the balance term and the bias rule are
DeepSeek-V3's (below), which the file names no more than it names any
training recipe; the mean square reported for a layer is of its mixer's
output. The program's router divides by the chosen scores' sum without the
1e-6 (a float32 sum of four sigmoids moves by under 1e-6 of itself).

Training's parts: the sequence-wise balance term ``sum_i f_i P_i`` a sequence
and routed layer, ``f_i = E / (k T) x`` the pairs expert i received from the
sequence (the chosen pairs, bias included: a constant), ``P_i`` the
sequence's mean of ``s_i / sum_j s_j``, averaged over the sequences, summed
over the layers, added to the loss times ``alpha``; after a step a selection
bias rises by ``gamma`` where its expert's count is under its layer's mean
and falls by it where over (:func:`bias_after`).

Weights are read through ``get(name, layer=None)``, which returns one stored
tensor of any float type (upcast here): ``embed`` [V, D], ``final_norm`` [D]
(``lm_head`` [D, V] where the head is untied); per layer ``operator_norm``,
``ffn_norm`` [D]; of a conv layer ``in_proj`` [D, 3 D], ``conv_w`` [K, D]
(tap k meets position t - (K - 1) + k), ``out_proj`` [D, D]; of an attention
layer ``wq`` [D, H d], ``wk``, ``wv`` [D, K d], ``wo`` [H d, D], ``q_norm``,
``k_norm`` [d]; of a dense layer ``w1``, ``w3`` [D, F], ``w2`` [F, D] (gate,
up, down); of a routed layer ``router`` [D, E], ``router_bias`` [E], ``w1``,
``w3`` [held, D, Fm], ``w2`` [held, Fm, D].
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32
_QUERY_BLOCK = 512
#: what the chosen scores' sum is raised by before the division (the
#: published code's)
SUM_EPS = 1e-6
MIXER = {"conv": ("in_proj", "conv_w", "out_proj"),
         "full": ("wq", "wk", "wv", "wo", "q_norm", "k_norm")}
FFN = {"dense": ("w1", "w3", "w2"),
       "moe": ("router", "router_bias", "w1", "w3", "w2")}
NORMS = ("operator_norm", "ffn_norm")
#: what picks and gets no gradient
NO_GRADIENT = ("router_bias",)
_LAYER_TYPES = {"conv": "conv", "full_attention": "full"}


def kinds(cfg: Dict) -> Sequence[str]:
    """``"<mixer>:<ffn>"`` of each layer kept: ``layer_types`` from
    ``first_layer`` on, the first ``num_dense_layers`` of them dense."""
    first, L = int(cfg.get("first_layer", 0)), int(cfg["num_hidden_layers"])
    types = list(cfg["layer_types"])[first:first + L]
    if len(types) != L or set(types) - set(_LAYER_TYPES):
        raise ValueError(f"layer_types[{first}:{first + L}] = {types}: only "
                         f"{sorted(_LAYER_TYPES)} are written down here")
    dense = int(cfg["num_dense_layers"])
    return tuple(f"{_LAYER_TYPES[t]}:{'dense' if i < dense else 'moe'}"
                 for i, t in enumerate(types))


def tensors(kind: str) -> Sequence[str]:
    mixer, _, ffn = kind.partition(":")
    return NORMS + MIXER[mixer] + FFN[ffn]


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rope(x, theta: float):
    """x [T, H, d] rotated by halves: channel j pairs with j + d / 2, the
    pair's angle ``t theta^(-2 j / d)``."""
    T, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]      # [T, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """q [T, H, d], k/v [T, K, d] (each key-value head repeated H / K times),
    causal, scores over sqrt(d): a whole softmax, in blocks of queries (a
    gradient computes a block's scores again from q, k and v and keeps none:
    32 heads of 8192 x 8192 float32 would be 8.6 GB)."""
    T, H, d = q.shape
    k = jnp.repeat(k, H // k.shape[1], axis=1)
    v = jnp.repeat(v, H // v.shape[1], axis=1)
    kpos = jnp.arange(T)

    @jax.checkpoint
    def block(qb, qpos, k, v):
        s = jnp.einsum("thd,shd->hts", qb, k) / math.sqrt(d)
        s = jnp.where((kpos[None, :] <= qpos[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)

    return jnp.concatenate([
        block(q[lo:lo + _QUERY_BLOCK],
              jnp.arange(lo, min(lo + _QUERY_BLOCK, T)), k, v)
        for lo in range(0, T, _QUERY_BLOCK)], axis=0)


def head_dim(cfg: Dict) -> int:
    return int(cfg.get("head_dim")
               or int(cfg["hidden_size"]) // int(cfg["num_attention_heads"]))


def attention_layer(u, w: Dict, cfg: Dict):
    """The attention mixer on u [T, D] (already normed): the norm of q and of
    k per head, then the rope."""
    H, K = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d, T, eps = head_dim(cfg), u.shape[0], float(cfg["norm_eps"])
    theta = float(cfg["rope_parameters"]["rope_theta"])
    q = rope(rms_norm((u @ w["wq"]).reshape(T, H, d), w["q_norm"], eps), theta)
    k = rope(rms_norm((u @ w["wk"]).reshape(T, K, d), w["k_norm"], eps), theta)
    o = attention(q, k, (u @ w["wv"]).reshape(T, K, d))
    return o.reshape(T, H * d) @ w["wo"]


def conv(v, w):
    """The sum of ``K`` shifted arrays: v [T, C], w [K, C]; ``c[t] = sum_k
    w[k] v[t - (K - 1) + k]``, positions before 0 read as zero."""
    T, K = v.shape[0], w.shape[0]
    c = jnp.zeros_like(v)
    for k in range(K):
        back = K - 1 - k
        c = c + w[k] * jnp.concatenate(
            [jnp.zeros((back,) + v.shape[1:], v.dtype), v[:T - back]], axis=0)
    return c


def short_conv(u, w: Dict, cfg: Dict):
    """The gated short convolution on u [T, D] (already normed)."""
    del cfg
    B, C, z = jnp.split(u @ w["in_proj"], 3, axis=-1)
    return (C * conv(B * z, w["conv_w"])) @ w["out_proj"]


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(x, router, bias, k: int, scale: float):
    """(s [T, E] the sigmoid scores, the k chosen by ``s + bias`` [T, k],
    their weights ``scale s_i / (sum_chosen s + 1e-6)`` [T, k])."""
    s = jax.nn.sigmoid(x @ router)
    _, top_e = jax.lax.top_k(s + bias, k)
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    return s, top_e, scale * top_s / (
        jnp.sum(top_s, axis=-1, keepdims=True) + SUM_EPS)


def held_experts(cfg: Dict) -> Sequence[int]:
    first = int(cfg.get("first_expert", 0))
    return range(first, first + int(cfg["num_experts"]))


def router_width(cfg: Dict) -> int:
    return int(cfg.get("router_width") or cfg["num_experts"])


def experts(x, w: Dict, cfg: Dict, held: Optional[Sequence[int]] = None):
    """The routed FFN on x [T, D] (already normed) for the experts ``held``
    (a list of expert indices, ``w["w1"][j]`` the j-th of them; default the
    configuration's share), one at a time over a mask: ``(the held experts'
    weighted sum, counts [E] the pairs every routed expert received, the
    sequence's balance term sum_i f_i P_i)``."""
    held = list(held_experts(cfg) if held is None else held)
    k, E = int(cfg["num_experts_per_tok"]), router_width(cfg)
    s, top_e, top_w = route(x, w["router"], w["router_bias"], k,
                            float(cfg["routed_scaling_factor"]))
    out = jnp.zeros_like(x)
    for j, e in enumerate(held):
        weight = jnp.sum(jnp.where(top_e == e, top_w, 0.0), axis=-1)
        out = out + weight[:, None] * swiglu(x, w["w1"][j], w["w3"][j],
                                             w["w2"][j])
    counts = jnp.sum(jax.nn.one_hot(top_e, E, dtype=F32), axis=(0, 1))
    f = jax.lax.stop_gradient(counts) * (E / (k * x.shape[0]))
    p = jnp.mean(s / jnp.sum(s, axis=-1, keepdims=True), axis=0)
    return out, counts, jnp.sum(f * p)


def block(x, w: Dict, cfg: Dict, kind: str):
    """One layer on x [T, D] float32: ``(y, the mixer output's mean square,
    counts [E], the balance term)``; a dense layer's counts and term are
    zeros."""
    mixer, _, ffn = kind.partition(":")
    eps = float(cfg["norm_eps"])
    u = rms_norm(x, w["operator_norm"], eps)
    mix = short_conv(u, w, cfg) if mixer == "conv" \
        else attention_layer(u, w, cfg)
    h = x + mix
    g = rms_norm(h, w["ffn_norm"], eps)
    counts, term = jnp.zeros((router_width(cfg),), F32), jnp.zeros((), F32)
    if ffn == "dense":
        out = swiglu(g, w["w1"], w["w3"], w["w2"])
    else:
        out, counts, term = experts(g, w, cfg)
    return h + out, jnp.mean(mix * mix), counts, term


def tied(cfg: Dict) -> bool:
    return bool(cfg.get("tie_word_embeddings", True))


def head_nll(x, norm, head, tokens, eps):
    """``nll`` [T - 1]: the cross-entropy of each position's logits (the
    final norm, then ``head`` [D, V]) against the next token."""
    lg = (rms_norm(x, norm, eps) @ head)[:-1]
    gold = jnp.take_along_axis(lg, tokens[1:, None], axis=-1)[:, 0]
    return jax.scipy.special.logsumexp(lg, axis=-1) - gold


def _f32(t):
    return jnp.asarray(t).astype(F32)


def _head(cfg: Dict, get: Callable, table):
    return table.T if tied(cfg) else _f32(get("lm_head"))


def sequence(cfg: Dict, get: Callable, tokens) -> Dict:
    """One sequence [T] through the model: ``nll`` [T - 1], by layer
    ``mix_out_ms`` [L], and by routed layer ``counts`` [Lr, E] and ``term``
    [Lr]."""
    block_jit = jax.jit(lambda x, w, kind: block(
        x, {n: t.astype(F32) for n, t in w.items()}, cfg, kind),
        static_argnums=2)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        table = _f32(get("embed"))
        x = table[tokens]
        ms, counts, terms = [], [], []
        for i, kind in enumerate(kinds(cfg)):
            x, m, c, t = block_jit(
                x, {n: jnp.asarray(get(n, i)) for n in tensors(kind)}, kind)
            ms.append(m)
            if kind.endswith(":moe"):
                counts.append(c)
                terms.append(t)
        nll = head_nll(x, _f32(get("final_norm")), _head(cfg, get, table),
                       tokens, float(cfg["norm_eps"]))
    return {"nll": nll, "mix_out_ms": jnp.stack(ms),
            "counts": jnp.stack(counts), "term": jnp.stack(terms)}


def _parts(cfg: Dict, ce, ms, counts, terms, alpha: float, rows: int) -> Dict:
    lb = jnp.sum(terms) / rows
    held = jnp.asarray(list(held_experts(cfg)))
    return {"loss": ce + alpha * lb, "ce": ce, "lb_loss": lb,
            "mix_out_ms": ms / rows, "router_counts": counts,
            "expert_pairs": counts[:, held]}


def batch_loss(cfg: Dict, get: Callable, rows, alpha: float) -> Dict:
    """The loss of a micro-batch ``rows`` [B, T] and its parts: ``loss`` =
    ``ce`` + alpha x ``lb_loss``; ``ce`` the mean cross-entropy over the
    B x (T - 1) targets; ``lb_loss`` the balance term, each routed layer's
    the mean over the sequences, summed over the layers; ``mix_out_ms`` [L]
    the mixer output's mean square over all B x T positions;
    ``router_counts`` [Lr, E] the pairs every routed expert received and
    ``expert_pairs`` [Lr, held] those of the experts held here."""
    per_row = [sequence(cfg, get, row) for row in rows]
    return _parts(cfg, jnp.mean(jnp.concatenate([r["nll"] for r in per_row])),
                  sum(r["mix_out_ms"] for r in per_row),
                  sum(r["counts"] for r in per_row),
                  sum(r["term"] for r in per_row), alpha, len(per_row))


def bias_after(bias, router_counts, gamma: float):
    """The selection biases [Lr, E] after a step whose tokens gave the routed
    experts ``router_counts`` [Lr, E] pairs: an expert under its layer's mean
    rises by ``gamma``, one over it falls by ``gamma``."""
    c = jnp.asarray(router_counts, F32)
    return jnp.asarray(bias, F32) + gamma * jnp.sign(
        jnp.mean(c, axis=-1, keepdims=True) - c)


def loss_and_grads(cfg: Dict, weights: Dict, rows, alpha: float):
    """``(loss, d loss / d weights)`` by ``jax.grad``; ``weights`` is a dict
    of float32 arrays keyed ``(name, layer)``, ``(name, None)`` for what no
    layer owns."""
    def loss(w):
        return batch_loss(cfg, dict_getter(w), rows, alpha)["loss"]

    return jax.value_and_grad(loss)(weights)


def dict_getter(weights: Dict) -> Callable:
    def get(name, layer=None):
        return weights[(name, layer)]

    return get


def batch_loss_and_grads(cfg: Dict, get: Callable, rows, alpha: float,
                         sink: Optional[Callable] = None):
    """:func:`batch_loss`'s parts and the gradient of the loss by every
    tensor ``get`` returns but :data:`NO_GRADIENT`'s (float32, taken at the
    tensor upcast to float32), a layer at a time so that it fits beside a
    program's state: the forward keeps each layer's input, the head gives the
    cotangent of the last, and each layer's ``jax.vjp`` in turn, last layer
    first, its weights' gradients and its input's cotangent (a routed
    layer's balance term enters with ``alpha`` over the rows); the tied
    table's gradient is the head's and the lookup's together. The same
    derivative as :func:`loss_and_grads`, which differentiates the whole.

    Returns ``(out, grads)`` with ``grads`` keyed ``(name, layer)``; given a
    ``sink``, each gradient is handed to ``sink(name, layer, grad)`` as soon
    as it is whole (so that the caller may move it off the device) and
    ``grads`` comes back empty."""
    eps, ks = float(cfg["norm_eps"]), kinds(cfg)
    targets = sum(len(row) - 1 for row in rows)
    held: Dict = {}
    if sink is None:
        def sink(name, layer, grad):
            held[(name, layer)] = grad

    forward = jax.jit(lambda x, w, kind: block(x, w, cfg, kind),
                      static_argnums=2)

    def back(x, w, dy, kind):
        def f(x, w):
            y, _, _, term = block(x, w, cfg, kind)
            return y, term
        return jax.vjp(f, x, w)[1]((dy, jnp.asarray(alpha / len(rows), F32)))

    back = jax.jit(back, static_argnums=3)
    head = jax.jit(jax.value_and_grad(
        lambda x, norm, head, tokens:
        jnp.sum(head_nll(x, norm, head, tokens, eps)) / targets,
        argnums=(0, 1, 2)))
    partial: Dict = {}
    ce, ms, counts, terms = 0.0, 0.0, 0.0, 0.0
    with jax.default_matmul_precision("highest"):
        for r, row in enumerate(rows):
            def give(name, layer, grad):
                grad = partial.pop((name, layer), 0.0) + grad
                if r == len(rows) - 1:
                    sink(name, layer, grad)
                else:
                    partial[(name, layer)] = grad

            tokens = jnp.asarray(row, jnp.int32)
            table = _f32(get("embed"))
            xs, row_ms, row_counts, row_terms = [table[tokens]], [], [], []
            for i, kind in enumerate(ks):
                y, m, c, t = forward(
                    xs[-1], {n: _f32(get(n, i)) for n in tensors(kind)}, kind)
                xs.append(y)
                row_ms.append(m)
                if kind.endswith(":moe"):
                    row_counts.append(c)
                    row_terms.append(t)
            part, (dx, d_norm, d_head) = head(
                xs.pop(), _f32(get("final_norm")), _head(cfg, get, table),
                tokens)
            give("final_norm", None, d_norm)
            if not tied(cfg):
                give("lm_head", None, d_head)
            for i in reversed(range(len(ks))):
                dx, dw = back(xs.pop(), {n: _f32(get(n, i))
                                         for n in tensors(ks[i])}, dx, ks[i])
                for n, g in dw.items():
                    if n not in NO_GRADIENT:
                        give(n, i, g)
            d_table = jnp.zeros_like(table).at[tokens].add(dx)
            give("embed", None, d_table + d_head.T if tied(cfg) else d_table)
            ce, ms = ce + part, ms + jnp.stack(row_ms)
            counts = counts + jnp.stack(row_counts)
            terms = terms + jnp.stack(row_terms)
    return _parts(cfg, ce, ms, counts, terms, alpha, len(rows)), held


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
