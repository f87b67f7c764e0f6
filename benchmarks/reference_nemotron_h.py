"""Plain reference of Nemotron-H with LatentMoE (``model_type:
"nemotron_h"``; NVIDIA-Nemotron-3-Super-120B-A12B): forward, the loss over
the vocabulary held with its balance term, each layer's branch-output mean
square, the router's counts, the selection bias after a step, gradients by
``jax.grad`` / ``jax.vjp``, and the AdamW update they give.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernels, no chunks, no cache, no
batching, no dispatch; one sequence at a time, one layer at a time (so that
it fits on the chip beside the program's state). Nothing is recomputed but,
for a gradient, what memory forces: a layer's forward from its input, and the
recurrence's states 64 positions at a time from the state before them (the
same arithmetic, run again). It imports nothing but JAX.

The model, from the published ``config.json``:

* ``x0 = E[ids]`` (no multiplier); layer i of kind k_i, the i-th letter of
  ``hybrid_override_pattern``: ``x <- x + f_i(RMSNorm_i(x))``, **one branch a
  layer**, eps ``layer_norm_epsilon``; ``logits = RMSNorm(x_L) W_head`` (an
  untied head); no projection bias anywhere.
* ``M``, a Mamba-2 mixer, for the normed input ``u`` [T, D]: ``[z, xBC, dt] =
  u W_in`` (inner, inner + 2 G N, H wide; inner = ``mamba_num_heads`` x
  ``mamba_head_dim``, G = ``n_groups``, N = ``ssm_state_size``); ``xBC =
  silu(conv(xBC))``, a depthwise causal convolution over the last
  ``conv_kernel`` positions with bias, zeros before the start; ``[x, B, C] =
  xBC``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head **the
  recurrence itself**, a ``lax.scan`` over the positions with the state ``h``
  [P, N], zero at the start: ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``,
  ``y_t = h_t C_t + D x_t`` (B and C of a head's group); then the gated norm
  **by group**: ``y = RMSNorm_g(y * silu(z)) * w`` over each group's inner /
  G channels on their own (the gate before the norm); ``y W_out``.
  Independent of the program's chunked form by construction.
* ``*``, attention: q, k, v without bias and **without rope**, each key-value
  head serving H / K query heads, heads of ``head_dim``, causal ``softmax(q
  k^T / sqrt(head_dim)) v``, then ``W_o``.
* ``E``, LatentMoE, for the normed input ``x`` [T, D]: ``s = sigmoid(x W_r)``
  over the ``router_width`` routed experts; the ``num_experts_per_tok`` with
  the largest ``s + b`` (``b`` the selection bias, which only picks); weights
  ``s`` of the chosen (without ``b``) over their sum (``norm_topk_prob``)
  times ``routed_scaling_factor``; ``z = x W_down`` (a plain linear map into
  the latent of ``moe_latent_size``); expert e: ``W2_e relu(W1_e z)^2`` (two
  products, no gate); ``routed = (sum_e w_e expert_e(z)) W_up``; ``shared =
  S2 relu(S1 x)^2`` on the full-width ``x``; ``f = routed + shared``. The
  router reads the full-width ``x``.
* ``-``, a dense ``relu^2`` MLP, does not occur in the pattern and is not
  written down.

**The cut.** ``vocab_size`` rows of the table and columns of the head are held
(ids, logits and loss over the slice), ``num_hidden_layers`` layers (the first
letters of ``hybrid_override_pattern``), ``mamba_num_heads`` heads in
``n_groups`` groups of each Mamba mixer, ``num_attention_heads`` /
``num_key_value_heads`` heads of attention, and ``n_routed_experts`` experts
from ``first_expert`` on of the ``router_width`` the router scores: the
projections have the held heads' columns, ``W_out`` / ``W_o`` their rows, and
a layer's output is the partial sum the held heads or experts give (projected
up as it is; the shared expert whole). A Mamba head reads its own channels
and its group's B and C and the norm is by group, attention heads are
independent: the shares of a mixer's heads, cut between groups, add up to the
whole layer exactly; the expert shares' routed parts and the shared expert
counted once add up to the whole expert layer. With every head, expert, row
and layer there is no departure from the reading above but this one:

**Multi-token prediction is left out** (``num_nextn_predict_layers`` 1,
``mtp_hybrid_override_pattern`` "*E"): an auxiliary training loss beside the
forward pass above, whose joining of shifted embedding and hidden state and
whose loss factor the published file does not give.

What the published file does not say, and this reading assumes (the program
follows the same reading; the configuration file lists them under
``assumed``): no rope in the attention layers (``rope_theta`` and
``partial_rotary_factor`` are not read); the latent maps are plain linear;
the router and the shared expert read the full width; ``time_step_limit`` (0,
inf), so dt is not clamped; ``n_group`` = ``topk_group`` = 1, no group limit
on the choice; the convolution is a cross-correlation whose last tap meets
the current position; ``expand`` is not read (inner = heads x head width); the
balance term and the bias rule are DeepSeek-V3's (below), which the file
names no more than it names any training recipe; the mean square reported
for a layer is of its one branch's output, an expert layer's too.

Training's parts: the sequence-wise balance term ``sum_i f_i P_i`` a sequence
and expert layer, ``f_i = E / (k T) x`` the pairs expert i received from the
sequence (the chosen pairs, bias included: a constant), ``P_i`` the
sequence's mean of ``s_i / sum_j s_j``, averaged over the sequences, summed
over the layers, added to the loss times ``alpha``; after a step a selection
bias rises by ``gamma`` where its expert's count is under its layer's mean
and falls by it where over (:func:`bias_after`).

Weights are read through ``get(name, layer=None)``, which returns one stored
tensor of any float type (upcast here): ``embed`` [V, D], ``lm_head`` [D, V],
``final_norm`` [D]; per layer ``norm`` [D]; of an ``M`` layer ``in_proj`` [D, 2
inner + 2 G N + H], ``conv_w`` [K, inner + 2 G N] (tap k meets position t -
(K - 1) + k), ``conv_b``, ``dt_bias``, ``A_log``, ``D`` [H], ``gate_norm``
[inner], ``out_proj`` [inner, D]; of a ``*`` layer ``wq`` [D, H d], ``wk``,
``wv`` [D, K d], ``wo`` [H d, D]; of an ``E`` layer ``router`` [D, E],
``router_bias`` [E], ``latent_down`` [D, Z], ``latent_up`` [Z, D], ``w1``
[held, Z, F], ``w2`` [held, F, Z], ``shared_w1`` [D, Fs], ``shared_w2`` [Fs,
D].
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32
_QUERY_BLOCK = 512
#: positions whose states a gradient of the recurrence computes again
#: together (memory only; no part of the arithmetic)
_STATE_BLOCK = 64
TENSORS = {
    "M": ("norm", "in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
          "gate_norm", "out_proj"),
    "*": ("norm", "wq", "wk", "wv", "wo"),
    "E": ("norm", "router", "router_bias", "latent_down", "latent_up", "w1",
          "w2", "shared_w1", "shared_w2")}
#: what picks and gets no gradient
NO_GRADIENT = ("router_bias",)


def kinds(cfg: Dict) -> str:
    """The letters of ``hybrid_override_pattern`` of the layers kept."""
    pattern = str(cfg["hybrid_override_pattern"])[:int(cfg["num_hidden_layers"])]
    if set(pattern) - set(TENSORS):
        raise ValueError(f"layers {pattern!r}: only 'M', '*' and 'E' are "
                         f"written down here")
    return pattern


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


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


def attention_layer(u, w: Dict, cfg: Dict):
    """The attention mixer on u [T, D] (already normed): no rope."""
    H, K = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d, T = int(cfg["head_dim"]), u.shape[0]
    o = attention((u @ w["wq"]).reshape(T, H, d),
                  (u @ w["wk"]).reshape(T, K, d),
                  (u @ w["wv"]).reshape(T, K, d))
    return o.reshape(T, H * d) @ w["wo"]


def conv(x, w, b):
    """The direct sum: x [T, C], w [K, C], b [C]; ``y[t] = b + sum_k w[k]
    x[t - (K - 1) + k]``, positions before 0 read as zero."""
    T, K = x.shape[0], w.shape[0]
    idx = jnp.arange(T)[:, None] - (K - 1) + jnp.arange(K)[None, :]  # [T, K]
    taps = jnp.where((idx >= 0)[..., None], x[jnp.maximum(idx, 0)], 0.0)
    return jnp.einsum("tkc,kc->tc", taps, w) + b


def recurrence(x, dt, A, B, C, D):
    """x [T, H, P], dt [T, H], A [H], B and C [T, G, N], D [H] -> y
    [T, H, P]: position by position over the state h [H, P, N]. (Where T
    allows, the positions are walked in runs of ``_STATE_BLOCK`` under
    ``jax.checkpoint``: a gradient then keeps one state a run and computes
    the run's again, and not 8192 states of [H, P, N]; the forward is the
    same steps in the same order.)"""
    T, H, P = x.shape
    rep = H // B.shape[1]

    def step(h, xs):
        x_t, dt_t, B_t, C_t = xs
        B_t, C_t = jnp.repeat(B_t, rep, axis=0), jnp.repeat(C_t, rep, axis=0)
        h = jnp.exp(dt_t * A)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        return h, jnp.sum(h * C_t[:, None, :], axis=-1) + D[:, None] * x_t

    start, xs = jnp.zeros((H, P, B.shape[2]), F32), (x, dt, B, C)
    if T % _STATE_BLOCK:
        return jax.lax.scan(step, start, xs)[1]
    run = jax.checkpoint(lambda h, xs: jax.lax.scan(step, h, xs))
    _, y = jax.lax.scan(run, start, jax.tree_util.tree_map(
        lambda a: a.reshape(T // _STATE_BLOCK, _STATE_BLOCK, *a.shape[1:]),
        xs))
    return y.reshape(T, H, P)


def mamba(u, w: Dict, cfg: Dict, norm_groups: Optional[int] = None):
    """The Mamba-2 mixer on u [T, D] (already normed); the gated norm over
    each of ``norm_groups`` groups of channels on its own (default:
    ``n_groups``, the published model's)."""
    H, P = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    G, N = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    inner, T = H * P, u.shape[0]
    ng = norm_groups or G
    z, xbc, dt = jnp.split(u @ w["in_proj"], [inner, 2 * inner + 2 * G * N],
                           axis=-1)
    xbc = jax.nn.silu(conv(xbc, w["conv_w"], w["conv_b"]))
    x, B, C = jnp.split(xbc, [inner, inner + G * N], axis=-1)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y = recurrence(x.reshape(T, H, P), dt, -jnp.exp(w["A_log"]),
                   B.reshape(T, G, N), C.reshape(T, G, N), w["D"])
    gated = (y.reshape(T, inner) * jax.nn.silu(z)).reshape(T, ng, inner // ng)
    y = rms_norm(gated, w["gate_norm"].reshape(ng, inner // ng),
                 float(cfg["layer_norm_epsilon"]))
    return y.reshape(T, inner) @ w["out_proj"]


def route(x, router, bias, k: int, scale: float):
    """(s [T, E] the sigmoid scores, the k chosen by ``s + bias`` [T, k],
    their weights ``scale s_i / sum_chosen s`` [T, k])."""
    s = jax.nn.sigmoid(x @ router)
    _, top_e = jax.lax.top_k(s + bias, k)
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    return s, top_e, scale * top_s / jnp.sum(top_s, axis=-1, keepdims=True)


def held_experts(cfg: Dict) -> Sequence[int]:
    first = int(cfg.get("first_expert", 0))
    return range(first, first + int(cfg["n_routed_experts"]))


def experts(x, w: Dict, cfg: Dict, held: Optional[Sequence[int]] = None,
            shared: bool = True):
    """The expert layer on x [T, D] (already normed) for the experts
    ``held`` (a list of expert indices, ``w["w1"][j]`` the j-th of them;
    default the configuration's share): ``(the held experts' weighted sum in
    the latent projected up + the shared expert, counts [E] the pairs every
    routed expert received, the sequence's balance term sum_i f_i P_i)``."""
    held = list(held_experts(cfg) if held is None else held)
    k = int(cfg["num_experts_per_tok"])
    E = int(cfg.get("router_width") or cfg["n_routed_experts"])
    s, top_e, top_w = route(x, w["router"], w["router_bias"], k,
                            float(cfg["routed_scaling_factor"]))
    z = x @ w["latent_down"]
    mixed = jnp.zeros_like(z)
    for j, e in enumerate(held):
        weight = jnp.sum(jnp.where(top_e == e, top_w, 0.0), axis=-1)
        mixed = mixed + weight[:, None] * (relu2(z @ w["w1"][j]) @ w["w2"][j])
    out = mixed @ w["latent_up"]
    if shared:
        out = out + relu2(x @ w["shared_w1"]) @ w["shared_w2"]
    counts = jnp.sum(jax.nn.one_hot(top_e, E, dtype=F32), axis=(0, 1))
    f = jax.lax.stop_gradient(counts) * (E / (k * x.shape[0]))
    p = jnp.mean(s / jnp.sum(s, axis=-1, keepdims=True), axis=0)
    return out, counts, jnp.sum(f * p)


def block(x, w: Dict, cfg: Dict, kind: str):
    """One layer on x [T, D] float32: ``(y, the branch output's mean square,
    counts [E], the balance term)``; a mixer layer's counts and term are
    zeros."""
    u = rms_norm(x, w["norm"], float(cfg["layer_norm_epsilon"]))
    E = int(cfg.get("router_width") or cfg["n_routed_experts"])
    counts, term = jnp.zeros((E,), F32), jnp.zeros((), F32)
    if kind == "M":
        out = mamba(u, w, cfg)
    elif kind == "*":
        out = attention_layer(u, w, cfg)
    else:
        out, counts, term = experts(u, w, cfg)
    return x + out, jnp.mean(out * out), counts, term


def head_nll(x, norm, head, tokens, eps):
    """``nll`` [T - 1]: the cross-entropy of each position's logits (the
    final norm, the untied head) against the next token."""
    lg = (rms_norm(x, norm, eps) @ head)[:-1]
    gold = jnp.take_along_axis(lg, tokens[1:, None], axis=-1)[:, 0]
    return jax.scipy.special.logsumexp(lg, axis=-1) - gold


def _f32(t):
    return jnp.asarray(t).astype(F32)


def sequence(cfg: Dict, get: Callable, tokens) -> Dict:
    """One sequence [T] through the model: ``nll`` [T - 1], by layer
    ``mix_out_ms`` [L], and by expert layer ``counts`` [Le, E] and ``term``
    [Le]."""
    block_jit = jax.jit(lambda x, w, kind: block(
        x, {n: t.astype(F32) for n, t in w.items()}, cfg, kind),
        static_argnums=2)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = _f32(jnp.asarray(get("embed"))[tokens])
        ms, counts, terms = [], [], []
        for i, kind in enumerate(kinds(cfg)):
            x, m, c, t = block_jit(
                x, {n: jnp.asarray(get(n, i)) for n in TENSORS[kind]}, kind)
            ms.append(m)
            if kind == "E":
                counts.append(c)
                terms.append(t)
        nll = head_nll(x, _f32(get("final_norm")), _f32(get("lm_head")),
                       tokens, float(cfg["layer_norm_epsilon"]))
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
    B x (T - 1) targets; ``lb_loss`` the balance term, each expert layer's
    the mean over the sequences, summed over the layers; ``mix_out_ms`` [L]
    the branch output's mean square over all B x T positions;
    ``router_counts`` [Le, E] the pairs every routed expert received and
    ``expert_pairs`` [Le, held] those of the experts held here."""
    per_row = [sequence(cfg, get, row) for row in rows]
    return _parts(cfg, jnp.mean(jnp.concatenate([r["nll"] for r in per_row])),
                  sum(r["mix_out_ms"] for r in per_row),
                  sum(r["counts"] for r in per_row),
                  sum(r["term"] for r in per_row), alpha, len(per_row))


def bias_after(bias, router_counts, gamma: float):
    """The selection biases [Le, E] after a step whose tokens gave the routed
    experts ``router_counts`` [Le, E] pairs: an expert under its layer's mean
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
    first, its weights' gradients and its input's cotangent (an expert
    layer's balance term enters with ``alpha`` over the rows). The same
    derivative as :func:`loss_and_grads`, which differentiates the whole.

    Returns ``(out, grads)`` with ``grads`` keyed ``(name, layer)``; given a
    ``sink``, each gradient is handed to ``sink(name, layer, grad)`` as soon
    as it is whole (so that the caller may move it off the device) and
    ``grads`` comes back empty."""
    eps, ks = float(cfg["layer_norm_epsilon"]), kinds(cfg)
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
                    xs[-1], {n: _f32(get(n, i)) for n in TENSORS[kind]}, kind)
                xs.append(y)
                row_ms.append(m)
                if kind == "E":
                    row_counts.append(c)
                    row_terms.append(t)
            part, (dx, d_norm, d_head) = head(
                xs.pop(), _f32(get("final_norm")), _f32(get("lm_head")),
                tokens)
            give("final_norm", None, d_norm)
            give("lm_head", None, d_head)
            for i in reversed(range(len(ks))):
                dx, dw = back(xs.pop(), {n: _f32(get(n, i))
                                         for n in TENSORS[ks[i]]}, dx, ks[i])
                for n, g in dw.items():
                    if n not in NO_GRADIENT:
                        give(n, i, g)
            give("embed", None, jnp.zeros_like(table).at[tokens].add(dx))
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
