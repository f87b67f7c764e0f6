"""Plain reference of Qwen3-Next (``model_type: "qwen3_next"``): the forward,
the loss over the vocabulary held and its balance term, each layer's
mixer-output mean square, the (token, expert) pairs each held expert received,
gradients by ``jax.grad`` / ``jax.vjp``, and the AdamW update they give.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernels, no chunks, no cache; one
row at a time, one layer at a time (so that it fits on the chip beside the
program's state); the delta layer is **the recurrence over positions** (a
``lax.scan``, not the chunked form), the attention a whole softmax over a
block of queries after the other, the experts a loop over the held ones. It
imports nothing but JAX. For memory alone (a layer's gradient has to fit in
the 5 GB the program's state leaves on the chip at 16,384 positions), and
with no part in the arithmetic: a mixer's heads are walked a key head (a
key-value head) with the heads it serves after the other, the FFN's tokens a
block after the other, each under ``jax.checkpoint``.

The model, from the published ``config.json`` and ``transformers``'
``modeling_qwen3_next.py`` (whose delta layer is ``fla``'s Gated DeltaNet,
arXiv:2412.06464). ``N`` is the zero-centred RMSNorm ``N(x; w) = x /
sqrt(mean(x^2) + eps) (1 + w)`` (fixed in the modelling code, no key):

* ``x0 = E[ids]``; layer ``i``: ``h = x + Mix(N(x; ln1))``, ``y = h +
  FFN(N(h; ln2))``; ``logits = N(x_L; final_norm) W_head`` (untied). Layer
  ``i`` is full attention where ``(i + 1) % full_attention_interval == 0``,
  a delta layer elsewhere.
* a full layer, on ``u``: a head's ``2 d`` columns of ``u W_q`` are its query
  then its gate; ``k = u W_k``, ``v = u W_v`` (K heads of ``d``), no bias;
  ``q_h <- N(q_h; q_norm)``, ``k_j <- N(k_j; k_norm)`` over a head's ``d``
  channels, one scale each for all heads; the rope (rotate-half, theta
  ``rope_theta``) on the first ``partial_rotary_factor d`` channels of q and
  k; causal ``softmax(q k^T / sqrt(d)) v``, query heads ``(H/K) j ..`` over
  key-value head ``j``; ``o_h <- o_h sigmoid(g_h)`` a channel; ``W_o``.
* a delta layer, on ``u``, with ``Hk`` key heads and ``H`` value heads::

      q~, k~, v~ = silu(conv(u W_q)), silu(conv(u W_k)), silu(conv(u W_v))
      q_t, k_t   = q~_t / sqrt(sum q~_t^2 + 1e-6), k~_t / sqrt(sum k~_t^2 + 1e-6)
                   (a key head's dk channels)
      beta_t     = sigmoid(u_t W_b),  g_t = -exp(A_log) softplus(u_t W_a + dt_bias)
      value head i reads key head i // (H / Hk)
      S_t        = exp(g_t) S_{t-1} + beta_t k_t (v~_t - exp(g_t) S_{t-1}^T k_t)^T
      o_t        = S_t^T q_t / sqrt(dk)
      y_t        = RMSNorm(o_t; o_norm) silu(u_t W_z)   (plain: times o_norm)
      Mix        = concat_heads(y) W_o

  ``S`` [dk, dv] zero at the start; the convolution causal and depthwise over
  the last ``linear_conv_kernel_dim`` positions, no bias, zeros before the
  start.
* the FFN, on ``h'``: ``p = softmax(h' W_r)`` over all ``router_width``
  experts; the ``num_experts_per_tok`` largest, weights ``p_e / sum of the
  kept``; an expert ``(silu(h' W_g) * h' W_u) W_d``; the shared expert the
  same, times ``sigmoid(h' w_sg)``; ``FFN = sum of the kept + shared``.

**The cut** (each a departure from the published model, noted where it
acts): ``vocab_size`` rows of the table and columns of the head are held (ids,
logits and loss over the slice); ``num_hidden_layers`` layers, the first of
the stack; ``num_experts`` experts from ``first_expert`` on are held of the
``router_width`` the router scores: the router, its top k and their weights
are the whole model's, what the absent experts would add is left out, the
shared expert is whole. The balance term is the family's, ``E sum_e mean_t
p[t, e] mean_t [top-1 = e]`` over the micro-batch a layer, summed over layers,
times ``deployment.balance_coef`` in the loss. ``cfg["fault"]`` puts one
wrong reading in (:data:`FAULTS`), for the controls.

Weights are read through ``get(name, layer=None)``, one stored tensor of any
float type (upcast here): ``embed`` [V, D], ``lm_head`` [D, V],
``final_norm`` [D]; per layer ``ln1``, ``ln2`` [D], ``router`` [D, E],
``w_gate``, ``w_up`` [held, D, F], ``w_down`` [held, F, D], ``s_gate``,
``s_up`` [D, Fs], ``s_down`` [Fs, D], ``s_sg`` [D, 1]; of a full layer ``wq``
[D, H 2d], ``wk``, ``wv`` [D, K d], ``q_norm``, ``k_norm`` [d], ``wo``
[H d, D]; of a delta layer ``wq``, ``wk`` [D, Hk dk], ``wv``, ``wz``
[D, H dv], ``wb``, ``wa`` [D, H], ``conv_q``, ``conv_k``, ``conv_v`` [taps,
width] (tap k meets position t - (taps - 1) + k), ``A_log``, ``dt_bias``
[H], ``o_norm`` [dv], ``wo`` [H dv, D].
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
_QUERY_BLOCK = 256
#: tokens the FFN takes together (memory only)
_TOKEN_BLOCK = 2048
#: key heads of a delta layer walked together, with the value heads they
#: serve (memory only: all 16 at once ask 10.6 GB for a layer's gradient at
#: 16,384 positions, four 2.6 GB; one at a time is four times the
#: recurrence's sequential steps)
_KEY_HEADS_AT_ONCE = 4
#: positions whose states a gradient of the recurrence computes again
#: together (memory only; no part of the arithmetic)
_STATE_BLOCK = 64
L2_EPS = 1e-6
COMMON = ("ln1", "ln2", "router", "w_gate", "w_up", "w_down", "s_gate",
          "s_up", "s_down", "s_sg")
TENSORS = {
    "full_attention": COMMON + ("wq", "wk", "wv", "q_norm", "k_norm", "wo"),
    "linear_attention": COMMON + (
        "wq", "wk", "wv", "wz", "wb", "wa", "conv_q", "conv_k", "conv_v",
        "A_log", "dt_bias", "o_norm", "wo")}
#: wrong readings a control can ask for (``cfg["fault"]``)
FAULTS = {
    "no_channel_gate": "the full layer's heads without their sigmoid gate a "
                       "channel",
    "wrong_key_sharing": "value head i reads key head i mod Hk, not i // "
                         "(H / Hk)"}


def kinds(cfg: Dict):
    """The kind of each layer kept: ``layer_types`` where the file has them,
    else from ``full_attention_interval``."""
    n = int(cfg["num_hidden_layers"])
    if cfg.get("layer_types"):
        return list(cfg["layer_types"])[:n]
    every = int(cfg["full_attention_interval"])
    return ["full_attention" if (i + 1) % every == 0 else "linear_attention"
            for i in range(n)]


def norm(x, w, eps):
    """The zero-centred RMSNorm: times ``1 + w``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


def plain_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w


def rope(x, theta: float, width: int):
    """Rotate-half on the first ``width`` channels of x [T, H, d], the rest
    as they are."""
    T = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, width, 2, dtype=F32) / width)
    f = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]        # [T, w/2]
    cos, sin = jnp.cos(f)[:, None, :], jnp.sin(f)[:, None, :]
    x1, x2 = x[..., :width // 2], x[..., width // 2:width]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., width:]], axis=-1)


def attention(q, k, v):
    """q [T, H, d], k/v [T, K, d] (each key-value head repeated H / K times),
    causal, scores over sqrt(d); in blocks of queries (so that 16,384 fit)."""
    T, H, d = q.shape
    k = jnp.repeat(k, H // k.shape[1], axis=1)
    v = jnp.repeat(v, H // v.shape[1], axis=1)
    kpos = jnp.arange(T)

    def rows(qb, lo):
        qpos = lo + jnp.arange(qb.shape[0])
        s = jnp.einsum("thd,shd->hts", qb, k) / math.sqrt(d)
        s = jnp.where((kpos[None, :] <= qpos[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)

    if T % _QUERY_BLOCK:
        return rows(q, 0)
    n = T // _QUERY_BLOCK
    out = jax.lax.map(
        jax.checkpoint(lambda xs: rows(xs[0], xs[1])),
        (q.reshape(n, _QUERY_BLOCK, H, d), jnp.arange(n) * _QUERY_BLOCK))
    return out.reshape(T, H, d)


def attention_layer(u, w: Dict, cfg: Dict):
    """The gated full-attention mixer on u [T, D]."""
    H, K = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d, eps, T = int(cfg["head_dim"]), float(cfg["rms_norm_eps"]), u.shape[0]
    width = 2 * (int(d * float(cfg["partial_rotary_factor"])) // 2)
    theta, r = float(cfg["rope_theta"]), H // K

    def served(ws):
        """A key-value head and the r query heads it serves: [T, r d]."""
        wq, wk, wv = ws
        # a head's columns of W_q: its query, then its gate
        q, gate = jnp.split((u @ wq).reshape(T, r, 2 * d), 2, axis=-1)
        q = rope(norm(q, w["q_norm"], eps), theta, width)
        k = rope(norm((u @ wk).reshape(T, 1, d), w["k_norm"], eps), theta,
                 width)
        o = attention(q, k, (u @ wv).reshape(T, 1, d))
        if cfg.get("fault") != "no_channel_gate":
            o = o * jax.nn.sigmoid(gate)
        return o.reshape(T, r * d)

    D = u.shape[1]
    o = jax.lax.map(jax.checkpoint(served), (
        w["wq"].reshape(D, K, r * 2 * d).transpose(1, 0, 2),
        w["wk"].reshape(D, K, d).transpose(1, 0, 2),
        w["wv"].reshape(D, K, d).transpose(1, 0, 2)))           # [K, T, r d]
    return o.transpose(1, 0, 2).reshape(T, H * d) @ w["wo"]


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
    keeps one state a run and computes the run's again; the forward is the
    same steps in the same order.)"""
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
    Hk, H = int(cfg["linear_num_key_heads"]), \
        int(cfg["linear_num_value_heads"])
    dk, dv = int(cfg["linear_key_head_dim"]), int(cfg["linear_value_head_dim"])
    (T, D), r, eps = u.shape, H // Hk, float(cfg["rms_norm_eps"])
    n = math.gcd(Hk, _KEY_HEADS_AT_ONCE)    # key heads walked together
    # value head i reads key head i // (H / Hk): row j names the value heads
    # key head j serves
    serves = jnp.arange(H).reshape(Hk, r)
    if cfg.get("fault") == "wrong_key_sharing":
        serves = jnp.arange(H).reshape(r, Hk).T             # i mod Hk
    serves = serves.reshape(Hk // n, n * r)     # by run of n key heads

    def served(ws):
        """``n`` key heads and the ``n r`` value heads they serve: y
        [T, n r, dv]."""
        wq, wk, cq, ck, wv, wz, cv, wb, wa, a_log, dt_bias = ws
        q = l2_norm(jax.nn.silu(conv(u @ wq, cq)).reshape(T, n, dk))
        k = l2_norm(jax.nn.silu(conv(u @ wk, ck)).reshape(T, n, dk))
        v = jnp.stack([jax.nn.silu(conv(u @ wv[i], cv[i]))
                       for i in range(n * r)], axis=1)      # [T, n r, dv]
        beta = jax.nn.sigmoid(u @ wb)                       # [T, n r]
        g = -jnp.exp(a_log) * jax.nn.softplus(u @ wa + dt_bias)
        o = recurrence(jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1),
                       v, g, beta)
        z = jnp.stack([u @ wz[i] for i in range(n * r)], axis=1)
        return plain_norm(o, w["o_norm"], eps) * jax.nn.silu(z)

    def heads(t, count, width):         # [.., count width] -> [count, .., width]
        return jnp.moveaxis(t.reshape(t.shape[:-1] + (count, width)), -2, 0)

    y = jax.lax.map(jax.checkpoint(served), (
        heads(w["wq"], Hk // n, n * dk), heads(w["wk"], Hk // n, n * dk),
        heads(w["conv_q"], Hk // n, n * dk),
        heads(w["conv_k"], Hk // n, n * dk),
        heads(w["wv"], H, dv)[serves], heads(w["wz"], H, dv)[serves],
        heads(w["conv_v"], H, dv)[serves], w["wb"].T[serves].transpose(
            0, 2, 1), w["wa"].T[serves].transpose(0, 2, 1),
        w["A_log"][serves], w["dt_bias"][serves]))      # [Hk / n, T, n r, dv]
    # back to the heads' own order, for W_o's rows
    y = y.transpose(1, 0, 2, 3).reshape(T, H, dv)[
        :, jnp.argsort(serves.reshape(-1))]
    return y.reshape(T, H * dv) @ w["wo"]


def route(x, router, k: int):
    """(p [T, E] the softmax over all routed experts, the k chosen [T, k],
    their weights renormalised to sum 1 [T, k])."""
    p = jax.nn.softmax(x @ router, axis=-1)
    top_p, top_e = jax.lax.top_k(p, k)
    return p, top_e, top_p / jnp.sum(top_p, axis=-1, keepdims=True)


def ffn(x, w: Dict, cfg: Dict):
    """The held experts' part of the layer and the whole gated shared expert
    on x [T, D]: ``(their sum, sum_t p [E], sum_t [top-1 = e] [E], the pairs
    each held expert received [held])``. (The cut: the experts not held add
    nothing.)"""
    T = x.shape[0]
    if T > _TOKEN_BLOCK and T % _TOKEN_BLOCK == 0:
        out, *sums = jax.lax.map(
            jax.checkpoint(lambda xb: ffn(xb, w, cfg)),
            x.reshape(T // _TOKEN_BLOCK, _TOKEN_BLOCK, -1))
        return (out.reshape(x.shape),) + tuple(s.sum(axis=0) for s in sums)
    held = int(cfg["num_experts"])
    first = int(cfg.get("first_expert", 0))
    E = int(cfg.get("router_width") or held)
    p, top_e, top_w = route(x, w["router"], int(cfg["num_experts_per_tok"]))
    out = (jax.nn.silu(x @ w["s_gate"]) * (x @ w["s_up"])) @ w["s_down"] \
        * jax.nn.sigmoid(x @ w["s_sg"])
    pairs = []
    for i in range(held):
        chosen = top_e == first + i                             # [T, k]
        weight = jnp.sum(jnp.where(chosen, top_w, 0.0), axis=-1)
        y = (jax.nn.silu(x @ w["w_gate"][i]) * (x @ w["w_up"][i])) \
            @ w["w_down"][i]
        out = out + weight[:, None] * y
        pairs.append(jnp.sum(chosen))
    top1 = jax.nn.one_hot(jnp.argmax(p, axis=-1), E, dtype=F32)
    return out, p.sum(axis=0), jax.lax.stop_gradient(top1.sum(axis=0)), \
        jnp.stack(pairs)


def block(x, w: Dict, cfg: Dict, kind: str):
    """One layer on x [T, D] float32: ``(y, sum_t p [E], sum_t top-1 [E],
    pairs [held], the mixer output's mean square)``."""
    eps = float(cfg["rms_norm_eps"])
    mix = (delta_layer if kind == "linear_attention" else attention_layer)(
        norm(x, w["ln1"], eps), w, cfg)
    h = x + mix
    y, gate_sum, top1_sum, pairs = ffn(norm(h, w["ln2"], eps), w, cfg)
    return h + y, gate_sum, top1_sum, pairs, jnp.mean(mix * mix)


def _f32(t):
    return jnp.asarray(t).astype(F32)


def head_nll(x, scale, head, tokens, eps):
    """The sum over a row's T - 1 targets of the cross-entropy of each
    position's logits (the final norm, the untied head) against the next
    token."""
    lg = (norm(x, scale, eps) @ head)[:-1]
    gold = jnp.take_along_axis(lg, tokens[1:, None], axis=-1)[:, 0]
    return jnp.sum(jax.scipy.special.logsumexp(lg, axis=-1) - gold)


def _alpha(cfg: Dict) -> float:
    return float(cfg["deployment"]["balance_coef"])


def _forward(cfg: Dict):
    """One layer's jitted forward (every output of :func:`block`)."""
    return jax.jit(lambda x, w, kind: block(x, w, cfg, kind),
                   static_argnums=2)


def batch_loss(cfg: Dict, get: Callable, rows, fwd=None) -> Dict:
    """The loss of the micro-batch ``rows`` [B, T] and its parts: ``loss = ce
    + balance_coef lb_loss``; ``ce`` the mean cross-entropy over the B (T -
    1) targets; ``lb_loss`` the balance term summed over the layers, each
    layer's over all B T positions; ``expert_pairs`` [layers, held];
    ``mix_out_ms`` [layers]."""
    eps, ks = float(cfg["rms_norm_eps"]), kinds(cfg)
    n = sum(len(r) for r in rows)
    fwd = fwd or _forward(cfg)
    gate = top1 = pairs = ms = nll = 0.0
    with jax.default_matmul_precision("highest"):
        for row in rows:
            tokens = jnp.asarray(row, jnp.int32)
            x = _f32(get("embed"))[tokens]
            st = []
            for i, kind in enumerate(ks):
                x, *rest = fwd(x, {t: _f32(get(t, i))
                                   for t in TENSORS[kind]}, kind)
                st.append(rest)
            gate = gate + jnp.stack([s[0] for s in st])
            top1 = top1 + jnp.stack([s[1] for s in st])
            pairs = pairs + jnp.stack([s[2] for s in st])
            ms = ms + jnp.stack([s[3] for s in st]) / len(rows)
            nll = nll + head_nll(x, _f32(get("final_norm")),
                                 _f32(get("lm_head")), tokens, eps)
    lb = jnp.sum(gate * top1, axis=-1) / (n * n) * gate.shape[-1]  # [layers]
    ce = nll / (n - len(rows))
    return {"loss": ce + _alpha(cfg) * jnp.sum(lb), "ce": ce,
            "lb_loss": jnp.sum(lb), "expert_pairs": pairs, "mix_out_ms": ms,
            "top1": top1}


def loss_and_grads(cfg: Dict, weights: Dict, rows):
    """``(loss, d loss / d weights)`` by ``jax.grad`` of the whole;
    ``weights`` a dict of float32 arrays keyed ``(name, layer)``, ``(name,
    None)`` for what no layer owns. For small sizes."""
    def loss(w):
        return batch_loss(cfg, lambda name, layer=None: w[(name, layer)],
                          rows)["loss"]

    return jax.value_and_grad(loss)(weights)


def batch_loss_and_grads(cfg: Dict, get: Callable, rows,
                         sink: Optional[Callable] = None):
    """:func:`batch_loss`'s parts and the gradient of the loss by every
    tensor ``get`` returns (float32, taken at the tensor upcast to float32),
    a layer at a time so that it fits beside a program's state: a first
    forward over the rows for the balance term's counts, then for each row
    the forward that keeps each layer's input, the head's cotangent of the
    last, and each layer's ``jax.vjp`` in turn, last layer first. The same
    derivative as :func:`loss_and_grads`.

    Returns ``(out, grads)`` with ``grads`` keyed ``(name, layer)``; given a
    ``sink``, each gradient is handed to ``sink(name, layer, grad)`` as soon
    as it is whole and ``grads`` comes back empty."""
    forward = _forward(cfg)         # one program a kind for both passes
    out = batch_loss(cfg, get, rows, forward)
    eps, ks, alpha = float(cfg["rms_norm_eps"]), kinds(cfg), _alpha(cfg)
    n = sum(len(r) for r in rows)
    top1 = out.pop("top1")                                      # [layers, E]
    held: Dict = {}
    if sink is None:
        def sink(name, layer, grad):
            held[(name, layer)] = grad

    def back(x, w, dy, d_gate, kind):
        def f(x, w):
            y, gate_sum, *_ = block(x, w, cfg, kind)
            return y, gate_sum
        return jax.vjp(f, x, w)[1]((dy, d_gate))

    back = jax.jit(back, static_argnums=4)
    head = jax.jit(jax.value_and_grad(
        lambda x, scale, head, tokens:
        head_nll(x, scale, head, tokens, eps) / (n - len(rows)),
        argnums=(0, 1, 2)))
    partial: Dict = {}
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
            xs = [table[tokens]]
            for i, kind in enumerate(ks):
                xs.append(forward(xs[-1], {t: _f32(get(t, i))
                                           for t in TENSORS[kind]}, kind)[0])
            _, (dx, d_norm, d_head) = head(
                xs.pop(), _f32(get("final_norm")), _f32(get("lm_head")),
                tokens)
            give("final_norm", None, d_norm)
            give("lm_head", None, d_head)
            for i in reversed(range(len(ks))):
                d_gate = alpha * top1[i] * top1.shape[-1] / (n * n)
                dx, dw = back(xs.pop(), {t: _f32(get(t, i))
                                         for t in TENSORS[ks[i]]}, dx, d_gate,
                              ks[i])
                for name, g in dw.items():
                    give(name, i, g)
            give("embed", None, jnp.zeros_like(table).at[tokens].add(dx))
    return out, held


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
