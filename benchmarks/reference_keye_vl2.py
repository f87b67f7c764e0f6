"""Plain reference of Keye-VL-2.0-30B-A3B's language model (``model_type:
"KeyeVL2"``): forward, the loss with its balance term and the indexer's own
loss, the router's counts, each query's selected set, and gradients.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernels, no cache, no batching,
no tiles but blocks of queries (``lax.map`` under ``jax.checkpoint``: one
block's scores live at a time, so that a row of 16,384 fits), ``lax.top_k``
for each query's set, every held expert applied to every token. It imports
nothing but JAX. Written from the equations below, not from the program.

The model, from the published ``config.json`` (hidden 2,048, 48 layers of one
kind, 32 query and 4 key-value heads of 128, 128 experts of width 768, 8 a
token, ``norm_topk_prob``, RMSNorm eps 1e-6, ``rope_theta`` 1e7,
``rope_scaling.mrope_section`` [16, 24, 24], ``sa_config``: a 16-head indexer
of 64 channels over one key head, ``topk`` 2,048; untied head). For a layer
with input ``x_t``, ``u_t = RMSNorm(x_t)``::

    q[t,h] = R_t(norm_q((u_t W_q)_h))    h = 1..32      k[t,g] = R_t(norm_k(
    (u_t W_k)_g)),  v[t,g] = (u_t W_v)_g    g = 1..4, head g serves query
    heads 8g-7..8g; norm_q, norm_k an RMSNorm over a head's 128 channels, one
    scale shared by the heads; no biases.

    R_t: each token carries three positions (p0, p1, p2) (time, height,
    width). Frequency pair i of 64 turns by p^{a(i)} theta^{-i/64}, a(i) = 0
    for i < 16, 1 for 16 <= i < 40, 2 for i >= 40; half-split pairing (pair
    i is channels i and i + 64). A text token has the three equal.

    the indexer, on ut = stop_gradient(u):
    qI[t,j] = R'_t((ut_t WI_q)_j) in R^64, j = 1..16
    kI[s]   = R'_s(LayerNorm(ut_s WI_k)) in R^64        (eps 1e-6)
    w[t]    = ut_t WI_w / sqrt(16) / sqrt(64)  in R^16
    I[t,s]  = sum_j w[t,j] relu(qI[t,j] . kI[s])
    R' the same rope over the 32 pairs of 64 channels, sections [8, 12, 12].

    S_t = the topk keys s <= t of largest I[t,s] (all where t < topk), a tie
    at the threshold to the lower position.

    a[t,h,s] = softmax_{s in S_t}(q[t,h] . k[s,g(h)] / sqrt(128))
    o[t,h]   = sum_{s in S_t} a[t,h,s] v[s,g(h)],   then W_o
    (no gradient through S_t)

    the indexer's loss: p[t,s] = stop_gradient(mean_h a[t,h,s]),
    L_I = (1 / tokens) sum_layers sum_t sum_{s in S_t} p[t,s] (log p[t,s]
          - log softmax_{S_t}(I[t,.])[s])

    the experts: p = softmax(u' W_r) over all 128, the 8 largest kept and
    renormalised to sum 1, MoE(u') = sum_e w_e W_down,e (silu(W_gate,e u') *
    W_up,e u'), no shared expert; a = x + Attn, y = a + MoE(RMSNorm(a)).

The step's loss is ``L_LM + balance_coef x L_balance + indexer_loss_coef x
L_I``: ``L_LM`` the mean next-token cross-entropy, ``L_balance = E sum_e
mean_t p[t,e] mean_t [argmax_e' p[t,e'] = e]`` over the micro-batch's tokens,
summed over layers (the counts are constants to the gradient). ``W^I_q``,
``W^I_k``, ``W^I_w`` and the LayerNorm get gradient from ``L_I`` alone, every
other tensor none from it.

**The share.** The configuration may hold a share of the experts
(``num_experts`` of them from ``first_expert`` on, of ``router_width``
routed) and a slice of the vocabulary (``vocab_size`` rows): the router, the
top k and their weights are the whole model's, the sum runs over the held
experts only and what the absent ones would add is left out; logits and loss
are over the slice. The program makes the same departure; with every expert
held there is none.

What the published file does not say is listed in the configuration file
under ``assumed``. :data:`FAULTS` are readings this file can take instead, one
at a time (``cfg["fault"]``): what a check has to tell from the model.

Weights are read through ``get(name, layer=None)``: ``embed`` [V, D],
``final_norm`` [D], ``lm_head`` [D, V], and per layer ``ln1``, ``ln2`` [D],
``wq`` [D, H d], ``wk``, ``wv`` [D, K d], ``wo`` [H d, D], ``q_norm``,
``k_norm`` [d], ``idx_wq`` [D, J c], ``idx_wk`` [D, c], ``idx_ww`` [D, J],
``idx_k_norm``, ``idx_k_bias`` [c], ``router`` [D, E], ``w_gate``, ``w_up``
[held, D, F], ``w_down`` [held, F, D].
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32
_QUERY_BLOCK = 256
LAYER_TENSORS = ("ln1", "ln2", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
                 "idx_wq", "idx_wk", "idx_ww", "idx_k_norm", "idx_k_bias",
                 "router", "w_gate", "w_up", "w_down")
INDEXER_TENSORS = ("idx_wq", "idx_wk", "idx_ww", "idx_k_norm", "idx_k_bias")
#: readings that are not the model: each leaves the check not correct
FAULTS = {
    "window": "each query's set the most recent topk keys, not the "
              "indexer's",
    "rope_one_axis": "every frequency pair turning by the first position "
                     "axis (time), in the heads and in the indexer",
    "no_indexer_loss": "L_I left out of the step's loss"}


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def layer_norm(x, scale, bias, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def rope(x, positions, theta: float, sections: Sequence[int]):
    """x [T, heads, d], positions [3, T]: pair i (channels i, i + d/2) turns
    by ``positions[a(i)] theta^(-2i/d)``, ``a`` from consecutive
    ``sections`` of the d/2 pairs."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    axis = jnp.asarray([a for a, n in enumerate(sections)
                        for _ in range(int(n))])
    pos = positions.astype(F32)[axis, :].T                     # [T, d/2]
    ang = pos * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def probe_positions(T: int, n: int = 8):
    """The queries whose sets a check compares: the last of each of ``n``
    equal stretches of the row."""
    return [(j + 1) * T // n - 1 for j in range(n)]


def selected_attention(q, k, v, qi, ki, wi, topk: int, window: bool = False):
    """``(o [T, H, d], sum_t KL_t, the set of every query as a mask [T, T])``
    for q [T, H, d], k, v [T, K, d], the indexer's qi [T, J, c], ki [T, c],
    wi [T, J]; with ``window`` the set is the most recent ``topk`` keys."""
    T, H, d = q.shape
    K = k.shape[1]
    k = jnp.repeat(k, H // K, axis=1)
    v = jnp.repeat(v, H // K, axis=1)
    n = T // _QUERY_BLOCK if T % _QUERY_BLOCK == 0 else 1
    kpos = jnp.arange(T)
    kk = min(int(topk), T)

    @jax.checkpoint
    def block(xs):
        qb, qib, wib, qpos = xs
        causal = kpos[None, :] <= qpos[:, None]
        head_scores = jax.nn.relu(jnp.einsum("tjc,sc->tjs", qib, ki))
        scores = jnp.einsum("tjs,tj->ts", head_scores, wib)
        scores = jnp.where(scores == 0, 0.0, scores)           # no -0.0
        if window:
            chosen = causal & (kpos[None, :] > qpos[:, None] - kk)
        else:
            _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), kk)
            chosen = jnp.zeros(causal.shape, bool).at[
                jnp.arange(qpos.shape[0])[:, None], idx].set(True) & causal
        chosen = jax.lax.stop_gradient(chosen)
        s = jnp.einsum("thd,shd->hts", qb, k) / math.sqrt(d)
        a = jax.nn.softmax(jnp.where(chosen[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hts,shd->thd", a, v)
        p = jax.lax.stop_gradient(jnp.mean(a, axis=0))         # [t, T]
        log_q = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf),
                                   axis=-1)
        on = chosen & (p > 0)
        kl = jnp.sum(jnp.where(on, p * (jnp.log(jnp.where(on, p, 1.0))
                                        - jnp.where(on, log_q, 0.0)), 0.0))
        return o, kl, chosen

    o, kl, chosen = jax.lax.map(block, (
        q.reshape(n, T // n, H, d), qi.reshape((n, T // n) + qi.shape[1:]),
        wi.reshape(n, T // n, -1), kpos.reshape(n, T // n)))
    return o.reshape(T, H, d), jnp.sum(kl), chosen.reshape(T, T)


def route(x, router, k: int):
    """(p [T, E] the softmax over all routed experts, the k chosen [T, k],
    their weights renormalised to sum 1 [T, k])."""
    p = jax.nn.softmax(x @ router, axis=-1)
    top_p, top_e = jax.lax.top_k(p, k)
    return p, top_e, top_p / jnp.sum(top_p, axis=-1, keepdims=True)


def experts(x, w: Dict, cfg: Dict):
    """The held experts' part of the layer on x [T, D]: ``(sum over the held
    experts, sum_t p [E], sum_t [top-1 = e] [E], the pairs each held expert
    received [held])``."""
    held = int(cfg["num_experts"])
    first = int(cfg.get("first_expert", 0))
    E = int(cfg.get("router_width") or held)
    p, top_e, top_w = route(x, w["router"], int(cfg["num_experts_per_tok"]))
    out = jnp.zeros_like(x)
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


def sections_of(cfg: Dict, width: int):
    """``mrope_section`` scaled to the ``width / 2`` pairs of a head of
    ``width`` channels (the main heads': as published)."""
    d = int(cfg["head_dim"])
    return [int(s) * width // d for s in cfg["rope_scaling"]["mrope_section"]]


def block(x, w: Dict, cfg: Dict, positions):
    """One layer on x [T, D] float32 with positions [3, T]: ``(y, sum_t p
    [E], sum_t top-1 [E], pairs [held], sum_t KL_t, the mixer output's mean
    square, the probe queries' sets [probes, T])``."""
    H, K = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d, eps = int(cfg["head_dim"]), float(cfg["rms_norm_eps"])
    sa = cfg["sa_config"]
    J, c = int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"])
    theta, T = float(cfg["rope_theta"]), x.shape[0]
    fault = cfg.get("fault")
    if fault == "rope_one_axis":
        positions = jnp.broadcast_to(positions[:1], positions.shape)
    u = rms_norm(x, w["ln1"], eps)
    q = rope(rms_norm((u @ w["wq"]).reshape(T, H, d), w["q_norm"], eps),
             positions, theta, sections_of(cfg, d))
    k = rope(rms_norm((u @ w["wk"]).reshape(T, K, d), w["k_norm"], eps),
             positions, theta, sections_of(cfg, d))
    v = (u @ w["wv"]).reshape(T, K, d)
    ut = jax.lax.stop_gradient(u)
    qi = rope((ut @ w["idx_wq"]).reshape(T, J, c), positions, theta,
              sections_of(cfg, c))
    ki = rope(layer_norm(ut @ w["idx_wk"], w["idx_k_norm"],
                         w["idx_k_bias"])[:, None, :], positions, theta,
              sections_of(cfg, c))[:, 0, :]
    wi = (ut @ w["idx_ww"]) / math.sqrt(J) / math.sqrt(c)
    o, kl, chosen = selected_attention(q, k, v, qi, ki, wi, int(sa["topk"]),
                                       window=fault == "window")
    mix = o.reshape(T, H * d) @ w["wo"]
    a = x + mix
    y, gate_sum, top1_sum, pairs = experts(rms_norm(a, w["ln2"], eps), w, cfg)
    return (a + y, gate_sum, top1_sum, pairs, kl, jnp.mean(jnp.square(mix)),
            chosen[jnp.asarray(probe_positions(T))])


def _f32(t):
    return jnp.asarray(t).astype(F32)


def head_nll(x, norm, head, tokens, eps):
    """Cross-entropy of each position's logits against the next token
    [T - 1], over the vocabulary the head holds."""
    lg = (rms_norm(x, norm, eps) @ head)[:-1]
    gold = jnp.take_along_axis(lg, tokens[1:, None], axis=-1)[:, 0]
    return jax.scipy.special.logsumexp(lg, axis=-1) - gold


def _positions(positions, r: int, T: int):
    if positions is None:
        return jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (3, T))
    return jnp.asarray(positions[r], jnp.int32)


def _coef(cfg: Dict, coef: Optional[float]) -> float:
    """What L_I is multiplied by: ``coef``, else the file's."""
    if coef is None:
        coef = float(cfg.get("deployment", {}).get("indexer_loss_coef", 1.0))
    return 0.0 if cfg.get("fault") == "no_indexer_loss" else coef


def batch_loss(cfg: Dict, get: Callable, rows, alpha: float,
               positions=None, coef: Optional[float] = None) -> Dict:
    """The loss of a micro-batch ``rows`` (B rows of T tokens; ``positions``
    B arrays [3, T], default the token's index on every axis) and its parts:
    ``loss = ce + alpha lb_loss + coef indexer_loss``; ``ce`` the mean
    cross-entropy over the B (T - 1) targets; ``lb_loss`` the balance term
    summed over the layers, each layer's over all B T tokens;
    ``indexer_loss`` L_I; ``expert_pairs`` [L, held]; ``mix_out_ms`` [L];
    ``probe_sets`` [L, B, probes, T] bool."""
    coef = _coef(cfg, coef)
    eps, L = float(cfg["rms_norm_eps"]), int(cfg["num_hidden_layers"])
    n = sum(len(row) for row in rows)
    fwd = jax.jit(lambda x, w, pos: block(x, w, cfg, pos))
    gate = top1 = pairs = kl = ms = 0.0
    nll, probes = [], []
    with jax.default_matmul_precision("highest"):
        for r, row in enumerate(rows):
            tokens = jnp.asarray(row, jnp.int32)
            pos = _positions(positions, r, tokens.shape[0])
            x = _f32(get("embed"))[tokens]
            st = []
            for i in range(L):
                x, *rest = fwd(x, {t: _f32(get(t, i)) for t in LAYER_TENSORS},
                               pos)
                st.append(rest)
            gate = gate + jnp.stack([s[0] for s in st])
            top1 = top1 + jnp.stack([s[1] for s in st])
            pairs = pairs + jnp.stack([s[2] for s in st])
            kl = kl + jnp.stack([s[3] for s in st])
            ms = ms + jnp.stack([s[4] for s in st]) / len(rows)
            probes.append(jnp.stack([s[5] for s in st]))
            nll.append(head_nll(x, _f32(get("final_norm")),
                                _f32(get("lm_head")), tokens, eps))
    lb = jnp.sum(gate * top1, axis=-1) / (n * n) * gate.shape[-1]   # [L]
    ce = jnp.mean(jnp.concatenate(nll))
    indexer = jnp.sum(kl) / n
    return {"loss": ce + alpha * jnp.sum(lb) + coef * indexer, "ce": ce,
            "lb_loss": jnp.sum(lb), "indexer_loss": indexer,
            "expert_pairs": pairs, "mix_out_ms": ms,
            "probe_sets": jnp.stack(probes, axis=1), "top1": top1}


def loss_and_grads(cfg: Dict, weights: Dict, rows, alpha: float,
                   positions=None, coef: Optional[float] = None):
    """``(loss, d loss / d weights)`` by ``jax.grad`` of the whole;
    ``weights`` a dict of float32 arrays keyed ``(name, layer)``, ``(name,
    None)`` for what no layer owns. For small sizes."""
    def loss(w):
        return batch_loss(cfg, lambda name, layer=None: w[(name, layer)],
                          rows, alpha, positions, coef)["loss"]

    return jax.value_and_grad(loss)(weights)


def batch_loss_and_grads(cfg: Dict, get: Callable, rows, alpha: float,
                         sink: Optional[Callable] = None, positions=None,
                         coef: Optional[float] = None):
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
    out = batch_loss(cfg, get, rows, alpha, positions, coef)
    coef = _coef(cfg, coef)
    eps, L = float(cfg["rms_norm_eps"]), int(cfg["num_hidden_layers"])
    n = sum(len(row) for row in rows)
    targets = sum(len(row) - 1 for row in rows)
    top1 = out.pop("top1")                                      # [L, E]
    held: Dict = {}
    if sink is None:
        def sink(name, layer, grad):
            held[(name, layer)] = grad

    forward = jax.jit(lambda x, w, pos: block(x, w, cfg, pos)[0])

    @jax.jit
    def back(x, w, pos, dy, d_gate):
        def f(x, w):
            y, gate_sum, _, _, kl, _, _ = block(x, w, cfg, pos)
            return y, gate_sum, kl
        return jax.vjp(f, x, w)[1]((dy, d_gate, jnp.asarray(coef / n, F32)))

    head = jax.jit(jax.value_and_grad(
        lambda x, norm, head, tokens:
        jnp.sum(head_nll(x, norm, head, tokens, eps)) / targets,
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
            pos = _positions(positions, r, tokens.shape[0])
            table = _f32(get("embed"))
            xs = [table[tokens]]
            for i in range(L):
                xs.append(forward(xs[-1], {t: _f32(get(t, i))
                                           for t in LAYER_TENSORS}, pos))
            _, (dx, d_norm, d_head) = head(
                xs.pop(), _f32(get("final_norm")), _f32(get("lm_head")),
                tokens)
            give("final_norm", None, d_norm)
            give("lm_head", None, d_head)
            for i in reversed(range(L)):
                d_gate = alpha * top1[i] * top1.shape[-1] / (n * n)
                dx, dw = back(xs.pop(), {t: _f32(get(t, i))
                                         for t in LAYER_TENSORS}, pos, dx,
                              d_gate)
                for name, g in dw.items():
                    give(name, i, g)
            give("embed", None, jnp.zeros_like(table).at[tokens].add(dx))
    return out, held


def adamw_first_step(g, w, lr: float, b1: float = 0.9, b2: float = 0.999,
                     eps: float = 1e-8, weight_decay: float = 0.0):
    """The change AdamW's first step makes to ``w`` given the gradient ``g``
    (moments from zero, both bias corrections, the decay decoupled)::

        m = (1 - b1) g,  v = (1 - b2) g^2
        -lr ((m / (1 - b1)) / (sqrt(v / (1 - b2)) + eps) + weight_decay w)
    """
    m, v = (1.0 - b1) * g, (1.0 - b2) * g * g
    return -lr * ((m / (1.0 - b1)) / (jnp.sqrt(v / (1.0 - b2)) + eps)
                  + weight_decay * w)
