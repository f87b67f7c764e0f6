"""Plain reference of Mellum2-12B-A2.5B (``model_type: "mellum"``): forward,
the loss with its load-balance term and the router's counts, and gradients
by ``jax.grad``.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernels, no scan, no sort, no
cache, no batching, no recomputation; one sequence at a time, every held
expert applied to every token and weighted by what the router gave it. It
imports nothing but JAX. ``tests/unit/mellum_reference.py`` is a copy of this
file, kept with the program's tests; ``benchmarks/tests/test_reference_mellum2.py``
holds the two equal.

The model, from the published ``config.json`` (hidden 2304, 32 query and 4
key-value heads of 128, ``layer_types`` = (sliding, sliding, sliding, full) x
7 with ``sliding_window`` 1024, ``rope_parameters`` by layer kind, 64 experts
of width 896, 8 a token, ``norm_topk_prob``, RMSNorm eps 1e-6, untied head):

* a layer: ``a = x + Attn_kind(RMSNorm(x))``, ``y = a + MoE(RMSNorm(a))``; a
  final RMSNorm and the head;
* attention: no biases, grouped queries (each key-value head serves 8 query
  heads), causal, rotary embeddings in the half-split ("rotate_half")
  convention. In a sliding layer query i sees key j when ``0 <= i - j <=
  window - 1`` and the rope is the plain one, ``inv_i = theta^(-2i/d)``. In a
  full layer the rope is yarn: ``inv = inv_interp * ramp + inv_extrap *
  (1 - ramp)`` with ``inv_extrap = theta^(-2i/d)``, ``inv_interp = inv_extrap
  / factor``, ``ramp = clip((i - low) / (high - low), 0, 1)`` over the band
  index i, ``low`` and ``high`` the floor and the ceiling of ``d ln(original
  / (2 pi n)) / (2 ln theta)`` at ``n = beta_fast`` and ``beta_slow``, and cos
  and sin both multiplied by ``attention_factor``;
* the experts: ``p = softmax(x W_r)`` over all routed experts, the k largest
  kept and renormalised to sum 1, ``MoE(x) = sum_e w_e W_down,e (silu(W_gate,e
  x) * W_up,e x)``.

**The share.** The configuration may hold a share of the experts
(``num_experts`` of them from ``first_expert`` on, of ``router_width``
routed) and a slice of the vocabulary (``vocab_size`` rows): the router, the
top k and their weights are the whole model's, the sum runs over the held
experts only, and what the absent experts would add is left out; that partial
sum goes on to the next layer. Logits and loss are over the slice. This is
the one departure from the whole model, and the program makes the same one;
with every expert held there is none. Attention is evaluated in blocks of
queries so that an 8192-token sequence does not hold 32 full score matrices.

What the published file does not say, and this reading assumes (the program
follows the same reading; ``configs/mellum2_12b_train_d4e16.json`` lists them
under ``assumed``): softmax scoring with no router bias; no QK-norm; no
shared expert; the load-balance term, the one the program's router computes
(``E sum_e mean_t p[t, e] * mean_t [argmax_e' p[t, e'] = e]`` over all the
tokens of the micro-batch, summed over layers, times ``coef``, 0.001 here);
no multi-token-prediction head.

Weights are read through ``get(name, layer=None)``, which returns one stored
tensor of any float type (upcast here, one layer at a time): ``embed`` [V, D],
``final_norm`` [D], ``head`` [D, V], and per layer ``ln1``, ``ln2`` [D],
``wq`` [D, H d], ``wk``, ``wv`` [D, K d], ``wo`` [H d, D], ``router`` [D, E],
``w_gate``, ``w_up`` [held, D, F], ``w_down`` [held, F, D].
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
_QUERY_BLOCK = 512
LAYER_TENSORS = ("ln1", "ln2", "wq", "wk", "wv", "wo", "router", "w_gate",
                 "w_up", "w_down")


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rope_inverse_frequencies(d: int, rp: Dict) -> Tuple[jax.Array, float]:
    """``(inv [d / 2], the factor on cos and sin)`` of one layer kind's
    ``rope_parameters``: the plain rope, or yarn."""
    theta = float(rp["rope_theta"])
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    if rp.get("rope_type", "default") == "default":
        return inv, 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rp['rope_type']!r} is not written down "
                         f"here")
    factor = float(rp["factor"])
    orig = float(rp["original_max_position_embeddings"])

    def band(turns):
        return d * math.log(orig / (turns * 2.0 * math.pi)) \
            / (2.0 * math.log(theta))

    low = max(math.floor(band(float(rp.get("beta_fast", 32)))), 0)
    high = min(math.ceil(band(float(rp.get("beta_slow", 1)))), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=F32) - low) / (high - low),
                    0.0, 1.0)
    given = rp.get("attention_factor")
    scale = float(given) if given is not None \
        else 0.1 * math.log(factor) + 1.0
    return inv / factor * ramp + inv * (1.0 - ramp), scale


def rope(x, positions, inv, scale):
    """x [T, heads, d]; rotate pairs (j, j + d/2) by positions * inv_j, cos
    and sin times ``scale``."""
    d = x.shape[-1]
    ang = positions.astype(F32)[:, None] * inv[None, :]        # [T, d/2]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window=None):
    """q [T, H, d], k/v [T, K, d] (each key-value head repeated H / K times),
    positions 0..T-1; causal, and with ``window`` only the last ``window``
    keys of each query."""
    T, H, d = q.shape
    k = jnp.repeat(k, H // k.shape[1], axis=1)
    v = jnp.repeat(v, H // v.shape[1], axis=1)
    kpos = jnp.arange(T)
    outs = []
    for lo in range(0, T, _QUERY_BLOCK):
        qb = q[lo:lo + _QUERY_BLOCK]
        qpos = jnp.arange(lo, lo + qb.shape[0])
        s = jnp.einsum("thd,shd->hts", qb, k) / jnp.sqrt(F32(d))
        seen = kpos[None, :] <= qpos[:, None]
        if window is not None:
            seen = seen & (kpos[None, :] > qpos[:, None] - int(window))
        s = jnp.where(seen[None], s, -jnp.inf)
        outs.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(outs, axis=0)


def route(x, router, k: int):
    """(p [T, E] the softmax over all routed experts, the k chosen [T, k],
    their weights renormalised to sum 1 [T, k])."""
    p = jax.nn.softmax(x @ router, axis=-1)
    top_p, top_e = jax.lax.top_k(p, k)
    return p, top_e, top_p / jnp.sum(top_p, axis=-1, keepdims=True)


def experts(x, w: Dict, cfg: Dict):
    """The held experts' part of the layer on x [T, D]: ``(sum over the held
    experts, sum_t p [E], sum_t [top-1 = e] [E], the pairs each held expert
    received [held])``. Every held expert is applied to every token and
    weighted by what the router gave it (0 where it was not chosen)."""
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
    return out, p.sum(axis=0), top1.sum(axis=0), jnp.stack(pairs)


def block(x, w: Dict, cfg: Dict, kind: str, positions):
    """One layer on x [T, D] float32; ``w`` holds the layer's tensors in
    float32, ``kind`` is its entry of ``layer_types``."""
    H, K = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d = int(cfg.get("head_dim") or cfg["hidden_size"] // H)
    eps = float(cfg["rms_norm_eps"])
    T = x.shape[0]
    inv, scale = rope_inverse_frequencies(d, cfg["rope_parameters"][kind])
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    h = rms_norm(x, w["ln1"], eps)
    q = rope((h @ w["wq"]).reshape(T, H, d), positions, inv, scale)
    k = rope((h @ w["wk"]).reshape(T, K, d), positions, inv, scale)
    v = (h @ w["wv"]).reshape(T, K, d)
    a = x + attention(q, k, v, window).reshape(T, H * d) @ w["wo"]
    y, gate_sum, top1_sum, pairs = experts(rms_norm(a, w["ln2"], eps), w, cfg)
    return a + y, gate_sum, top1_sum, pairs


def _f32(t):
    return jnp.asarray(t).astype(F32)


def sequence(cfg: Dict, get: Callable, tokens) -> Dict:
    """One sequence [T] through the model: ``nll`` [T - 1] (cross-entropy of
    each position's logits against the next token, over the vocabulary the
    configuration holds) and by layer ``gate_sum`` [L, E], ``top1_sum``
    [L, E], ``expert_pairs`` [L, held]."""
    eps = float(cfg["rms_norm_eps"])
    kinds = list(cfg["layer_types"])[:int(cfg["num_hidden_layers"])]
    block_jit = jax.jit(lambda x, w, pos, kind: block(
        x, {n: t.astype(F32) for n, t in w.items()}, cfg, kind, pos),
        static_argnums=3)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        pos = jnp.arange(tokens.shape[0])
        x = _f32(jnp.asarray(get("embed"))[tokens])
        stats = []
        for i, kind in enumerate(kinds):
            x, *st = block_jit(x, {n: jnp.asarray(get(n, i))
                                   for n in LAYER_TENSORS}, pos, kind)
            stats.append(st)
        x = rms_norm(x, _f32(get("final_norm")), eps)
        logits = x @ _f32(get("head"))
    lg = logits[:-1]
    gold = jnp.take_along_axis(lg, tokens[1:, None], axis=-1)[:, 0]
    return {"nll": jax.scipy.special.logsumexp(lg, axis=-1) - gold,
            "gate_sum": jnp.stack([s[0] for s in stats]),
            "top1_sum": jnp.stack([s[1] for s in stats]),
            "expert_pairs": jnp.stack([s[2] for s in stats])}


def batch_loss(cfg: Dict, get: Callable, rows, coef: float) -> Dict:
    """The loss of a micro-batch ``rows`` [B, T] and its parts: ``loss`` =
    ``ce`` + coef x ``lb_loss``; ``ce`` the mean cross-entropy over the
    B x (T - 1) targets; ``lb_loss`` the load-balance term summed over the
    layers, each layer's over all B x T tokens; ``expert_pairs`` [L, held],
    the (token, expert) pairs each held expert received."""
    per_row = [sequence(cfg, get, row) for row in rows]
    n = sum(int(jnp.shape(row)[0]) for row in rows)
    gate = sum(r["gate_sum"] for r in per_row)                  # [L, E]
    top1 = sum(r["top1_sum"] for r in per_row)
    lb = jnp.sum(gate * top1, axis=-1) / (n * n) * gate.shape[-1]   # [L]
    ce = jnp.mean(jnp.concatenate([r["nll"] for r in per_row]))
    return {"loss": ce + coef * jnp.sum(lb), "ce": ce, "lb_loss": jnp.sum(lb),
            "expert_pairs": sum(r["expert_pairs"] for r in per_row)}


def loss_and_grads(cfg: Dict, weights: Dict, rows, coef: float):
    """``(loss, d loss / d weights)`` by ``jax.grad``; ``weights`` is a dict
    of float32 arrays keyed ``(name, layer)``, ``(name, None)`` for what no
    layer owns."""
    def loss(w):
        return batch_loss(cfg, dict_getter(w), rows, coef)["loss"]

    return jax.value_and_grad(loss)(weights)


def dict_getter(weights: Dict) -> Callable:
    def get(name, layer=None):
        return weights[(name, layer)]

    return get
