"""Plain reference of kanana-2-30b-a3b (``model_type: "deepseek_v3"``):
forward, the loss with its balance term, the router's counts, the selection
bias after a step, and gradients by ``jax.grad``.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernels, no scan, no sort, no
cache, no batching, no recomputation; one sequence at a time, every held
expert applied to every token in a loop and weighted by what the router gave
it. It imports nothing but JAX. ``tests/unit/kanana_reference.py`` is a copy
of this file, kept with the program's tests; ``benchmarks/tests/test_kanana2.py``
holds the two equal.

The model, from the published ``config.json`` (hidden 2048, 48 layers, 32
heads, ``kv_lora_rank`` 512, ``qk_nope_head_dim`` 128, ``qk_rope_head_dim``
64, ``v_head_dim`` 128, ``q_lora_rank`` null, ``rope_theta`` 1e6 without
scaling, ``rope_interleave``, ``first_k_dense_replace`` 1 at
``intermediate_size`` 6144, 128 routed experts of width 768, 6 a token,
``scoring_func`` sigmoid, ``topk_method`` noaux_tc with ``n_group`` =
``topk_group`` = 1, ``norm_topk_prob``, ``routed_scaling_factor`` 2.448, 2
shared experts, RMSNorm eps 1e-6, untied head):

* a layer: ``a = x + MLA(RMSNorm(x))``, ``y = a + FFN(RMSNorm(a))``; a final
  RMSNorm and the head;
* latent attention, ``h`` the normed input [T, D]: ``q = h Wq`` -> [T, H, dn
  + dr] (one matrix, no norm), its first ``dn`` columns without rope, its
  last ``dr`` with; ``ckv = h Wkv_a`` -> [T, r + dr]; ``c = RMSNorm(ckv[:,
  :r])`` (its own scale); ``k_rope = ckv[:, r:]``, ONE vector a position that
  all heads use; ``kv = c Wkv_b`` -> [T, H, dn + dv], a head's key columns
  then its value columns; rope over the ``dr`` dimensions of ``q_rope`` and
  ``k_rope`` in pairs (2i, 2i + 1), ``inv_i = theta^(-2i/dr)``; ``k = [k_nope
  | k_rope]``; scores ``q k^T / sqrt(dn + dr)``, causal softmax, ``o = P v``
  -> [T, H, dv]; ``out = o Wo``. (The config's ``head_dim`` 64 is the rope
  width, no head's.)
* the FFN of the first ``first_k_dense_replace`` layers: a dense SwiGLU,
  ``W_down (silu(W_gate x) * W_up x)``. Of the others: ``s = sigmoid(x W_r)``
  over all routed experts; the k experts with the largest ``s + b`` (``b``
  the selection bias, ``e_score_correction_bias``: in the choice, not in the
  weights); ``w_i = scale s_i / sum_{j chosen} s_j``; ``y = sum_i w_i E_i(x) +
  S(x)``, each ``E_i`` a SwiGLU of the experts' width, ``S`` one SwiGLU of
  ``n_shared_experts`` times that.

**What training adds** (the DeepSeek-V3 report, which ``model_type`` names;
the configuration file lists these under ``assumed``): the sequence-wise
balance term, for each routed layer and each sequence ``sum_i f_i P_i`` with
``f_i = E / (k T) x`` the pairs expert i received from the sequence and ``P_i``
the sequence's mean of ``s_i / sum_j s_j``, averaged over the sequences,
summed over the layers, times ``alpha``. *Departure*: the report counts
``f_i`` over the top k by ``s`` alone; here, as in the program, the chosen
pairs (``s + b``) are counted, the ones the layer computes. And the bias
rule: after a step ``b_i += gamma sign(mean_j c_j - c_i)``, ``c`` the pairs
each routed expert received from the step's tokens in that layer
(:func:`bias_after`).

**The share.** The configuration may hold a share of the routed experts
(``n_routed_experts`` of them from ``first_expert`` on, of ``router_width``
scored; :func:`experts` also takes them as a list) and a slice of the
vocabulary: the router, the choice and the weights are the whole model's,
the sum runs over the held experts only, the shared experts are whole, and
what the absent experts would add is left out; that partial sum goes on to
the next layer. Logits and loss are over the slice. With every expert held
there is no departure. Attention is evaluated in blocks of queries so that
an 8192-token sequence does not hold 32 full score matrices.

Weights are read through ``get(name, layer=None)``, which returns one stored
tensor of any float type (upcast here, one layer at a time), in the published
layout: ``embed`` [V, D], ``final_norm`` [D], ``head`` [D, V]; per layer
``ln1``, ``ln2`` [D], ``wq`` [D, H (dn + dr)], ``wkv_a`` [D, r + dr],
``kv_norm`` [r], ``wkv_b`` [r, H (dn + dv)], ``wo`` [H dv, D]; a dense layer's
``w_gate``, ``w_up`` [D, F], ``w_down`` [F, D]; a routed layer's ``router``
[D, E], ``router_bias`` [E], ``w_gate``, ``w_up`` [held, D, Fm], ``w_down``
[held, Fm, D], ``shared_gate``, ``shared_up`` [D, n Fm], ``shared_down``
[n Fm, D].
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32
_QUERY_BLOCK = 512
MLA_TENSORS = ("ln1", "ln2", "wq", "wkv_a", "kv_norm", "wkv_b", "wo")
DENSE_TENSORS = ("w_gate", "w_up", "w_down")
ROUTED_TENSORS = ("router", "router_bias", "w_gate", "w_up", "w_down",
                  "shared_gate", "shared_up", "shared_down")


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rope_pairs(x, positions, theta: float):
    """x [T, heads, dr] in the published layout: rotate each pair (2i, 2i +
    1) by ``positions * theta^(-2i/dr)``."""
    dr = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dr, 2, dtype=F32) / dr))
    ang = positions.astype(F32)[:, None] * inv[None, :]        # [T, dr/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def attention(q, k, v):
    """q, k [T, H, dk], v [T, H, dv], positions 0..T-1, causal, scores over
    ``sqrt(dk)``; a block of queries at a time."""
    T, _, dk = q.shape
    kpos = jnp.arange(T)
    outs = []
    for lo in range(0, T, _QUERY_BLOCK):
        qb = q[lo:lo + _QUERY_BLOCK]
        qpos = jnp.arange(lo, lo + qb.shape[0])
        s = jnp.einsum("thd,shd->hts", qb, k) / jnp.sqrt(F32(dk))
        s = jnp.where((kpos[None, :] <= qpos[:, None])[None], s, -jnp.inf)
        outs.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(outs, axis=0)


def mla(h, w: Dict, cfg: Dict, positions):
    """The mixer on the normed input h [T, D]."""
    H = int(cfg["num_attention_heads"])
    r, dn, dr, dv = (int(cfg["kv_lora_rank"]), int(cfg["qk_nope_head_dim"]),
                     int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]))
    T, theta = h.shape[0], float(cfg["rope_theta"])
    q = (h @ w["wq"]).reshape(T, H, dn + dr)
    ckv = h @ w["wkv_a"]
    c = rms_norm(ckv[:, :r], w["kv_norm"], float(cfg["rms_norm_eps"]))
    kv = (c @ w["wkv_b"]).reshape(T, H, dn + dv)
    q_rope = rope_pairs(q[..., dn:], positions, theta)
    k_rope = rope_pairs(ckv[:, None, r:], positions, theta)     # [T, 1, dr]
    q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (T, H, dr))], axis=-1)
    return attention(q, k, kv[..., dn:]).reshape(T, H * dv) @ w["wo"]


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


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
    """The routed layer's FFN on x [T, D] for the experts ``held`` (a list of
    expert indices, ``w["w_gate"][j]`` the j-th of them; default the
    configuration's share): ``(sum over the held experts + the shared
    experts, counts [E] the pairs every routed expert received, the
    sequence's balance term sum_i f_i P_i)``."""
    held = list(held_experts(cfg) if held is None else held)
    k = int(cfg["num_experts_per_tok"])
    E = int(cfg.get("router_width") or cfg["n_routed_experts"])
    s, top_e, top_w = route(x, w["router"], w["router_bias"], k,
                            float(cfg["routed_scaling_factor"]))
    out = jnp.zeros_like(x)
    for j, e in enumerate(held):
        weight = jnp.sum(jnp.where(top_e == e, top_w, 0.0), axis=-1)
        out = out + weight[:, None] * swiglu(
            x, w["w_gate"][j], w["w_up"][j], w["w_down"][j])
    if shared and int(cfg.get("n_shared_experts", 0)):
        out = out + swiglu(x, w["shared_gate"], w["shared_up"],
                           w["shared_down"])
    counts = jnp.sum(jax.nn.one_hot(top_e, E, dtype=F32), axis=(0, 1))
    f = jax.lax.stop_gradient(counts) * (E / (k * x.shape[0]))
    p = jnp.mean(s / jnp.sum(s, axis=-1, keepdims=True), axis=0)
    return out, counts, jnp.sum(f * p)


def block(x, w: Dict, cfg: Dict, dense: bool, positions):
    """One layer on x [T, D] float32: ``(y, the mixer output's mean square,
    counts [E], the balance term)``; a dense layer's counts and term are
    zeros."""
    eps = float(cfg["rms_norm_eps"])
    mix = mla(rms_norm(x, w["ln1"], eps), w, cfg, positions)
    a = x + mix
    h = rms_norm(a, w["ln2"], eps)
    if dense:
        E = int(cfg.get("router_width") or cfg["n_routed_experts"])
        y, counts, term = (swiglu(h, w["w_gate"], w["w_up"], w["w_down"]),
                           jnp.zeros((E,), F32), jnp.zeros((), F32))
    else:
        y, counts, term = experts(h, w, cfg)
    return a + y, jnp.mean(mix * mix), counts, term


def _f32(t):
    return jnp.asarray(t).astype(F32)


def is_dense(cfg: Dict, layer: int) -> bool:
    return layer < int(cfg.get("first_k_dense_replace", 0))


def sequence(cfg: Dict, get: Callable, tokens) -> Dict:
    """One sequence [T] through the model: ``nll`` [T - 1] (cross-entropy of
    each position's logits against the next token, over the vocabulary the
    configuration holds), by layer ``mix_out_ms`` [L], and by routed layer
    ``counts`` [Lm, E] and ``term`` [Lm]."""
    eps = float(cfg["rms_norm_eps"])
    L = int(cfg["num_hidden_layers"])
    block_jit = jax.jit(lambda x, w, pos, dense: block(
        x, {n: t.astype(F32) for n, t in w.items()}, cfg, dense, pos),
        static_argnums=3)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        pos = jnp.arange(tokens.shape[0])
        x = _f32(jnp.asarray(get("embed"))[tokens])
        ms, counts, terms = [], [], []
        for i in range(L):
            dense = is_dense(cfg, i)
            names = MLA_TENSORS + (DENSE_TENSORS if dense else ROUTED_TENSORS)
            x, m, c, t = block_jit(
                x, {n: jnp.asarray(get(n, i)) for n in names}, pos, dense)
            ms.append(m)
            if not dense:
                counts.append(c)
                terms.append(t)
        x = rms_norm(x, _f32(get("final_norm")), eps)
        logits = x @ _f32(get("head"))
    lg = logits[:-1]
    gold = jnp.take_along_axis(lg, tokens[1:, None], axis=-1)[:, 0]
    return {"nll": jax.scipy.special.logsumexp(lg, axis=-1) - gold,
            "mix_out_ms": jnp.stack(ms), "counts": jnp.stack(counts),
            "term": jnp.stack(terms)}


def batch_loss(cfg: Dict, get: Callable, rows, alpha: float) -> Dict:
    """The loss of a micro-batch ``rows`` [B, T] and its parts: ``loss`` =
    ``ce`` + alpha x ``lb_loss``; ``ce`` the mean cross-entropy over the
    B x (T - 1) targets; ``lb_loss`` the balance term, each routed layer's
    the mean over the sequences, summed over the layers; ``mix_out_ms`` [L]
    the mixer output's mean square over all B x T positions; ``router_counts``
    [Lm, E] the pairs every routed expert received and ``expert_pairs`` [Lm,
    held] those of the experts held here."""
    per_row = [sequence(cfg, get, row) for row in rows]
    counts = sum(r["counts"] for r in per_row)
    lb = jnp.sum(sum(r["term"] for r in per_row)) / len(per_row)
    ce = jnp.mean(jnp.concatenate([r["nll"] for r in per_row]))
    held = jnp.asarray(list(held_experts(cfg)))
    return {"loss": ce + alpha * lb, "ce": ce, "lb_loss": lb,
            "mix_out_ms": sum(r["mix_out_ms"] for r in per_row) / len(per_row),
            "router_counts": counts, "expert_pairs": counts[:, held]}


def bias_after(bias, router_counts, gamma: float):
    """The selection biases [Lm, E] after a step whose tokens gave the routed
    experts ``router_counts`` [Lm, E] pairs: an expert under its layer's mean
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
