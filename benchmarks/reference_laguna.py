"""Plain reference of Laguna-S-2.1 (poolside, ``model_type: "laguna"``):
forward, the loss with its balance term, the router's counts, the selection
bias after a step, and gradients by ``jax.grad``.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernels, no scan, no sort, no
cache, no batching, no recomputation; one sequence at a time, every held
expert applied to every token in a loop and weighted by what the router gave
it. It imports nothing but JAX.

The model, from the published ``config.json`` (hidden 3072, 48 layers, heads
of 128, 8 key-value heads in every layer; ``layer_types`` full, sliding,
sliding, sliding in turn, window 512; ``num_attention_heads_per_layer`` 48 on
the full layers and 72 on the sliding ones; ``gating`` per-head;
``rope_parameters`` a rope for each kind; ``mlp_only_layers`` [0] at
``intermediate_size`` 12288, the other layers 256 experts of 1024 at 10 a
token beside a shared expert of 1024, ``norm_topk_prob``,
``moe_routed_scaling_factor`` 2.5; RMSNorm eps 1e-6; untied head):

* a layer: ``a = x + Mix(RMSNorm(x))``, ``y = a + FFN(RMSNorm(a))``; a final
  RMSNorm and the head;
* the mixer of layer ``i``, ``u`` the normed input [T, D], ``H`` =
  ``num_attention_heads_per_layer[i]``, ``K`` = ``num_key_value_heads``, ``d``
  = ``head_dim``: ``q = u Wq`` -> [T, H, d], ``k = u Wk``, ``v = u Wv`` -> [T,
  K, d] (no norm on q or k); query head ``h`` reads key-value head ``h // (H /
  K)``; the rope of the layer's kind (``rope_parameters[layer_types[i]]``) on
  q and k: the first ``rotary = int(d x partial_rotary_factor)`` channels of a
  head turn, channel ``j`` with channel ``j + rotary / 2`` (halves order,
  transformers' ``rotate_half``), the others pass through; ``rope_type``
  "default": ``inv_j = theta^(-2j / rotary)``; "yarn" (transformers'
  ``_compute_yarn_parameters`` over ``rotary`` dimensions, at every length):
  bands that turn more than ``beta_fast`` times over
  ``original_max_position_embeddings`` keep their frequency, bands that turn
  less than ``beta_slow`` times divide it by ``factor``, a linear ramp
  between, and cos and sin are multiplied by ``attention_factor`` (so only
  the turned channels are scaled); scores ``q k^T / sqrt(d)``, causal, in a
  sliding layer query ``i`` sees key ``j`` when ``0 <= i - j < sliding_window``;
  ``a_h = softmax(.) v``; the gate ``g = sigmoid(u Wg)`` [T, H], one scalar a
  head and position, ``o_h = g_h a_h``; ``out = concat_h(o_h) Wo``;
* the FFN of the layers in ``mlp_only_layers``: a dense SwiGLU, ``W_down
  (silu(W_gate x) * W_up x)``. Of the others: ``s = sigmoid(x W_r)`` over all
  routed experts; the k experts with the largest ``s + b`` (``b`` the
  selection bias: in the choice, not in the weights); ``w_i = scale s_i /
  sum_{j chosen} s_j``; ``y = sum_i w_i E_i(x) + S(x)``, each ``E_i`` a SwiGLU
  of ``moe_intermediate_size``, ``S`` one of
  ``shared_expert_intermediate_size``, weight 1.

**What the published file does not say**, set by the family's convention and
listed under the configuration file's ``assumed``: the router's score
function (sigmoid with a selection bias: ``norm_topk_prob`` beside a routed
scale of 2.5 is the DeepSeek-V3 family's pair; no group limit, no
soft-capping, the weight on the expert's output); the gate's function
(sigmoid of a linear map of the layer's normed input, arXiv:2505.06708's
head-wise form); SwiGLU with silu; no norm on q and k; the first ``rotary``
channels the ones a partial rope turns. No departure from the family's code
is known to the writer; none was at hand to check against.

**What training adds** (the DeepSeek-V3 report, as ``reference_kanana2``
has it): the sequence-wise balance term, for each routed layer and each
sequence ``sum_i f_i P_i`` with ``f_i = E / (k T) x`` the pairs expert i
received from the sequence and ``P_i`` the sequence's mean of ``s_i / sum_j
s_j``, averaged over the sequences, summed over the layers, times ``alpha``;
``f_i`` counts the chosen pairs (``s + b``). And the bias rule: after a step
``b_i += gamma sign(mean_j c_j - c_i)`` (:func:`bias_after`).

**The share.** The configuration may hold a share of each layer's query heads
(``num_attention_heads_per_layer`` then counts the heads held, the first of
each layer's, ``num_key_value_heads`` the key-value heads that serve them:
the group ``H / K`` is the model's), of the routed experts (``num_experts`` of
them from ``first_expert`` on, of ``router_width`` scored) and a slice of the
vocabulary: ``Wo`` has the held heads' rows and the mixer's output is their
partial sum; the router, the choice and the weights are the whole model's,
the sum runs over the held experts only, the shared expert is whole; what
the absent heads and experts would add is left out; that partial sum goes on
to the next layer. Logits and loss are over the slice. With everything held
there is no departure. Attention is evaluated in blocks of queries so that an
8192-token sequence does not hold 36 full score matrices.

Weights are read through ``get(name, layer=None)``, which returns one stored
tensor of any float type (upcast here, one layer at a time): ``embed`` [V,
D], ``final_norm`` [D], ``head`` [D, V]; per layer ``ln1``, ``ln2`` [D],
``wq`` [D, H d], ``wk``, ``wv`` [D, K d], ``wg`` [D, H], ``wo`` [H d, D]; a
dense layer's ``w_gate``, ``w_up`` [D, F], ``w_down`` [F, D]; a routed
layer's ``router`` [D, E], ``router_bias`` [E], ``w_gate``, ``w_up`` [held,
D, Fm], ``w_down`` [held, Fm, D], ``shared_gate``, ``shared_up`` [D, Fs],
``shared_down`` [Fs, D].
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32
_QUERY_BLOCK = 512
ATTN_TENSORS = ("ln1", "ln2", "wq", "wk", "wv", "wg", "wo")
DENSE_TENSORS = ("w_gate", "w_up", "w_down")
ROUTED_TENSORS = ("router", "router_bias", "w_gate", "w_up", "w_down",
                  "shared_gate", "shared_up", "shared_down")


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def inv_frequencies(rotary: int, rp: Dict):
    """``(inv [rotary / 2], what cos and sin are multiplied by)`` of the rope
    parameters ``rp`` over ``rotary`` dimensions."""
    theta = float(rp["rope_theta"])
    inv = 1.0 / (theta ** (jnp.arange(0, rotary, 2, dtype=F32) / rotary))
    if rp.get("rope_type", "default") == "default":
        return inv, 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rp['rope_type']!r}")
    factor = float(rp["factor"])
    orig = float(rp["original_max_position_embeddings"])

    def band(turns):
        return rotary * math.log(orig / (turns * 2.0 * math.pi)) \
            / (2.0 * math.log(theta))

    low = max(math.floor(band(float(rp.get("beta_fast", 32)))), 0)
    high = min(math.ceil(band(float(rp.get("beta_slow", 1)))), rotary - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(rotary // 2, dtype=F32) - low)
                    / (high - low), 0.0, 1.0)
    scale = rp.get("attention_factor")
    return inv / factor * ramp + inv * (1.0 - ramp), float(
        scale if scale is not None else 0.1 * math.log(factor) + 1.0)


def rope(x, positions, rp: Dict):
    """x [T, heads, d]: the first ``int(d x partial_rotary_factor)`` channels
    turned in halves order, the others passed through."""
    rotary = int(x.shape[-1] * float(rp.get("partial_rotary_factor", 1.0)))
    inv, scale = inv_frequencies(rotary, rp)
    ang = positions.astype(F32)[:, None] * inv[None, :]       # [T, rotary/2]
    cos, sin = (jnp.cos(ang) * scale)[:, None, :], \
        (jnp.sin(ang) * scale)[:, None, :]
    a, b = x[..., :rotary // 2], x[..., rotary // 2:rotary]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rotary:]], axis=-1)


def kv_head_of(h: int, H: int, K: int) -> int:
    """The key-value head query head ``h`` of ``H`` reads, of ``K``."""
    return h // (H // K)


def attention(q, k, v, window: Optional[int]):
    """q [T, H, d], k, v [T, K, d], positions 0..T-1, causal, scores over
    ``sqrt(d)``; with ``window`` a query sees the last ``window`` keys, itself
    among them; a block of queries at a time."""
    T, H, d = q.shape
    heads = jnp.asarray([kv_head_of(h, H, k.shape[1]) for h in range(H)])
    k, v = k[:, heads], v[:, heads]
    kpos = jnp.arange(T)
    outs = []
    for lo in range(0, T, _QUERY_BLOCK):
        qb = q[lo:lo + _QUERY_BLOCK]
        qpos = jnp.arange(lo, lo + qb.shape[0])
        s = jnp.einsum("thd,shd->hts", qb, k) / jnp.sqrt(F32(d))
        seen = kpos[None, :] <= qpos[:, None]
        if window is not None:
            seen = seen & (qpos[:, None] - kpos[None, :] < window)
        s = jnp.where(seen[None], s, -jnp.inf)
        outs.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(outs, axis=0)


def head_gate(u, wg):
    """One scalar a head and position [T, H]."""
    return jax.nn.sigmoid(u @ wg)


def layer_kind(cfg: Dict, layer: int) -> str:
    return cfg["layer_types"][layer]


def mixer(u, w: Dict, cfg: Dict, layer: int, positions):
    """The mixer of layer ``layer`` on the normed input u [T, D]."""
    kind = layer_kind(cfg, layer)
    H = int(cfg["num_attention_heads_per_layer"][layer])
    K, d = int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    T = u.shape[0]
    rp = cfg["rope_parameters"][kind]
    q = rope((u @ w["wq"]).reshape(T, H, d), positions, rp)
    k = rope((u @ w["wk"]).reshape(T, K, d), positions, rp)
    v = (u @ w["wv"]).reshape(T, K, d)
    # (a rehearsal nulls the window: one as long as the sequence is none)
    window = int(cfg["sliding_window"]) if kind == "sliding_attention" \
        and cfg.get("sliding_window") else None
    a = attention(q, k, v, window) * head_gate(u, w["wg"])[..., None]
    return a.reshape(T, H * d) @ w["wo"]


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
    return range(first, first + int(cfg["num_experts"]))


def experts(x, w: Dict, cfg: Dict, held: Optional[Sequence[int]] = None,
            shared: bool = True):
    """The routed layer's FFN on x [T, D] for the experts ``held`` (a list of
    expert indices, ``w["w_gate"][j]`` the j-th of them; default the
    configuration's share): ``(sum over the held experts + the shared
    expert, counts [E] the pairs every routed expert received, the
    sequence's balance term sum_i f_i P_i)``."""
    held = list(held_experts(cfg) if held is None else held)
    k = int(cfg["num_experts_per_tok"])
    E = int(cfg.get("router_width") or cfg["num_experts"])
    s, top_e, top_w = route(x, w["router"], w["router_bias"], k,
                            float(cfg["moe_routed_scaling_factor"]))
    out = jnp.zeros_like(x)
    for j, e in enumerate(held):
        weight = jnp.sum(jnp.where(top_e == e, top_w, 0.0), axis=-1)
        out = out + weight[:, None] * swiglu(
            x, w["w_gate"][j], w["w_up"][j], w["w_down"][j])
    if shared and int(cfg.get("shared_expert_intermediate_size", 0)):
        out = out + swiglu(x, w["shared_gate"], w["shared_up"],
                           w["shared_down"])
    counts = jnp.sum(jax.nn.one_hot(top_e, E, dtype=F32), axis=(0, 1))
    f = jax.lax.stop_gradient(counts) * (E / (k * x.shape[0]))
    p = jnp.mean(s / jnp.sum(s, axis=-1, keepdims=True), axis=0)
    return out, counts, jnp.sum(f * p)


def is_dense(cfg: Dict, layer: int) -> bool:
    return layer in cfg.get("mlp_only_layers", ())


def block(x, w: Dict, cfg: Dict, layer: int, positions):
    """One layer on x [T, D] float32: ``(y, the mixer output's mean square,
    counts [E], the balance term)``; a dense layer's counts and term are
    zeros."""
    eps = float(cfg["rms_norm_eps"])
    mix = mixer(rms_norm(x, w["ln1"], eps), w, cfg, layer, positions)
    a = x + mix
    h = rms_norm(a, w["ln2"], eps)
    if is_dense(cfg, layer):
        E = int(cfg.get("router_width") or cfg["num_experts"])
        y, counts, term = (swiglu(h, w["w_gate"], w["w_up"], w["w_down"]),
                           jnp.zeros((E,), F32), jnp.zeros((), F32))
    else:
        y, counts, term = experts(h, w, cfg)
    return a + y, jnp.mean(mix * mix), counts, term


def _f32(t):
    return jnp.asarray(t).astype(F32)


def sequence(cfg: Dict, get: Callable, tokens) -> Dict:
    """One sequence [T] through the model: ``nll`` [T - 1] (cross-entropy of
    each position's logits against the next token, over the vocabulary the
    configuration holds), by layer ``mix_out_ms`` [L], and by routed layer
    ``counts`` [Lm, E] and ``term`` [Lm]."""
    eps = float(cfg["rms_norm_eps"])
    L = int(cfg["num_hidden_layers"])
    block_jit = jax.jit(lambda x, w, pos, layer: block(
        x, {n: t.astype(F32) for n, t in w.items()}, cfg, layer, pos),
        static_argnums=3)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        pos = jnp.arange(tokens.shape[0])
        x = _f32(jnp.asarray(get("embed"))[tokens])
        ms, counts, terms = [], [], []
        for i in range(L):
            dense = is_dense(cfg, i)
            names = ATTN_TENSORS + (DENSE_TENSORS if dense
                                    else ROUTED_TENSORS)
            x, m, c, t = block_jit(
                x, {n: jnp.asarray(get(n, i)) for n in names}, pos, i)
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
