"""Plain reference of Ling-3.0-flash's language model (inclusionAI; this
repo's ``model_type`` label ``"bailing_hybrid"``): forward, the loss over the
vocabulary held with its balance term, each layer's mixer-output mean square,
the router's counts and kept groups, the selection bias after a step,
gradients by ``jax.grad`` / ``jax.vjp``, and the AdamW update they give.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernels, no chunked rule, no
cache, no batching, no dispatch; one sequence at a time, one layer at a time
(so that it fits on the chip beside the program's state). Nothing is
recomputed but, for a gradient, what memory forces: a layer's forward from its
input, a block of queries' scores from q, k and v, and a run of 64 positions'
states from the state before them (the same arithmetic, run again; the blocks
of queries and the runs of positions are walked one after the other, so that
one block's scores and one run's states are alive at a time). It
imports nothing but JAX.

The model, from the published ``config.json``'s keys, ``N`` an RMSNorm with
eps ``rms_norm_eps``; each reading the keys do not settle is marked (*) and
listed in the configuration file under ``assumed``:

* ``x0 = E[ids]``; layer i: ``h = x + Mix_i(N(x))``, ``y = h + FFN_i(N(h))``;
  ``logits = N(x_L) W_head`` (untied). Published layer ``i`` is latent
  attention where ``(i + 1) % layer_group_size == 0`` and KDA elsewhere (*:
  ``described_as`` says 3 : 1, the keys and the parameter count 5 : 1); its
  FFN is dense for ``i < first_k_dense_replace`` and routed after.
* ``Mix`` of a KDA layer (Kimi Delta Attention, arXiv:2510.26692) on ``u`` [T,
  D], ``H`` heads of ``dk = dv = head_dim``: ``q, k, v = silu(conv(u wq)),
  silu(conv(u wk)), silu(conv(u wv))``, the convolution depthwise and causal
  over ``short_conv_kernel_size`` positions, **the sum of K shifted arrays**,
  no bias (*); ``q, k`` scaled to unit length a head (``use_qk_norm``, (*): the
  L2 norm, eps 1e-6 under the root), ``q`` then by ``1 / sqrt(dk)``; ``beta =
  sigmoid(u wb)`` [T, H]; ``g = kda_lower_bound x sigmoid(exp(A_log)[h] (u wf
  + dt_bias))`` [T, H, dk] (``kda_safe_gate``; ``wf`` full rank,
  ``no_kda_lora``; (*) the form is ``fla``'s lower-bound gate); **the
  recurrence over positions**, ``S`` [dk, dv] a head from zero::

      S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t

  ``Mix = (N_dv(o; o_norm) sigmoid(u wg)[h]) wo``: the norm over a head's dv
  channels with one scale for every head, the gate one scalar a head (*:
  ``gated_attention_proj_granularity_type: head_wise``). No rope (*).
* ``Mix`` of a latent-attention layer, ``h`` the normed input: ``q = h wq`` ->
  [T, H, dn + dr] (one matrix, ``q_lora_rank`` null), its first ``dn``
  columns without rope, its last ``dr`` with; ``ckv = h wkv_a`` -> [T, r +
  dr]; ``c = N(ckv[:, :r]; kv_norm)`` (``use_qk_norm`` read as the latent's
  norm here, (*)); ``k_rope = ckv[:, r:]``, one vector a position that all
  heads use; ``kv = c wkv_b`` -> [T, H, dn + dv], a head's key columns then
  its value columns; rope over the ``dr`` = ``rotary_dim`` columns in pairs
  (2i, 2i + 1) (``rope_interleave``), ``inv_i = rope_theta^(-2i/dr)``; scores
  ``q k^T / sqrt(dn + dr)``, a whole causal softmax; each head's output times
  ``sigmoid(h wg)[h]`` (*: the same head-wise gate), then ``wo``.
* ``FFN`` dense: ``W_down (silu(W_gate x) * W_up x)`` at
  ``intermediate_size``. Routed: ``s = sigmoid(x W_r)`` over the
  ``router_width`` experts; selection on ``s + b`` (``b`` the selection bias,
  ``moe_router_enable_expert_bias``: in the choice, not in the weights): the
  experts in ``n_group`` groups of neighbours, a group's score the sum of its
  two largest ``s + b`` (*: DeepSeek-V3's rule), the ``topk_group`` best
  groups kept, the ``num_experts_per_tok`` largest ``s + b`` among their
  experts; ``w_i = routed_scaling_factor s_i / sum_{chosen} s_j``
  (``norm_topk_prob``); ``y = sum_i w_i E_i(x) + S(x)``, each ``E_i`` a
  SwiGLU at ``moe_intermediate_size``, **one expert at a time over a mask**,
  ``S`` one SwiGLU at ``moe_shared_expert_intermediate_size``. The clamp
  ``expert_swiglu_limit_list`` is 0 on every layer written down here.

**The cut.** ``vocab_size`` rows of the table and of the head are held (ids,
logits and loss over the slice), ``num_hidden_layers`` layers from published
layer ``first_layer`` on, ``num_attention_heads`` heads of every mixer (the
tensors arrive cut: ``wq``, ``wk``, ``wv``, ``wf``, ``wb``, ``wg``, the
convolutions, ``A_log``, ``dt_bias`` by the heads' columns, ``wo`` by their
rows; latent attention's ``wkv_a`` and ``kv_norm`` whole), and ``num_experts``
experts from ``first_expert`` on of the ``router_width`` the router scores: a
mixer's output is the partial sum its heads give, a routed layer's the held
experts' (the shared expert whole), and what the absent ones would add is
left out. The shares add up to the whole layer. With every head, expert, row
and layer there is no departure from the reading above.

Training's parts (*: the DeepSeek-V3 report's, as ``reference_kanana2.py``
has them): the sequence-wise balance term ``sum_i f_i P_i`` a sequence and
routed layer, ``f_i = E / (k T) x`` the pairs expert i received from the
sequence (the chosen pairs, bias included: a constant), ``P_i`` the
sequence's mean of ``s_i / sum_j s_j``, averaged over the sequences, summed
over the layers, added to the loss times ``alpha``; after a step a selection
bias rises by ``gamma`` where its expert's count is under its layer's mean
and falls by it where over (:func:`bias_after`).

``cfg["fault"]`` puts one wrong reading in the right one's place, for the
tests and the runner's controls that show a comparison sees it:
``decay_mean`` (a head's decay the mean of ``g`` over its channels),
``softplus`` (the gate ``-exp(A_log) softplus(.)``, the lower bound left
out), ``gate_by_channel`` (the gate's H scalars laid over the H dv channels
one after the other and not a head at a time), ``group_max`` (a group's score
its largest and not its two largest's sum), ``mla_no_gate``.

Weights are read through ``get(name, layer=None)``, which returns one stored
tensor of any float type (upcast here): ``embed`` [V, D], ``final_norm`` [D],
``lm_head`` [D, V]; per layer ``ln1``, ``ln2`` [D]; of a KDA layer ``wq``,
``wk``, ``wf`` [D, H dk], ``wv`` [D, H dv], ``wb``, ``wg`` [D, H],
``conv_q``, ``conv_k``, ``conv_v`` [K, width] (tap k meets position t - (K -
1) + k), ``A_log`` [H], ``dt_bias`` [H dk], ``o_norm`` [dv], ``wo`` [H dv,
D]; of a latent-attention layer ``wq`` [D, H (dn + dr)], ``wkv_a`` [D, r +
dr], ``kv_norm`` [r], ``wkv_b`` [r, H (dn + dv)], ``wg`` [D, H], ``wo`` [H
dv, D]; of a dense layer ``w_gate``, ``w_up`` [D, F], ``w_down`` [F, D]; of a
routed layer ``router`` [D, E], ``router_bias`` [E], ``w_gate``, ``w_up``
[held, D, Fm], ``w_down`` [held, Fm, D], ``shared_gate``, ``shared_up`` [D,
Fs], ``shared_down`` [Fs, D].
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
L2_EPS = 1e-6
MIXER = {"kda": ("wq", "wk", "wv", "wf", "wb", "wg", "conv_q", "conv_k",
                 "conv_v", "A_log", "dt_bias", "o_norm", "wo"),
         "mla": ("wq", "wkv_a", "kv_norm", "wkv_b", "wg", "wo")}
FFN = {"dense": ("w_gate", "w_up", "w_down"),
       "moe": ("router", "router_bias", "w_gate", "w_up", "w_down",
               "shared_gate", "shared_up", "shared_down")}
NORMS = ("ln1", "ln2")
#: what picks and gets no gradient
NO_GRADIENT = ("router_bias",)
FAULTS = ("decay_mean", "softplus", "gate_by_channel", "group_max",
          "mla_no_gate")


def kinds(cfg: Dict) -> Sequence[str]:
    """``"<mixer>:<ffn>"`` of each layer kept: published layers
    ``first_layer`` on, latent attention where ``(i + 1) %
    layer_group_size == 0``, the first ``first_k_dense_replace`` of the kept
    ones dense."""
    first, L = int(cfg.get("first_layer", 0)), int(cfg["num_hidden_layers"])
    period, dense = int(cfg["layer_group_size"]), \
        int(cfg["first_k_dense_replace"])
    return tuple(
        ("mla" if (first + i + 1) % period == 0 else "kda")
        + (":dense" if i < dense else ":moe") for i in range(L))


def tensors(kind: str) -> Sequence[str]:
    mixer, _, ffn = kind.partition(":")
    return NORMS + MIXER[mixer] + FFN[ffn]


def _fault(cfg: Dict) -> Optional[str]:
    fault = cfg.get("fault")
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r}: one of {FAULTS}")
    return fault


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


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


def l2_norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def recurrence(q, k, v, g, beta):
    """q, k [T, H, dk] (normed, q scaled), v [T, H, dv], g [T, H, dk] (each
    key channel's decay's logarithm) and beta [T, H] -> o [T, H, dv]:
    position by position over the state S [H, dk, dv]. (Where T allows, the
    positions are walked in runs of ``_STATE_BLOCK`` under
    ``jax.checkpoint``: a gradient then keeps one state a run and computes
    the run's again, and not 8192 states of [H, dk, dv]; the forward is the
    same steps in the same order.)"""
    T, H, dk = q.shape

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[:, :, None] * S               # the decay first
        wrote = b_t[:, None] * (v_t - jnp.einsum("hde,hd->he", S, k_t))
        S = S + k_t[:, :, None] * wrote[:, None, :]
        return S, jnp.einsum("hde,hd->he", S, q_t)

    start, xs = jnp.zeros((H, dk, v.shape[2]), F32), (q, k, v, g, beta)
    if T % _STATE_BLOCK:
        return jax.lax.scan(step, start, xs)[1]
    run = jax.checkpoint(lambda S, xs: jax.lax.scan(step, S, xs))
    _, o = jax.lax.scan(run, start, jax.tree_util.tree_map(
        lambda a: a.reshape(T // _STATE_BLOCK, _STATE_BLOCK, *a.shape[1:]),
        xs))
    return o.reshape(T, H, -1)


def decay_log(u, w: Dict, cfg: Dict):
    """``g`` [T, H, dk], the logarithm of each key channel's decay."""
    H, T = int(cfg["num_attention_heads"]), u.shape[0]
    fault = _fault(cfg)
    a = jnp.exp(w["A_log"])[:, None]
    z = (u @ w["wf"] + w["dt_bias"]).reshape(T, H, -1)
    if fault == "softplus":
        return -a * jax.nn.softplus(z)
    g = float(cfg["kda_lower_bound"]) * jax.nn.sigmoid(a * z)
    if fault == "decay_mean":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    return g


def head_gate(u, wg, cfg: Dict, dv: int):
    """sigmoid(u wg) [T, H] as the factor of each of the H dv channels, [T,
    H, dv]."""
    gate = jax.nn.sigmoid(u @ wg)                               # [T, H]
    T, H = gate.shape
    if _fault(cfg) == "gate_by_channel":
        return jnp.tile(gate, (1, dv)).reshape(T, H, dv)
    return jnp.broadcast_to(gate[..., None], (T, H, dv))


def kda_layer(u, w: Dict, cfg: Dict):
    """The KDA mixer on u [T, D] (already normed)."""
    H, d, T = int(cfg["num_attention_heads"]), int(cfg["head_dim"]), \
        u.shape[0]
    q, k, v = (jax.nn.silu(conv(u @ w[p], w[c])).reshape(T, H, d)
               for p, c in (("wq", "conv_q"), ("wk", "conv_k"),
                            ("wv", "conv_v")))
    o = recurrence(l2_norm(q) / math.sqrt(d), l2_norm(k), v,
                   decay_log(u, w, cfg), jax.nn.sigmoid(u @ w["wb"]))
    y = rms_norm(o, w["o_norm"], float(cfg["rms_norm_eps"])) \
        * head_gate(u, w["wg"], cfg, d)
    return y.reshape(T, H * d) @ w["wo"]


def rope_pairs(x, theta: float):
    """x [T, heads, dr] in the published layout: rotate each pair (2i, 2i +
    1) by ``t theta^(-2i/dr)``."""
    T, _, dr = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dr, 2, dtype=F32) / dr))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]      # [T, dr/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def attention(q, k, v):
    """q, k [T, H, dk], v [T, H, dv], causal, scores over sqrt(dk): a whole
    softmax, a block of queries after the other (``lax.map``: one block's
    scores live at a time, and a gradient computes a block's scores again
    from q, k and v and keeps none; 16 heads of 8192 x 8192 float32 would be
    4.3 GB)."""
    T, H, dk = q.shape
    n = T // _QUERY_BLOCK if T % _QUERY_BLOCK == 0 else 1
    kpos = jnp.arange(T)

    @jax.checkpoint
    def block(xs):
        qb, qpos = xs
        s = jnp.einsum("thd,shd->hts", qb, k) / math.sqrt(dk)
        s = jnp.where((kpos[None, :] <= qpos[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(block, (q.reshape(n, T // n, H, dk),
                              kpos.reshape(n, T // n)))
    return out.reshape(T, H, v.shape[-1])


def mla_layer(h, w: Dict, cfg: Dict):
    """The latent-attention mixer on the normed input h [T, D]."""
    H = int(cfg["num_attention_heads"])
    r, dn, dr, dv = (int(cfg["kv_lora_rank"]), int(cfg["qk_nope_head_dim"]),
                     int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]))
    T, theta = h.shape[0], float(cfg["rope_theta"])
    q = (h @ w["wq"]).reshape(T, H, dn + dr)
    ckv = h @ w["wkv_a"]
    c = rms_norm(ckv[:, :r], w["kv_norm"], float(cfg["rms_norm_eps"]))
    kv = (c @ w["wkv_b"]).reshape(T, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], rope_pairs(q[..., dn:], theta)], -1)
    k_rope = rope_pairs(ckv[:, None, r:], theta)                # [T, 1, dr]
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (T, H, dr))], axis=-1)
    o = attention(q, k, kv[..., dn:])
    if _fault(cfg) != "mla_no_gate":
        o = o * head_gate(h, w["wg"], cfg, dv)
    return o.reshape(T, H * dv) @ w["wo"]


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def router_width(cfg: Dict) -> int:
    return int(cfg.get("router_width") or cfg["num_experts"])


def held_experts(cfg: Dict) -> Sequence[int]:
    first = int(cfg.get("first_expert", 0))
    return range(first, first + int(cfg["num_experts"]))


def kept_groups(sb, cfg: Dict):
    """keep [T, n_group] bool: the ``topk_group`` groups whose two largest
    selection scores ``sb`` [T, E] sum highest (``lax.top_k``: of equal
    scores the lower group)."""
    n, kept = int(cfg.get("n_group", 1) or 1), int(cfg.get("topk_group", 1)
                                                    or 1)
    T, E = sb.shape
    grouped = sb.reshape(T, n, E // n)
    score = jnp.max(grouped, axis=-1) if _fault(cfg) == "group_max" \
        else jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    _, best = jax.lax.top_k(score, kept)
    return jnp.zeros((T, n), bool).at[jnp.arange(T)[:, None], best].set(True)


def route(x, router, bias, cfg: Dict):
    """(s [T, E] the sigmoid scores, the k chosen [T, k], their weights
    ``scale s_i / sum_chosen s`` [T, k], keep [T, n_group])."""
    k, E = int(cfg["num_experts_per_tok"]), router_width(cfg)
    s = jax.nn.sigmoid(x @ router)
    sb = s + bias
    keep = kept_groups(sb, cfg)
    n = keep.shape[1]
    if n > 1:
        sb = jnp.where(jnp.repeat(keep, E // n, axis=1), sb, -jnp.inf)
    _, top_e = jax.lax.top_k(sb, k)
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    return s, top_e, float(cfg["routed_scaling_factor"]) * top_s \
        / jnp.sum(top_s, axis=-1, keepdims=True), keep


def experts(x, w: Dict, cfg: Dict, held: Optional[Sequence[int]] = None,
            shared: bool = True):
    """The routed FFN on x [T, D] (already normed) for the experts ``held``
    (a list of expert indices, ``w["w_gate"][j]`` the j-th of them; default
    the configuration's share), one at a time over a mask, plus (``shared``)
    the shared expert: ``(the sum, counts [E] the pairs every routed expert
    received, the sequence's balance term sum_i f_i P_i, the tokens that
    kept each group [n_group])``."""
    held = list(held_experts(cfg) if held is None else held)
    k, E = int(cfg["num_experts_per_tok"]), router_width(cfg)
    s, top_e, top_w, keep = route(x, w["router"], w["router_bias"], cfg)
    out = jnp.zeros_like(x)
    for j, e in enumerate(held):
        weight = jnp.sum(jnp.where(top_e == e, top_w, 0.0), axis=-1)
        out = out + weight[:, None] * swiglu(x, w["w_gate"][j], w["w_up"][j],
                                             w["w_down"][j])
    if shared:
        out = out + swiglu(x, w["shared_gate"], w["shared_up"],
                           w["shared_down"])
    counts = jnp.sum(jax.nn.one_hot(top_e, E, dtype=F32), axis=(0, 1))
    f = jax.lax.stop_gradient(counts) * (E / (k * x.shape[0]))
    p = jnp.mean(s / jnp.sum(s, axis=-1, keepdims=True), axis=0)
    return out, counts, jnp.sum(f * p), jnp.sum(keep.astype(F32), axis=0)


def mixer(u, w: Dict, cfg: Dict, kind: str):
    return kda_layer(u, w, cfg) if kind.startswith("kda") \
        else mla_layer(u, w, cfg)


def block(x, w: Dict, cfg: Dict, kind: str):
    """One layer on x [T, D] float32: ``(y, the mixer output's mean square,
    counts [E], the balance term, groups kept [n_group])``; a dense layer's
    counts, term and groups are zeros."""
    eps = float(cfg["rms_norm_eps"])
    mix = mixer(rms_norm(x, w["ln1"], eps), w, cfg, kind)
    h = x + mix
    g = rms_norm(h, w["ln2"], eps)
    counts, term = jnp.zeros((router_width(cfg),), F32), jnp.zeros((), F32)
    groups = jnp.zeros((int(cfg.get("n_group", 1) or 1),), F32)
    if kind.endswith(":dense"):
        out = swiglu(g, w["w_gate"], w["w_up"], w["w_down"])
    else:
        out, counts, term, groups = experts(g, w, cfg)
    return h + out, jnp.mean(mix * mix), counts, term, groups


def head_nll(x, norm, head, tokens, eps):
    """``nll`` [T - 1]: the cross-entropy of each position's logits (the
    final norm, then ``head`` [D, V]) against the next token."""
    lg = (rms_norm(x, norm, eps) @ head)[:-1]
    gold = jnp.take_along_axis(lg, tokens[1:, None], axis=-1)[:, 0]
    return jax.scipy.special.logsumexp(lg, axis=-1) - gold


def _f32(t):
    return jnp.asarray(t).astype(F32)


def sequence(cfg: Dict, get: Callable, tokens) -> Dict:
    """One sequence [T] through the model: ``nll`` [T - 1], by layer
    ``mix_out_ms`` [L], and by routed layer ``counts`` [Lr, E], ``term``
    [Lr] and ``groups`` [Lr, n_group]."""
    block_jit = jax.jit(lambda x, w, kind: block(
        x, {n: t.astype(F32) for n, t in w.items()}, cfg, kind),
        static_argnums=2)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = _f32(get("embed"))[tokens]
        ms, counts, terms, groups = [], [], [], []
        for i, kind in enumerate(kinds(cfg)):
            x, m, c, t, g = block_jit(
                x, {n: jnp.asarray(get(n, i)) for n in tensors(kind)}, kind)
            ms.append(m)
            if kind.endswith(":moe"):
                counts.append(c)
                terms.append(t)
                groups.append(g)
        nll = head_nll(x, _f32(get("final_norm")), _f32(get("lm_head")),
                       tokens, float(cfg["rms_norm_eps"]))
    return {"nll": nll, "mix_out_ms": jnp.stack(ms),
            "counts": jnp.stack(counts), "term": jnp.stack(terms),
            "groups": jnp.stack(groups)}


def _parts(cfg: Dict, ce, ms, counts, terms, groups, alpha: float,
           rows: int) -> Dict:
    lb = jnp.sum(terms) / rows
    held = jnp.asarray(list(held_experts(cfg)))
    return {"loss": ce + alpha * lb, "ce": ce, "lb_loss": lb,
            "mix_out_ms": ms / rows, "router_counts": counts,
            "expert_pairs": counts[:, held], "groups_kept": groups}


def batch_loss(cfg: Dict, get: Callable, rows, alpha: float) -> Dict:
    """The loss of a micro-batch ``rows`` [B, T] and its parts: ``loss`` =
    ``ce`` + alpha x ``lb_loss``; ``ce`` the mean cross-entropy over the
    B x (T - 1) targets; ``lb_loss`` the balance term, each routed layer's
    the mean over the sequences, summed over the layers; ``mix_out_ms`` [L]
    the mixer output's mean square over all B x T positions;
    ``router_counts`` [Lr, E] the pairs every routed expert received,
    ``expert_pairs`` [Lr, held] those of the experts held here and
    ``groups_kept`` [Lr, n_group] the tokens that kept each group."""
    per_row = [sequence(cfg, get, row) for row in rows]
    return _parts(cfg, jnp.mean(jnp.concatenate([r["nll"] for r in per_row])),
                  sum(r["mix_out_ms"] for r in per_row),
                  sum(r["counts"] for r in per_row),
                  sum(r["term"] for r in per_row),
                  sum(r["groups"] for r in per_row), alpha, len(per_row))


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
    layer's balance term enters with ``alpha`` over the rows). The same
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

    forward = jax.jit(lambda x, w, kind: block(x, w, cfg, kind),
                      static_argnums=2)

    def back(x, w, dy, kind):
        def f(x, w):
            y, _, _, term, _ = block(x, w, cfg, kind)
            return y, term
        return jax.vjp(f, x, w)[1]((dy, jnp.asarray(alpha / len(rows), F32)))

    back = jax.jit(back, static_argnums=3)
    head = jax.jit(jax.value_and_grad(
        lambda x, norm, head, tokens:
        jnp.sum(head_nll(x, norm, head, tokens, eps)) / targets,
        argnums=(0, 1, 2)))
    partial: Dict = {}
    ce, ms, counts, terms, groups = 0.0, 0.0, 0.0, 0.0, 0.0
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
            xs, row_ms, row_counts, row_terms, row_groups = \
                [table[tokens]], [], [], [], []
            for i, kind in enumerate(ks):
                y, m, c, t, g = forward(
                    xs[-1], {n: _f32(get(n, i)) for n in tensors(kind)}, kind)
                xs.append(y)
                row_ms.append(m)
                if kind.endswith(":moe"):
                    row_counts.append(c)
                    row_terms.append(t)
                    row_groups.append(g)
            part, (dx, d_norm, d_head) = head(
                xs.pop(), _f32(get("final_norm")), _f32(get("lm_head")),
                tokens)
            give("final_norm", None, d_norm)
            give("lm_head", None, d_head)
            for i in reversed(range(len(ks))):
                dx, dw = back(xs.pop(), {n: _f32(get(n, i))
                                         for n in tensors(ks[i])}, dx, ks[i])
                for n, g in dw.items():
                    if n not in NO_GRADIENT:
                        give(n, i, g)
            give("embed", None, jnp.zeros_like(table).at[tokens].add(dx))
            ce, ms = ce + part, ms + jnp.stack(row_ms)
            counts = counts + jnp.stack(row_counts)
            terms = terms + jnp.stack(row_terms)
            groups = groups + jnp.stack(row_groups)
    return _parts(cfg, ce, ms, counts, terms, groups, alpha, len(rows)), held


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
