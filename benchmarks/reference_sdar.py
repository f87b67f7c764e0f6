"""Plain reference of SDAR-30B-A3B-Chat (``model_type: "sdar_moe"``) trained
by block diffusion: forward over the ``[noised ; clean]`` row, the weighted
loss with the router's balance term, the router's counts, and gradients.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernels, no cache, no batching,
no tiles but blocks of queries (``lax.map`` under ``jax.checkpoint``: one
block's ``[heads, 256, 2L]`` scores and mask bits live at a time, so that a
row of 16,384 positions fits), the mask as booleans from the four lines
below, a whole softmax, every held expert applied to every position. It
imports nothing but JAX. Written from the equations below, not from the
program.

The model, from the published ``config.json`` (hidden 2,048, 48 layers of one
kind, 32 query and 4 key-value heads of 128, ``rope_theta`` 1e6 without
scaling, 128 experts of width 768, 8 a token, ``norm_topk_prob``, RMSNorm eps
1e-6, untied head; no biases). A layer on a row of ``P`` positions with
positions ``p``::

    u   = rmsnorm(h; g1)
    q   = u Wq -> [P, 32, 128]   k = u Wk -> [P, 4, 128]   v = u Wv -> [P, 4, 128]
    q   = rope(rmsnorm_128(q; gq), p)   k = rope(rmsnorm_128(k; gk), p)
          (theta 1e6, all 128 channels, half-split pairs: pair i is channels
          i and i + 64; the per-head norm shares one scale of 128 over the heads)
    a_i = sum_j softmax_j(q_i . k_j / sqrt(128) | M_ij) v_j     head h reads key-value head h // 8
    h'  = h + a Wo
    u2  = rmsnorm(h'; g2)
    s   = softmax_128(u2 Wr);  E_i = the 8 largest;  w_e = s_e / sum_{E_i} s
    h'' = h' + sum_{e in E_i} w_e (silu(u2 W1_e) * (u2 W3_e)) W2_e

then the final RMSNorm and the head.

Training by block diffusion, block length ``B``, a row of ``L`` tokens ``x0``,
``nb = L / B`` blocks, ``beta(i) = (i mod L) // B``. The noising is the
batch's (``xt`` and the weights ``w_i = [xt_i == MASK] / t_beta(i)`` arrive
with it); the row, its positions, the mask and the loss are::

    row  = [xt ; x0]            2L positions;  p = [0..L-1 ; 0..L-1]
    M_ij (i the query, j the key; "noised" means index < L):
        noised i, noised j :  beta(j) == beta(i)
        noised i, clean  j :  beta(j) <  beta(i)
        clean  i, noised j :  never
        clean  i, clean  j :  beta(j) <= beta(i)
    loss = (1 / (rows L)) sum_rows sum_{i < L} w_i CE(logits_i, x0_i)     no shift; the noised half's logits
         + alpha x the balance term, E sum_e mean_t s[t,e] mean_t [argmax_e' s[t,e'] = e]
           over all rows x 2L positions, summed over layers (the counts are
           constants to the gradient)

**The share.** The configuration may hold a share of the experts
(``num_experts`` of them from ``first_expert`` on, of ``router_width``
routed) and a slice of the vocabulary (``vocab_size`` rows): the router, the
top k and their weights are the whole model's, the sum runs over the held
experts only and what the absent ones would add is left out; logits and loss
are over the slice. The program makes the same departure; with every expert
held there is none.

What the published file does not say is listed in the configuration file
under ``assumed`` (the per-head norm, the block length, the schedule, one
``t`` a block, no shift, the normaliser, the mask token). :data:`FAULTS` are
readings this file can take instead, one at a time (``cfg["fault"]``): what a
check has to tell from the model.

Weights are read through ``get(name, layer=None)``: ``embed`` [V, D],
``final_norm`` [D], ``lm_head`` [D, V], and per layer ``ln1``, ``ln2`` [D],
``wq`` [D, H d], ``wk``, ``wv`` [D, K d], ``wo`` [H d, D], ``q_norm``,
``k_norm`` [d], ``router`` [D, E], ``w_gate``, ``w_up`` [held, D, F],
``w_down`` [held, F, D].
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
_QUERY_BLOCK = 256
#: the first positions of each half whose mixer output a check reads on its
#: own (``early_ms``): there a block's 4 keys are a large share of a query's
#: set, so a mask that is off by a block shows
EARLY = 64
LAYER_TENSORS = ("ln1", "ln2", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
                 "router", "w_gate", "w_up", "w_down")
#: readings that are not the model: each leaves the check not correct
FAULTS = {
    "mask_token_causal": "the clean half causal by token (j <= i), not by "
                         "block",
    "mask_leak": "the noised queries also read their own clean block "
                 "(beta(j) <= beta(i)): the fault that lets a diffusion "
                 "loss collapse",
    "no_own_block": "the noised queries do not read their own noised block",
    "positions_unrepeated": "the clean half at positions L..2L-1",
    "loss_unweighted": "every masked position weighs 1, not 1 / t",
    "loss_on_clean_half": "the loss reads the clean half's logits"}


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rope(x, positions, theta: float):
    """x [P, heads, d], positions [P]: pair i (channels i, i + d/2) turns by
    ``positions theta^(-2i/d)``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mask_rows(qpos, P: int, L: Optional[int], B: int,
              fault: Optional[str] = None):
    """``M[qpos, :]`` as booleans [len(qpos), P]. ``L`` None: a plain row
    of ``P`` positions, block-causal (what a model that decodes a block at a
    time sees); else the ``[noised ; clean]`` row of ``P = 2L``."""
    kpos = jnp.arange(P)
    if L is None:
        return (kpos[None, :] // B) <= (qpos[:, None] // B)
    q_noised, k_noised = (qpos < L)[:, None], (kpos < L)[None, :]
    q_tok, k_tok = (qpos % L)[:, None], (kpos % L)[None, :]
    q_beta, k_beta = q_tok // B, k_tok // B
    own = k_beta == q_beta
    before = k_beta <= q_beta if fault == "mask_leak" else k_beta < q_beta
    upto = k_tok <= q_tok if fault == "mask_token_causal" \
        else k_beta <= q_beta
    if fault == "no_own_block":
        own = jnp.zeros_like(own)
    return jnp.where(q_noised, jnp.where(k_noised, own, before),
                     ~k_noised & upto)


def masked_attention(q, k, v, L: Optional[int], B: int,
                     fault: Optional[str] = None):
    """o [P, H, d] for q [P, H, d], k, v [P, K, d] under :func:`mask_rows`;
    a query with no key (a fault's) gives 0."""
    P, H, d = q.shape
    K = k.shape[1]
    k = jnp.repeat(k, H // K, axis=1)
    v = jnp.repeat(v, H // K, axis=1)
    n = P // _QUERY_BLOCK if P % _QUERY_BLOCK == 0 else 1

    @jax.checkpoint
    def block(xs):
        qb, qpos = xs
        m = mask_rows(qpos, P, L, B, fault)
        s = jnp.einsum("thd,shd->hts", qb, k) / math.sqrt(d)
        s = jnp.where(m[None], s, -jnp.inf)
        top = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.where(m[None], jnp.exp(s - jnp.where(
            jnp.isfinite(top), top, 0.0)), 0.0)
        den = jnp.sum(e, axis=-1, keepdims=True)
        a = e / jnp.where(den > 0, den, 1.0)
        return jnp.einsum("hts,shd->thd", a, v)

    o = jax.lax.map(block, (q.reshape(n, P // n, H, d),
                            jnp.arange(P).reshape(n, P // n)))
    return o.reshape(P, H, d)


def route(x, router, k: int):
    """(s [P, E] the softmax over all routed experts, the k chosen [P, k],
    their weights renormalised to sum 1 [P, k])."""
    p = jax.nn.softmax(x @ router, axis=-1)
    top_p, top_e = jax.lax.top_k(p, k)
    return p, top_e, top_p / jnp.sum(top_p, axis=-1, keepdims=True)


def experts(x, w: Dict, cfg: Dict):
    """The held experts' part of the layer on x [P, D]: ``(sum over the held
    experts, sum_t s [E], sum_t [top-1 = e] [E], the pairs each held expert
    received [held])``."""
    held = int(cfg["num_experts"])
    first = int(cfg.get("first_expert", 0))
    E = int(cfg.get("router_width") or held)
    p, top_e, top_w = route(x, w["router"], int(cfg["num_experts_per_tok"]))
    out = jnp.zeros_like(x)
    pairs = []
    for i in range(held):
        chosen = top_e == first + i                             # [P, k]
        weight = jnp.sum(jnp.where(chosen, top_w, 0.0), axis=-1)
        y = (jax.nn.silu(x @ w["w_gate"][i]) * (x @ w["w_up"][i])) \
            @ w["w_down"][i]
        out = out + weight[:, None] * y
        pairs.append(jnp.sum(chosen))
    top1 = jax.nn.one_hot(jnp.argmax(p, axis=-1), E, dtype=F32)
    return out, p.sum(axis=0), jax.lax.stop_gradient(top1.sum(axis=0)), \
        jnp.stack(pairs)


def mixer(x, w: Dict, cfg: Dict, L: Optional[int]):
    """A layer's attention branch on x [P, D]: ``mix`` [P, D], what the
    branch adds to the residual stream."""
    H, K = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d, eps = int(cfg["head_dim"]), float(cfg["rms_norm_eps"])
    theta, P = float(cfg["rope_theta"]), x.shape[0]
    B, fault = int(cfg["block_length"]), cfg.get("fault")
    pos = jnp.arange(P)
    if L is not None and fault != "positions_unrepeated":
        pos = pos % L
    u = rms_norm(x, w["ln1"], eps)
    q = rope(rms_norm((u @ w["wq"]).reshape(P, H, d), w["q_norm"], eps),
             pos, theta)
    k = rope(rms_norm((u @ w["wk"]).reshape(P, K, d), w["k_norm"], eps),
             pos, theta)
    v = (u @ w["wv"]).reshape(P, K, d)
    o = masked_attention(q, k, v, L, B, fault)
    return o.reshape(P, H * d) @ w["wo"]


def block(x, w: Dict, cfg: Dict, L: Optional[int]):
    """One layer on x [P, D] float32, the ``[noised ; clean]`` row of
    ``P = 2L`` (``L`` None: a plain row): ``(y, sum_t s [E], sum_t top-1
    [E], pairs [held], the mixer output's mean square, its mean square over
    the first EARLY positions of each half [2])``."""
    eps, P = float(cfg["rms_norm_eps"]), x.shape[0]
    mix = mixer(x, w, cfg, L)
    a = x + mix
    y, gate_sum, top1_sum, pairs = experts(rms_norm(a, w["ln2"], eps), w, cfg)
    half = P if L is None else L
    n = min(EARLY, half)
    early = jnp.stack([jnp.mean(jnp.square(mix[:n])),
                       jnp.mean(jnp.square(mix[half:half + n]))
                       if L is not None else jnp.zeros((), F32)])
    return a + y, gate_sum, top1_sum, pairs, jnp.mean(jnp.square(mix)), early


def _f32(t):
    return jnp.asarray(t).astype(F32)


def deal(counts, shares: int):
    """Experts dealt to ``shares`` shares by load, as an expert-parallel
    load balancer places them from observed counts: ranked by ``counts``
    [E], heaviest first, and dealt out in a snake (ranks 0..7 one to each
    share, 8..15 back again), so that every share's load is the mean's but
    for the last few experts' scatter. Returns ``src`` [E]: the expert that
    stands at index j afterwards, share s holding indices ``[s E / shares,
    (s + 1) E / shares)``."""
    order = [int(e) for e in jnp.argsort(-jnp.asarray(counts), stable=True)]
    held = len(order) // shares
    src = [0] * len(order)
    for rank, expert in enumerate(order):
        turn, at = divmod(rank, shares)
        share = at if turn % 2 == 0 else shares - 1 - at
        src[share * held + turn] = expert
    return src


def place_experts(cfg: Dict, get: Callable, batch: Dict, shares: int):
    """Where a deployment that balances its expert-parallel shares by load
    would put each layer's experts, for the weights ``get`` returns and the
    micro-batch ``batch``: ``[layers][E]``, :func:`deal`'s ``src`` of each
    layer's router columns. One pass through the layers in float32: a
    layer's routing counts come from the residual stream the layers before
    it leave **as placed** (the held experts' partial sums reach the next
    layer's router), so the placement of layer ``i`` is made before its
    experts run.

    Why it matters here: a masked position's router input is led by the
    mask token's embedding, the same for every masked position, so a
    quarter of a row's positions send their pairs to the same 8 experts of
    a layer; whether the 16 experts a chip holds by index include 0, 1 or 3
    of them moves the chip's pairs by 4,096 each (72,000-99,000 pairs a
    step read over 12 seeds, the step's time with them), and a deployment
    would not leave that to the indices."""
    eps, nl = float(cfg["rms_norm_eps"]), int(cfg["num_hidden_layers"])
    E, k = int(cfg["router_width"]), int(cfg["num_experts_per_tok"])
    rows, L = batch["input_ids"].shape
    attend = jax.jit(lambda x, w: x + mixer(x, w, cfg, L))
    count = jax.jit(lambda a, ln2, router: jnp.zeros(E, jnp.int32).at[
        route(rms_norm(a, ln2, eps), router, k)[1].reshape(-1)].add(1))
    ffn = jax.jit(lambda a, w: a + experts(rms_norm(a, w["ln2"], eps), w,
                                           cfg)[0])
    placed = []
    with jax.default_matmul_precision("highest"):
        xs = [_f32(get("embed"))[_row(batch, r, cfg)[0]] for r in range(rows)]
        for i in range(nl):
            w = {t: _f32(get(t, i)) for t in LAYER_TENSORS}
            after = [attend(x, w) for x in xs]
            src = deal(sum(count(a, w["ln2"], w["router"]) for a in after),
                       shares)
            placed.append(src)
            w["router"] = w["router"][:, jnp.asarray(src)]
            xs = [ffn(a, w) for a in after]
    return placed


def head_logits(x, norm, head, eps):
    return rms_norm(x, norm, eps) @ head


def weighted_nll(x, norm, head, targets, weights, eps):
    """``sum_i w_i CE(logits_i, targets_i)`` over x [L, D]."""
    lg = head_logits(x, norm, head, eps)
    gold = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(weights * (jax.scipy.special.logsumexp(lg, axis=-1)
                              - gold))


def _row(batch: Dict, r: int, cfg: Dict):
    """``(row ids [2L], x0 [L], weights [L])`` of row ``r`` of a batch of
    ``input_ids``, ``noised_ids``, ``loss_weights``."""
    x0 = jnp.asarray(batch["input_ids"][r], jnp.int32)
    xt = jnp.asarray(batch["noised_ids"][r], jnp.int32)
    w = _f32(batch["loss_weights"][r])
    if cfg.get("fault") == "loss_unweighted":
        w = (w > 0).astype(F32)
    return jnp.concatenate([xt, x0]), x0, w


def _read_half(x, L: int, cfg: Dict):
    """The half of the last layer's output the head reads."""
    return x[L:] if cfg.get("fault") == "loss_on_clean_half" else x[:L]


def plain_logits(cfg: Dict, get: Callable, ids) -> jax.Array:
    """The logits [T, V] of a plain row ``ids`` [T] (a whole number of
    blocks) at positions ``0..T-1`` under the block-causal mask: the model as
    it decodes, block ``b`` reading the blocks up to its own. What ties the
    training row to ``p(x^b | x_t^b, x^{<b})``: at its last block this gives
    what the ``2L`` row gives at the noised block of the same content."""
    eps, nl = float(cfg["rms_norm_eps"]), int(cfg["num_hidden_layers"])
    with jax.default_matmul_precision("highest"):
        x = _f32(get("embed"))[jnp.asarray(ids, jnp.int32)]
        for i in range(nl):
            x = block(x, {t: _f32(get(t, i)) for t in LAYER_TENSORS}, cfg,
                      None)[0]
        return head_logits(x, _f32(get("final_norm")), _f32(get("lm_head")),
                           eps)


def row_logits(cfg: Dict, get: Callable, batch: Dict, r: int = 0):
    """The noised half's logits [L, V] of row ``r`` of ``batch``."""
    eps, nl = float(cfg["rms_norm_eps"]), int(cfg["num_hidden_layers"])
    tokens, x0, _ = _row(batch, r, cfg)
    with jax.default_matmul_precision("highest"):
        x = _f32(get("embed"))[tokens]
        for i in range(nl):
            x = block(x, {t: _f32(get(t, i)) for t in LAYER_TENSORS}, cfg,
                      x0.shape[0])[0]
        return head_logits(_read_half(x, x0.shape[0], cfg),
                           _f32(get("final_norm")), _f32(get("lm_head")), eps)


def batch_loss(cfg: Dict, get: Callable, batch: Dict, alpha: float) -> Dict:
    """The loss of a micro-batch (``input_ids``, ``noised_ids``,
    ``loss_weights``, each [rows, L]) and its parts: ``loss = ce + alpha
    lb_loss``; ``ce`` the weighted cross-entropy over rows x L; ``lb_loss``
    the balance term summed over the layers, each layer's over all rows x 2L
    positions; ``expert_pairs`` [layers, held]; ``mix_out_ms`` [layers];
    ``early_ms`` [layers, 2] (the mixer output's mean square over the first
    :data:`EARLY` positions of the noised and of the clean half)."""
    eps, nl = float(cfg["rms_norm_eps"]), int(cfg["num_hidden_layers"])
    rows, L = batch["input_ids"].shape
    n = rows * 2 * L
    fwd = jax.jit(lambda x, w: block(x, w, cfg, L))
    gate = top1 = pairs = ms = early = 0.0
    nll = 0.0
    with jax.default_matmul_precision("highest"):
        for r in range(rows):
            tokens, x0, w = _row(batch, r, cfg)
            x = _f32(get("embed"))[tokens]
            st = []
            for i in range(nl):
                x, *rest = fwd(x, {t: _f32(get(t, i)) for t in LAYER_TENSORS})
                st.append(rest)
            gate = gate + jnp.stack([s[0] for s in st])
            top1 = top1 + jnp.stack([s[1] for s in st])
            pairs = pairs + jnp.stack([s[2] for s in st])
            ms = ms + jnp.stack([s[3] for s in st]) / rows
            early = early + jnp.stack([s[4] for s in st]) / rows
            nll = nll + weighted_nll(
                _read_half(x, L, cfg), _f32(get("final_norm")),
                _f32(get("lm_head")), x0, w, eps)
    lb = jnp.sum(gate * top1, axis=-1) / (n * n) * gate.shape[-1]  # [layers]
    ce = nll / (rows * L)
    return {"loss": ce + alpha * jnp.sum(lb), "ce": ce,
            "lb_loss": jnp.sum(lb), "expert_pairs": pairs, "mix_out_ms": ms,
            "early_ms": early, "top1": top1}


def loss_and_grads(cfg: Dict, weights: Dict, batch: Dict, alpha: float):
    """``(loss, d loss / d weights)`` by ``jax.grad`` of the whole;
    ``weights`` a dict of float32 arrays keyed ``(name, layer)``, ``(name,
    None)`` for what no layer owns. For small sizes."""
    def loss(w):
        return batch_loss(cfg, lambda name, layer=None: w[(name, layer)],
                          batch, alpha)["loss"]

    return jax.value_and_grad(loss)(weights)


def batch_loss_and_grads(cfg: Dict, get: Callable, batch: Dict, alpha: float,
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
    out = batch_loss(cfg, get, batch, alpha)
    eps, nl = float(cfg["rms_norm_eps"]), int(cfg["num_hidden_layers"])
    rows, L = batch["input_ids"].shape
    n = rows * 2 * L
    top1 = out.pop("top1")                                      # [layers, E]
    held: Dict = {}
    if sink is None:
        def sink(name, layer, grad):
            held[(name, layer)] = grad

    forward = jax.jit(lambda x, w: block(x, w, cfg, L)[0])

    @jax.jit
    def back(x, w, dy, d_gate):
        def f(x, w):
            y, gate_sum, *_ = block(x, w, cfg, L)
            return y, gate_sum
        return jax.vjp(f, x, w)[1]((dy, d_gate))

    head = jax.jit(jax.value_and_grad(
        lambda x, norm, head, targets, weights:
        weighted_nll(_read_half(x, L, cfg), norm, head, targets, weights,
                     eps) / (rows * L),
        argnums=(0, 1, 2)))
    partial: Dict = {}
    with jax.default_matmul_precision("highest"):
        for r in range(rows):
            def give(name, layer, grad):
                grad = partial.pop((name, layer), 0.0) + grad
                if r == rows - 1:
                    sink(name, layer, grad)
                else:
                    partial[(name, layer)] = grad

            tokens, x0, w = _row(batch, r, cfg)
            table = _f32(get("embed"))
            xs = [table[tokens]]
            for i in range(nl):
                xs.append(forward(xs[-1], {t: _f32(get(t, i))
                                           for t in LAYER_TENSORS}))
            _, (dx, d_norm, d_head) = head(
                xs.pop(), _f32(get("final_norm")), _f32(get("lm_head")),
                x0, w)
            give("final_norm", None, d_norm)
            give("lm_head", None, d_head)
            for i in reversed(range(nl)):
                d_gate = alpha * top1[i] * top1.shape[-1] / (n * n)
                dx, dw = back(xs.pop(), {t: _f32(get(t, i))
                                         for t in LAYER_TENSORS}, dx, d_gate)
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
