"""Plain reference of the two blocks the benchmark runs: the dense
(Mistral) and the top-2 sparse (Mixtral) decoder, forward and loss.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernels, no cache, no batching,
one sequence at a time. It follows the published model descriptions:

* RMSNorm, pre-norm residual blocks, untied output head;
* grouped-query attention with rotary embeddings in the half-split
  ("rotate_half") convention of the published checkpoints, causal, with an
  optional sliding window that keeps the last ``window`` keys of a query;
* SwiGLU feed-forward ``down(silu(gate(x)) * up(x))``;
* Mixtral routing: softmax over all experts, keep the top k, renormalise
  the kept weights to sum to one, sum the experts' outputs.

It reads weights through a callable ``get(name, layer, expert)`` that returns
one stored tensor, so that the bf16 weights the system itself holds are upcast
one layer (one expert) at a time; see :func:`forward` for the names.

Departures from the publications: none in the mathematics. Attention is
evaluated in blocks of queries so that a 4096-token sequence does not
materialise 32 full score matrices at once; the result is the same.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
_QUERY_BLOCK = 512


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rope(x, positions, theta):
    """x [T, heads, d]; rotate pairs (j, j + d/2) by positions * theta^(-2j/d)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv[None, :]        # [T, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window: Optional[int]):
    """q [T, H, d], k/v [T, K, d], all positions 0..T-1; causal (+window)."""
    T, H, d = q.shape
    K = k.shape[1]
    rep = H // K
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    kpos = jnp.arange(T)
    outs = []
    for lo in range(0, T, _QUERY_BLOCK):
        qb = q[lo:lo + _QUERY_BLOCK]
        qpos = jnp.arange(lo, lo + qb.shape[0])
        s = jnp.einsum("thd,shd->hts", qb, k) / jnp.sqrt(F32(d))
        keep = kpos[None, :] <= qpos[:, None]
        if window is not None:
            keep = keep & (kpos[None, :] > qpos[:, None] - window)
        s = jnp.where(keep[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hts,shd->thd", p, v))
    return jnp.concatenate(outs, axis=0)


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def top_k_experts(x, get: Callable, i: int, cfg: Dict):
    """Mixtral sparse block on x [T, D]. Every expert is evaluated densely
    on every token and masked by its routing weight: slow and obviously
    right. Experts are fetched (and upcast) one at a time."""
    top_k = int(cfg["num_experts_per_tok"])
    gates = jax.nn.softmax(x @ _f32(get("router", i)), axis=-1)   # [T, E]
    vals, idx = jax.lax.top_k(gates, top_k)
    vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(int(cfg["num_local_experts"])):
        weight = jnp.sum(jnp.where(idx == e, vals, 0.0), axis=-1)  # [T]
        y = _swiglu_jit(x, get("w_gate", i, e), get("w_up", i, e),
                        get("w_down", i, e))
        out = out + weight[:, None] * y
    return out


def _f32(t):
    return jnp.asarray(t).astype(F32)


@jax.jit
def _swiglu_jit(x, w_gate, w_up, w_down):
    return swiglu(x, w_gate.astype(F32), w_up.astype(F32),
                  w_down.astype(F32))


def attention_half(x, w: Dict, cfg: Dict, positions):
    """x + attention(norm(x)) for one layer, x [T, D] float32."""
    H = int(cfg["num_attention_heads"])
    K = int(cfg["num_key_value_heads"])
    d = int(cfg.get("head_dim") or cfg["hidden_size"] // H)
    eps = float(cfg["rms_norm_eps"])
    T = x.shape[0]
    h = rms_norm(x, w["ln1"], eps)
    q = rope((h @ w["wq"]).reshape(T, H, d), positions, cfg["rope_theta"])
    k = rope((h @ w["wk"]).reshape(T, K, d), positions, cfg["rope_theta"])
    v = (h @ w["wv"]).reshape(T, K, d)
    a = attention(q, k, v, cfg.get("sliding_window"))
    return x + a.reshape(T, H * d) @ w["wo"]


def forward(cfg: Dict, get: Callable, tokens, keep_last: Optional[int] = None):
    """Logits [T or keep_last, V] in float32 for one sequence of token ids.

    ``get(name, layer=None, expert=None)`` returns one stored tensor (any
    float type; upcast here, one layer or one expert at a time, and dropped
    after use): ``embed`` [V, D], ``final_norm`` [D], ``head`` [D, V], and
    per layer ``ln1``, ``ln2``, ``wq``, ``wk``, ``wv``, ``wo``, then either
    ``w_gate``, ``w_up``, ``w_down`` or, when sparse, ``router`` and the
    same three per expert."""
    sparse = int(cfg.get("num_local_experts", 1) or 1) > 1
    eps = float(cfg["rms_norm_eps"])
    attn_jit = jax.jit(lambda x, w, pos: attention_half(
        x, {n: t.astype(F32) for n, t in w.items()}, cfg, pos))
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        pos = jnp.arange(tokens.shape[0])
        x = _f32(jnp.asarray(get("embed"))[tokens])
        for i in range(int(cfg["num_hidden_layers"])):
            x = attn_jit(x, {n: jnp.asarray(get(n, i)) for n in
                             ("ln1", "wq", "wk", "wv", "wo")}, pos)
            h = rms_norm(x, _f32(get("ln2", i)), eps)
            if sparse:
                x = x + top_k_experts(h, get, i, cfg)
            else:
                x = x + _swiglu_jit(h, get("w_gate", i), get("w_up", i),
                                    get("w_down", i))
        if keep_last:
            x = x[-keep_last:]
        x = rms_norm(x, _f32(get("final_norm")), eps)
        return x @ _f32(get("head"))


def next_token_loss(logits, tokens):
    """Mean cross-entropy of logits[t] against tokens[t + 1]."""
    tokens = jnp.asarray(tokens, jnp.int32)
    lg = logits[:-1].astype(F32)
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, tokens[1:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)
