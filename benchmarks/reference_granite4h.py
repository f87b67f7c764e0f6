"""Plain reference of Granite-4.0-H (``model_type: "granitemoehybrid"`` with
no routed expert): forward, the loss over the vocabulary held, each layer's
mixer-output mean square, and gradients by ``jax.grad``.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernels, no chunks, no cache, no
batching, no recomputation; one sequence at a time, one layer at a time (so
that it fits on the chip beside the program's state). It imports nothing but
JAX. ``tests/unit/granite_reference.py`` is a copy of this file, kept with the
program's tests; ``benchmarks/tests/test_granite4h.py`` holds the two equal.

The model, from the published ``config.json``:

* ``x0 = embedding_multiplier * E[ids]``; a layer: ``a = x +
  residual_multiplier * Mix_kind(RMSNorm(x))``, ``y = a + residual_multiplier
  * MLP(RMSNorm(a))``; ``logits = (RMSNorm(x_L) E^T) / logits_scaling`` (tied
  table); ``MLP(h) = W_down (silu(W_gate h) * W_up h)`` (the "shared" SwiGLU
  MLP, the published ``input_linear`` as its two halves).
* an ``attention`` layer: q, k, v without bias and **without rope**
  (``position_embedding_type: "nope"``), each key-value head serving H / K
  query heads, causal, ``softmax(q k^T * attention_multiplier) v``, then
  ``W_o``. The multiplier is the published 0.015625, not ``1 / sqrt(d)``.
* a ``mamba`` layer (Mamba-2), for input ``u`` [T, D]: ``[z, xBC, dt] = W_in
  u`` (inner, inner + 2 G N, H wide); ``xBC = silu(conv(xBC))``, a depthwise
  causal convolution over the last ``mamba_d_conv`` positions with bias,
  zeros before the start; ``[x, B, C] = xBC``; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; per head **the recurrence itself**, a ``lax.scan`` over
  the positions with the state ``h`` [P, N], zero at the start: ``h_t =
  exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t + D x_t`` (B and C
  of a head's group); then ``y = RMSNorm_inner(y * silu(z)) * w`` (the gate
  applied **before** the norm, one group over all inner channels) and
  ``W_out y``. Independent of the program's chunked form by construction.

**The cut.** ``vocab_size`` rows of the tied table are held (ids, logits and
loss over the slice) and ``num_hidden_layers`` layers, the first of
``layer_types``. With the whole table and every layer there is no departure
from the published model.

What the published file does not say, and this reading assumes (the program
follows the same reading; the configuration file lists them under
``assumed``): ``time_step_limit`` (0, inf), so dt is not clamped; no
projection bias (``mamba_proj_bias`` false is published); the convolution is
a cross-correlation whose last tap meets the current position (PyTorch's
``Conv1d`` with left padding); the mean square reported for a layer is of the
mixer's output before the residual multiplier.

Weights are read through ``get(name, layer=None)``, which returns one stored
tensor of any float type (upcast here): ``embed`` [V, D], ``final_norm`` [D];
per layer ``ln1``, ``ln2`` [D], ``w_gate``, ``w_up`` [D, F], ``w_down`` [F, D];
of an attention layer ``wq`` [D, H d], ``wk``, ``wv`` [D, K d], ``wo`` [H d, D];
of a mamba layer ``in_proj`` [D, 2 inner + 2 G N + H], ``conv_w`` [K, inner +
2 G N] (tap k meets position t - (K - 1) + k), ``conv_b``, ``dt_bias``,
``A_log``, ``D`` [H], ``norm`` [inner], ``out_proj`` [inner, D].
"""

from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
_QUERY_BLOCK = 512
COMMON = ("ln1", "ln2", "w_gate", "w_up", "w_down")
TENSORS = {"attention": COMMON + ("wq", "wk", "wv", "wo"),
           "mamba": COMMON + ("in_proj", "conv_w", "conv_b", "dt_bias",
                              "A_log", "D", "norm", "out_proj")}


def kinds(cfg: Dict):
    """``layer_types`` of the layers kept."""
    return list(cfg["layer_types"])[:int(cfg["num_hidden_layers"])]


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def attention(q, k, v, multiplier: float):
    """q [T, H, d], k/v [T, K, d] (each key-value head repeated H / K times),
    causal, scores times ``multiplier``; in blocks of queries."""
    T, H, _ = q.shape
    k = jnp.repeat(k, H // k.shape[1], axis=1)
    v = jnp.repeat(v, H // v.shape[1], axis=1)
    kpos = jnp.arange(T)
    outs = []
    for lo in range(0, T, _QUERY_BLOCK):
        qb = q[lo:lo + _QUERY_BLOCK]
        qpos = jnp.arange(lo, lo + qb.shape[0])
        s = jnp.einsum("thd,shd->hts", qb, k) * multiplier
        s = jnp.where((kpos[None, :] <= qpos[:, None])[None], s, -jnp.inf)
        outs.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(outs, axis=0)


def conv(x, w, b):
    """The direct sum: x [T, C], w [K, C], b [C]; ``y[t] = b + sum_k w[k]
    x[t - (K - 1) + k]``, positions before 0 read as zero."""
    T, K = x.shape[0], w.shape[0]
    idx = jnp.arange(T)[:, None] - (K - 1) + jnp.arange(K)[None, :]  # [T, K]
    taps = jnp.where((idx >= 0)[..., None], x[jnp.maximum(idx, 0)], 0.0)
    return jnp.einsum("tkc,kc->tc", taps, w) + b


def recurrence(x, dt, A, B, C, D):
    """x [T, H, P], dt [T, H], A [H], B and C [T, G, N], D [H] -> y
    [T, H, P]: position by position over the state h [H, P, N]."""
    H, P = x.shape[1], x.shape[2]
    rep = H // B.shape[1]

    def step(h, xs):
        x_t, dt_t, B_t, C_t = xs
        B_t, C_t = jnp.repeat(B_t, rep, axis=0), jnp.repeat(C_t, rep, axis=0)
        h = jnp.exp(dt_t * A)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        return h, jnp.sum(h * C_t[:, None, :], axis=-1) + D[:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, B.shape[2]), F32),
                        (x, dt, B, C))
    return y


def mamba(u, w: Dict, cfg: Dict):
    """The Mamba-2 mixer on u [T, D] (already normed)."""
    H, P = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    G, N = int(cfg["mamba_n_groups"]), int(cfg["mamba_d_state"])
    inner, T = H * P, u.shape[0]
    z, xbc, dt = jnp.split(u @ w["in_proj"], [inner, 2 * inner + 2 * G * N],
                           axis=-1)
    xbc = jax.nn.silu(conv(xbc, w["conv_w"], w["conv_b"]))
    x, B, C = jnp.split(xbc, [inner, inner + G * N], axis=-1)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y = recurrence(x.reshape(T, H, P), dt, -jnp.exp(w["A_log"]),
                   B.reshape(T, G, N), C.reshape(T, G, N), w["D"])
    y = rms_norm(y.reshape(T, inner) * jax.nn.silu(z), w["norm"],
                 float(cfg["rms_norm_eps"]))
    return y @ w["out_proj"]


def attention_layer(u, w: Dict, cfg: Dict):
    H, K = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d = int(cfg.get("head_dim") or cfg["hidden_size"] // H)
    T = u.shape[0]
    if cfg.get("position_embedding_type", "nope") != "nope":
        raise ValueError("only position_embedding_type 'nope' is written "
                         "down here")
    o = attention((u @ w["wq"]).reshape(T, H, d), (u @ w["wk"]).reshape(T, K, d),
                  (u @ w["wv"]).reshape(T, K, d),
                  float(cfg["attention_multiplier"]))
    return o.reshape(T, H * d) @ w["wo"]


def block(x, w: Dict, cfg: Dict, kind: str):
    """One layer on x [T, D] float32 -> (y, the mean square of the mixer's
    output); ``w`` holds the layer's tensors in float32, ``kind`` is its
    entry of ``layer_types``."""
    eps, res = float(cfg["rms_norm_eps"]), float(cfg["residual_multiplier"])
    mix = (mamba if kind == "mamba" else attention_layer)(
        rms_norm(x, w["ln1"], eps), w, cfg)
    a = x + res * mix
    h = rms_norm(a, w["ln2"], eps)
    y = a + res * ((jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"]))
                   @ w["w_down"])
    return y, jnp.mean(mix * mix)


def sequence(cfg: Dict, get: Callable, tokens) -> Dict:
    """One sequence [T] through the model: ``nll`` [T - 1] (cross-entropy of
    each position's logits against the next token, over the rows held) and
    ``mix_out_ms`` [L]."""
    eps = float(cfg["rms_norm_eps"])
    block_jit = jax.jit(lambda x, w, kind: block(
        x, {n: t.astype(F32) for n, t in w.items()}, cfg, kind),
        static_argnums=2)
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        table = jnp.asarray(get("embed")).astype(F32)
        x = float(cfg["embedding_multiplier"]) * table[tokens]
        ms = []
        for i, kind in enumerate(kinds(cfg)):
            x, m = block_jit(x, {n: jnp.asarray(get(n, i))
                                 for n in TENSORS[kind]}, kind)
            ms.append(m)
        x = rms_norm(x, jnp.asarray(get("final_norm")).astype(F32), eps)
        logits = (x @ table.T) / float(cfg["logits_scaling"])
    lg = logits[:-1]
    gold = jnp.take_along_axis(lg, tokens[1:, None], axis=-1)[:, 0]
    return {"nll": jax.scipy.special.logsumexp(lg, axis=-1) - gold,
            "mix_out_ms": jnp.stack(ms)}


def batch_loss(cfg: Dict, get: Callable, rows) -> Dict:
    """``loss``: the mean cross-entropy over the B x (T - 1) targets of the
    micro-batch ``rows`` [B, T]; ``mix_out_ms`` [L]: each layer's mixer-output
    mean square over all B x T positions."""
    per_row = [sequence(cfg, get, row) for row in rows]
    return {"loss": jnp.mean(jnp.concatenate([r["nll"] for r in per_row])),
            "mix_out_ms": sum(r["mix_out_ms"] for r in per_row)
            / len(per_row)}


def loss_and_grads(cfg: Dict, weights: Dict, rows):
    """``(loss, d loss / d weights)`` by ``jax.grad``; ``weights`` is a dict
    of float32 arrays keyed ``(name, layer)``, ``(name, None)`` for what no
    layer owns."""
    def loss(w):
        return batch_loss(cfg, dict_getter(w), rows)["loss"]

    return jax.value_and_grad(loss)(weights)


def dict_getter(weights: Dict) -> Callable:
    def get(name, layer=None):
        return weights[(name, layer)]

    return get
