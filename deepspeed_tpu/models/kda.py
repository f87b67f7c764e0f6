"""Kimi Delta Attention (KDA: the delta rule with a decay a key channel, Kimi
Linear, arXiv:2510.26692) as a layer kind of
:mod:`deepspeed_tpu.models.transformer` (``attn_pattern`` kind ``"kda"``):
its parameters, their sharding and the block. Loaded only by a model that has
such a layer.

The mixer on its normed input ``u`` [B, T, D], ``H`` heads (``cfg.heads_held``
of ``cfg.num_heads``; all of them where it is None) with keys ``dk =
cfg.delta_key_dim`` and values ``dv = cfg.delta_value_dim`` wide::

    q, k, v = u wq, u wk, u wv                    [B, T, H dk], [B, T, H dv]
    q, k, v = silu(causal_conv(.))                depthwise, cfg.delta_conv
                                                  taps, no bias
    q = l2norm_head(q) / sqrt(dk),  k = l2norm_head(k)
    beta = sigmoid(u wb)                          [B, T, H]
    g = lower sigmoid(exp(A_log)[h] (u wf + dt_bias))     [B, T, H, dk]
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                               S_0 = 0, S [dk, dv] a head
    y = (rmsnorm_head(o; o_norm) sigmoid(u wg)[h]) wo

``lower = cfg.kda_lower_bound`` (< 0): the gate's logarithm lies in (lower,
0), which is what lets the chunked rule put the decays into its operands
(``ops/kda_rule.py``). ``wf`` is full rank; the output gate is one scalar a
head.

A layer's leaves (``params["layers"]["kda"]``, one row per KDA layer):
``wq``, ``wk``, ``wf`` [D, H dk], ``wv`` [D, H dv], ``wb``, ``wg`` [D, H],
``conv_q``, ``conv_k``, ``conv_v`` [K, width] (tap k meets position t - (K -
1) + k), ``A_log`` [H], ``dt_bias`` [H dk] (both float32 in the compute copy
of the weights), ``o_norm`` [dv] (one scale for every head) and ``wo`` [H dv,
D]. Heads are independent, the output norm and gate are per head, so a share
of the heads gives its part of the sum ``wo`` takes over them.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops.causal_conv import causal_conv_silu
from deepspeed_tpu.ops.head_norm_gate import head_norm_gate
from deepspeed_tpu.ops.kda_rule import kda_rule_lanes

F32 = jnp.float32
#: what the sum of squares of a head's q or k is raised by before its root
L2_EPS = 1e-6
#: ``dt_bias`` is drawn uniform in this range and ``A_log`` uniform in
#: +-:data:`A_LOG_RANGE`: with a projection of unit variance a channel's mean
#: decay a position, ``lower x E sigmoid(A (x + dt_bias))``, then runs from
#: about ``lower / 9`` to ``lower e^-10``, so that some channels forget within
#: a chunk and some keep a write over thousands of positions (with the
#: delta kind's ``A`` in (0, 16) the sigmoid saturates and every channel sits
#: at one end)
DT_BIAS_RANGE = (-10.0, -2.0)
A_LOG_RANGE = 0.5


def sizes(cfg) -> Dict[str, int]:
    H = cfg.heads_held or cfg.num_heads
    return {"heads": H, "key": H * cfg.delta_key_dim,
            "value": H * cfg.delta_value_dim}


def num_params(cfg) -> int:
    s, D = sizes(cfg), cfg.hidden_size
    return (D * (3 * s["key"] + s["value"] + 2 * s["heads"])
            + cfg.delta_conv * (2 * s["key"] + s["value"])
            + s["heads"] + s["key"] + cfg.delta_value_dim + s["value"] * D)


def init(rng: jax.Array, cfg, n: int, pd) -> Dict[str, jax.Array]:
    """``n`` layers' leaves: ``A_log`` and ``dt_bias`` as :data:`A_LOG_RANGE`
    and :data:`DT_BIAS_RANGE` say, the convolutions uniform in +-1/sqrt(K)
    (PyTorch's ``Conv1d``), the norm's scale 1, the matrices normal at
    1/sqrt(fan_in) like the program's others."""
    s, D, K = sizes(cfg), cfg.hidden_size, cfg.delta_conv
    k = jax.random.split(rng, 12)
    bound = 1.0 / math.sqrt(K)

    def dense(key, fan_in, width):
        return jax.random.normal(key, (n, fan_in, width), pd) \
            / math.sqrt(fan_in)

    def conv(key, width):
        return jax.random.uniform(key, (n, K, width), pd, -bound, bound)

    return {
        "wq": dense(k[0], D, s["key"]), "wk": dense(k[1], D, s["key"]),
        "wv": dense(k[2], D, s["value"]), "wf": dense(k[3], D, s["key"]),
        "wb": dense(k[4], D, s["heads"]), "wg": dense(k[5], D, s["heads"]),
        "conv_q": conv(k[6], s["key"]), "conv_k": conv(k[7], s["key"]),
        "conv_v": conv(k[8], s["value"]),
        "A_log": jax.random.uniform(k[9], (n, s["heads"]), pd,
                                    -A_LOG_RANGE, A_LOG_RANGE),
        "dt_bias": jax.random.uniform(k[10], (n, s["key"]), pd,
                                      *DT_BIAS_RANGE),
        "o_norm": jnp.ones((n, cfg.delta_value_dim), pd),
        "wo": dense(k[11], s["value"], D),
    }


def param_specs() -> Dict[str, Any]:
    """Replicated over ``tp`` (a model with KDA layers refuses the axis);
    ZeRO shards the leaves over ``fsdp`` like any other."""
    mat, row = P(None, None, None), P(None, None)
    return {"wq": mat, "wk": mat, "wv": mat, "wf": mat, "wb": mat, "wg": mat,
            "conv_q": mat, "conv_k": mat, "conv_v": mat, "A_log": row,
            "dt_bias": row, "o_norm": row, "wo": mat}


def decay_log(f: jax.Array, A_log: jax.Array, dt_bias: jax.Array,
              lower: float) -> jax.Array:
    """The gate's logarithm ``g`` [B, T, H dk] in (``lower``, 0), float32,
    from the projection ``f`` [B, T, H dk]: a head's rate ``exp(A_log[h])``
    on each of its ``dk`` lanes, the heads side by side as ``wf``'s product
    wrote them and as the rule's kernels read them."""
    rate = jnp.repeat(jnp.exp(A_log.astype(F32)),
                      f.shape[-1] // A_log.shape[0])
    return lower * jax.nn.sigmoid(
        rate * (f.astype(F32) + dt_bias.astype(F32)))


def kda_block(u: jax.Array, w: Dict[str, jax.Array], cfg) -> jax.Array:
    """The mixer on its input u [B, T, D] -> [B, T, D]. Its operations lie
    under the nested scopes ``kda_proj`` (the seven products), ``kda_conv``
    (three :func:`causal_conv_silu`: q and k to float32, which the rule
    norms, v to ``u``'s dtype), ``kda_scan`` (the gate's ``g`` and ``beta``,
    then the chunked rule, which takes the norms of q and k: on a TPU two
    Mosaic kernels that read q, k, v, ``g`` and ``beta`` and write o, the
    einsum form elsewhere; ``ops/kda_rule.py:kda_lowering``) and
    ``kda_gate`` (the per-head norm and the head-wise gate as one op on o
    [B, T, H dv], a row's heads side by side as the rule's kernels wrote them
    and as ``wo`` reads them: on a TPU two Mosaic row kernels, forward and
    backward, so that neither o nor its cotangent is ever tiled over the
    heads; the ``jax.numpy`` lines elsewhere;
    ``ops/head_norm_gate.py:gate_lowering``), inside the caller's ``attn``."""
    dk = cfg.delta_key_dim
    with jax.named_scope("kda_proj"):
        q, k, v, f = (u @ w[n] for n in ("wq", "wk", "wv", "wf"))
        b, z = u @ w["wb"], u @ w["wg"]
    with jax.named_scope("kda_conv"):
        q, k = (causal_conv_silu(x, w[n], out_dtype=F32)
                for x, n in ((q, "conv_q"), (k, "conv_k")))
        v = causal_conv_silu(v, w["conv_v"], out_dtype=u.dtype)
    with jax.named_scope("kda_scan"):
        beta = jax.nn.sigmoid(b.astype(F32))
        g = decay_log(f, w["A_log"], w["dt_bias"], cfg.kda_lower_bound)
        o = kda_rule_lanes(q, k, v, g, beta,
                           unit=(1.0 / math.sqrt(dk), L2_EPS))
    with jax.named_scope("kda_gate"):
        # the norm first, over a head's dv channels, then the head's gate
        y = head_norm_gate(o, z, w["o_norm"], cfg.norm_eps)
    with jax.named_scope("kda_proj"):
        return y @ w["wo"]
