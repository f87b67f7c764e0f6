"""The gated delta rule (Gated DeltaNet, a linear-attention layer) as a layer
kind of :mod:`deepspeed_tpu.models.transformer` (``attn_pattern`` kind
``"delta"``): its parameters, their sharding and the block. Loaded only by a
model that has such a layer.

Two head counts: ``cfg.delta_heads`` value heads (v, z, the step, the decay
and the state are a value head's) and ``cfg.delta_key_heads`` key heads (q, k
and their convolutions; None: as many), a divisor of them: value head ``i``
reads key head ``i // (delta_heads / delta_key_heads)`` (Qwen3-Next: 16 key
heads for 32 value heads; Olmo-Hybrid: one each). The rule reads a key
head's q and k once for its value heads (``ops/delta_rule.py``).

A layer's leaves (``params["layers"]["delta"]``, one row per delta layer),
for the ``H`` value heads held (``cfg.heads_held`` of ``cfg.delta_heads``;
all of them where it is None) and the ``Hk`` key heads that serve them, keys
``dk`` and values ``dv`` wide: ``wq``, ``wk`` [D, Hk dk], ``wv``, ``wz``
[D, H dv] (``wz`` the output gate's), ``wb``, ``wa`` [D, H] (the step's and
the decay's), ``conv_q``, ``conv_k`` [K, Hk dk], ``conv_v`` [K, H dv] (causal
depthwise, no bias; tap k meets position t - (K - 1) + k),
``A_log``, ``dt_bias`` [H] (float32 in the compute copy of the weights),
``o_norm`` [dv] (the output norm's scale, one for every head, plain: times
the scale, drawn at 1, whatever ``cfg.norm_zero_centred`` says of the
block's norms) and ``wo``
[H dv, D]. Heads are independent and the output norm is per head, so a share
of the value heads, with the key heads that serve them, gives its part of
the sum ``wo`` takes over them.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.transformer import _norm
from deepspeed_tpu.ops.causal_conv import causal_conv_silu
from deepspeed_tpu.ops.delta_rule import chunked_delta_rule

F32 = jnp.float32
#: what the sum of squares of a head's q or k is raised by before its root
L2_EPS = 1e-6


def sizes(cfg) -> Dict[str, int]:
    """The value heads held, the key heads that serve them, and the widths
    of a key-head and of a value-head projection."""
    H = cfg.heads_held or cfg.delta_heads
    Hk = H * (cfg.delta_key_heads or cfg.delta_heads) // cfg.delta_heads
    return {"heads": H, "key_heads": Hk, "key": Hk * cfg.delta_key_dim,
            "value": H * cfg.delta_value_dim}


def num_params(cfg) -> int:
    s, D = sizes(cfg), cfg.hidden_size
    return (D * (2 * s["key"] + 2 * s["value"] + 2 * s["heads"])
            + cfg.delta_conv * (2 * s["key"] + s["value"])
            + 2 * s["heads"] + cfg.delta_value_dim + s["value"] * D)


def init(rng: jax.Array, cfg, n: int, pd) -> Dict[str, jax.Array]:
    """``n`` layers' leaves, the family's initialiser: ``A`` uniform in
    (0, 16) (``A_log`` its logarithm), ``dt`` log-uniform in [0.001, 0.1]
    (``dt_bias`` its inverse softplus), the convolutions uniform in
    +-1/sqrt(K) (PyTorch's ``Conv1d``), the norm's scale 1, the matrices
    normal at 1/sqrt(fan_in) like the program's others."""
    s, D, K = sizes(cfg), cfg.hidden_size, cfg.delta_conv
    k = jax.random.split(rng, 12)
    bound = 1.0 / math.sqrt(K)

    def dense(key, fan_in, width):
        return jax.random.normal(key, (n, fan_in, width), pd) \
            / math.sqrt(fan_in)

    def conv(key, width):
        return jax.random.uniform(key, (n, K, width), pd, -bound, bound)

    dt = jnp.exp(jax.random.uniform(k[10], (n, s["heads"]), pd)
                 * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    return {
        "wq": dense(k[0], D, s["key"]), "wk": dense(k[1], D, s["key"]),
        "wv": dense(k[2], D, s["value"]), "wz": dense(k[3], D, s["value"]),
        "wb": dense(k[4], D, s["heads"]), "wa": dense(k[5], D, s["heads"]),
        "conv_q": conv(k[6], s["key"]), "conv_k": conv(k[7], s["key"]),
        "conv_v": conv(k[8], s["value"]),
        # (a draw of exactly 0 has no logarithm)
        "A_log": jnp.log(jnp.maximum(jax.random.uniform(
            k[9], (n, s["heads"]), pd, 0.0, 16.0), jnp.finfo(pd).tiny)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "o_norm": jnp.ones((n, cfg.delta_value_dim), pd),
        "wo": dense(k[11], s["value"], D),
    }


def param_specs() -> Dict[str, Any]:
    """Replicated over ``tp`` (a model with delta layers refuses the axis);
    ZeRO shards the leaves over ``fsdp`` like any other."""
    mat, row = P(None, None, None), P(None, None)
    return {"wq": mat, "wk": mat, "wv": mat, "wz": mat, "wb": mat, "wa": mat,
            "conv_q": mat, "conv_k": mat, "conv_v": mat, "A_log": row,
            "dt_bias": row, "o_norm": row, "wo": mat}


def delta_block(u: jax.Array, w: Dict[str, jax.Array], cfg) -> jax.Array:
    """The mixer on its input u [B, T, D] -> [B, T, D]. Its operations lie
    under the nested scopes ``delta_proj``, ``delta_conv`` (three
    :func:`causal_conv_silu`: convolution and silu in float32, rounded once,
    q and k to float32, which the rule norms, v to ``u``'s dtype; on the
    chip in bf16 two Mosaic kernels each, ``.../delta_conv/jit(conv_fwd)/
    pallas_call`` and ``jit(conv_bwd)`` under ``transpose``, elsewhere
    ``jax.numpy``'s shifted multiply-adds), ``delta_scan`` (the norms of q
    and k, the step and the decay, the chunked rule) and ``delta_gate``
    (inside the caller's ``attn``)."""
    B, T, _ = u.shape
    s, dk, dv = sizes(cfg), cfg.delta_key_dim, cfg.delta_value_dim
    H, Hk = s["heads"], s["key_heads"]
    with jax.named_scope("delta_proj"):
        q, k, v, z = (u @ w[n] for n in ("wq", "wk", "wv", "wz"))
        b, a = u @ w["wb"], u @ w["wa"]
    with jax.named_scope("delta_conv"):
        q, k = (causal_conv_silu(x, w[n], out_dtype=F32)
                for x, n in ((q, "conv_q"), (k, "conv_k")))
        v = causal_conv_silu(v, w["conv_v"], out_dtype=u.dtype)
    with jax.named_scope("delta_scan"):
        beta = jax.nn.sigmoid(b.astype(F32))
        if cfg.delta_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(w["A_log"].astype(F32)) * jax.nn.softplus(
            a.astype(F32) + w["dt_bias"].astype(F32))
        # the rule puts the norms on a head's q and k itself
        o = chunked_delta_rule(
            q.reshape(B, T, Hk, dk), k.reshape(B, T, Hk, dk),
            v.reshape(B, T, H, dv), g, beta,
            unit=(1.0 / math.sqrt(dk), L2_EPS))
    with jax.named_scope("delta_gate"):
        # the norm first, over a head's dv channels, then the gate
        o = _norm(o.astype(F32), {"scale": w["o_norm"]}, "rmsnorm",
                  cfg.norm_eps)
        y = (o * jax.nn.silu(z.astype(F32)).reshape(B, T, H, dv)
             ).astype(u.dtype).reshape(B, T, H * dv)
    with jax.named_scope("delta_proj"):
        return y @ w["wo"]
