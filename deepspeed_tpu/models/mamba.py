"""The Mamba-2 mixer as a layer kind of :mod:`deepspeed_tpu.models.transformer`
(``attn_pattern`` kind ``"ssm"``): its parameters, their sharding and the
block. Loaded only by a model that has such a layer.

A layer's leaves (``params["layers"]["ssm"]``, one row per state-space
layer): ``in_proj`` [D, 2 inner + 2 G N + H] (gate ``z``, ``xBC``, ``dt``),
``conv_w`` [K, inner + 2 G N] (tap k meets position t - (K - 1) + k),
``conv_b``, ``dt_bias``, ``A_log``, ``D`` [H], ``norm`` [inner] (the gated
norm's scale; the norm over all inner channels, or with
``cfg.ssm_group_norm`` over each group's inner / G on their own) and
``out_proj`` [inner, D]; inner = H x P.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.transformer import _norm
from deepspeed_tpu.ops.causal_conv import causal_conv_silu
from deepspeed_tpu.ops.ssd_scan import ssd_scan


def sizes(cfg) -> Dict[str, int]:
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    conv = inner + 2 * cfg.ssm_groups * cfg.ssm_state
    return {"inner": inner, "conv": conv, "proj": inner + conv + cfg.ssm_heads}


def num_params(cfg) -> int:
    s, D, H = sizes(cfg), cfg.hidden_size, cfg.ssm_heads
    return (D * s["proj"] + (cfg.ssm_conv + 1) * s["conv"] + 3 * H
            + s["inner"] + s["inner"] * D)


def init(rng: jax.Array, cfg, n: int, pd) -> Dict[str, jax.Array]:
    """``n`` layers' leaves, the family's initialiser: ``A`` uniform in
    [1, 16], ``dt`` log-uniform in [0.001, 0.1] (``dt_bias`` its inverse
    softplus), ``D`` 1, the convolution uniform in +-1/sqrt(K) (PyTorch's
    ``Conv1d``), the projections normal at 1/sqrt(fan_in) like the program's
    other matrices."""
    s, D, H = sizes(cfg), cfg.hidden_size, cfg.ssm_heads
    k = jax.random.split(rng, 6)
    bound = 1.0 / math.sqrt(cfg.ssm_conv)
    dt = jnp.exp(jax.random.uniform(k[3], (n, H), pd)
                 * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    return {
        "in_proj": jax.random.normal(k[0], (n, D, s["proj"]), pd)
        / math.sqrt(D),
        "conv_w": jax.random.uniform(k[1], (n, cfg.ssm_conv, s["conv"]), pd,
                                     -bound, bound),
        "conv_b": jax.random.uniform(k[2], (n, s["conv"]), pd, -bound, bound),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(k[4], (n, H), pd, 1.0, 16.0)),
        "D": jnp.ones((n, H), pd),
        "norm": jnp.ones((n, s["inner"]), pd),
        "out_proj": jax.random.normal(k[5], (n, s["inner"], D), pd)
        / math.sqrt(s["inner"]),
    }


def param_specs() -> Dict[str, Any]:
    """Replicated over ``tp`` (the fused ``in_proj`` holds three projections
    of different widths side by side); ZeRO shards the leaves over ``fsdp``
    like any other."""
    return {"in_proj": P(None, None, None), "conv_w": P(None, None, None),
            "conv_b": P(None, None), "dt_bias": P(None, None),
            "A_log": P(None, None), "D": P(None, None),
            "norm": P(None, None), "out_proj": P(None, None, None)}


def ssm_block(u: jax.Array, w: Dict[str, jax.Array], cfg) -> jax.Array:
    """The mixer on the normed input u [B, T, D] -> [B, T, D]. Its operations
    lie under the nested scopes ``ssm_proj``, ``ssm_conv``, ``ssm_scan`` and
    ``ssm_gate`` (inside the caller's ``attn``). ``ssm_conv`` holds
    :func:`causal_conv_silu` on ``xBC``, the convolution, its bias and silu
    in float32 with one rounding to ``u``'s dtype: two Mosaic kernels with a
    backward of their own (``.../ssm_conv/jit(conv_fwd)/pallas_call``,
    ``jit(conv_bwd)`` under ``transpose``) that read ``xBC`` where it lies in
    ``in_proj``'s product and write ``x``, ``B`` and ``C`` as arrays of their
    own (the backward takes their cotangents as it gets them: nothing is put
    side by side), where the call's backend, dtype and shapes allow, and
    ``jax.numpy``'s shifted multiply-adds and a split elsewhere. ``ssm_scan``
    holds the softplus and :func:`ssd_scan`, which is two Mosaic kernels
    with a backward of their own (``.../ssm_scan/jit(ssd_fwd)/pallas_call``,
    ``jit(ssd_bwd)`` under ``transpose``) where the call's backend, dtype and
    shapes allow, and einsums with autodiff's backward elsewhere."""
    B, T, _ = u.shape
    H, Pd, G, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
    s = sizes(cfg)
    inner = s["inner"]
    f32 = jnp.float32
    with jax.named_scope("ssm_proj"):
        z, xbc, dt = jnp.split(u @ w["in_proj"], [inner, inner + s["conv"]],
                               axis=-1)
    with jax.named_scope("ssm_conv"):
        x, Bm, Cm = causal_conv_silu(xbc, w["conv_w"], w["conv_b"], u.dtype,
                                     splits=(inner, inner + G * N))
    with jax.named_scope("ssm_scan"):
        dt = jax.nn.softplus(dt.astype(f32) + w["dt_bias"].astype(f32))
        y = ssd_scan(x.reshape(B, T, H, Pd), dt,
                     -jnp.exp(w["A_log"].astype(f32)),
                     Bm.reshape(B, T, G, N), Cm.reshape(B, T, G, N), w["D"],
                     cfg.ssm_chunk)
    with jax.named_scope("ssm_gate"):
        # the gate before the norm: one group over all inner channels, or
        # (``ssm_group_norm``) each group's channels on their own
        g = y.reshape(B, T, inner).astype(f32) * jax.nn.silu(z.astype(f32))
        scale = w["norm"]
        if cfg.ssm_group_norm and G > 1:
            g, scale = g.reshape(B, T, G, inner // G), scale.reshape(G, -1)
        g = _norm(g, {"scale": scale}, "rmsnorm", cfg.norm_eps
                  ).astype(u.dtype).reshape(B, T, inner)
    with jax.named_scope("ssm_proj"):
        return g @ w["out_proj"]
