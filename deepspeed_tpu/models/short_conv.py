"""The gated short convolution (the LFM2 family's token mixer) as a layer
kind of :mod:`deepspeed_tpu.models.transformer` (``attn_pattern`` kind
``"conv"``): its parameters, their sharding and the block. Loaded only by a
model that has such a layer.

A layer's leaves (``params["layers"]["conv"]``, one row per conv layer), at
the model's width ``D``: ``in_proj`` [D, 3 D] (``B``, ``C`` and ``z`` side by
side, in that order), ``conv_w`` [K, D] (``K = cfg.conv_taps``; causal
depthwise, no bias, no activation; tap k meets position t - (K - 1) + k) and
``out_proj`` [D, D]. The mixer keeps no state but the last ``K - 1``
positions of ``B * z``: it is neither attention nor a scan.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops.causal_conv import causal_conv_act


def num_params(cfg) -> int:
    D = cfg.hidden_size
    return D * 3 * D + cfg.conv_taps * D + D * D


def init(rng: jax.Array, cfg, n: int, pd) -> Dict[str, jax.Array]:
    """``n`` layers' leaves: the taps uniform in +-1/sqrt(K) (PyTorch's
    ``Conv1d``, as the state-space and delta mixers' convolutions), the
    projections normal at 1/sqrt(fan_in) like the program's other
    matrices."""
    D, K = cfg.hidden_size, cfg.conv_taps
    k = jax.random.split(rng, 3)
    bound = 1.0 / math.sqrt(K)
    return {
        "in_proj": jax.random.normal(k[0], (n, D, 3 * D), pd) / math.sqrt(D),
        "conv_w": jax.random.uniform(k[1], (n, K, D), pd, -bound, bound),
        "out_proj": jax.random.normal(k[2], (n, D, D), pd) / math.sqrt(D),
    }


def param_specs() -> Dict[str, Any]:
    """Replicated over ``tp`` (a model with conv layers refuses the axis: the
    fused ``in_proj`` holds three projections side by side); ZeRO shards the
    leaves over ``fsdp`` like any other."""
    mat = P(None, None, None)
    return {"in_proj": mat, "conv_w": mat, "out_proj": mat}


def conv_block(u: jax.Array, w: Dict[str, jax.Array], cfg) -> jax.Array:
    """The mixer on the normed input u [B, T, D] -> [B, T, D]: ``(B, C, z) =
    split3(u W_in)``, ``c = conv(B * z)``, ``(C * c) W_out``. Its operations
    lie under the nested scopes ``sconv_proj`` (the two products) and
    ``sconv_conv`` (the two gates and :func:`causal_conv_act` without an
    activation: the taps in float32 with one rounding to ``u``'s dtype; on
    the chip in bf16 two Mosaic kernels with a backward of their own,
    ``.../sconv_conv/jit(conv_fwd)/pallas_call`` and ``jit(conv_bwd)`` under
    ``transpose``, elsewhere ``jax.numpy``'s shifted multiply-adds), inside
    the caller's ``attn``. The gates are ``jax.numpy`` products round the
    call."""
    with jax.named_scope("sconv_proj"):
        Bg, Cg, z = jnp.split(u @ w["in_proj"], 3, axis=-1)
    with jax.named_scope("sconv_conv"):
        c = causal_conv_act(Bg * z, w["conv_w"], out_dtype=u.dtype,
                            activation=None)
        y = Cg * c
    with jax.named_scope("sconv_proj"):
        return y @ w["out_proj"]
