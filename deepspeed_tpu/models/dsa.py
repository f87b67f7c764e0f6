"""Attention over the keys a learned indexer picks (DeepSeek sparse attention)
as a layer kind of :mod:`deepspeed_tpu.models.transformer` (``attn_pattern``
kind ``"dsa"``): the indexer's parameters, their sharding and the block.
Loaded only by a model that has such a layer.

The mixer on its normed input ``u`` [B, T, D]: the attention layer's own
grouped-query projections, head norms and rope (``params["layers"]["attn"]``,
the leaves every attention kind has), and beside them the indexer
(``params["layers"]["indexer"]``, one row per "dsa" layer), which reads
``stop_gradient(u)``::

    qi = R'((u wq).reshape(J, c))           J = cfg.dsa_index_heads heads of
    ki = R'(LayerNorm(u wk))                c = cfg.dsa_index_head_dim, one
    wi = (u ww) / sqrt(J c)                 key head, float32 weights

``R'`` the model's rope over all c channels (the three position axes' sections
scaled to c / 2 pairs where the model has them). The set of ``cfg.dsa_topk``
keys a query, the attention over it and the loss that trains the indexer are
``ops/dsa.py:dsa_attention``'s: the block returns the mixer's output and the
layer's indexer loss, ``sum_rows kl / (B T)``. The main branch gets no gradient
from that loss and the indexer none from the model's.

What is a kernel and what is not: the projections, the LayerNorm and the
ropes here are XLA's; of ``dsa_attention``, the indexer's weighted head-score
sum (under ``dsa_indexer``) and the gradient of its loss by ``qi``, ``ki`` and
``wi`` (under ``dsa_loss``) are two Mosaic kernels on a TPU in bf16 where the
shapes fit (tiles a multiple of 128, heads of 64 or 128 that fill lane tiles:
``ops/dsa.py:index_lowering``), and ``jax.numpy`` einsums everywhere else; the
selection (``dsa_select``), the attention over the set (``dsa_attend``) and
the loss's own lines are ``jax.numpy`` on every backend. Nothing here chooses:
the op does, by platform, dtype and shapes, and counts what it took.

The indexer's leaves: ``wq`` [D, J c], ``wk`` [D, c], ``ww`` [D, J],
``k_norm`` and ``k_bias`` [c] (the LayerNorm on its key).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.transformer import (_norm, apply_rope,
                                              attn_out_proj, qkv_proj,
                                              rope_frequencies)
from deepspeed_tpu.ops import dsa as ops
from deepspeed_tpu.parallel.sharding import constrain

F32 = jnp.float32
#: the LayerNorm on the indexer's key
K_NORM_EPS = 1e-6


def num_params(cfg) -> int:
    J, c, D = cfg.dsa_index_heads, cfg.dsa_index_head_dim, cfg.hidden_size
    return D * (J * c + c + J) + 2 * c


def init(rng: jax.Array, cfg, n: int, pd) -> Dict[str, jax.Array]:
    """``n`` layers' indexer leaves: the matrices normal at 1/sqrt(fan_in)
    like the program's others, the norm's scale 1 and bias 0."""
    J, c, D = cfg.dsa_index_heads, cfg.dsa_index_head_dim, cfg.hidden_size
    k = jax.random.split(rng, 3)

    def dense(key, width):
        return jax.random.normal(key, (n, D, width), pd) / math.sqrt(D)

    return {"wq": dense(k[0], J * c), "wk": dense(k[1], c),
            "ww": dense(k[2], J), "k_norm": jnp.ones((n, c), pd),
            "k_bias": jnp.zeros((n, c), pd)}


def param_specs() -> Dict[str, Any]:
    """Replicated over ``tp`` (the indexer is whole on every chip that has
    the layer's queries); ZeRO shards the leaves over ``fsdp`` like any
    other."""
    mat, row = P(None, None, None), P(None, None)
    return {"wq": mat, "wk": mat, "ww": mat, "k_norm": row, "k_bias": row}


def index_sections(cfg) -> Optional[Tuple[int, ...]]:
    """The position axes' sections over the indexer's c / 2 frequency
    pairs: the model's, scaled from its head's pairs to the indexer's."""
    if cfg.mrope_section is None:
        return None
    return tuple(s * cfg.dsa_index_head_dim // cfg.head_dim
                 for s in cfg.mrope_section)


def indexer(u: jax.Array, w: Dict[str, jax.Array], cfg,
            positions: Optional[jax.Array]):
    """``(qi [B, T, J, c], ki [B, T, c], wi [B, T, J] float32)`` of the
    normed input ``u``, which gets no gradient from them."""
    B, T, _ = u.shape
    J, c = cfg.dsa_index_heads, cfg.dsa_index_head_dim
    u = jax.lax.stop_gradient(u)
    qi = (u @ w["wq"]).reshape(B, T, J, c)
    ki = _norm(u @ w["wk"], {"scale": w["k_norm"], "bias": w["k_bias"]},
               "layernorm", K_NORM_EPS).reshape(B, T, 1, c)
    wi = (u @ w["ww"]).astype(F32) * (1.0 / math.sqrt(J * c))
    if cfg.use_rope:
        freqs = rope_frequencies(c, cfg.max_seq_len, cfg.rope_theta)
        sections = index_sections(cfg)
        qi = apply_rope(qi, freqs, positions, sections=sections)
        ki = apply_rope(ki, freqs, positions, sections=sections)
    return qi, ki.reshape(B, T, c), wi


def dsa_block(u: jax.Array, w: Dict[str, jax.Array],
              wx: Dict[str, jax.Array], cfg, freqs: Optional[jax.Array],
              positions: Optional[jax.Array] = None):
    """The mixer on its input u [B, T, D] -> ``([B, T, D], the layer's
    indexer loss, the probe queries' sets packed [B, probes, T / 8])``:
    ``w`` the attention leaves, ``wx`` the indexer's. The
    indexer's projections and scores lie under the nested scope
    ``dsa_indexer``, the threshold and the set under ``dsa_select``, the
    attention over the set under ``dsa_attend`` and the indexer's loss with
    its gradient under ``dsa_loss``, inside the caller's ``attn``."""
    B, T, _ = u.shape
    q, k, v = qkv_proj(u, w, cfg)
    q = constrain(q, P(("dp", "fsdp"), "sp", "tp", None))
    k = constrain(k, P(("dp", "fsdp"), "sp", "tp", None))
    if cfg.use_rope:
        q = apply_rope(q, freqs, positions, sections=cfg.mrope_section)
        k = apply_rope(k, freqs, positions, sections=cfg.mrope_section)
    with jax.named_scope("dsa_indexer"):
        qi, ki, wi = indexer(u, wx, cfg, positions)
    tile = ops.tile_for(T, cfg.dsa_topk, cfg.dsa_q_chunk)
    out, kl, probes = ops.dsa_attention(q, k, v, qi, ki, wi, cfg.dsa_topk,
                                        tile)
    with jax.named_scope("dsa_loss"):
        loss = jnp.sum(kl) / (B * T)
    o = attn_out_proj(out, w, cfg)
    return constrain(o, P(("dp", "fsdp"), "sp", None)), loss, probes
