"""Latent attention (MLA, the DeepSeek-V2 / V3 family) as a layer kind of
:mod:`deepspeed_tpu.models.transformer` (kind ``"mla"``, every layer's mixer
where ``kv_lora_rank`` is set): its parameters, their sharding and the block.
Loaded only by a model that has such a layer.

A layer's leaves (``params["layers"]["mla"]``, one row per layer), in the
published layout: ``wq`` [D, H (dn + dr)] (a head's ``dn`` columns without
rope, then its ``dr`` rope columns), ``wkv_a`` [D, r + dr] (the latent, then
the one rope key every head shares), ``kv_norm`` [r] (the latent's RMSNorm
scale), ``wkv_b`` [r, H (dn + dv)] (a head's key columns, then its value
columns) and ``wo`` [H dv, D]. Keys are ``dn + dr`` wide, values ``dv``; the
softmax scale is ``1 / sqrt(dn + dr)``.
"""

from __future__ import annotations

import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.transformer import _norm, apply_rope
from deepspeed_tpu.parallel.sharding import constrain


def sizes(cfg) -> Dict[str, int]:
    H, dn, dr, dv = (cfg.num_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    return {"q": H * (dn + dr), "kv_a": cfg.kv_lora_rank + dr,
            "kv_b": H * (dn + dv), "o": H * dv}


def num_params(cfg) -> int:
    s, D, r = sizes(cfg), cfg.hidden_size, cfg.kv_lora_rank
    return D * s["q"] + D * s["kv_a"] + r + r * s["kv_b"] + s["o"] * D


def init(rng: jax.Array, cfg, n: int, pd) -> Dict[str, jax.Array]:
    """``n`` layers' leaves, normal at 1/sqrt(fan_in) like the program's
    other matrices."""
    s, D, r = sizes(cfg), cfg.hidden_size, cfg.kv_lora_rank
    k = jax.random.split(rng, 4)

    def dense(key, fan_in, shape):
        return jax.random.normal(key, (n,) + shape, pd) / math.sqrt(fan_in)

    return {"wq": dense(k[0], D, (D, s["q"])),
            "wkv_a": dense(k[1], D, (D, s["kv_a"])),
            "kv_norm": jnp.ones((n, r), pd),
            "wkv_b": dense(k[2], r, (r, s["kv_b"])),
            "wo": dense(k[3], s["o"], (s["o"], D))}


def param_specs() -> Dict[str, P]:
    """Heads over tp, as the attention group's: the per-head products
    column-parallel, ``wo`` row-parallel; the latent projection and its norm
    whole on every shard."""
    return {"wq": P(None, None, "tp"), "wkv_a": P(None, None, None),
            "kv_norm": P(None, None), "wkv_b": P(None, None, "tp"),
            "wo": P(None, "tp", None)}


def _halves(w: jax.Array, dr: int) -> jax.Array:
    """The last ``dr`` columns of ``w`` [..., dr], published as rope pairs
    ``(2i, 2i + 1)``, in the order ``apply_rope`` rotates (``i`` with
    ``i + dr / 2``): even columns, then odd. Done to the weights, so that no
    activation is shuffled across lanes; q's and k's rope columns move alike,
    so their products are the published model's."""
    lead = w.shape[:-1]
    return w.reshape(lead + (dr // 2, 2)).swapaxes(-1, -2).reshape(
        lead + (dr,))


def mla_block(x: jax.Array, w: Dict[str, jax.Array], cfg,
              freqs: jax.Array, attn_fn: Callable) -> jax.Array:
    """``x`` [B, T, D] (normed) -> the mixer's output [B, T, D]. The four
    products and the latent's norm lie under ``mla_proj``; the rope and the
    assembly of q and k at their full width under ``mla_rope``; the attention
    kernels take keys ``dn + dr`` wide over values ``dv`` wide
    (``ops/flash_attention.py``), so no zero-padded q, k or v is written."""
    B, T, D = x.shape
    H, dn, dr, dv = (cfg.num_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    r = cfg.kv_lora_rank
    with jax.named_scope("mla_proj"):
        wq = w["wq"].reshape(D, H, dn + dr)
        wkv_a = w["wkv_a"]
        if cfg.rope_interleave:
            wq = jnp.concatenate(
                [wq[..., :dn], _halves(wq[..., dn:], dr)], axis=-1)
            wkv_a = jnp.concatenate(
                [wkv_a[:, :r], _halves(wkv_a[:, r:], dr)], axis=-1)
        q = jnp.einsum("btd,dhe->bthe", x, wq)               # [B, T, H, dn+dr]
        ckv = x @ wkv_a                                       # [B, T, r + dr]
        c = _norm(ckv[..., :r], {"scale": w["kv_norm"]}, "rmsnorm",
                  cfg.norm_eps)
        kv = (c @ w["wkv_b"]).reshape(B, T, H, dn + dv)
        q = constrain(q, P(("dp", "fsdp"), "sp", "tp", None))
        kv = constrain(kv, P(("dp", "fsdp"), "sp", "tp", None))
    with jax.named_scope("mla_rope"):
        q = jnp.concatenate(
            [q[..., :dn], apply_rope(q[..., dn:], freqs)], axis=-1)
        k_rope = apply_rope(ckv[..., None, r:], freqs)        # [B, T, 1, dr]
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_rope, (B, T, H, dr))], axis=-1)
        v = kv[..., dn:]
    out = attn_fn(q, k, v, causal=True)                       # [B, T, H, dv]
    with jax.named_scope("mla_proj"):
        o = out.reshape(B, T, H * dv) @ w["wo"]
    return constrain(o, P(("dp", "fsdp"), "sp", None))
