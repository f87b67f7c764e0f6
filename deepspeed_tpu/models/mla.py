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
softmax scale is ``1 / sqrt(dn + dr)``. ``H`` is the heads held
(``cfg.heads_held`` of ``cfg.num_heads``, the first of them; all where it is
None): ``wq``, ``wkv_b`` and ``wo`` are by heads, ``wkv_a`` and the latent's
norm whole on every share, and the output is the partial sum the held heads
give. With ``cfg.mla_head_gate`` also ``wg`` [D, H]: each head's output is
multiplied by ``sigmoid(x wg)[h]`` before ``wo`` (one scalar a head and
position).
"""

from __future__ import annotations

import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.transformer import (_attn_takes, _norm,
                                              apply_rope)
from deepspeed_tpu.parallel.sharding import constrain


def sizes(cfg) -> Dict[str, int]:
    H, dn, dr, dv = (cfg.heads_here, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    return {"q": H * (dn + dr), "kv_a": cfg.kv_lora_rank + dr,
            "kv_b": H * (dn + dv), "o": H * dv,
            "gate": H if cfg.mla_head_gate else 0}


def num_params(cfg) -> int:
    s, D, r = sizes(cfg), cfg.hidden_size, cfg.kv_lora_rank
    return (D * s["q"] + D * s["kv_a"] + r + r * s["kv_b"] + s["o"] * D
            + D * s["gate"])


def init(rng: jax.Array, cfg, n: int, pd) -> Dict[str, jax.Array]:
    """``n`` layers' leaves, normal at 1/sqrt(fan_in) like the program's
    other matrices."""
    s, D, r = sizes(cfg), cfg.hidden_size, cfg.kv_lora_rank
    k = jax.random.split(rng, 5)

    def dense(key, fan_in, shape):
        return jax.random.normal(key, (n,) + shape, pd) / math.sqrt(fan_in)

    out = {"wq": dense(k[0], D, (D, s["q"])),
           "wkv_a": dense(k[1], D, (D, s["kv_a"])),
           "kv_norm": jnp.ones((n, r), pd),
           "wkv_b": dense(k[2], r, (r, s["kv_b"])),
           "wo": dense(k[3], s["o"], (s["o"], D))}
    if s["gate"]:
        out["wg"] = dense(k[4], D, (D, s["gate"]))
    return out


def param_specs(cfg) -> Dict[str, P]:
    """Heads over tp, as the attention group's: the per-head products
    column-parallel, ``wo`` row-parallel; the latent projection and its norm
    whole on every shard."""
    out = {"wq": P(None, None, "tp"), "wkv_a": P(None, None, None),
           "kv_norm": P(None, None), "wkv_b": P(None, None, "tp"),
           "wo": P(None, "tp", None)}
    if cfg.mla_head_gate:
        out["wg"] = P(None, None, "tp")
    return out


def _halves(w: jax.Array, dr: int) -> jax.Array:
    """The last ``dr`` columns of ``w`` [..., dr], published as rope pairs
    ``(2i, 2i + 1)``, in the order ``apply_rope`` rotates (``i`` with
    ``i + dr / 2``): even columns, then odd. Done to the weights, so that no
    activation is shuffled across lanes; q's and k's rope columns move alike,
    so their products are the published model's."""
    lead = w.shape[:-1]
    return w.reshape(lead + (dr // 2, 2)).swapaxes(-1, -2).reshape(
        lead + (dr,))


def _rope_q(x: jax.Array, freqs: jax.Array) -> jax.Array:
    """The rope on q's rope columns, ``x`` [B, T, H, dr] in halves order.
    Where a Mosaic call runs as it stands, the one of ``ops/rope.py`` over the
    heads-first rows the flash kernels read (the transposes fold into the
    product that writes ``x`` and into the kernels' operand): the columns go
    through HBM once each way. Else ``apply_rope``."""
    from deepspeed_tpu.ops import mosaic_runs_whole

    if not mosaic_runs_whole():
        return apply_rope(x, freqs)
    from deepspeed_tpu.ops.rope import rope_heads_first

    return rope_heads_first(x.transpose(0, 2, 1, 3), freqs).transpose(
        0, 2, 1, 3)


def mla_block(x: jax.Array, w: Dict[str, jax.Array], cfg,
              freqs: jax.Array, attn_fn: Callable) -> jax.Array:
    """``x`` [B, T, D] (normed) -> the mixer's output [B, T, D]. The products
    and the latent's norm lie under ``mla_proj``, the rope on q's ``dr`` rope
    columns and on the one rope key under ``mla_rope``. q and k go to the
    attention in the parts the products write, and nothing ``dn + dr`` wide
    is put together here: ``wq`` is cut on the weights, so q arrives ``dn``
    wide beside its rope columns; the one rope key stays one head; keys and
    values stay the one product of ``wkv_b`` where a column block can name
    the values in it. The flash kernels take the parts as operands
    (``ops/flash_attention.py``: the rope columns join q's and k's tiles in
    VMEM, every head reads the one rope key, the backward writes dk and dv
    side by side); an attention that takes no parts gets q, k and v whole
    (``flash_attention.assembled``)."""
    from deepspeed_tpu.ops.flash_attention import assembled

    B, T, D = x.shape
    H, dn, dr, dv = (cfg.heads_here, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    r = cfg.kv_lora_rank
    with jax.named_scope("mla_proj"):
        wq = w["wq"].reshape(D, H, dn + dr)
        wq_rope, wkv_a = wq[..., dn:], w["wkv_a"]
        if cfg.rope_interleave:
            wq_rope = _halves(wq_rope, dr)
            wkv_a = jnp.concatenate(
                [wkv_a[:, :r], _halves(wkv_a[:, r:], dr)], axis=-1)
        q = jnp.einsum("btd,dhe->bthe", x, wq[..., :dn])      # [B, T, H, dn]
        q_rope = jnp.einsum("btd,dhe->bthe", x, wq_rope)      # [B, T, H, dr]
        ckv = x @ wkv_a                                       # [B, T, r + dr]
        c = _norm(ckv[..., :r], {"scale": w["kv_norm"]}, "rmsnorm",
                  cfg.norm_eps)
        kv = (c @ w["wkv_b"]).reshape(B, T, H, dn + dv)
        q = constrain(q, P(("dp", "fsdp"), "sp", "tp", None))
        q_rope = constrain(q_rope, P(("dp", "fsdp"), "sp", "tp", None))
        kv = constrain(kv, P(("dp", "fsdp"), "sp", "tp", None))
    with jax.named_scope("mla_rope"):
        q_rope = _rope_q(q_rope, freqs)
        k_rope = apply_rope(ckv[..., None, r:], freqs)        # [B, T, 1, dr]
        # keys then values in one array, where the values' width counts the
        # keys' in whole column blocks; else an array each
        k, v = (kv, None) if dn % dv == 0 else (kv[..., :dn], kv[..., dn:])
    if _attn_takes(attn_fn, "q_rope"):
        out = attn_fn(q, k, v, q_rope=q_rope, k_rope=k_rope, causal=True)
    else:
        with jax.named_scope("mla_rope"):
            whole = assembled(q, k, v, q_rope, k_rope)
        out = attn_fn(*whole, causal=True)                    # [B, T, H, dv]
    with jax.named_scope("mla_proj"):
        if "wg" in w:
            # one scalar a head and position, on the heads' outputs
            out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                (x @ w["wg"]).astype(jnp.float32))[..., None]
            ).astype(x.dtype)
        o = out.reshape(B, T, H * dv) @ w["wo"]
    return constrain(o, P(("dp", "fsdp"), "sp", None))
