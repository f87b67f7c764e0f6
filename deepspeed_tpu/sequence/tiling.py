"""Tiled sequence compute — activation-memory capping for long context.

Parity target: ``deepspeed/runtime/sequence_parallel/ulysses_sp.py`` — ``TiledMLP``
(:943), ``TiledFusedLogitsLoss`` (:1065), ``sequence_tiled_compute`` (:720). The torch
version re-runs forward shard-by-shard with hand-managed autograd; on TPU a
``lax.map`` over sequence chunks + ``jax.checkpoint`` gives the same activation
ceiling and XLA schedules the chunk loop.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.transformer import token_cross_entropy


def sequence_tiled_compute(fn: Callable, x: jax.Array, num_shards: int,
                           seq_dim: int = 1, remat: bool = True) -> jax.Array:
    """Apply a seq-pointwise ``fn`` over ``num_shards`` chunks of ``seq_dim``."""
    T = x.shape[seq_dim]
    while T % num_shards != 0:
        num_shards -= 1
    if num_shards <= 1:
        return fn(x)
    chunked = jnp.moveaxis(x, seq_dim, 0)
    chunked = chunked.reshape((num_shards, T // num_shards) + chunked.shape[1:])
    body = jax.checkpoint(fn) if remat else fn

    def apply_chunk(c):
        return jnp.moveaxis(body(jnp.moveaxis(c, 0, seq_dim)), seq_dim, 0)

    out = jax.lax.map(apply_chunk, chunked)
    out = out.reshape((T,) + out.shape[2:])
    return jnp.moveaxis(out, 0, seq_dim)


def TiledMLP(mlp_fn: Callable, num_shards: int = 4) -> Callable:
    """Wrap an MLP block so each sequence tile is computed (and rematerialized)
    independently (TiledMLP ulysses_sp.py:943)."""

    def tiled(x, *args, **kwargs):
        return sequence_tiled_compute(lambda c: mlp_fn(c, *args, **kwargs), x,
                                      num_shards)

    return tiled


def tiled_logits_loss(hidden: jax.Array, head: jax.Array, labels: jax.Array,
                      num_shards: int = 8, ignore_index: int = -100,
                      z_loss: float = 0.0) -> jax.Array:
    """Fused tiled logits+CE loss — never materializes [B, T, V]
    (TiledFusedLogitsLoss ulysses_sp.py:1065). ``z_loss`` adds the
    stabilizing ``z_loss * logsumexp^2`` term per token."""
    B, T, D = hidden.shape
    while T % num_shards != 0:
        num_shards -= 1
    hc = hidden.reshape(B, num_shards, T // num_shards, D)
    lc = labels.reshape(B, num_shards, T // num_shards)

    def chunk_loss(args):
        h, l = args
        # ALL negative labels are padding (dense lm_loss masks labels < 0;
        # -100 is just the HF spelling of it)
        mask = (l >= 0) & (l != ignore_index)
        nll = token_cross_entropy(h @ head, jnp.maximum(l, 0), z_loss)
        return jnp.where(mask, nll, 0.0).sum(), mask.sum()

    body = jax.checkpoint(chunk_loss)
    sums, counts = jax.lax.map(body, (hc.transpose(1, 0, 2, 3), lc.transpose(1, 0, 2)))
    return sums.sum() / jnp.maximum(counts.sum(), 1)
