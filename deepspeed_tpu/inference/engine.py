"""Inference engine v1: TP-sharded forward + autoregressive generation.

Parity target: ``deepspeed/inference/engine.py:40`` ``InferenceEngine`` — wraps a
model with tensor-parallel sharding (:247), checkpoint load (:303) and ``forward``
(:557). The CUDA-graph replay path (:497) is XLA's default (every jitted step IS a
captured graph). Generation runs a jitted prefill + a jitted single-token decode loop
over a static-shape KV cache.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.config import from_config
from deepspeed_tpu.models.transformer import TransformerLM
from deepspeed_tpu.parallel import Topology, build_mesh
from deepspeed_tpu.parallel import sharding as shd
from deepspeed_tpu.utils.logging import log_dist


def sample_token(logits, temperature: float, top_k: int, rng,
                 with_logprob: bool = False, top_p: float = 1.0):
    """Greedy / temperature / top-k / nucleus (top-p) sampling of the next
    token; optionally also the token's logprob under the SAMPLING
    distribution (the behavior policy — collected here because re-scoring a
    filtered distribution later is numerically fragile at the boundary)."""
    if temperature <= 0.0:
        tok = jnp.argmax(logits, axis=-1)
        lp = logits.astype(jnp.float32)
    elif top_k > 0:
        # fast path: sample within the top-k subset — top-p then needs a
        # cumsum over k elements instead of a full-vocab sort (which costs
        # ~30% of fused-loop decode throughput at V=32k)
        lp_full = (logits / temperature).astype(jnp.float32)
        vals, idx = jax.lax.top_k(lp_full, top_k)       # sorted descending
        if top_p < 1.0:
            probs = jax.nn.softmax(vals, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # keep the smallest prefix whose mass reaches top_p (cutoff
            # token inclusive): entries whose PRECEDING mass is < top_p
            keep = jnp.concatenate(
                [jnp.ones_like(cum[:, :1], bool), cum[:, :-1] < top_p],
                axis=-1)
            vals = jnp.where(keep, vals, -jnp.inf)
        j = jax.random.categorical(rng, vals, axis=-1)
        tok = jnp.take_along_axis(idx, j[:, None], axis=-1)[:, 0]
        if not with_logprob:
            return tok
        # behavior-policy logprob under the filtered distribution
        logp_k = jax.nn.log_softmax(vals, axis=-1)
        return tok, jnp.take_along_axis(logp_k, j[:, None], axis=-1)[:, 0]
    else:
        lp = (logits / temperature).astype(jnp.float32)
        if top_p < 1.0:
            # nucleus: keep the smallest prefix of the sorted distribution
            # whose mass reaches top_p (the cutoff token inclusive)
            probs = jax.nn.softmax(lp, axis=-1)
            sorted_p = jnp.sort(probs, axis=-1)[..., ::-1]
            cum = jnp.cumsum(sorted_p, axis=-1)
            k_idx = jnp.argmax(cum >= top_p, axis=-1)
            cutoff = jnp.take_along_axis(sorted_p, k_idx[:, None], axis=-1)
            lp = jnp.where(probs < cutoff, -jnp.inf, lp)
        tok = jax.random.categorical(rng, lp, axis=-1)
    if not with_logprob:
        return tok
    logp = jax.nn.log_softmax(lp, axis=-1)
    return tok, jnp.take_along_axis(logp, tok[:, None], axis=-1)[:, 0]


def generate_loop(step_fn, params, mesh, init_cache_fn, ids: np.ndarray,
                  total: int, temperature: float, top_k: int, seed: int,
                  eos_token_id: Optional[int],
                  return_logprobs: bool = False, top_p: float = 1.0):
    """The autoregressive prefill+decode loop shared by the inference and
    hybrid engines: jitted prefill, per-token sample, pad-with-EOS after a
    sequence finishes, early exit when all are done. With
    ``return_logprobs``, also returns the behavior-policy logprob of every
    generated token (forced post-EOS pads get 0.0 — mask them)."""
    B, T = ids.shape
    cache = init_cache_fn(B, total)
    rng = jax.random.key(seed)
    with jax.sharding.set_mesh(mesh):
        logits, cache = step_fn(params, jnp.asarray(ids), cache)
        next_logits = logits[:, -1]
        out = [ids]
        lps = []
        finished = np.zeros((B,), bool)
        for _ in range(total - T):
            rng, sub = jax.random.split(rng)
            nxt, lp = sample_token(next_logits, temperature, top_k, sub,
                                   with_logprob=True, top_p=top_p)
            nxt_np = np.asarray(nxt)
            lp_np = np.asarray(lp)
            if eos_token_id is not None:
                lp_np = np.where(finished, 0.0, lp_np)
                nxt_np = np.where(finished, eos_token_id, nxt_np)
                finished |= nxt_np == eos_token_id
            out.append(nxt_np[:, None])
            lps.append(lp_np[:, None])
            if eos_token_id is not None and finished.all():
                break
            logits, cache = step_fn(params, jnp.asarray(nxt_np)[:, None],
                                    cache)
            next_logits = logits[:, -1]
    seqs = np.concatenate(out, axis=1)
    if return_logprobs:
        return seqs, np.concatenate(lps, axis=1)
    return seqs


class InferenceEngine:
    def __init__(self, model: TransformerLM, config=None, params=None,
                 topology: Optional[Topology] = None, dtype=None,
                 max_seq_len: Optional[int] = None, **kw):
        if hasattr(model, "_one_pass_only"):
            model._one_pass_only("InferenceEngine (one key-value cache a "
                                 "layer)")
        self.module = model
        self.cfg = model.cfg
        self.config = from_config(config) if not hasattr(config, "mesh") else config
        self.topology = topology or build_mesh(self.config.mesh)
        self.mesh = self.topology.mesh
        self.max_seq_len = max_seq_len or self.cfg.max_seq_len

        specs = model.param_specs() if hasattr(model, "param_specs") else None
        spec_tree = shd.zero_param_specs(
            jax.eval_shape(model.init, jax.random.key(0)), specs, self.topology,
            stage=0)
        self.param_sharding = shd.named(self.topology, spec_tree)
        with jax.sharding.set_mesh(self.mesh):
            if params is None:
                params = jax.jit(model.init,
                                 out_shardings=self.param_sharding)(jax.random.key(0))
            else:
                params = jax.device_put(params, self.param_sharding)
        from deepspeed_tpu.inference.quant import (parse_weight_dtype,
                                                   quantize_serving_params)

        wd = parse_weight_dtype(dtype)
        if wd != "bf16":
            # reference init_inference(dtype=torch.int8): serve packed
            # weights through the fused dequant-matmul kernel (the model's
            # linear() seam picks the QuantizedWeight leaves up on every
            # path, including generate's cached decode)
            params = quantize_serving_params(
                params, self.cfg, 4 if wd == "int4" else 8, self.mesh)
        self.params = params

        self._step = jax.jit(model.forward_with_cache)
        self._logits = jax.jit(lambda p, ids: model.logits(p, ids))
        log_dist(f"inference engine ready: mesh={self.topology}")

    def forward(self, input_ids, **kw):
        """Full-sequence logits (reference ``InferenceEngine.forward`` :557)."""
        ids = jnp.asarray(input_ids)
        with jax.sharding.set_mesh(self.mesh):
            return self._logits(self.params, ids)

    __call__ = forward

    def generate(self, input_ids, max_new_tokens: int = 32, temperature: float = 0.0,
                 top_k: int = 0, seed: int = 0, eos_token_id: Optional[int] = None,
                 top_p: float = 1.0):
        """Greedy / top-k / nucleus sampled generation with a static KV cache."""
        ids = np.asarray(input_ids)
        total = min(self.max_seq_len, ids.shape[1] + max_new_tokens)
        return generate_loop(self._step, self.params, self.mesh,
                             self.module.init_kv_cache, ids, total,
                             temperature, top_k, seed, eos_token_id,
                             top_p=top_p)

    # back-compat alias (hybrid engine + older call sites)
    _sample = staticmethod(sample_token)
