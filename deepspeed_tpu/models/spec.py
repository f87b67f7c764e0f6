"""ModelSpec — the contract between models and the engine.

The reference engine wraps any ``torch.nn.Module`` (``runtime/engine.py:238``); the JAX
equivalent of "a module" is a pair of pure functions over a params pytree. Anything that
implements this protocol can be handed to :func:`deepspeed_tpu.initialize`.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Protocol, runtime_checkable


@runtime_checkable
class ModelSpec(Protocol):
    """Minimal surface the engine needs from a model.

    ``params`` is an arbitrary pytree. ``batch`` is whatever the user's data loader
    yields (the in-tree LMs take ``{"input_ids": i32[B, T]}`` with optional
    ``"labels"``/``"attention_mask"``).
    """

    def init(self, rng: Any) -> Any:
        """Create the initial parameter pytree."""
        ...

    def loss_fn(self, params: Any, batch: Any, rng: Optional[Any] = None) -> Any:
        """Scalar training loss for one micro-batch (plus optional aux dict)."""
        ...

    def param_specs(self) -> Any:
        """Pytree (matching ``init``'s output) of ``jax.sharding.PartitionSpec``
        giving the model-parallel layout (tp/sp axes). The engine overlays the ZeRO
        (fsdp) axis on top of these. Return ``None`` for "fully replicated"."""
        ...


def num_params(params: Any) -> int:
    import jax

    return sum(x.size for x in jax.tree_util.tree_leaves(params))


def param_bytes(params: Any) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params))


def model_flops_per_token(cfg: "Any", include_backward: bool = True) -> float:
    """Approximate transformer FLOPs/token (6ND rule + attention term).

    Used by the ThroughputTimer MFU estimate (reference: ``utils/timer.py:199``
    ``ThroughputTimer`` TFLOPS estimate).
    """
    n = getattr(cfg, "num_params_estimate", None)
    if callable(n):
        n = n()
    factor = 6.0 if include_backward else 2.0
    attn = 0.0
    if hasattr(cfg, "num_layers") and hasattr(cfg, "max_seq_len") and hasattr(cfg, "hidden_size"):
        # per-token attention score+value FLOPs: 2 * 2 * L * T * D (fwd), ×3 with bwd
        attn = (factor / 2.0) * 2 * cfg.num_layers * cfg.max_seq_len * cfg.hidden_size
    # a looped model uses every weight but the embedding table once a pass
    # (the layers, the final norm, the head), and attends once a pass
    passes = int(getattr(cfg, "num_passes", 1))
    if passes > 1:
        once = 0 if cfg.tie_embeddings else cfg.vocab_size * cfg.hidden_size
        return factor * (once + passes * (float(n) - once)) + passes * attn
    return factor * float(n) + attn


class Batch(Dict[str, Any]):
    """Convenience alias; batches are plain dicts of arrays."""
