"""Safe access to full (unsharded) params and optimizer state.

Parity target: ``deepspeed/utils/tensor_fragment.py:19`` — the public
``safe_get_full_fp32_param`` / ``safe_set_full_fp32_param`` /
``safe_get_full_optimizer_state`` API (:134) that hides ZeRO partitioning from user
code. On TPU a "partitioned" param is a global jax.Array with sharded layout; reading
the full value is ``jax.device_get``; writing re-distributes with the original
sharding — no gather choreography needed.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Union

import jax
import numpy as np

PathLike = Union[str, Sequence[Any]]


def _resolve(tree: Any, path: PathLike):
    keys = path.split("/") if isinstance(path, str) else list(path)
    node = tree
    trail = []
    for k in keys:
        if isinstance(node, (list, tuple)):
            k = int(k)
        node = node[k]
        trail.append(k)
    return node, trail


def _set_in(tree: Any, trail: List[Any], value):
    if len(trail) == 1:
        tree[trail[0]] = value
        return
    _set_in(tree[trail[0]], trail[1:], value)


def safe_get_full_fp32_param(engine, path: PathLike) -> np.ndarray:
    """Full fp32 master value of one param, regardless of ZeRO stage/sharding."""
    leaf, _ = _resolve(engine.params, path)
    return np.asarray(jax.device_get(leaf), dtype=np.float32)


def safe_set_full_fp32_param(engine, path: PathLike, value) -> None:
    """Overwrite one param globally, preserving its sharding."""
    leaf, trail = _resolve(engine.params, path)
    new = jax.device_put(np.asarray(value, dtype=np.asarray(leaf).dtype),
                         leaf.sharding)
    if new.shape != leaf.shape:
        raise ValueError(f"shape mismatch for {path}: {new.shape} vs {leaf.shape}")
    _set_in(engine.params, trail, new)
    # written in place: tell the engine its masters changed (it drops what it
    # derived from them)
    engine.params = engine.params


def safe_get_full_grad(engine, path: PathLike) -> Optional[np.ndarray]:
    """Accumulated gradient for one param (None before any backward)."""
    acc = engine._grad_acc if engine._grad_acc is not None else engine._pending
    if acc is None:
        return None
    leaf, _ = _resolve(acc, path)
    return np.asarray(jax.device_get(leaf), dtype=np.float32)


def safe_get_full_optimizer_state(engine, path: PathLike, state_key: str
                                  ) -> Optional[np.ndarray]:
    """One optimizer-state fragment (e.g. 'mu'/'nu' for adam) for one param."""
    for piece in jax.tree_util.tree_leaves(
            engine.opt_state, is_leaf=lambda x: hasattr(x, "_fields")):
        if hasattr(piece, "_fields") and state_key in piece._fields:
            sub = getattr(piece, state_key)
            leaf, _ = _resolve(sub, path)
            return np.asarray(jax.device_get(leaf), dtype=np.float32)
    return None
