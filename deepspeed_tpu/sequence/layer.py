"""Ulysses sequence parallelism: all-to-all head-scatter / seq-gather.

Parity target: ``deepspeed/sequence/layer.py`` — ``DistributedAttention`` (:351) and
``_SeqAllToAll`` (:297). The torch version shuffles per-head tensors through process
groups; on TPU each a2a is one ``lax.all_to_all`` on the ``sp`` mesh axis riding ICI.
Constraint (same as reference :246-255): heads must divide the sp axis size — ring
attention (``ops/ring_attention.py``) covers the GQA/few-heads regime.

Call inside ``shard_map`` with sequence sharded over ``axis``:
  q/k/v: [B, T/sp, H, d]  →(a2a)→  [B, T, H/sp, d]  →attn→  →(a2a)→  [B, T/sp, H, d]
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax


def seq_all_to_all(x: jax.Array, axis: str, scatter_dim: int, gather_dim: int
                   ) -> jax.Array:
    """reference ``_SeqAllToAll.apply`` (sequence/layer.py:297)."""
    return lax.all_to_all(x, axis, split_axis=scatter_dim, concat_axis=gather_dim,
                          tiled=True)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, axis: str = "sp",
                      attn_fn: Optional[Callable] = None, causal: bool = True,
                      window: Optional[int] = None) -> jax.Array:
    """Full-sequence attention with heads sharded over ``axis``. ``window``
    reaches the inner kernel (each head shard holds the FULL sequence after
    the a2a, so the flash kernel's block-skipping window applies directly —
    windowed long-context models keep O(T*W) attention under SP)."""
    if attn_fn is None:
        from deepspeed_tpu.models.transformer import get_attention_impl

        attn_fn = get_attention_impl("auto")
    # scatter heads (dim 2), gather sequence (dim 1)
    q_full = seq_all_to_all(q, axis, 2, 1)
    k_full = seq_all_to_all(k, axis, 2, 1)
    v_full = seq_all_to_all(v, axis, 2, 1)
    kw = {} if window is None else {"window": window}
    out = attn_fn(q_full, k_full, v_full, causal=causal, **kw)
    # scatter sequence back, gather heads
    return seq_all_to_all(out, axis, 1, 2)


class DistributedAttention:
    """Class-shaped parity wrapper (``DistributedAttention`` sequence/layer.py:351)."""

    def __init__(self, local_attention: Optional[Callable] = None,
                 sequence_process_group: str = "sp", scatter_idx: int = 2,
                 gather_idx: int = 1):
        self.local_attn = local_attention
        self.axis = sequence_process_group
        self.scatter_idx = scatter_idx
        self.gather_idx = gather_idx

    def __call__(self, query, key, value, *args, causal: bool = True,
                 window: Optional[int] = None, **kwargs):
        return ulysses_attention(query, key, value, axis=self.axis,
                                 attn_fn=self.local_attn, causal=causal,
                                 window=window)


# ---------------------------------------------------------------------------
# Engine-reachable SP: attention impls that self-enter the sp manual region.
# ---------------------------------------------------------------------------

def sp_shard_map(inner: Callable, q: jax.Array, k: jax.Array, v: jax.Array,
                 axis: str = "sp") -> Optional[jax.Array]:
    """Run ``inner(q, k, v)`` inside a shard_map that is MANUAL over ``axis``
    (sequence dim sharded; batch/head axes stay GSPMD-auto), so
    sequence-parallel attention is selectable from inside the engine's ordinary
    jit — the registry analog of wrapping a module in ``DistributedAttention``
    (reference sequence/layer.py:351).

    Returns None when there is no active mesh with a >1 ``axis`` (caller falls
    back to dense attention). If ``axis`` is already manual (the caller sits
    inside another shard_map, e.g. a hand-rolled SP region), ``inner`` runs
    directly on the already-local chunks.

    Inside a parent manual region (the pipeline's pp shard_map), ``tp`` is
    bound manual as well: XLA's partitioner check-fails when a nested-manual
    all_to_all splits a dimension that is simultaneously auto-sharded over tp,
    and heads are embarrassingly parallel anyway.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or axis not in mesh.axis_names \
            or mesh.shape[axis] <= 1:
        return None
    parent_manual = set(getattr(mesh, "manual_axes", ()) or ())
    if axis in parent_manual:
        return inner(q, k, v)
    from jax.sharding import PartitionSpec as P

    axes = {axis}
    head_entry = None
    if parent_manual and "tp" in mesh.axis_names and mesh.shape["tp"] > 1 \
            and "tp" not in parent_manual:
        axes.add("tp")
        head_entry = "tp"
    spec = P(None, axis, head_entry, None)
    return jax.shard_map(inner, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, axis_names=axes,
                         check_vma=False)(q, k, v)


def ulysses_attention_spmd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True,
                           segment_ids: Optional[jax.Array] = None,
                           window: Optional[int] = None) -> jax.Array:
    """``attention_impl="ulysses"``: the engine-selectable Ulysses path.

    Heads (and kv heads) must be divisible by the sp axis — same constraint as
    the reference (sequence/layer.py:246-255); the ``ring`` impl covers the
    GQA/few-heads regime. Falls back to dense attention when no sp axis is
    active (single chip, tests off-mesh).
    """
    if segment_ids is not None:
        raise NotImplementedError("ulysses attention does not take segment_ids")
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is not None and not mesh.empty and "sp" in mesh.axis_names:
        sp = mesh.shape["sp"]
        # inside a parent manual region tp is bound manual too (see
        # sp_shard_map), so the a2a splits per-tp-shard heads
        tp = 1
        if (getattr(mesh, "manual_axes", ()) and "tp" in mesh.axis_names
                and "tp" not in mesh.manual_axes):
            tp = mesh.shape["tp"]
        h, kh = q.shape[2] // tp, max(k.shape[2] // tp, 1)
        if sp > 1 and (h % sp or kh % sp):
            raise ValueError(
                f"ulysses needs num_heads ({q.shape[2]}) and num_kv_heads "
                f"({k.shape[2]}) (per tp shard) divisible by sp={sp}; use "
                f"attention_impl='ring' for the GQA/few-heads regime")
    out = sp_shard_map(
        lambda a, b, c: ulysses_attention(a, b, c, axis="sp", causal=causal,
                                          window=window),
        q, k, v)
    if out is not None:
        return out
    from deepspeed_tpu.models.transformer import get_attention_impl

    kw = {} if window is None else {"window": window}
    return get_attention_impl("auto")(q, k, v, causal=causal, **kw)
