"""Activation checkpointing (rematerialization).

Parity target: ``deepspeed/runtime/activation_checkpointing/checkpointing.py`` —
``checkpoint`` (:948), ``CheckpointFunction`` (:488) with partitioned activations,
CPU offload and RNG trackers. On TPU the whole subsystem is ``jax.checkpoint``:

* ``partition_activations`` → unnecessary (saved residuals are already sharded by
  SPMD; nothing is replicated to begin with);
* RNG state tracking (``CudaRNGStatesTracker`` :124) → free (jax PRNG is functional);
* CPU offload (:474) → ``policy="offload_dots"`` (XLA host-offload of saved dots);
* the policy knob maps to ``jax.checkpoint_policies``.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax

#: canonical policy names → how :func:`checkpoint_wrapper` resolves them
POLICIES = (
    "none", "full", "dots_saveable", "nothing_saveable",
    "dots_with_no_batch_dims_saveable", "attn_saveable",
    "dots_and_attn_saveable", "offload_dots", "offload_attn",
)

#: the checkpoint_name tags ops/flash_attention.py attaches, in the forward
#: rule of its kernels' ``custom_vjp`` (``RESIDUAL_NAMES`` there: literals, so
#: that module imports nothing of this package), to the kernel's output
#: ``[B, H, T, dv]`` in the kernel's layout and to its log-sum-exp rows
#: (float32): the two values the backward reads of the forward. The XLA
#: fallback (``models/transformer.py:xla_attention``) tags its output with the
#: first; it has no log-sum-exp, and its products are dots
ATTN_CHECKPOINT_NAME = "flash_attn_out"
ATTN_LSE_CHECKPOINT_NAME = "flash_attn_lse"
#: the tags ops/delta_rule.py attaches, in the forward of its kernels'
#: ``custom_vjp``, to the rule's output and to the chunks' incoming states:
#: the products a policy that keeps dots keeps of the einsum form
RULE_CHECKPOINT_NAMES = ("delta_rule_out", "delta_rule_states")
#: the tags ops/dsa.py attaches, in the forward of its ``custom_vjp``, to what
#: its backward reads of the forward beside its inputs (``RESIDUAL_NAMES``
#: there): the output, the heads' log-sum-exps, each query's set packed to
#: bits, and the indexer's gradients to a unit cotangent
DSA_CHECKPOINT_NAMES = ("dsa_out", "dsa_lse", "dsa_set", "dsa_index_grads")


def resolve_policy(policy: str):
    """Map a policy name to a ``jax.checkpoint_policies`` callable (or None).

    This is the single mapping used by both the model-side remat
    (``models/transformer.py``) and the engine-side :func:`checkpoint_wrapper`.
    """
    if policy in (None, "none", "full"):
        return None
    cp = jax.checkpoint_policies
    # (attention over a selected set names its own residuals: kept with the
    # flash kernel's, so that the region holds no second selection either)
    attn = (ATTN_CHECKPOINT_NAME, ATTN_LSE_CHECKPOINT_NAME,
            *DSA_CHECKPOINT_NAMES)
    if policy == "attn_saveable":
        # keep what the flash kernel named, its output and its log-sum-exp:
        # the two values its backward reads, so the recomputed region holds
        # no second run of the forward kernel (what stands in front of it,
        # norms, projections and rope, is made again from the layer's input)
        return cp.save_only_these_names(*attn)
    if policy in ("dots_saveable", "dots_and_attn_saveable"):
        # a kernel's products are no dots: keep what a kernel named of what
        # the einsum form's dots would have been (the flash kernel's output
        # and log-sum-exp, the delta rule's output and chunk states), so that
        # the recomputed region holds no second forward of either (a program
        # without those names is unchanged). A flash layer costs one
        # ``[B, H, T, dv]`` array and one float32 row set. One policy under
        # two names: keeping dots has to keep the kernels' names to run them
        # once, and the XLA fallback's named output is a dot's result
        return cp.save_from_both_policies(
            cp.dots_saveable,
            cp.save_only_these_names(*attn, *RULE_CHECKPOINT_NAMES))
    if policy == "offload_attn":
        # the FPDT/Ulysses-Offload memory tier (sequence/fpdt_layer.py:545):
        # attention outputs live in HOST memory between forward and backward,
        # freeing HBM ∝ L·B·T·D for long-context training; XLA schedules the
        # D2H/H2D copies asynchronously around the remat boundaries. The
        # log-sum-exp (float32 rows, 2/dv of the output's bytes at bf16)
        # stays on the device, so no forward is run again for it
        return cp.save_and_offload_only_these_names(
            names_which_can_be_saved=[ATTN_LSE_CHECKPOINT_NAME],
            names_which_can_be_offloaded=[ATTN_CHECKPOINT_NAME],
            offload_src="device", offload_dst="pinned_host")
    if policy == "offload_dots":
        if hasattr(cp, "offload_dot_with_no_batch_dims"):
            return cp.offload_dot_with_no_batch_dims("device", "pinned_host")
        # older JAX: no dot-offload policy — the named-attention offload is
        # the closest available behavior (== offload_attn)
        return resolve_policy("offload_attn")
    if policy not in POLICIES:
        raise ValueError(f"unknown remat policy '{policy}' "
                         f"(have {sorted(POLICIES)})")
    return getattr(cp, policy)


def configure(config) -> dict:
    """Read the ``activation_checkpointing`` config section into remat kwargs."""
    return {"policy": config.policy}


def checkpoint(function: Callable, *args, policy: str = "full") -> Any:
    """Run ``function(*args)`` under remat (reference ``checkpoint`` :948)."""
    return checkpoint_wrapper(function, policy=policy)(*args)


def checkpoint_wrapper(function: Callable, policy: str = "full") -> Callable:
    if policy in (None, "none"):
        return function
    if policy == "full":
        return jax.checkpoint(function)
    return jax.checkpoint(function, policy=resolve_policy(policy))
