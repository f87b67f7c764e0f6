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

#: the checkpoint_name tag attached by ops/flash_attention.py (and the XLA
#: fallback) to the attention output so policies can pin it
ATTN_CHECKPOINT_NAME = "flash_attn_out"
#: the tags ops/delta_rule.py attaches, in the forward of its kernels'
#: ``custom_vjp``, to the rule's output and to the chunks' incoming states:
#: the products a policy that keeps dots keeps of the einsum form
RULE_CHECKPOINT_NAMES = ("delta_rule_out", "delta_rule_states")


def resolve_policy(policy: str):
    """Map a policy name to a ``jax.checkpoint_policies`` callable (or None).

    This is the single mapping used by both the model-side remat
    (``models/transformer.py``) and the engine-side :func:`checkpoint_wrapper`.
    """
    if policy in (None, "none", "full"):
        return None
    cp = jax.checkpoint_policies
    if policy == "attn_saveable":
        # save only the attention output. Meant to spare the backward a
        # second run of the attention forward; it does not today: the flash
        # kernel's log-sum-exp is a residual this policy cannot name, so the
        # forward kernel runs again for it all the same (PERF.md section 4;
        # ROADMAP L2 (a) says what would fix it)
        return cp.save_only_these_names(ATTN_CHECKPOINT_NAME)
    if policy == "dots_and_attn_saveable":
        # dots_saveable alone keeps nothing of the (opaque-to-XLA) pallas
        # attention call; keep its named output as well (the forward kernel
        # still runs again for the log-sum-exp, as above)
        return cp.save_from_both_policies(
            cp.dots_saveable, cp.save_only_these_names(ATTN_CHECKPOINT_NAME))
    if policy == "dots_saveable":
        # a kernel's products are no dots: where the delta rule ran as
        # kernels, keep what it named, so that the recomputed region holds
        # no second forward (a program without those names is unchanged)
        return cp.save_from_both_policies(
            cp.dots_saveable, cp.save_only_these_names(*RULE_CHECKPOINT_NAMES))
    if policy == "offload_attn":
        # the FPDT/Ulysses-Offload memory tier (sequence/fpdt_layer.py:545):
        # attention outputs live in HOST memory between forward and backward,
        # freeing HBM ∝ L·B·T·D for long-context training; XLA schedules the
        # D2H/H2D copies asynchronously around the remat boundaries
        return cp.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
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
