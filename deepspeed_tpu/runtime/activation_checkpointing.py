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
#: the tags ops/delta_rule.py and ops/kda_rule.py attach, in the forward of
#: their kernels' ``custom_vjp``, to the rule's output and to the chunks'
#: incoming states: the products a policy that keeps dots keeps of the einsum
#: form, and what the backward's region reads of the forward kernel
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
    # what the token mixer's kernels named, whatever the mixer: the flash
    # kernel's output and log-sum-exp, the selected-key op's four residuals,
    # the delta and KDA rules' output and chunk states. Each is what its
    # kernel's backward reads of the forward, so the recomputed region holds
    # no second run of a forward kernel (a layer none of whose ops carries a
    # name keeps nothing more). What they cost between the forward and the
    # backward: a flash layer one ``[B, H, T, dv]`` array and one float32 row
    # set; a rule layer its output and ``T / 64`` float32 ``[H, dk, dv]``
    # states (168 MB a KDA layer at 8,192 positions of 16 heads, 671 MB a
    # delta layer at 16,384 of 32, keys and values of 128)
    named = cp.save_only_these_names(
        ATTN_CHECKPOINT_NAME, ATTN_LSE_CHECKPOINT_NAME,
        *DSA_CHECKPOINT_NAMES, *RULE_CHECKPOINT_NAMES)
    if policy == "attn_saveable":
        # the kernels' names alone: what stands in front of a kernel (norms,
        # projections, convolutions, rope) is made again from the layer's
        # input
        return named
    if policy in ("dots_saveable", "dots_and_attn_saveable"):
        # a kernel's products are no dots: keeping dots has to keep the
        # kernels' names as well to run them once (what the einsum forms'
        # dots would have been), and the XLA fallback's named output is a
        # dot's result. One policy under two names
        return cp.save_from_both_policies(cp.dots_saveable, named)
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
