"""Accelerator selection (reference ``real_accelerator.py:51``):
``DS_ACCELERATOR`` env override, else import-probing auto-detect (:112-140) —
here the probe is JAX's default backend."""

from __future__ import annotations

import os
from typing import Optional

from deepspeed_tpu.accelerator.abstract_accelerator import DeepSpeedAccelerator
from deepspeed_tpu.accelerator.cpu_accelerator import CpuAccelerator
from deepspeed_tpu.accelerator.tpu_accelerator import TpuAccelerator

_ACCELERATORS = {"tpu": TpuAccelerator, "cpu": CpuAccelerator}


def on_tpu() -> bool:
    """The one answer to "does this process compile for a TPU": JAX's default
    backend is ``tpu``. A runtime that cannot initialise raises here — it is
    never read as "no" (that would turn a broken chip into interpret mode)."""
    import jax

    return jax.default_backend() == "tpu"
_accelerator: Optional[DeepSpeedAccelerator] = None


def get_accelerator() -> DeepSpeedAccelerator:
    """The process-wide accelerator (cached after first resolution)."""
    global _accelerator
    if _accelerator is None:
        name = os.environ.get("DS_ACCELERATOR")
        if name is not None:
            if name not in _ACCELERATORS:
                raise ValueError(
                    f"DS_ACCELERATOR={name!r} — known: {sorted(_ACCELERATORS)}")
        else:
            import jax

            backend = jax.default_backend()
            if backend not in _ACCELERATORS:
                raise RuntimeError(
                    f"jax.default_backend()={backend!r} has no accelerator "
                    f"implementation here — known: {sorted(_ACCELERATORS)}")
            name = backend
        _accelerator = _ACCELERATORS[name]()
    return _accelerator


def set_accelerator(accel: Optional[DeepSpeedAccelerator]) -> None:
    """Install (or with ``None`` reset) the global accelerator — the seam a
    new platform implementation plugs into."""
    global _accelerator
    if accel is not None and not isinstance(accel, DeepSpeedAccelerator):
        raise TypeError("set_accelerator expects a DeepSpeedAccelerator")
    _accelerator = accel


def is_current_accelerator_supported() -> bool:
    return get_accelerator().device_type() in _ACCELERATORS
