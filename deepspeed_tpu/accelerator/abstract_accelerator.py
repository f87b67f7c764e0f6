"""Accelerator abstraction — the porting seam of the framework.

Parity target: ``accelerator/abstract_accelerator.py:10`` (``DeepSpeedAccelerator``,
~70 abstract methods) and the ``get_accelerator()`` selection logic in
``accelerator/real_accelerator.py:51``. On TPU most of the reference surface
(streams, events, per-stream memory pools, graph capture) collapses into the XLA
runtime, so this ABC keeps the part that *survives* the translation:

* device enumeration / placement (over ``jax.devices()``),
* dtype capability (bf16-native, fp8 availability),
* RNG (functional ``jax.random`` keys replace stateful generators),
* collective backend identification (XLA owns transport),
* memory introspection (``device.memory_stats()``),
* the op-builder hook that JIT-compiles native host ops
  (``op_builder_dir``/``create_op_builder``/``get_op_builder``,
  reference :268-279 — the seam the reference calls "the first-class porting
  seam" because new hardware plugs in here).

Stream/event methods are intentionally absent: XLA orders device work; the
synchronization primitive that remains is :meth:`synchronize`.
"""

from __future__ import annotations

import abc
from typing import Any, Dict, List, Optional, Type


class DeepSpeedAccelerator(abc.ABC):
    """Capability surface the rest of the framework programs against."""

    _name: str = "abstract"
    _communication_backend: str = "xla"

    # ---- identity -------------------------------------------------------
    def device_type(self) -> str:
        """Short platform name ("tpu", "cpu")."""
        return self._name

    def is_available(self) -> bool:
        """True when at least one device of this platform is reachable."""
        return self.device_count() > 0

    def communication_backend_name(self) -> str:
        """reference ``communication_backend_name`` (:199) — always the XLA
        collective runtime here (ICI intra-slice / DCN cross-slice)."""
        return self._communication_backend

    # ---- device management ----------------------------------------------
    @abc.abstractmethod
    def devices(self) -> List[Any]:
        """The ``jax.Device`` list for this platform."""

    def device_count(self) -> int:
        try:
            return len(self.devices())
        except RuntimeError:
            return 0

    def device_name(self, device_index: Optional[int] = None) -> str:
        if device_index is None:
            return self._name
        d = self.devices()[device_index]
        return f"{self._name}:{device_index} ({getattr(d, 'device_kind', '?')})"

    def current_device(self) -> int:
        """Index of the default device (SPMD: placement is sharding-driven;
        this exists for reference-API parity, e.g. logging prefixes)."""
        return 0

    def current_device_name(self) -> str:
        return self.device_name(self.current_device())

    def device(self, device_index: Optional[int] = None):
        """Context manager pinning computations to one device
        (``jax.default_device``) — the analog of ``torch.cuda.device``."""
        import jax

        return jax.default_device(self.devices()[device_index or 0])

    def synchronize(self, device_index: Optional[int] = None) -> None:
        """Block until all dispatched work on the device finished (the one
        synchronization primitive XLA leaves us; replaces streams/events)."""
        import jax

        d = self.devices()[device_index or 0]
        jax.device_put(0.0, d).block_until_ready()

    # ---- RNG -------------------------------------------------------------
    def manual_seed(self, seed: int):
        """Return a fresh functional PRNG key (reference ``manual_seed`` — but
        JAX RNG is explicit state, so the key is returned, not stored)."""
        import jax

        return jax.random.key(seed)

    def initial_seed(self) -> int:
        return 0

    # ---- memory ----------------------------------------------------------
    def memory_stats(self, device_index: Optional[int] = None) -> Dict[str, int]:
        d = self.devices()[device_index or 0]
        try:
            return dict(d.memory_stats() or {})
        except Exception:
            return {}

    def memory_allocated(self, device_index: Optional[int] = None) -> int:
        return int(self.memory_stats(device_index).get("bytes_in_use", 0))

    def max_memory_allocated(self, device_index: Optional[int] = None) -> int:
        s = self.memory_stats(device_index)
        return int(s.get("peak_bytes_in_use", s.get("bytes_in_use", 0)))

    def total_memory(self, device_index: Optional[int] = None) -> int:
        return int(self.memory_stats(device_index).get("bytes_limit", 0))

    def available_memory(self, device_index: Optional[int] = None) -> int:
        s = self.memory_stats(device_index)
        return int(s.get("bytes_limit", 0)) - int(s.get("bytes_in_use", 0))

    # ---- dtype capability -------------------------------------------------
    @abc.abstractmethod
    def is_bf16_supported(self) -> bool: ...

    @abc.abstractmethod
    def is_fp16_supported(self) -> bool: ...

    def is_fp8_supported(self) -> bool:
        return False

    def supported_dtypes(self) -> List[Any]:
        import jax.numpy as jnp

        out = [jnp.float32]
        if self.is_bf16_supported():
            out.append(jnp.bfloat16)
        if self.is_fp16_supported():
            out.append(jnp.float16)
        if self.is_fp8_supported():
            out += [jnp.float8_e4m3fn, jnp.float8_e5m2]
        return out

    # ---- tensor placement --------------------------------------------------
    def on_accelerator(self, x: Any) -> bool:
        # membership in the device list this accelerator enumerates
        try:
            ours = set(self.devices())
            return any(d in ours for d in x.devices())
        except AttributeError:
            return False

    def pin_memory(self, x: Any):
        """Host-pinned placement for fast H2D (reference ``pin_memory`` :256).
        On TPU this is the ``pinned_host`` memory space; elsewhere a no-op."""
        return x

    def empty_cache(self) -> None:
        """XLA owns the device memory arena; live-buffer release happens via
        python refs, so the portable action is a GC pass."""
        import gc

        gc.collect()

    # ---- graph capture -----------------------------------------------------
    def graph_capture(self, fn, **jit_kw):
        """reference graph capture/replay (:207-217): under XLA, ``jax.jit``
        IS capture (trace once) + replay (cached executable)."""
        import jax

        return jax.jit(fn, **jit_kw)

    # ---- op builder (the porting seam, reference :268-279) -----------------
    def op_builder_dir(self) -> str:
        return "deepspeed_tpu.ops.op_builder"

    def get_op_builder(self, class_name: str) -> Optional[Type]:
        """Resolve a builder CLASS by its reference name or class name."""
        import importlib

        mod = importlib.import_module(self.op_builder_dir())
        aliases = {"cpu_adam": "CPUAdamBuilder", "async_io": "AsyncIOBuilder"}
        return getattr(mod, aliases.get(class_name, class_name), None)

    def create_op_builder(self, class_name: str):
        cls = self.get_op_builder(class_name)
        return cls() if cls is not None else None
