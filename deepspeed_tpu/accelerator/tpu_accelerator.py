"""TPU implementation of the accelerator abstraction.

The TPU peer of the reference's ``cuda_accelerator.py`` (404 LoC of stream/
event/memory plumbing): device enumeration over the JAX TPU client, bf16-native
dtype capability, ``pinned_host`` placement, XLA collective backend.
"""

from __future__ import annotations

from typing import Any, List, Optional

from deepspeed_tpu.accelerator.abstract_accelerator import DeepSpeedAccelerator


class TpuAccelerator(DeepSpeedAccelerator):
    _name = "tpu"

    def devices(self) -> List[Any]:
        import jax

        return jax.devices("tpu")

    def is_bf16_supported(self) -> bool:
        return True  # the MXU's native accumulate format

    def is_fp16_supported(self) -> bool:
        # fp16 compiles on TPU but has no native matmul path and loses the
        # MXU's bf16 throughput — report unsupported so the engine's "auto"
        # precision resolution picks bf16 (reference semantics: capability,
        # not representability)
        return False

    def is_fp8_supported(self) -> bool:
        # fp8 dtypes lower on all current gens; native MXU fp8 on v5p+
        kinds = " ".join(getattr(d, "device_kind", "") for d in self.devices())
        return any(g in kinds.lower() for g in ("v5p", "v6", "v7"))

    def pin_memory(self, x: Any):
        """Place on the TPU host's pinned memory space so later device_put
        rides DMA (the aio/offload staging tier)."""
        import jax

        dev = self.devices()[0]
        kinds = {m.kind for m in dev.addressable_memories()}
        if "pinned_host" not in kinds:
            raise RuntimeError(
                f"{dev} has no pinned_host memory space (has {sorted(kinds)})")
        return jax.device_put(
            x, jax.sharding.SingleDeviceSharding(dev, memory_kind="pinned_host"))
