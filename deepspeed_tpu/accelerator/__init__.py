from deepspeed_tpu.accelerator.abstract_accelerator import DeepSpeedAccelerator
from deepspeed_tpu.accelerator.cpu_accelerator import CpuAccelerator
from deepspeed_tpu.accelerator.real_accelerator import (on_tpu,  # noqa: F401
    get_accelerator, is_current_accelerator_supported, set_accelerator)
from deepspeed_tpu.accelerator.tpu_accelerator import TpuAccelerator

__all__ = ["DeepSpeedAccelerator", "TpuAccelerator", "CpuAccelerator",
           "get_accelerator", "set_accelerator",
           "is_current_accelerator_supported"]
