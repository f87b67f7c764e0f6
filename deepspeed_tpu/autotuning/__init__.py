"""Autotuning: measured search over mesh shape / ZeRO stage / micro-batch /
remat configs.

Parity target: ``deepspeed/autotuning/`` — ``Autotuner`` (autotuner.py:42) profiles
model info then schedules experiments over ZeRO stages and micro-batch sizes. Here an
experiment is a jit-compile + a few timed steps in-process (no cluster scheduler
needed: one trial == one XLA program), and the search gains the axis the
reference never had: mesh shape, ranked by the cost model
(``parallel/cost_model.py``, its default rates) with the measured winner
persisted for ``mesh: "auto"`` engine configs (``mesh_store.py``).
"""

from deepspeed_tpu.autotuning.autotuner import Autotuner, TrialResult  # noqa: F401
from deepspeed_tpu.autotuning.mesh_store import (  # noqa: F401
    WinnerStore, device_kind, resolve_auto_axis_sizes,
)
from deepspeed_tpu.autotuning.scheduler import (  # noqa: F401
    Experiment, ExperimentScheduler, ResourceManager, subprocess_runner,
)
