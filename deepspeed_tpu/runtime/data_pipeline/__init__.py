"""Data-efficiency pipeline.

Parity target: ``deepspeed/runtime/data_pipeline/`` — ``CurriculumScheduler``
(curriculum_scheduler.py:11), ``DeepSpeedDataSampler`` (data_sampling/
data_sampler.py:36), ``indexed_dataset.py`` mmap binary datasets, and the ALST
sequence-sharding loader (``UlyssesSPDataLoaderAdapter`` ulysses_sp.py:564).
"""

from deepspeed_tpu.runtime.data_pipeline.block_noise import noise_batch  # noqa: F401
from deepspeed_tpu.runtime.data_pipeline.curriculum import CurriculumScheduler  # noqa: F401
from deepspeed_tpu.runtime.data_pipeline.data_sampler import (  # noqa: F401
    DataEfficiencySampler,
)
from deepspeed_tpu.runtime.data_pipeline.indexed_dataset import (  # noqa: F401
    MMapIndexedDataset, MMapIndexedDatasetBuilder,
)
from deepspeed_tpu.runtime.data_pipeline.sp_dataloader import (  # noqa: F401
    SPDataLoaderAdapter,
)
from deepspeed_tpu.runtime.data_pipeline.variable_batch import (  # noqa: F401
    VariableBatchDataLoader, VariableBatchLRSchedule, batch_by_tokens,
    lr_scale_for_batch,
)
