"""deepspeed_tpu — a TPU-native training & inference framework.

Brand-new JAX/XLA/Pallas implementation of the capability surface of DeepSpeed
(reference: ``deepspeed/__init__.py``). The one-call entry point mirrors
``deepspeed.initialize()`` (reference :93): hand in a model + JSON config, get back an
engine with ``forward/backward/step`` plus data loader and LR scheduler.
"""

import sys as _sys

from deepspeed_tpu import _hoststate  # the standard library alone

_at_import, _jax_preloaded = _hoststate.host_state(), "jax" in _sys.modules

from deepspeed_tpu.observability import steplog as _steplog  # noqa: E402
from deepspeed_tpu.observability.events import get_bus as _get_bus  # noqa: E402

# the package's import is the first set-up span (sampled above, where the
# import began, and closed on the last line: the thread's counters run from
# its start, so the first sample also says how the time before the import
# was spent) and every program the process builds from here on enters the
# build record
_steplog.install_build_hook()
_import_span = _steplog.span(_get_bus(), "setup", "import",
                             host_start=_at_import,
                             jax_preloaded=_jax_preloaded)
_import_span.__enter__()

from deepspeed_tpu.version import __version__  # noqa: F401,E402

from deepspeed_tpu import comm  # noqa: F401,E402
from deepspeed_tpu import ops  # noqa: F401,E402  (registers Pallas kernels, e.g. 'flash')
from deepspeed_tpu.accelerator import get_accelerator, set_accelerator  # noqa: F401,E402
from deepspeed_tpu.config import DeepSpeedTpuConfig, from_config  # noqa: F401,E402
from deepspeed_tpu.parallel import Topology, build_mesh  # noqa: F401,E402
from deepspeed_tpu.utils.compile_cache import place_compile_cache  # noqa: E402


def initialize(model=None, config=None, optimizer=None, model_parameters=None,
               training_data=None, lr_scheduler=None, mesh=None, dist_init_required=None,
               collate_fn=None, config_params=None):
    """Build the training engine (parity: ``deepspeed.initialize`` __init__.py:93).

    Args:
        model: a model spec — any object exposing ``init(rng) -> params`` and
            ``apply(params, batch) -> loss`` (see ``deepspeed_tpu.models``), or a flax
            module wrapped with ``deepspeed_tpu.models.FlaxModelSpec``.
        config: dict / path to JSON / :class:`DeepSpeedTpuConfig`.
        optimizer: optional pre-built optax transformation (overrides config optimizer).
        training_data: optional dataset for the engine-managed data loader.
        lr_scheduler: optional schedule fn ``step -> lr`` (overrides config scheduler).
        mesh: optional pre-built :class:`Topology`.

    Returns:
        (engine, optimizer, training_dataloader, lr_scheduler) — same 4-tuple as the
        reference.
    """
    bus = _get_bus()
    with _steplog.span(bus, "setup", "initialize"):
        # the span's own time is what none of its four parts holds: first of
        # all the import of the engine's module, which happens here
        try:
            from deepspeed_tpu.runtime.engine import DeepSpeedTpuEngine
        except ImportError as e:  # pragma: no cover
            raise NotImplementedError(
                "deepspeed_tpu.runtime.engine is not available in this build") from e

        if config is None and config_params is not None:
            config = config_params
        with _steplog.span(bus, "setup", "config"):
            ds_config = from_config(config)
            place_compile_cache()
            comm.init_distributed()
        engine_cls = DeepSpeedTpuEngine
        if ds_config.hybrid_engine.enabled:
            from deepspeed_tpu.runtime.hybrid_engine import DeepSpeedTpuHybridEngine

            engine_cls = DeepSpeedTpuHybridEngine
        engine = engine_cls(
            model=model,
            config=ds_config,
            optimizer=optimizer,
            training_data=training_data,
            lr_scheduler=lr_scheduler,
            topology=mesh,
            collate_fn=collate_fn,
        )
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def init_inference(model=None, config=None, checkpoint=None, dtype=None,
                   **kwargs):
    """Build the inference engine (parity: ``deepspeed.init_inference``
    __init__.py:328, incl. the ``checkpoint=`` loading surface of
    ``inference/engine.py:303``).

    ``checkpoint`` accepts either an engine checkpoint directory (written by
    ``engine.save_checkpoint``; pass the ``model``) or a HuggingFace
    checkpoint directory (``config.json`` + safetensors; ``model`` may be
    omitted — the family importer builds it).

    ``dtype="int8"``/``"int4"`` serves quantized weights through the fused
    dequant-matmul kernel (reference ``init_inference(dtype=torch.int8)``).
    """
    import os as _os

    from deepspeed_tpu.inference.engine import InferenceEngine

    place_compile_cache()
    if checkpoint is not None and "params" not in kwargs:
        if _os.path.exists(_os.path.join(checkpoint, "config.json")):
            from deepspeed_tpu.inference.quant import parse_weight_dtype
            from deepspeed_tpu.models.hf import load_hf_checkpoint

            # int dtypes quantize in the engine; the checkpoint loads float
            load_dtype = (dtype if parse_weight_dtype(dtype) == "bf16"
                          else None) or "float32"
            hf_model, params = load_hf_checkpoint(checkpoint,
                                                  dtype=load_dtype)
            model = model if model is not None else hf_model
            kwargs["params"] = params
        else:
            if model is None:
                raise ValueError(
                    "init_inference(checkpoint=<engine checkpoint>) needs "
                    "the model; only HF checkpoint dirs are self-describing")
            from deepspeed_tpu.runtime.checkpoint import load_params_only

            kwargs["params"] = load_params_only(checkpoint)
    return InferenceEngine(model=model, config=config, dtype=dtype, **kwargs)


_import_span.__exit__(None, None, None)
