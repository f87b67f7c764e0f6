"""Custom ops: Pallas TPU kernels with XLA fallbacks.

Parity target: ``deepspeed/ops/`` + ``op_builder/`` + ``csrc/``. The reference
JIT-compiles CUDA/C++ per accelerator through ``OpBuilder.load()``
(op_builder/builder.py:526); here every op is a Pallas kernel (device code) or XLA
composition, and the builder registry keeps the same discovery/compatibility surface
(``ds_report`` parity) without a compile step — XLA is the JIT.
"""

from __future__ import annotations

import contextlib
from typing import Callable, Dict, List, Optional, Type

import jax

from deepspeed_tpu.accelerator.real_accelerator import on_tpu


class OpBuilder:
    """Compatibility/discovery shim (reference ``op_builder/builder.py`` OpBuilder)."""

    NAME = "base"

    def is_compatible(self, verbose: bool = False) -> bool:
        return True

    def load(self) -> Callable:
        raise NotImplementedError

    on_tpu = staticmethod(on_tpu)


class FlashAttentionBuilder(OpBuilder):
    NAME = "flash_attn"

    def load(self):
        from deepspeed_tpu.ops.flash_attention import flash_attention

        return flash_attention


class RMSNormBuilder(OpBuilder):
    NAME = "rms_norm"

    def load(self):
        from deepspeed_tpu.ops.rms_norm import fused_rms_norm

        return fused_rms_norm


class QuantizerBuilder(OpBuilder):
    NAME = "quantizer"

    def load(self):
        from deepspeed_tpu.ops import quantization

        return quantization


class RingAttentionBuilder(OpBuilder):
    NAME = "ring_attention"

    def load(self):
        from deepspeed_tpu.ops.ring_attention import ring_attention

        return ring_attention


ALL_OPS: Dict[str, Type[OpBuilder]] = {
    b.NAME: b for b in (FlashAttentionBuilder, RMSNormBuilder, QuantizerBuilder,
                        RingAttentionBuilder)
}


def get_op_builder(name: str) -> OpBuilder:
    return ALL_OPS[name]()


def op_report() -> List[tuple]:
    """``ds_report`` op table (reference env_report.py)."""
    return [(name, cls().is_compatible()) for name, cls in ALL_OPS.items()]


def _live_axes() -> List[str]:
    """The ambient mesh's axes that a computation is still partitioned over:
    more than one device, and not manual."""
    mesh = jax.sharding.get_abstract_mesh()
    return [] if mesh is None or mesh.empty else [
        a for a in mesh.axis_names
        if a not in mesh.manual_axes and mesh.shape[a] > 1]


def _innermost_scope() -> Optional[str]:
    """The name of the innermost ``jax.named_scope`` open at the call, None
    outside every one (JAX keeps the stack; it has no public reader)."""
    try:
        from jax._src import source_info_util as info
        names = [s.name for s in info.current_name_stack().stack
                 if isinstance(s, info.Scope)]
    except (ImportError, AttributeError):
        return None
    return names[-1] if names else None


def mosaic_runs_whole() -> bool:
    """On the TPU with no mesh axis to partition over: a Mosaic call runs as
    it stands (Mosaic kernels cannot be partitioned automatically)."""
    return on_tpu() and not _live_axes()


def _flash_on_mesh(q, k, v, q_rope=None, k_rope=None, **kw):
    """The flash kernel under whatever mesh is ambient. Mosaic kernels cannot
    be partitioned automatically: on a mesh with several devices the kernel
    runs per shard — batch over the data axes (the engine's batch layout),
    heads over tp. A layout that cannot run it per shard (sequence-sharded,
    indivisible batch or heads) takes XLA attention, with a warning. ``v``
    None, ``q_rope``, ``k_rope``: the parts ``flash_attention`` takes (the
    one rope key whole on every tp shard)."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.models.transformer import xla_attention
    from deepspeed_tpu.ops.flash_attention import assembled, flash_attention
    from deepspeed_tpu.utils.logging import logger

    mesh = jax.sharding.get_abstract_mesh()
    live = _live_axes()
    if not live:                            # one device, or all manual
        return flash_attention(q, k, v, q_rope=q_rope, k_rope=k_rope, **kw)
    batch = tuple(a for a in ("dp", "fsdp") if a in live)
    heads = "tp" if "tp" in live else None
    nb = 1
    for a in batch:
        nb *= mesh.shape[a]
    if "sp" in live or q.shape[0] % nb or (heads and (
            q.shape[2] % mesh.shape["tp"] or k.shape[2] % mesh.shape["tp"])):
        logger.warning(
            f"attention_impl auto: flash kernel cannot run per shard for "
            f"q{q.shape} on mesh {dict(mesh.shape)} — XLA attention runs "
            f"instead (a sequence-sharded mesh wants 'ulysses' or 'ring')")
        return xla_attention(*assembled(q, k, v, q_rope, k_rope), **kw)
    spec = P(batch or None, None, heads, None)
    parts = {"v": (v, spec), "q_rope": (q_rope, spec),
             "k_rope": (k_rope, P(batch or None, None, None, None))}
    given = {n: x for n, (x, _) in parts.items() if x is not None}
    # the compiled kernel takes the innermost scope's name, and inside the
    # ``shard_map`` that would be the map's own (``%shard_map.n`` on a mesh
    # where one chip's is ``%attn.n`` or ``%attn_full.n``): the caller's
    # innermost scope is opened again in there, whatever the model named it
    scope = _innermost_scope()

    def per_shard(a, b, *rest):
        with (jax.named_scope(scope) if scope
              else contextlib.nullcontext()):
            return flash_attention(a, b, **dict(zip(given, rest)), **kw)

    # every remaining axis goes manual (unnamed ones replicate): Mosaic
    # refuses a region that is only partly manual
    return jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(spec, spec) + tuple(parts[n][1] for n in given),
        out_specs=spec,
        axis_names=set(mesh.axis_names) - set(mesh.manual_axes),
        check_vma=False)(q, k, *given.values())


def _register_model_attention() -> None:
    """Plug the flash kernel into the model attention registry ('auto' dispatch)."""
    from deepspeed_tpu.models import transformer as tfm
    from deepspeed_tpu.ops.flash_attention import assembled, flash_attention

    def flash_or_xla(q, k, v=None, *, q_rope=None, k_rope=None, causal=True,
                     segment_ids=None, window=None):
        kw = dict(causal=causal, segment_ids=segment_ids, window=window)
        if on_tpu():
            return _flash_on_mesh(q, k, v, q_rope, k_rope, **kw)
        return tfm.xla_attention(*assembled(q, k, v, q_rope, k_rope), **kw)

    tfm.register_attention_impl("flash", flash_or_xla)
    tfm.register_attention_impl("flash_pallas", flash_attention)  # force kernel (tests)

    # sequence-parallel impls: selectable via attention_impl="ulysses"/"ring"
    # under the engine jit (reference DistributedAttention, sequence/layer.py:351)
    from deepspeed_tpu.ops.ring_attention import ring_attention_spmd
    from deepspeed_tpu.sequence.layer import ulysses_attention_spmd

    tfm.register_attention_impl("ulysses", ulysses_attention_spmd)
    tfm.register_attention_impl("ring", ring_attention_spmd)
    from deepspeed_tpu.sequence.fpdt import fpdt_attention

    tfm.register_attention_impl("fpdt", fpdt_attention)


_register_model_attention()
