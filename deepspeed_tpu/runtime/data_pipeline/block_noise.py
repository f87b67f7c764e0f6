"""The noising of a block-diffusion batch, on the host (numpy, no device
work).

Block diffusion (``models/block_diffusion.py``; SDAR, arXiv:2510.06303, in
BD3-LM's training form, arXiv:2503.09573, Algorithm 1) trains a row of ``L``
tokens ``x0`` in blocks of ``B``: one noise level a block under the linear
schedule, ``t_b = t_min + (1 - t_min) u``, ``u`` uniform in [0, 1); each
position of block ``b`` becomes the mask token with probability ``t_b``,
independently; a masked position's loss weighs ``1 / t_b``, every other
position's nothing. :func:`noise_batch` adds ``noised_ids`` and
``loss_weights`` to a batch of ``input_ids``: the three keys
``engine.fused_train_step`` takes for a model whose config has
``diffusion_block``. The whole of it runs under the host span
``ds.data.block_noise``.
"""

from __future__ import annotations

from typing import Dict, Union

import numpy as np

T_DRAWS = ("block", "row")


def noise_batch(batch: Dict[str, np.ndarray], *, block: int,
                mask_token_id: int,
                seed: Union[int, np.random.Generator],
                t_min: float = 1e-3, t_draw: str = "block"
                ) -> Dict[str, np.ndarray]:
    """``batch`` with ``noised_ids`` [rows, L] (``input_ids``, the masked
    positions set to ``mask_token_id``) and ``loss_weights`` [rows, L]
    float32 (``1 / t`` of the position's block where it was masked, else 0)
    beside what it held. ``seed``: an integer (the same seed, the same
    noise) or a generator to draw on from. ``t_draw``: one noise level a
    "block" or one a "row"."""
    from deepspeed_tpu.observability.events import get_bus

    with get_bus().span("data", "block_noise"):
        ids = np.asarray(batch["input_ids"])
        rows, L = ids.shape
        if block < 1 or L % block:
            raise ValueError(f"rows of {L} tokens are no whole number of "
                             f"blocks of {block}")
        if t_draw not in T_DRAWS:
            raise ValueError(f"t_draw={t_draw!r}: one of {T_DRAWS}")
        if not 0.0 < t_min <= 1.0:
            raise ValueError(f"t_min={t_min} outside (0, 1]")
        if np.any(ids == mask_token_id):
            raise ValueError(f"input_ids hold mask_token_id={mask_token_id}: "
                             f"a clean row never does")
        rng = (seed if isinstance(seed, np.random.Generator)
               else np.random.default_rng(seed))
        nb = L // block
        u = rng.random((rows, nb if t_draw == "block" else 1))
        t = np.broadcast_to((t_min + (1.0 - t_min) * u).astype(np.float32),
                            (rows, nb))
        t_pos = np.repeat(t, block, axis=1)                     # [rows, L]
        masked = rng.random((rows, L), dtype=np.float32) < t_pos
        return {**batch,
                "noised_ids": np.where(masked, ids.dtype.type(mask_token_id),
                                       ids),
                "loss_weights": np.where(masked, np.float32(1.0) / t_pos,
                                         np.float32(0.0))}
