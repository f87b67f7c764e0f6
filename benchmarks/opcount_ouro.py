"""Operations the looped decoder needs, from shapes alone.

Like ``opcount.py``: nothing here imports the program. A "config" is the dict
of ``configs/ouro2_6b_train_d6.json`` (Hugging Face key names). FLOPs count a
multiply-add as 2. Recomputation is never counted, so a utilisation computed
from these numbers can only under-state, never pass 100 %.

The looped model uses its L layers ``R = total_ut_steps`` times a token and
its head R times; parameters are shared, so the stored count does not grow
with R and the work does. The flash kernels' operations and bytes per call
are ``opcount.flash_forward`` / ``flash_backward`` as they stand (a call is
one layer of one pass; the configuration file carries the keys they read).
"""

from __future__ import annotations

from typing import Dict

from benchmarks.opcount import causal_pairs, layer_matmul_params
from benchmarks.opcount import sizes as _dense_sizes

__all__ = ["sizes", "layer_matmul_params", "total_params",
           "layer_applications", "train_flops_per_token"]


def sizes(cfg: Dict) -> Dict[str, int]:
    """``opcount.sizes`` (the dense block's widths: q, k, v, o and the
    SwiGLU's three are ``opcount.layer_matmul_params``) and the passes."""
    return {**_dense_sizes(cfg), "R": int(cfg["total_ut_steps"])}


def total_params(cfg: Dict) -> int:
    """Every stored parameter: layers (matrices + four RMSNorm scales),
    embedding, untied head, final norm, the exit gate and its bias."""
    s = sizes(cfg)
    return (s["L"] * (layer_matmul_params(cfg) + 4 * s["D"])
            + 2 * s["V"] * s["D"] + s["D"] + s["D"] + 1)


def layer_applications(cfg: Dict) -> int:
    s = sizes(cfg)
    return s["R"] * s["L"]


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    """Forward plus backward of one training token on packed sequences of
    ``seq``: 6 x the matrix parameters it visits (R x L layers, R head
    applications; the embedding gather and the gate's D multiply-adds a pass
    are left out) plus attention's 12 x H x d x mean context for each of the
    R x L block applications. Recomputation is not counted."""
    s = sizes(cfg)
    mat = s["R"] * (s["L"] * layer_matmul_params(cfg) + s["D"] * s["V"])
    pairs = causal_pairs(seq, seq, None)
    attn = 12.0 * s["R"] * s["L"] * s["H"] * s["d"] * pairs / seq
    return 6.0 * mat + attn
