"""Operations and bytes SDAR-30B-A3B-Chat's layers need under block-diffusion
training, from shapes alone: the work **the equations ask for**, whatever
implements it.

Like ``opcount.py``: nothing here imports the program. A "config" is the dict
of ``configs/sdar_30b_a3b_train_d5e16v8.json`` (Hugging Face key names, plus
``router_width``: the experts the router scores, where ``num_experts`` is how
many are held here, and ``block_length``). FLOPs count a multiply-add as 2. A
row of ``seq`` tokens is ``2 seq`` positions to every product of a layer and
``seq`` to the head; the attention is counted over the (query, key) pairs the
block-diffusion mask keeps, ``seq^2 + seq B`` a head a row: a program that
works whole tiles under a mask does more, and reads a lower share.
Recomputation is never counted in ``train_flops_per_token``; the rooflines
take the number of times the program runs each part as an argument.
"""

from __future__ import annotations

from typing import Dict

from benchmarks.opcount import BF16

__all__ = ["sizes", "attn_params", "expert_params", "layer_params",
           "total_params", "whole_model_params", "active_params_per_token",
           "mask_pairs", "cross_pairs", "attend", "expected_pairs_per_token",
           "grouped_products", "train_flops_per_token"]


def sizes(cfg: Dict) -> Dict[str, int]:
    held = int(cfg["num_experts"])
    return {"D": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
            "K": int(cfg["num_key_value_heads"]), "d": int(cfg["head_dim"]),
            "F": int(cfg["moe_intermediate_size"]), "V": int(cfg["vocab_size"]),
            "L": int(cfg["num_hidden_layers"]), "held": held,
            "E": int(cfg.get("router_width") or held),
            "k": int(cfg["num_experts_per_tok"]),
            "B": int(cfg["block_length"])}


def attn_params(cfg: Dict) -> int:
    """q, k, v, o and the two head norms' scales."""
    s = sizes(cfg)
    return 2 * s["D"] * s["H"] * s["d"] + 2 * s["D"] * s["K"] * s["d"] \
        + 2 * s["d"]


def expert_params(cfg: Dict) -> int:
    s = sizes(cfg)
    return 3 * s["D"] * s["F"]


def layer_params(cfg: Dict, experts: int = None) -> int:
    """Stored parameters of one layer with ``experts`` experts (default:
    those held here): attention, router, experts, two norms."""
    s = sizes(cfg)
    n = s["held"] if experts is None else experts
    return attn_params(cfg) + s["D"] * s["E"] + n * expert_params(cfg) \
        + 2 * s["D"]


def total_params(cfg: Dict) -> int:
    """What this configuration stores."""
    s = sizes(cfg)
    return s["L"] * layer_params(cfg) + 2 * s["V"] * s["D"] + s["D"]


def _published(cfg: Dict, key: str) -> int:
    return int(cfg.get("reduced", {}).get(key, {}).get("published",
                                                       cfg[key]))


def whole_model_params(cfg: Dict) -> int:
    """The model as published, by the file's keys: every layer, every
    expert, the whole vocabulary."""
    s = sizes(cfg)
    return _published(cfg, "num_hidden_layers") * layer_params(
        cfg, _published(cfg, "num_experts")) \
        + 2 * _published(cfg, "vocab_size") * s["D"] + s["D"]


def active_params_per_token(cfg: Dict) -> int:
    """Of :func:`whole_model_params`, what is active for one position as
    model cards count it ("A3B"): k experts a layer, everything else whole."""
    s = sizes(cfg)
    return _published(cfg, "num_hidden_layers") * layer_params(cfg, s["k"]) \
        + 2 * _published(cfg, "vocab_size") * s["D"] + s["D"]


# ---- the attention under the block-diffusion mask --------------------------

def mask_pairs(cfg: Dict, seq: int) -> int:
    """(query, key) pairs a head keeps in a row of ``seq`` tokens: clean
    over clean ``B^2 nb (nb + 1) / 2``, noised over clean ``B^2 nb (nb - 1)
    / 2``, the own noised blocks ``nb B^2``: ``seq^2 + seq B``."""
    return seq * seq + seq * sizes(cfg)["B"]


def cross_pairs(cfg: Dict, seq: int) -> int:
    """Of :func:`mask_pairs`, those over the clean keys (what the flash
    kernels under the rounded diagonal are asked for): ``seq^2``."""
    return seq * seq


def attend(cfg: Dict, seq: int, batch: int = 1, forwards: int = 1,
           backwards: int = 0, pairs: int = None) -> Dict[str, float]:
    """The attention of one layer over ``pairs`` kept pairs a head a row
    (default :func:`mask_pairs`): a forward is QK^T and PV (4 H d a pair), a
    backward the four products the gradient needs (8 H d; a recomputed QK^T
    is not counted). Bytes: a forward reads q, k, v of the ``2 seq``
    positions and writes o; a backward reads q, k, v, o, do and writes dq,
    dk, dv."""
    s = sizes(cfg)
    pairs = (mask_pairs(cfg, seq) if pairs is None else pairs) * batch
    rows = batch * 2 * seq * (s["H"] + s["K"]) * s["d"] * BF16
    return {"flops": (4.0 * forwards + 8.0 * backwards) * pairs * s["H"]
            * s["d"],
            "bytes": float((2 * forwards + 4 * backwards) * rows)}


# ---- the experts ----------------------------------------------------------

def expected_pairs_per_token(cfg: Dict) -> float:
    """(position, expert) pairs a position sends to the held experts under a
    uniform router: k x held / routed."""
    s = sizes(cfg)
    return s["k"] * s["held"] / s["E"]


def grouped_products(cfg: Dict, pairs: float, forwards: int = 1,
                     backwards: int = 0) -> Dict[str, float]:
    """The grouped products of one expert layer over ``pairs`` (position,
    expert) pairs that were computed: a forward is three products (6 D F a
    pair), a backward six. Bytes: the held experts' weights read once a
    product (their gradients written once a backward), the pairs' rows read
    and written once a product."""
    s = sizes(cfg)
    D, F = s["D"], s["F"]
    flops = (6.0 * forwards + 12.0 * backwards) * pairs * D * F
    weights = s["held"] * 3 * D * F * BF16
    rows_fwd = pairs * (3 * D + 4 * F) * BF16
    byts = forwards * (weights + rows_fwd) + backwards * (2 * weights
                                                          + 2 * rows_fwd)
    return {"flops": flops, "bytes": float(byts)}


# ---- the whole step -------------------------------------------------------

def train_flops_per_token(cfg: Dict, seq: int) -> float:
    """Forward plus backward of one training **token** in a row of ``seq``
    (two positions in every layer, one at the head): 6 x the matrix
    parameters its two positions visit (attention, the router, the experts'
    share at its expectation under a uniform router) and the head over the
    vocabulary held once, and the attention's 12 H d a kept pair.
    Recomputation is not counted, nor is the clean half's last layer left
    out, whose output nothing reads: the equations run it."""
    s = sizes(cfg)
    per_layer = attn_params(cfg) + s["D"] * s["E"] \
        + expected_pairs_per_token(cfg) * expert_params(cfg)
    mat = 2 * s["L"] * per_layer + s["D"] * s["V"]
    attn = 12.0 * s["H"] * s["d"] * mask_pairs(cfg, seq) / seq
    return 6.0 * mat + s["L"] * attn
