"""Operations and bytes Mellum2's layers need, from shapes alone.

Like ``opcount.py``: nothing here imports the program. A "config" is the dict
of ``configs/mellum2_12b_train_d4e16.json`` (Hugging Face key names, plus
``router_width``: the experts the router scores, where ``num_experts`` is
how many are held here). FLOPs count a multiply-add as 2. Recomputation is
never counted in ``train_flops_per_token``; the grouped products' roofline
takes the number of times the program runs each product as an argument.
"""

from __future__ import annotations

from typing import Dict, List

from benchmarks.opcount import BF16, causal_pairs

__all__ = ["sizes", "kinds", "attn_params", "expert_params", "layer_params",
           "total_params", "flash_pairs", "flash_forward", "flash_backward",
           "expected_pairs_per_token", "train_flops_per_token",
           "grouped_products"]


def sizes(cfg: Dict) -> Dict[str, int]:
    held = int(cfg["num_experts"])
    return {"D": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
            "K": int(cfg["num_key_value_heads"]), "d": int(cfg["head_dim"]),
            "F": int(cfg["moe_intermediate_size"]), "V": int(cfg["vocab_size"]),
            "L": int(cfg["num_hidden_layers"]), "held": held,
            "E": int(cfg.get("router_width") or held),
            "k": int(cfg["num_experts_per_tok"]),
            "window": cfg["sliding_window"]}


def kinds(cfg: Dict) -> List[str]:
    """``layer_types`` of the layers kept."""
    return list(cfg["layer_types"])[:int(cfg["num_hidden_layers"])]


def attn_params(cfg: Dict) -> int:
    s = sizes(cfg)
    return 2 * s["D"] * s["H"] * s["d"] + 2 * s["D"] * s["K"] * s["d"]


def expert_params(cfg: Dict) -> int:
    s = sizes(cfg)
    return 3 * s["D"] * s["F"]


def layer_params(cfg: Dict) -> int:
    """Stored parameters of one layer here: attention, router, the held
    experts, two RMSNorm scales."""
    s = sizes(cfg)
    return attn_params(cfg) + s["D"] * s["E"] \
        + s["held"] * expert_params(cfg) + 2 * s["D"]


def total_params(cfg: Dict) -> int:
    s = sizes(cfg)
    return s["L"] * layer_params(cfg) + 2 * s["V"] * s["D"] + s["D"]


# ---- attention: the work of each call follows its layer's kind ------------

def flash_pairs(cfg: Dict, seq: int) -> List[int]:
    """(query, key) pairs each kept layer's attention keeps at ``seq``."""
    s = sizes(cfg)
    return [causal_pairs(seq, seq, s["window"] if kind == "sliding_attention"
                         else None) for kind in kinds(cfg)]


def _flash(cfg: Dict, seq: int, batch: int, per_pair: float, tensors: int
           ) -> Dict[str, float]:
    s = sizes(cfg)
    pairs = sum(flash_pairs(cfg, seq))
    byts = len(kinds(cfg)) * batch * seq * tensors * (s["H"] + s["K"]) \
        * s["d"] * BF16
    return {"flops": per_pair * pairs * s["H"] * s["d"] * batch,
            "bytes": float(byts)}


def flash_forward(cfg: Dict, seq: int, batch: int = 1) -> Dict[str, float]:
    """One forward call of every kept layer (three window layers and a full
    one in a period) over ``batch`` sequences of ``seq``: QK^T and PV over
    the kept pairs; q, k, v read and o written once."""
    return _flash(cfg, seq, batch, 4.0, 2)


def flash_backward(cfg: Dict, seq: int, batch: int = 1) -> Dict[str, float]:
    """One backward call of every kept layer: the four matmuls the gradient
    needs; the kernel's recomputation of QK^T is not counted. Reads q, k, v,
    o, do; writes dq, dk, dv."""
    return _flash(cfg, seq, batch, 8.0, 4)


# ---- the experts ----------------------------------------------------------

def expected_pairs_per_token(cfg: Dict) -> float:
    """(token, expert) pairs a token sends to the held experts under a
    uniform router: k x held / routed."""
    s = sizes(cfg)
    return s["k"] * s["held"] / s["E"]


def grouped_products(cfg: Dict, pairs: float, forwards: int = 1,
                     backwards: int = 0) -> Dict[str, float]:
    """The grouped products of one expert layer over ``pairs`` (token,
    expert) pairs that were computed: a forward is three products (gate, up,
    down: 6 D F operations a pair), a backward six (each product's two
    transposes: 12 D F). Bytes: the held experts' weights read once a
    product (and their gradients written once a backward), the pairs' rows
    read and written once a product."""
    s = sizes(cfg)
    D, F = s["D"], s["F"]
    flops = (6.0 * forwards + 12.0 * backwards) * pairs * D * F
    weights = s["held"] * 3 * D * F * BF16
    # a forward: xs read twice, two [pairs, F] written and read, ys written
    rows_fwd = pairs * (3 * D + 4 * F) * BF16
    # a backward: twice as many products over the same rows
    rows_bwd = 2 * rows_fwd
    byts = forwards * (weights + rows_fwd) + backwards * (2 * weights
                                                          + rows_bwd)
    return {"flops": flops, "bytes": float(byts)}


# ---- the whole step -------------------------------------------------------

def train_flops_per_token(cfg: Dict, seq: int) -> float:
    """Forward plus backward of one training token on packed sequences of
    ``seq``: 6 x the matrix parameters it visits (attention, the router, the
    experts' share at its expectation under a uniform router, the head over
    the vocabulary held) plus attention's 12 x H x d x mean context of each
    layer by its kind. Recomputation is not counted."""
    s = sizes(cfg)
    per_layer = attn_params(cfg) + s["D"] * s["E"] \
        + expected_pairs_per_token(cfg) * expert_params(cfg)
    mat = s["L"] * per_layer + s["D"] * s["V"]
    attn = 12.0 * s["H"] * s["d"] * sum(flash_pairs(cfg, seq)) / seq
    return 6.0 * mat + attn
