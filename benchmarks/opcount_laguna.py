"""Operations and bytes Laguna-S-2.1's layers need, from shapes alone.

Like ``opcount.py``: nothing here imports the program. A "config" is the dict
of ``configs/laguna_s21_train_d5h24e8v8.json`` (Hugging Face key names, plus
``router_width``: the experts the router scores, where ``num_experts`` is how
many are held here; ``num_attention_heads_per_layer`` and
``num_key_value_heads`` count the heads held). FLOPs count a multiply-add as
2. Recomputation is never counted in ``train_flops_per_token``; the rooflines
take the number of times the program runs a forward as an argument.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from benchmarks.opcount import BF16, causal_pairs

__all__ = ["sizes", "kinds", "heads", "attn_params", "expert_params",
           "shared_params", "layer_params", "total_params", "flash_pairs",
           "flash_forward", "flash_backward", "expected_pairs_per_token",
           "grouped_products", "train_flops_per_token"]


def sizes(cfg: Dict) -> Dict[str, int]:
    held = int(cfg["num_experts"])
    return {"D": int(cfg["hidden_size"]), "K": int(cfg["num_key_value_heads"]),
            "d": int(cfg["head_dim"]), "F": int(cfg["intermediate_size"]),
            "Fm": int(cfg["moe_intermediate_size"]),
            "Fs": int(cfg["shared_expert_intermediate_size"]),
            "V": int(cfg["vocab_size"]), "L": int(cfg["num_hidden_layers"]),
            "held": held, "E": int(cfg.get("router_width") or held),
            "k": int(cfg["num_experts_per_tok"]),
            "window": cfg["sliding_window"]}


def kinds(cfg: Dict) -> List[Tuple[str, str]]:
    """``(layer type, "dense" | "moe")`` of each layer kept."""
    L = int(cfg["num_hidden_layers"])
    dense = set(cfg.get("mlp_only_layers", ()))
    return [(kind, "dense" if i in dense else "moe")
            for i, kind in enumerate(list(cfg["layer_types"])[:L])]


def heads(cfg: Dict) -> List[int]:
    """The query heads each kept layer holds."""
    L = int(cfg["num_hidden_layers"])
    return [int(n) for n in cfg["num_attention_heads_per_layer"][:L]]


def attn_params(cfg: Dict, layer: int) -> int:
    """``wq``, ``wk``, ``wv``, ``wo`` and the gate's ``wg`` of one layer."""
    s, H = sizes(cfg), heads(cfg)[layer]
    return 2 * s["D"] * H * s["d"] + 2 * s["D"] * s["K"] * s["d"] \
        + s["D"] * H


def expert_params(cfg: Dict) -> int:
    s = sizes(cfg)
    return 3 * s["D"] * s["Fm"]


def shared_params(cfg: Dict) -> int:
    s = sizes(cfg)
    return 3 * s["D"] * s["Fs"]


def layer_params(cfg: Dict, layer: int) -> int:
    """Stored parameters of one layer here: attention with its gate, two
    RMSNorm scales, and the dense FFN or the shared expert, the router with
    its selection bias and the held experts."""
    s = sizes(cfg)
    ffn = 3 * s["D"] * s["F"] if kinds(cfg)[layer][1] == "dense" else (
        shared_params(cfg) + s["D"] * s["E"] + s["E"]
        + s["held"] * expert_params(cfg))
    return attn_params(cfg, layer) + ffn + 2 * s["D"]


def total_params(cfg: Dict) -> int:
    s = sizes(cfg)
    return sum(layer_params(cfg, i) for i in range(s["L"])) \
        + 2 * s["V"] * s["D"] + s["D"]


# ---- attention: the work of each call follows its layer's kind ------------

def flash_pairs(cfg: Dict, seq: int) -> List[int]:
    """(query, key) pairs each kept layer's attention keeps at ``seq``."""
    s = sizes(cfg)
    return [causal_pairs(seq, seq, s["window"] if kind == "sliding_attention"
                         else None) for kind, _ in kinds(cfg)]


def _flash(cfg: Dict, seq: int, batch: int, per_pair: float, tensors: int
           ) -> Dict[str, float]:
    s = sizes(cfg)
    flops = sum(per_pair * pairs * H * s["d"] * batch
                for pairs, H in zip(flash_pairs(cfg, seq), heads(cfg)))
    byts = sum(batch * seq * tensors * (H + s["K"]) * s["d"] * BF16
               for H in heads(cfg))
    return {"flops": flops, "bytes": float(byts)}


def flash_forward(cfg: Dict, seq: int, batch: int = 1) -> Dict[str, float]:
    """One forward call of every kept layer (two full layers at one head
    count, three window layers at another) over ``batch`` sequences of
    ``seq``, each layer's work from its own heads and its own kept pairs:
    QK^T and PV over the pairs; q, k, v read and o written once."""
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
    """The grouped products of one routed layer over ``pairs`` (token,
    expert) pairs that were computed (``opcount_mellum2.grouped_products``
    at this configuration's widths): a forward is three products (6 D Fm
    operations a pair), a backward six; the held experts' weights read once
    a product (their gradients written once a backward), the pairs' rows
    read and written once a product."""
    s = sizes(cfg)
    D, F = s["D"], s["Fm"]
    flops = (6.0 * forwards + 12.0 * backwards) * pairs * D * F
    weights = s["held"] * 3 * D * F * BF16
    rows_fwd = pairs * (3 * D + 4 * F) * BF16
    byts = forwards * (weights + rows_fwd) + backwards * (2 * weights
                                                          + 2 * rows_fwd)
    return {"flops": flops, "bytes": float(byts)}


# ---- the whole step -------------------------------------------------------

def train_flops_per_token(cfg: Dict, seq: int) -> float:
    """Forward plus backward of one training token on packed sequences of
    ``seq``: 6 x the matrix parameters it visits (each layer's attention
    with its gate, the dense layer's FFN, each routed layer's shared expert,
    router and its held experts' share at its expectation under a uniform
    router, the head over the vocabulary held) plus attention's 12 x H x d x
    mean context of each layer by its kind and its heads. Recomputation is
    not counted."""
    s = sizes(cfg)
    routed = (shared_params(cfg) + s["D"] * s["E"]
              + expected_pairs_per_token(cfg) * expert_params(cfg))
    mat = s["D"] * s["V"] + sum(
        attn_params(cfg, i) + (3 * s["D"] * s["F"] if ffn == "dense"
                               else routed)
        for i, (_, ffn) in enumerate(kinds(cfg)))
    attn = sum(12.0 * H * s["d"] * pairs / seq
               for pairs, H in zip(flash_pairs(cfg, seq), heads(cfg)))
    return 6.0 * mat + attn
