"""Operations and bytes LFM2-MoE's layers need, from shapes alone.

Like ``opcount.py``: nothing here imports the program. A "config" is the dict
of ``configs/lfm2_24b_train_d5e8v8.json`` (Hugging Face key names;
``num_experts`` the experts held here of ``router_width``, ``layer_types``
the published list of which ``num_hidden_layers`` from ``first_layer`` on are
run). FLOPs count a multiply-add as 2. Recomputation is never counted in
``train_flops_per_token``; the convolution's and the experts' rooflines take
the number of times the program runs their forward as an argument.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from benchmarks.opcount import BF16, causal_pairs

__all__ = ["sizes", "kinds", "conv_mixer_params", "attn_mixer_params",
           "expert_params", "layer_params", "total_params", "published",
           "whole_model_params", "active_params", "short_conv",
           "expected_pairs_per_token", "grouped_products",
           "layer_forward_flops_per_token", "train_flops_per_token"]


def sizes(cfg: Dict) -> Dict[str, int]:
    D, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    held = int(cfg["num_experts"])
    return {"D": D, "H": H, "K": int(cfg["num_key_value_heads"]),
            "d": int(cfg.get("head_dim") or D // H),
            "F": int(cfg["intermediate_size"]),
            "Fm": int(cfg["moe_intermediate_size"]),
            "V": int(cfg["vocab_size"]), "L": int(cfg["num_hidden_layers"]),
            "dense": int(cfg["num_dense_layers"]),
            "taps": int(cfg["conv_L_cache"]),
            "held": held, "E": int(cfg.get("router_width") or held),
            "k": int(cfg["num_experts_per_tok"]),
            "tied": bool(cfg.get("tie_word_embeddings", True))}


def kinds(cfg: Dict) -> List[Tuple[str, str]]:
    """``(layer type, "dense" | "moe")`` of the layers kept:
    ``layer_types`` from ``first_layer`` on, the first ``num_dense_layers``
    of them dense."""
    s, first = sizes(cfg), int(cfg.get("first_layer", 0))
    types = list(cfg["layer_types"])[first:first + s["L"]]
    return [(t, "dense" if i < s["dense"] else "moe")
            for i, t in enumerate(types)]


# ---- parameters -----------------------------------------------------------

def conv_mixer_params(cfg: Dict) -> int:
    """in_proj [D, 3 D], out_proj [D, D] and the taps [K, D]."""
    s = sizes(cfg)
    return 4 * s["D"] * s["D"] + s["taps"] * s["D"]


def attn_mixer_params(cfg: Dict) -> int:
    """wq, wo [D, H d], wk, wv [D, K d] and the two per-head norm scales."""
    s = sizes(cfg)
    return 2 * s["D"] * s["H"] * s["d"] + 2 * s["D"] * s["K"] * s["d"] \
        + 2 * s["d"]


def expert_params(cfg: Dict) -> int:
    s = sizes(cfg)
    return 3 * s["D"] * s["Fm"]


def _ffn_params(cfg: Dict, ffn: str, experts: int) -> int:
    s = sizes(cfg)
    if ffn == "dense":
        return 3 * s["D"] * s["F"]
    return s["D"] * s["E"] + s["E"] + experts * expert_params(cfg)


def layer_params(cfg: Dict, kind: Tuple[str, str], experts: int = None
                 ) -> int:
    """A layer of ``kind`` with its two norms; ``experts`` counted in a
    routed layer (default: those held)."""
    s = sizes(cfg)
    mixer = conv_mixer_params(cfg) if kind[0] == "conv" \
        else attn_mixer_params(cfg)
    return mixer + 2 * s["D"] + _ffn_params(
        cfg, kind[1], s["held"] if experts is None else experts)


def _outside_layers(cfg: Dict) -> int:
    s = sizes(cfg)
    return s["V"] * s["D"] * (1 if s["tied"] else 2) + s["D"]


def total_params(cfg: Dict) -> int:
    """Every stored parameter of the configuration as it is run."""
    return sum(layer_params(cfg, k) for k in kinds(cfg)) \
        + _outside_layers(cfg)


def published(cfg: Dict) -> Dict:
    """The file with its ``reduced`` keys at their published values."""
    out = {k: v for k, v in cfg.items() if k not in ("router_width",
                                                     "first_layer")}
    out.update({k: v["published"] for k, v in cfg["reduced"].items()})
    return out


def whole_model_params(cfg: Dict, experts_counted: int = None) -> int:
    """The published model's parameters (every layer, expert and row), or
    with ``experts_counted`` a routed layer's experts counted that many
    times (:func:`active_params`)."""
    whole = published(cfg)
    return sum(layer_params(whole, k, experts_counted)
               for k in kinds(whole)) + _outside_layers(whole)


def active_params(cfg: Dict) -> int:
    """The parameters a token visits in the published model."""
    return whole_model_params(cfg, int(cfg["num_experts_per_tok"]))


# ---- the short convolution ------------------------------------------------

def short_conv(cfg: Dict, seq: int, batch: int = 1, forwards: int = 1,
               backwards: int = 0) -> Dict[str, float]:
    """The gates and the convolution of one conv layer over ``batch``
    sequences of ``seq``, whatever implements them: a forward reads ``B``,
    ``C`` and ``z`` and writes the gated result once (4 arrays of [T, D],
    bf16; 2 K + 2 operations an element: the first gate, K multiply-adds,
    the second gate); a backward reads the result's cotangent, ``B``, ``C``
    and ``z`` and writes their three cotangents (7 arrays; 4 K + 4
    operations: the two gates' four products, the transposed convolution and
    the taps' gradient), the convolution it computes again for ``dC`` not
    counted. The taps themselves are a few KB."""
    s = sizes(cfg)
    n = float(batch * seq * s["D"])
    K = s["taps"]
    return {"flops": n * (forwards * (2 * K + 2) + backwards * (4 * K + 4)),
            "bytes": n * BF16 * (forwards * 4 + backwards * 7)}


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

def layer_forward_flops_per_token(cfg: Dict, seq: int) -> Dict[str, float]:
    """A layer's forward operations a token, by part (the cell's ``why``
    quotes these): a conv mixer's two products, an attention mixer's four
    and its scores and values at the mean causal context, the dense FFN, the
    held routed experts at their expectation, the router."""
    s = sizes(cfg)
    ctx = causal_pairs(seq, seq, None) / seq
    return {"conv_proj": 2.0 * 4 * s["D"] * s["D"],
            "attn_proj": 2.0 * (attn_mixer_params(cfg) - 2 * s["d"]),
            "scores_values": 4.0 * s["H"] * s["d"] * ctx,
            "dense": 2.0 * 3 * s["D"] * s["F"],
            "routed": 2.0 * expected_pairs_per_token(cfg) * expert_params(cfg),
            "router": 2.0 * s["D"] * s["E"]}


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    """Forward plus backward of one training token on packed sequences of
    ``seq``: 6 x the matrix parameters it visits (each mixer's products, the
    dense layer's FFN, each routed layer's router and its held experts'
    share at its expectation under a uniform router, the tied head over the
    vocabulary held) plus attention's 12 x H x d x mean context an attention
    layer. The gates and taps (a few operations an element) and
    recomputation are not counted."""
    s = sizes(cfg)
    part = layer_forward_flops_per_token(cfg, seq)
    mat = 2.0 * s["D"] * s["V"]
    for mixer, ffn in kinds(cfg):
        mat += part["conv_proj"] if mixer == "conv" \
            else part["attn_proj"] + part["scores_values"]
        mat += part["dense"] if ffn == "dense" \
            else part["routed"] + part["router"]
    return 3.0 * mat
