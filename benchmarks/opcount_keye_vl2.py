"""Operations and bytes Keye-VL-2.0's layers need, from shapes alone: the work
**the equations ask for**, whatever implements it.

Like ``opcount.py``: nothing here imports the program. A "config" is the dict
of ``configs/keye_vl2_30b_train_d5e16v8.json`` (Hugging Face key names, plus
``router_width``: the experts the router scores, where ``num_experts`` is how
many are held here). FLOPs count a multiply-add as 2. The attention is
counted over the (query, key) pairs each query's set holds, the indexer over
the causal pairs it has to score: a program that evaluates the attention over
every causal pair under a mask does more, and reads a lower share.
Recomputation is never counted in ``train_flops_per_token``; the rooflines
take the number of times the program runs each part as an argument.
"""

from __future__ import annotations

from typing import Dict

from benchmarks.opcount import BF16, causal_pairs

__all__ = ["sizes", "attn_params", "indexer_params", "expert_params",
           "layer_params", "total_params", "whole_model_params",
           "active_params_per_token", "selected_pairs", "selected_share",
           "attend", "indexer", "expected_pairs_per_token",
           "grouped_products", "train_flops_per_token"]

F32 = 4


def sizes(cfg: Dict) -> Dict[str, int]:
    held, sa = int(cfg["num_experts"]), cfg["sa_config"]
    return {"D": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
            "K": int(cfg["num_key_value_heads"]), "d": int(cfg["head_dim"]),
            "F": int(cfg["moe_intermediate_size"]), "V": int(cfg["vocab_size"]),
            "L": int(cfg["num_hidden_layers"]), "held": held,
            "E": int(cfg.get("router_width") or held),
            "k": int(cfg["num_experts_per_tok"]),
            "J": int(sa["indexer_num_heads"]), "c": int(sa["indexer_head_dim"]),
            "topk": int(sa["topk"])}


def attn_params(cfg: Dict) -> int:
    """q, k, v, o and the two head norms' scales."""
    s = sizes(cfg)
    return 2 * s["D"] * s["H"] * s["d"] + 2 * s["D"] * s["K"] * s["d"] \
        + 2 * s["d"]


def indexer_params(cfg: Dict) -> int:
    """The indexer's three projections and its key's LayerNorm."""
    s = sizes(cfg)
    return s["D"] * (s["J"] * s["c"] + s["c"] + s["J"]) + 2 * s["c"]


def expert_params(cfg: Dict) -> int:
    s = sizes(cfg)
    return 3 * s["D"] * s["F"]


def layer_params(cfg: Dict, experts: int = None) -> int:
    """Stored parameters of one layer with ``experts`` experts (default:
    those held here): attention, indexer, router, experts, two norms."""
    s = sizes(cfg)
    n = s["held"] if experts is None else experts
    return attn_params(cfg) + indexer_params(cfg) + s["D"] * s["E"] \
        + n * expert_params(cfg) + 2 * s["D"]


def total_params(cfg: Dict) -> int:
    """What this configuration stores."""
    s = sizes(cfg)
    return s["L"] * layer_params(cfg) + 2 * s["V"] * s["D"] + s["D"]


def _published(cfg: Dict, key: str) -> int:
    return int(cfg.get("reduced", {}).get(key, {}).get("published",
                                                       cfg[key]))


def whole_model_params(cfg: Dict) -> int:
    """The language model as published, by the file's keys: every layer,
    every expert, the whole vocabulary (the tower left out)."""
    s = sizes(cfg)
    return _published(cfg, "num_hidden_layers") * layer_params(
        cfg, _published(cfg, "num_experts")) \
        + 2 * _published(cfg, "vocab_size") * s["D"] + s["D"]


def active_params_per_token(cfg: Dict) -> int:
    """Of :func:`whole_model_params`, what is active for one token as model
    cards count it ("A3B"): k experts a layer, everything else whole."""
    s = sizes(cfg)
    return _published(cfg, "num_hidden_layers") * layer_params(cfg, s["k"]) \
        + 2 * _published(cfg, "vocab_size") * s["D"] + s["D"]


# ---- the selected-key attention and its indexer ---------------------------

def selected_pairs(cfg: Dict, seq: int) -> int:
    """(query, key) pairs the sets of one row of ``seq`` hold: every causal
    pair of the first ``topk`` queries, ``topk`` of each later one."""
    k = min(sizes(cfg)["topk"], seq)
    return k * (k + 1) // 2 + (seq - k) * k


def selected_share(cfg: Dict, seq: int) -> float:
    return selected_pairs(cfg, seq) / causal_pairs(seq, seq)


def attend(cfg: Dict, seq: int, batch: int = 1, forwards: int = 1,
           backwards: int = 0) -> Dict[str, float]:
    """The attention of one layer over the selected pairs: a forward is QK^T
    and PV (4 H d a pair), a backward the four products the gradient needs
    (8 H d; a recomputed QK^T is not counted). Bytes: a forward reads q, k,
    v and writes o; a backward reads q, k, v, o, do and writes dq, dk, dv;
    beside them each selected key and value read once a query at the group's
    width would be the gather form's traffic and is not asked for."""
    s = sizes(cfg)
    pairs = selected_pairs(cfg, seq) * batch
    rows = batch * seq * (s["H"] + s["K"]) * s["d"] * BF16
    return {"flops": (4.0 * forwards + 8.0 * backwards) * pairs * s["H"]
            * s["d"],
            "bytes": float((2 * forwards + 4 * backwards) * rows)}


def indexer(cfg: Dict, seq: int, batch: int = 1, proj_forwards: int = 1,
            score_forwards: int = 1, proj_backwards: int = 0,
            score_backwards: int = 0) -> Dict[str, float]:
    """The indexer of one layer: its three projections over every token
    (``proj``) and its heads' scores over every causal pair (2 J c a pair;
    ``score``), each as often forward as said; the projections' backward and
    the scores' (the gradient of the indexer's own loss, which ``dsa_loss``
    holds) are each twice their forward. Bytes: the input and the
    projections' outputs once a pass of the projections, the weighted scores
    [seq, seq / 2] float32 written once a pass of the scores."""
    s = sizes(cfg)
    tokens = batch * seq
    proj = 2.0 * tokens * indexer_params(cfg)
    score = 2.0 * batch * causal_pairs(seq, seq) * s["J"] * s["c"]
    b_proj = tokens * (s["D"] + s["J"] * s["c"] + s["c"]) * BF16
    b_score = batch * causal_pairs(seq, seq) * F32
    n_proj = proj_forwards + 2.0 * proj_backwards
    n_score = score_forwards + 2.0 * score_backwards
    return {"flops": n_proj * proj + n_score * score,
            "bytes": float((proj_forwards + proj_backwards) * b_proj
                           + (score_forwards + score_backwards) * b_score)}


# ---- the experts ----------------------------------------------------------

def expected_pairs_per_token(cfg: Dict) -> float:
    """(token, expert) pairs a token sends to the held experts under a
    uniform router: k x held / routed."""
    s = sizes(cfg)
    return s["k"] * s["held"] / s["E"]


def grouped_products(cfg: Dict, pairs: float, forwards: int = 1,
                     backwards: int = 0) -> Dict[str, float]:
    """The grouped products of one expert layer over ``pairs`` (token,
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
    """Forward plus backward of one training token in a row of ``seq``: 6 x
    the matrix parameters it visits (attention, the router, the experts'
    share at its expectation under a uniform router, the head over the
    vocabulary held), the attention's 12 H d a selected pair, and the
    indexer, which has no backward into the model: its projections' and
    scores' forward and their gradient from its own loss, 3 x the forward.
    Recomputation is not counted."""
    s = sizes(cfg)
    per_layer = attn_params(cfg) + s["D"] * s["E"] \
        + expected_pairs_per_token(cfg) * expert_params(cfg)
    mat = s["L"] * per_layer + s["D"] * s["V"]
    attn = 12.0 * s["H"] * s["d"] * selected_pairs(cfg, seq) / seq
    index = indexer(cfg, seq, proj_backwards=1,
                    score_backwards=1)["flops"] / seq
    return 6.0 * mat + s["L"] * (attn + index)
