"""Operations and bytes kanana-2-30b-a3b's layers need, from shapes alone.

Like ``opcount.py``: nothing here imports the program. A "config" is the dict
of ``configs/kanana2_30b_train_d5e16.json`` (Hugging Face key names, plus
``router_width``: the experts the router scores, where ``n_routed_experts``
is how many are held here). FLOPs count a multiply-add as 2. Recomputation
is never counted in ``train_flops_per_token``; the rooflines take the number
of times the program runs a forward as an argument.
"""

from __future__ import annotations

from typing import Dict

from benchmarks.opcount import BF16, causal_pairs

__all__ = ["sizes", "mla_params", "expert_params", "shared_params",
           "dense_layer_params", "routed_layer_params", "total_params",
           "flash_forward", "flash_backward", "expected_pairs_per_token",
           "grouped_products", "layer_forward_flops_per_token",
           "train_flops_per_token"]


def sizes(cfg: Dict) -> Dict[str, int]:
    held = int(cfg["n_routed_experts"])
    return {"D": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
            "r": int(cfg["kv_lora_rank"]), "dn": int(cfg["qk_nope_head_dim"]),
            "dr": int(cfg["qk_rope_head_dim"]), "dv": int(cfg["v_head_dim"]),
            "F": int(cfg["intermediate_size"]),
            "Fm": int(cfg["moe_intermediate_size"]),
            "V": int(cfg["vocab_size"]), "L": int(cfg["num_hidden_layers"]),
            "dense": int(cfg["first_k_dense_replace"]), "held": held,
            "E": int(cfg.get("router_width") or held),
            "k": int(cfg["num_experts_per_tok"]),
            "shared": int(cfg["n_shared_experts"])}


def mla_params(cfg: Dict) -> int:
    """``wq``, ``wkv_a``, the latent's norm, ``wkv_b``, ``wo``."""
    s = sizes(cfg)
    return (s["D"] * s["H"] * (s["dn"] + s["dr"]) + s["D"] * (s["r"] + s["dr"])
            + s["r"] + s["r"] * s["H"] * (s["dn"] + s["dv"])
            + s["H"] * s["dv"] * s["D"])


def expert_params(cfg: Dict) -> int:
    s = sizes(cfg)
    return 3 * s["D"] * s["Fm"]


def shared_params(cfg: Dict) -> int:
    return sizes(cfg)["shared"] * expert_params(cfg)


def dense_layer_params(cfg: Dict) -> int:
    s = sizes(cfg)
    return mla_params(cfg) + 3 * s["D"] * s["F"] + 2 * s["D"]


def routed_layer_params(cfg: Dict) -> int:
    """Stored parameters of one routed layer here: the mixer, the shared
    experts, the router and its selection bias, the held experts, two
    RMSNorm scales."""
    s = sizes(cfg)
    return (mla_params(cfg) + shared_params(cfg) + s["D"] * s["E"] + s["E"]
            + s["held"] * expert_params(cfg) + 2 * s["D"])


def total_params(cfg: Dict) -> int:
    s = sizes(cfg)
    return (s["dense"] * dense_layer_params(cfg)
            + (s["L"] - s["dense"]) * routed_layer_params(cfg)
            + 2 * s["V"] * s["D"] + s["D"])


# ---- attention: keys dn + dr wide over values dv wide ---------------------

def _flash(cfg: Dict, seq: int, batch: int, products: float, reads_writes
           ) -> Dict[str, float]:
    """``products``: matmuls over the causal pairs, counted in units of one
    pair x one lane of width (a QK^T-like one costs dn + dr a pair, a PV-like
    one dv); the bytes: each of q, k (dn + dr wide), v, o (dv wide) and their
    gradients as often as ``reads_writes`` says, unpadded."""
    s = sizes(cfg)
    dk, dv = s["dn"] + s["dr"], s["dv"]
    n_qk, n_pv = products
    wide, narrow = reads_writes
    flops = 2.0 * causal_pairs(seq, seq, None) * s["H"] * batch \
        * (n_qk * dk + n_pv * dv)
    byts = batch * seq * s["H"] * (wide * dk + narrow * dv) * BF16
    return {"flops": flops, "bytes": float(byts)}


def flash_forward(cfg: Dict, seq: int, batch: int = 1) -> Dict[str, float]:
    """One layer's forward over ``batch`` sequences of ``seq``: QK^T over
    192 and PV over 128 for every causal pair of the 32 heads; q, k read, v
    read and o written once."""
    return _flash(cfg, seq, batch, (1, 1), (2, 2))


def flash_backward(cfg: Dict, seq: int, batch: int = 1) -> Dict[str, float]:
    """One layer's backward: dV = P^T dO and dP = dO V^T over 128, dQ = dS K
    and dK = dS^T Q over 192; the kernel's recomputation of QK^T is not
    counted. Reads q, k, v, o, do; writes dq, dk, dv."""
    return _flash(cfg, seq, batch, (2, 2), (4, 4))


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
    """A routed layer's forward operations a token, by part (the cell's
    ``why`` quotes these): the four MLA products, scores and values at the
    mean causal context, the shared experts, the held routed experts at
    their expectation, the router."""
    s = sizes(cfg)
    ctx = causal_pairs(seq, seq, None) / seq
    return {"mla_proj": 2.0 * (mla_params(cfg) - s["r"]),
            "scores_values": 2.0 * s["H"] * (s["dn"] + s["dr"] + s["dv"]) * ctx,
            "shared": 2.0 * shared_params(cfg),
            "routed": 2.0 * expected_pairs_per_token(cfg) * expert_params(cfg),
            "router": 2.0 * s["D"] * s["E"]}


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    """Forward plus backward of one training token on packed sequences of
    ``seq``: 6 x the matrix parameters it visits (the mixer, the dense
    layer's FFN, each routed layer's shared experts, router and its held
    experts' share at its expectation under a uniform router, the head over
    the vocabulary held) plus attention's 6 x H x (dk + dv) x mean context a
    layer. Recomputation is not counted."""
    s = sizes(cfg)
    mixer = mla_params(cfg) - s["r"]
    routed = (shared_params(cfg) + s["D"] * s["E"]
              + expected_pairs_per_token(cfg) * expert_params(cfg))
    mat = (s["L"] * mixer + s["dense"] * 3 * s["D"] * s["F"]
           + (s["L"] - s["dense"]) * routed + s["D"] * s["V"])
    ctx = causal_pairs(seq, seq, None) / seq
    attn = 6.0 * s["L"] * s["H"] * (s["dn"] + s["dr"] + s["dv"]) * ctx
    return 6.0 * mat + attn
