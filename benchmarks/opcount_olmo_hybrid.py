"""Operations and bytes Olmo-Hybrid's layers need, from shapes alone.

Like ``opcount.py``: nothing here imports the program. A "config" is the dict
of ``configs/olmo_hybrid_7b_train_d4h15v8.json`` (Hugging Face key names;
the head counts are those held here). FLOPs count a multiply-add as 2.
Recomputation is never counted in ``train_flops_per_token``; the rule's
roofline takes the number of times the program runs its forward as an
argument.
"""

from __future__ import annotations

from typing import Dict, List

from benchmarks.opcount import BF16, causal_pairs

__all__ = ["sizes", "kinds", "delta_params", "attn_params", "mlp_params",
           "layer_params", "total_params", "matmul_params_per_token",
           "delta_rule", "train_flops_per_token"]

F32 = 4


def sizes(cfg: Dict) -> Dict[str, int]:
    Hl = int(cfg["linear_num_value_heads"])
    dk, dv = int(cfg["linear_key_head_dim"]), int(cfg["linear_value_head_dim"])
    return {"D": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
            "K": int(cfg["num_key_value_heads"]), "d": int(cfg["head_dim"]),
            "F": int(cfg["intermediate_size"]), "V": int(cfg["vocab_size"]),
            "L": int(cfg["num_hidden_layers"]), "Hl": Hl, "dk": dk, "dv": dv,
            "key": Hl * dk, "value": Hl * dv,
            "taps": int(cfg["linear_conv_kernel_dim"]),
            "chunk": int(cfg.get("deployment", {}).get("delta_chunk", 64))}


def kinds(cfg: Dict) -> List[str]:
    """``layer_types`` of the layers kept."""
    return list(cfg["layer_types"])[:int(cfg["num_hidden_layers"])]


def delta_params(cfg: Dict) -> Dict[str, int]:
    """A delta mixer's stored parameters: the projections (q, k, v, the
    gate, the step's and the decay's, the output's), and everything else
    (the three convolutions, A_log, dt_bias, the output norm's scale)."""
    s = sizes(cfg)
    return {"matrices": s["D"] * (2 * s["key"] + 2 * s["value"] + 2 * s["Hl"])
            + s["value"] * s["D"],
            "other": s["taps"] * (2 * s["key"] + s["value"]) + 2 * s["Hl"]
            + s["dv"]}


def attn_params(cfg: Dict) -> Dict[str, int]:
    s = sizes(cfg)
    return {"matrices": 2 * s["D"] * s["H"] * s["d"]
            + 2 * s["D"] * s["K"] * s["d"],
            "other": (s["H"] + s["K"]) * s["d"]}       # the q and k norms


def mlp_params(cfg: Dict) -> int:
    s = sizes(cfg)
    return 3 * s["D"] * s["F"]


def layer_params(cfg: Dict, kind: str) -> int:
    """Stored parameters of one layer of ``kind``: its mixer, the SwiGLU
    FFN, two RMSNorm scales."""
    mixer = delta_params(cfg) if kind == "linear_attention" \
        else attn_params(cfg)
    return sum(mixer.values()) + mlp_params(cfg) + 2 * sizes(cfg)["D"]


def total_params(cfg: Dict) -> int:
    """Every stored parameter: the kept layers, the table, the untied head,
    the final norm."""
    s = sizes(cfg)
    return sum(layer_params(cfg, k) for k in kinds(cfg)) \
        + 2 * s["V"] * s["D"] + s["D"]


def matmul_params_per_token(cfg: Dict) -> int:
    """Matrix parameters a token's forward multiplies by: each kept layer's
    projections and FFN, and the head."""
    s = sizes(cfg)
    per = {"linear_attention": delta_params(cfg)["matrices"],
           "full_attention": attn_params(cfg)["matrices"]}
    return sum(per[k] + mlp_params(cfg) for k in kinds(cfg)) \
        + s["D"] * s["V"]


# ---- the rule -------------------------------------------------------------

def delta_rule(cfg: Dict, seq: int, batch: int = 1, forwards: int = 1,
               backwards: int = 0) -> Dict[str, float]:
    """One delta layer's rule over ``batch`` sequences of ``seq``, from the
    shapes alone, whatever implements it. Operations: the chunked form's
    matmuls at chunk C, a position of a head: ``K K^T`` and ``Q K^T`` (2 C dk
    each), the triangular inverse by substitution (2 C^2 / 3), its products
    with ``beta V`` and ``beta K`` (2 C (dk + dv)), the masked product with
    the written values (2 C dv), and the state's three: its read for the
    written values, its read for the output, its update (2 dk dv each); a
    backward is twice a forward. Bytes: ``q``, ``k``, ``v`` and ``o`` (bf16),
    ``g`` and ``beta`` (float32) read or written once, and the chunk states
    (float32) written once and read once; a backward reads what the forward
    read and the cotangent of ``o`` and writes the cotangents of the inputs,
    twice a forward's."""
    s = sizes(cfg)
    C, H, dk, dv = s["chunk"], s["Hl"], s["dk"], s["dv"]
    tokens = batch * seq
    chunks = batch * -(-seq // C)
    flops = tokens * H * (C * (6.0 * dk + 4.0 * dv) + 2.0 * C * C / 3.0
                          + 6.0 * dk * dv)
    byts = tokens * H * (2 * dk * BF16 + 2 * dv * BF16 + 2 * F32) \
        + 2.0 * chunks * H * dk * dv * F32
    times = forwards + 2 * backwards
    return {"flops": flops * times, "bytes": float(byts) * times}


# ---- the whole step -------------------------------------------------------

def train_flops_per_token(cfg: Dict, seq: int) -> float:
    """Forward plus backward of one training token on packed sequences of
    ``seq``: 6 x the matrix parameters it visits, plus for each delta layer
    three times the rule's and the convolutions' forward operations a token,
    plus attention's 12 x H x d x mean context for each full layer.
    Recomputation is not counted."""
    s = sizes(cfg)
    n_delta = kinds(cfg).count("linear_attention")
    n_attn = len(kinds(cfg)) - n_delta
    rule = delta_rule(cfg, seq)["flops"] / seq
    conv = 2.0 * s["taps"] * (2 * s["key"] + s["value"])
    attn = 12.0 * s["H"] * s["d"] * causal_pairs(seq, seq, None) / seq
    return 6.0 * matmul_params_per_token(cfg) \
        + 3.0 * n_delta * (rule + conv) + n_attn * attn
