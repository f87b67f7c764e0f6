"""Operations and bytes Granite-4.0-H's layers need, from shapes alone.

Like ``opcount.py``: nothing here imports the program. A "config" is the dict
of ``configs/granite4_h_micro_train_d10v8.json`` (Hugging Face key names).
FLOPs count a multiply-add as 2. Recomputation is never counted in
``train_flops_per_token``; the scan's roofline takes the number of times the
program runs its forward as an argument.
"""

from __future__ import annotations

from typing import Dict, List

from benchmarks.opcount import BF16, causal_pairs

__all__ = ["sizes", "kinds", "mamba_params", "attn_params", "mlp_params",
           "layer_params", "total_params", "matmul_params_per_token",
           "ssd_scan", "train_flops_per_token"]

F32 = 4


def sizes(cfg: Dict) -> Dict[str, int]:
    D, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    Hm, P = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    G, N = int(cfg["mamba_n_groups"]), int(cfg["mamba_d_state"])
    return {"D": D, "H": H, "K": int(cfg["num_key_value_heads"]),
            "d": int(cfg.get("head_dim") or D // H),
            "F": int(cfg["shared_intermediate_size"]),
            "V": int(cfg["vocab_size"]), "L": int(cfg["num_hidden_layers"]),
            "Hm": Hm, "P": P, "G": G, "N": N, "inner": Hm * P,
            "conv": Hm * P + 2 * G * N, "taps": int(cfg["mamba_d_conv"]),
            "chunk": int(cfg["mamba_chunk_size"])}


def kinds(cfg: Dict) -> List[str]:
    """``layer_types`` of the layers kept."""
    return list(cfg["layer_types"])[:int(cfg["num_hidden_layers"])]


def mamba_params(cfg: Dict) -> Dict[str, int]:
    """A mamba mixer's stored parameters: the two projections (matrices),
    and everything else (convolution and its bias, dt_bias, A_log, D, the
    gated norm's scale)."""
    s = sizes(cfg)
    return {"matrices": s["D"] * (s["inner"] + s["conv"] + s["Hm"])
            + s["inner"] * s["D"],
            "other": (s["taps"] + 1) * s["conv"] + 3 * s["Hm"] + s["inner"]}


def attn_params(cfg: Dict) -> int:
    s = sizes(cfg)
    return 2 * s["D"] * s["H"] * s["d"] + 2 * s["D"] * s["K"] * s["d"]


def mlp_params(cfg: Dict) -> int:
    s = sizes(cfg)
    return 3 * s["D"] * s["F"]


def layer_params(cfg: Dict, kind: str) -> int:
    """Stored parameters of one layer of ``kind``: its mixer, the shared
    MLP, two RMSNorm scales."""
    mixer = sum(mamba_params(cfg).values()) if kind == "mamba" \
        else attn_params(cfg)
    return mixer + mlp_params(cfg) + 2 * sizes(cfg)["D"]


def total_params(cfg: Dict) -> int:
    """Every stored parameter: the kept layers, the tied table, the final
    norm."""
    s = sizes(cfg)
    return sum(layer_params(cfg, k) for k in kinds(cfg)) \
        + s["V"] * s["D"] + s["D"]


def matmul_params_per_token(cfg: Dict) -> int:
    """Matrix parameters a token's forward multiplies by: each kept layer's
    projections and MLP, and the tied table once, as the head."""
    s = sizes(cfg)
    per = {"mamba": mamba_params(cfg)["matrices"], "attention":
           attn_params(cfg)}
    return sum(per[k] + mlp_params(cfg) for k in kinds(cfg)) \
        + s["D"] * s["V"]


# ---- the scan -------------------------------------------------------------

def ssd_scan(cfg: Dict, seq: int, batch: int = 1, forwards: int = 1,
             backwards: int = 0) -> Dict[str, float]:
    """One mamba layer's scan over ``batch`` sequences of ``seq``, from the
    shapes alone, whatever implements it. Operations: the chunked form's
    matmuls at chunk Q, a position: ``C B^T`` once a group (2 Q N G), the
    masked product with ``dt x`` (2 Q P H), a chunk's end state and the
    carried state's part (2 P N H each); a backward is twice a forward.
    Bytes: ``x`` and ``y`` (bf16), ``dt`` (float32), ``B`` and ``C`` (bf16)
    read or written once, and the chunk states (float32) written once and
    read once; a backward reads what the forward read and the cotangent of
    ``y`` and writes the cotangents of the inputs, twice a forward's."""
    s = sizes(cfg)
    Q, H, P, G, N = s["chunk"], s["Hm"], s["P"], s["G"], s["N"]
    tokens = batch * seq
    chunks = batch * -(-seq // Q)
    flops = tokens * (2.0 * Q * N * G + 2.0 * Q * P * H + 4.0 * P * N * H)
    byts = tokens * (2 * H * P * BF16 + H * F32 + 2 * G * N * BF16) \
        + 2.0 * chunks * H * P * N * F32
    times = forwards + 2 * backwards
    return {"flops": flops * times, "bytes": float(byts) * times}


# ---- the whole step -------------------------------------------------------

def train_flops_per_token(cfg: Dict, seq: int) -> float:
    """Forward plus backward of one training token on packed sequences of
    ``seq``: 6 x the matrix parameters it visits, plus for each mamba layer
    three times the scan's and the convolution's forward operations a token,
    plus attention's 12 x H x d x mean context for each attention layer.
    Recomputation is not counted."""
    s = sizes(cfg)
    n_mamba = kinds(cfg).count("mamba")
    n_attn = len(kinds(cfg)) - n_mamba
    scan = ssd_scan(cfg, seq)["flops"] / seq
    conv = 2.0 * s["taps"] * s["conv"]
    attn = 12.0 * s["H"] * s["d"] * causal_pairs(seq, seq, None) / seq
    return 6.0 * matmul_params_per_token(cfg) \
        + 3.0 * n_mamba * (scan + conv) + n_attn * attn
