"""Operations and bytes the algorithm needs, from shapes alone.

The benchmark's own arithmetic: nothing here imports the program, so a
refactor of the program cannot move a utilisation or a roofline share. A
"config" is the dict of a file under ``benchmarks/configs/`` (Hugging Face
key names). FLOPs count a multiply-add as 2. Bytes are what a call must move
through HBM once: weights read, activations read and written.
Recomputation (remat, flash backward's second QK^T) is never counted, so a
share computed from these numbers can only under-state, never pass 100 %.
"""

from __future__ import annotations

from typing import Dict

BF16 = 2


def sizes(cfg: Dict) -> Dict[str, int]:
    """The widths every function below reads, under short names."""
    H = int(cfg["num_attention_heads"])
    D = int(cfg["hidden_size"])
    return {
        "D": D, "H": H, "K": int(cfg["num_key_value_heads"]),
        "d": int(cfg.get("head_dim") or D // H),
        "F": int(cfg["intermediate_size"]), "V": int(cfg["vocab_size"]),
        "L": int(cfg["num_hidden_layers"]),
        "E": int(cfg.get("num_local_experts", 1) or 1),
        "k": int(cfg.get("num_experts_per_tok", 1) or 1),
        "tied": bool(cfg.get("tie_word_embeddings", False)),
        "window": cfg.get("sliding_window"),
    }


# ---- parameters -----------------------------------------------------------

def attn_params(cfg: Dict) -> int:
    s = sizes(cfg)
    return s["D"] * s["H"] * s["d"] * 2 + 2 * s["D"] * s["K"] * s["d"]


def ffn_params(cfg: Dict) -> int:
    """One expert's (or the dense block's) gate, up and down matrices."""
    s = sizes(cfg)
    return 3 * s["D"] * s["F"]


def layer_matmul_params(cfg: Dict, active_only: bool = False) -> int:
    """Matrix parameters of one layer; with ``active_only`` the experts a
    token really visits (top-k of E) instead of all of them."""
    s = sizes(cfg)
    if s["E"] > 1:
        n = s["k"] if active_only else s["E"]
        return attn_params(cfg) + n * ffn_params(cfg) + s["D"] * s["E"]
    return attn_params(cfg) + ffn_params(cfg)


def total_params(cfg: Dict) -> int:
    """Every stored parameter: layers (matrices + two RMSNorm scales),
    embedding, untied head, final norm."""
    s = sizes(cfg)
    per_layer = layer_matmul_params(cfg) + 2 * s["D"]
    head = 0 if s["tied"] else s["D"] * s["V"]
    return s["L"] * per_layer + s["V"] * s["D"] + head + s["D"]


# ---- attention ------------------------------------------------------------

def causal_pairs(q_len: int, ctx_len: int, window=None) -> int:
    """(query, key) pairs a causal mask keeps when ``q_len`` queries sit at
    the end of a context of ``ctx_len`` keys; a window keeps the last
    ``window`` keys of each query."""
    first = ctx_len - q_len            # keys before the first query
    total = 0
    if window is None or window >= ctx_len:
        # query i (0-based) sees first + i + 1 keys
        return q_len * first + q_len * (q_len + 1) // 2
    for i in range(q_len):
        total += min(first + i + 1, int(window))
    return total


def flash_forward(cfg: Dict, seq: int, batch: int = 1) -> Dict[str, float]:
    """Causal self-attention forward over ``batch`` sequences of ``seq``:
    QK^T and PV over the kept pairs; q, k, v read and o written once."""
    s = sizes(cfg)
    pairs = causal_pairs(seq, seq, s["window"])
    flops = 4.0 * pairs * s["H"] * s["d"] * batch
    byts = batch * seq * (2 * s["H"] + 2 * s["K"]) * s["d"] * BF16
    return {"flops": flops, "bytes": float(byts)}


def flash_backward(cfg: Dict, seq: int, batch: int = 1) -> Dict[str, float]:
    """The four matmuls the gradient needs (dV, dP, dQ, dK); the kernel's
    recomputation of QK^T is not counted. Reads q, k, v, o, do; writes dq,
    dk, dv."""
    s = sizes(cfg)
    pairs = causal_pairs(seq, seq, s["window"])
    flops = 8.0 * pairs * s["H"] * s["d"] * batch
    byts = batch * seq * (4 * s["H"] + 4 * s["K"]) * s["d"] * BF16
    return {"flops": flops, "bytes": float(byts)}


# ---- whole steps ----------------------------------------------------------

def train_flops_per_token(cfg: Dict, seq: int) -> float:
    """Forward plus backward of next-token training on packed sequences of
    ``seq`` tokens: 6 x the matrix parameters a token visits (layers and
    head; the embedding gather does no arithmetic) plus attention's
    12 x H x d x mean context per layer. Remat is not counted."""
    s = sizes(cfg)
    mat = s["L"] * layer_matmul_params(cfg, active_only=True) \
        + s["D"] * s["V"]
    pairs = causal_pairs(seq, seq, s["window"])
    attn = 12.0 * s["L"] * s["H"] * s["d"] * pairs / seq
    return 6.0 * mat + attn


def roofline_seconds(ops: Dict[str, float], peak: Dict[str, float]
                     ) -> Dict[str, object]:
    """The least time the chip could take and which roof sets it."""
    t_f = ops["flops"] / peak["bf16_flops_per_s"]
    t_b = ops["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_f, t_b),
            "bound": "compute" if t_f >= t_b else "memory"}
