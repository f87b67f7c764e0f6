"""Operations and bytes Qwen3-Next's layers need, from shapes alone: the work
**the equations ask for**, whatever implements it.

Like ``opcount.py``: nothing here imports the program. A "config" is the dict
of ``configs/qwen3_next_80b_train_d4e32v8.json`` (Hugging Face key names,
plus ``router_width``: the experts the router scores, where ``num_experts``
is how many are held here). FLOPs count a multiply-add as 2. Recomputation is
never counted in ``train_flops_per_token``; the rooflines take the number of
times the program runs each part as an argument.

What the counts assume of a least implementation: a delta layer's ``K K^T``
and ``Q K^T`` are a key head's (the value heads that share it differ only in
their decays and steps, which multiply the products afterwards), q and k are
read once a key head in bf16, everything else is a value head's; the
attention is counted over the causal pairs only; an expert's weights are read
once a product whatever the number of pairs.
"""

from __future__ import annotations

from typing import Dict, List

from benchmarks.opcount import BF16, causal_pairs

__all__ = ["sizes", "kinds", "delta_params", "attn_params", "expert_params",
           "ffn_params", "layer_params", "total_params", "whole_model_params",
           "active_params_per_token", "delta_rule", "flash",
           "expected_pairs_per_token", "grouped_products",
           "matmul_params_per_token", "train_flops_per_token"]

F32 = 4


def sizes(cfg: Dict) -> Dict[str, int]:
    held = int(cfg["num_experts"])
    return {"D": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
            "K": int(cfg["num_key_value_heads"]), "d": int(cfg["head_dim"]),
            "Hk": int(cfg["linear_num_key_heads"]),
            "Hv": int(cfg["linear_num_value_heads"]),
            "dk": int(cfg["linear_key_head_dim"]),
            "dv": int(cfg["linear_value_head_dim"]),
            "taps": int(cfg["linear_conv_kernel_dim"]),
            "F": int(cfg["moe_intermediate_size"]),
            "Fs": int(cfg["shared_expert_intermediate_size"]),
            "V": int(cfg["vocab_size"]), "L": int(cfg["num_hidden_layers"]),
            "held": held, "E": int(cfg.get("router_width") or held),
            "k": int(cfg["num_experts_per_tok"]),
            "every": int(cfg["full_attention_interval"]),
            "chunk": int(cfg.get("deployment", {}).get("delta_chunk", 64))}


def kinds(cfg: Dict, layers: int = None) -> List[str]:
    """The kind of each of ``layers`` layers (default: those kept)."""
    s = sizes(cfg)
    return ["full_attention" if (i + 1) % s["every"] == 0
            else "linear_attention"
            for i in range(s["L"] if layers is None else layers)]


# ---- parameters -----------------------------------------------------------

def delta_params(cfg: Dict) -> Dict[str, int]:
    """A delta mixer's stored parameters: the projections (q and k a key
    head's, v, the gate, the step's and the decay's a value head's, the
    output's), and everything else (the three convolutions, A_log, dt_bias,
    the output norm's scale)."""
    s = sizes(cfg)
    key, value = s["Hk"] * s["dk"], s["Hv"] * s["dv"]
    return {"matrices": s["D"] * (2 * key + 2 * value + 2 * s["Hv"])
            + value * s["D"],
            "other": s["taps"] * (2 * key + value) + 2 * s["Hv"] + s["dv"]}


def attn_params(cfg: Dict) -> Dict[str, int]:
    """The full mixer's: q with a gate a channel (twice as wide), k, v, o;
    the two head norms' scales."""
    s = sizes(cfg)
    return {"matrices": 3 * s["D"] * s["H"] * s["d"]
            + 2 * s["D"] * s["K"] * s["d"],
            "other": 2 * s["d"]}


def expert_params(cfg: Dict) -> int:
    s = sizes(cfg)
    return 3 * s["D"] * s["F"]


def ffn_params(cfg: Dict, experts: int = None) -> int:
    """A layer's FFN with ``experts`` experts (default: those held): the
    router over all the routed, the experts, the shared expert and its
    gate."""
    s = sizes(cfg)
    n = s["held"] if experts is None else experts
    return s["D"] * s["E"] + n * expert_params(cfg) + 3 * s["D"] * s["Fs"] \
        + s["D"]


def layer_params(cfg: Dict, kind: str, experts: int = None) -> int:
    """Stored parameters of one layer of ``kind``: its mixer, its FFN, two
    norms."""
    mixer = delta_params(cfg) if kind == "linear_attention" \
        else attn_params(cfg)
    return sum(mixer.values()) + ffn_params(cfg, experts) \
        + 2 * sizes(cfg)["D"]


def total_params(cfg: Dict) -> int:
    """What this configuration stores."""
    s = sizes(cfg)
    return sum(layer_params(cfg, k) for k in kinds(cfg)) \
        + 2 * s["V"] * s["D"] + s["D"]


def _published(cfg: Dict, key: str) -> int:
    return int(cfg.get("reduced", {}).get(key, {}).get("published",
                                                       cfg[key]))


def _whole(cfg: Dict, experts: int) -> int:
    s = sizes(cfg)
    return sum(layer_params(cfg, k, experts) for k in kinds(
        cfg, _published(cfg, "num_hidden_layers"))) \
        + 2 * _published(cfg, "vocab_size") * s["D"] + s["D"]


def whole_model_params(cfg: Dict) -> int:
    """The model as published, by the file's keys: every layer, every
    expert, the whole vocabulary (the multi-token module left out)."""
    return _whole(cfg, _published(cfg, "num_experts"))


def active_params_per_token(cfg: Dict) -> int:
    """Of :func:`whole_model_params`, what is active for one token as model
    cards count it ("A3B"): k experts a layer, everything else whole."""
    return _whole(cfg, sizes(cfg)["k"])


# ---- the rule -------------------------------------------------------------

def delta_rule(cfg: Dict, seq: int, batch: int = 1, forwards: int = 1,
               backwards: int = 0) -> Dict[str, float]:
    """One delta layer's rule over ``batch`` sequences of ``seq``, from the
    shapes alone. Operations, a position: a key head's ``K K^T`` and ``Q
    K^T`` at chunk C (2 C dk each); a value head's triangular inverse by
    substitution (2 C^2 / 3), its products with ``beta V`` and ``beta K``
    (2 C (dk + dv)), the masked product with the written values (2 C dv),
    and the state's three: its read for the written values, its read for
    the output, its update (2 dk dv each); a backward is twice a forward.
    Bytes: ``q`` and ``k`` once a key head and ``v`` and ``o`` once a value
    head (bf16), ``g`` and ``beta`` (float32), and the chunk states
    (float32) written once and read once; a backward reads what the forward
    read and the cotangent of ``o`` and writes the cotangents of the inputs,
    twice a forward's."""
    s = sizes(cfg)
    C, Hk, Hv, dk, dv = s["chunk"], s["Hk"], s["Hv"], s["dk"], s["dv"]
    tokens = batch * seq
    chunks = batch * -(-seq // C)
    flops = tokens * (Hk * 4.0 * C * dk
                      + Hv * (C * (2.0 * dk + 4.0 * dv) + 2.0 * C * C / 3.0
                              + 6.0 * dk * dv))
    byts = tokens * (Hk * 2 * dk * BF16 + Hv * (2 * dv * BF16 + 2 * F32)) \
        + 2.0 * chunks * Hv * dk * dv * F32
    times = forwards + 2 * backwards
    return {"flops": flops * times, "bytes": float(byts) * times}


# ---- the full layer's attention -------------------------------------------

def flash(cfg: Dict, seq: int, batch: int = 1, forwards: int = 1,
          backwards: int = 0) -> Dict[str, float]:
    """The full layer's attention over the causal pairs of ``batch``
    sequences of ``seq``: a forward is QK^T and PV (4 H d a pair), a backward
    the four products the gradient needs (8 H d; a recomputed QK^T is not
    counted). Bytes: a forward reads q, k, v and writes o; a backward reads
    q, k, v, o, do and writes dq, dk, dv."""
    s = sizes(cfg)
    pairs = causal_pairs(seq, seq, None) * batch
    rows = batch * seq * (s["H"] + s["K"]) * s["d"] * BF16
    return {"flops": (4.0 * forwards + 8.0 * backwards) * pairs * s["H"]
            * s["d"],
            "bytes": float((2 * forwards + 4 * backwards) * rows)}


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
    and written once a product. At 320 pairs an expert of 512 the weights'
    read is what bounds them."""
    s = sizes(cfg)
    D, F = s["D"], s["F"]
    flops = (6.0 * forwards + 12.0 * backwards) * pairs * D * F
    weights = s["held"] * 3 * D * F * BF16
    rows_fwd = pairs * (3 * D + 4 * F) * BF16
    byts = forwards * (weights + rows_fwd) + backwards * (2 * weights
                                                          + 2 * rows_fwd)
    return {"flops": flops, "bytes": float(byts)}


# ---- the whole step -------------------------------------------------------

def matmul_params_per_token(cfg: Dict) -> float:
    """Matrix parameters a token's forward multiplies by in the cut: each
    kept layer's projections, router, shared expert (its gate a column) and
    the held experts' share at its expectation under a uniform router, and
    the head."""
    s = sizes(cfg)
    per = {"linear_attention": delta_params(cfg)["matrices"],
           "full_attention": attn_params(cfg)["matrices"]}
    ffn = s["D"] * s["E"] + 3 * s["D"] * s["Fs"] + s["D"] \
        + expected_pairs_per_token(cfg) * expert_params(cfg)
    return sum(per[k] + ffn for k in kinds(cfg)) + s["D"] * s["V"]


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    """Forward plus backward of one training token on packed sequences of
    ``seq``, of the work the cut does: 6 x the matrix parameters it visits,
    plus for each delta layer three times the rule's and the convolutions'
    forward operations a token, plus attention's 12 H d x mean context for
    each full layer. Recomputation is not counted."""
    s = sizes(cfg)
    n_delta = kinds(cfg).count("linear_attention")
    n_attn = len(kinds(cfg)) - n_delta
    rule = delta_rule(cfg, seq)["flops"] / seq
    conv = 2.0 * s["taps"] * (2 * s["Hk"] * s["dk"] + s["Hv"] * s["dv"])
    attn = 12.0 * s["H"] * s["d"] * causal_pairs(seq, seq, None) / seq
    return 6.0 * matmul_params_per_token(cfg) \
        + 3.0 * n_delta * (rule + conv) + n_attn * attn
