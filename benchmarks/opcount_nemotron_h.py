"""Operations and bytes Nemotron-H's layers need (``model_type:
"nemotron_h"`` with LatentMoE), from shapes alone.

Like ``opcount.py``: nothing here imports the program. A "config" is the dict
of ``configs/nemotron3_super_120b_train_d11h16e8v8.json`` (Hugging Face key
names, plus ``router_width``: the experts the router scores, where
``n_routed_experts`` is how many are held here; the head counts are those
held). FLOPs count a multiply-add as 2. Recomputation is never counted in
``train_flops_per_token``; the rooflines take the number of times the
program runs a forward as an argument. An expert is **two** products (1024
-> 2688 -> 1024 round ``relu^2``), and the pairs counted are those really
held.
"""

from __future__ import annotations

from typing import Dict

from benchmarks.opcount import BF16, causal_pairs

F32 = 4

__all__ = ["sizes", "kinds", "mamba_layer_params", "attention_layer_params",
           "expert_params", "expert_layer_params", "total_params",
           "published", "whole_model_params", "active_params", "ssd_scan",
           "expected_pairs_per_token", "grouped_products",
           "layer_forward_flops_per_token", "train_flops_per_token"]


def sizes(cfg: Dict) -> Dict[str, int]:
    held = int(cfg["n_routed_experts"])
    Hm, P = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    G, N = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    return {"D": int(cfg["hidden_size"]), "V": int(cfg["vocab_size"]),
            "L": int(cfg["num_hidden_layers"]),
            "H": int(cfg["num_attention_heads"]),
            "K": int(cfg["num_key_value_heads"]), "d": int(cfg["head_dim"]),
            "Hm": Hm, "P": P, "G": G, "N": N, "inner": Hm * P,
            "conv": Hm * P + 2 * G * N, "taps": int(cfg["conv_kernel"]),
            "chunk": int(cfg["chunk_size"]),
            "Z": int(cfg["moe_latent_size"]),
            "Fm": int(cfg["moe_intermediate_size"]),
            "Fs": int(cfg["moe_shared_expert_intermediate_size"]),
            "held": held, "E": int(cfg.get("router_width") or held),
            "k": int(cfg["num_experts_per_tok"])}


def kinds(cfg: Dict) -> str:
    """The letters of the layers kept."""
    return str(cfg["hybrid_override_pattern"])[:int(cfg["num_hidden_layers"])]


# ---- parameters -----------------------------------------------------------

def mamba_layer_params(cfg: Dict) -> int:
    """``in_proj`` (z, x, B, C, dt), the convolution with its bias,
    ``dt_bias``, ``A_log``, ``D``, the gated norm, ``out_proj``, the
    layer's norm."""
    s = sizes(cfg)
    return (s["D"] * (s["inner"] + s["conv"] + s["Hm"])
            + (s["taps"] + 1) * s["conv"] + 3 * s["Hm"] + s["inner"]
            + s["inner"] * s["D"] + s["D"])


def attention_layer_params(cfg: Dict) -> int:
    s = sizes(cfg)
    return 2 * s["D"] * s["H"] * s["d"] + 2 * s["D"] * s["K"] * s["d"] \
        + s["D"]


def expert_params(cfg: Dict) -> int:
    """One routed expert: two products in the latent."""
    s = sizes(cfg)
    return 2 * s["Z"] * s["Fm"]


def _expert_layer(cfg: Dict, experts: int) -> int:
    s = sizes(cfg)
    return (s["D"] * s["E"] + s["E"] + 2 * s["D"] * s["Z"]
            + 2 * s["D"] * s["Fs"] + experts * expert_params(cfg) + s["D"])


def expert_layer_params(cfg: Dict) -> int:
    """The router and its selection biases, the two latent maps, the shared
    expert, the held experts, the layer's norm."""
    return _expert_layer(cfg, sizes(cfg)["held"])


def _by_kind(cfg: Dict, experts: int) -> Dict[str, int]:
    return {"M": mamba_layer_params(cfg), "*": attention_layer_params(cfg),
            "E": _expert_layer(cfg, experts)}


def total_params(cfg: Dict) -> int:
    """Every parameter stored here: the kept layers, embedding, untied head,
    final norm."""
    s, each = sizes(cfg), _by_kind(cfg, sizes(cfg)["held"])
    return sum(each[c] for c in kinds(cfg)) + 2 * s["V"] * s["D"] + s["D"]


def published(cfg: Dict) -> Dict:
    """``cfg`` with every ``reduced`` key at its published value and the
    router's width as the expert count: the whole model's sizes."""
    whole = {**cfg, **{k: v["published"] for k, v in cfg["reduced"].items()}}
    whole["router_width"] = whole["n_routed_experts"]
    return whole


def whole_model_params(cfg: Dict, experts_counted: int = None) -> int:
    """The published model's parameters by the same formulas (the
    multi-token module apart): every layer of the whole pattern, every head,
    every row, and ``experts_counted`` experts a layer (default all)."""
    whole = published(cfg)
    s = sizes(whole)
    each = _by_kind(whole, s["E"] if experts_counted is None
                    else experts_counted)
    return sum(each[c] for c in kinds(whole)) + 2 * s["V"] * s["D"] + s["D"]


def active_params(cfg: Dict) -> int:
    """The whole model's parameters a token visits: ``num_experts_per_tok``
    routed experts a layer."""
    return whole_model_params(cfg, sizes(cfg)["k"])


# ---- the scan -------------------------------------------------------------

def ssd_scan(cfg: Dict, seq: int, batch: int = 1, forwards: int = 1,
             backwards: int = 0) -> Dict[str, float]:
    """One Mamba layer's scan over ``batch`` sequences of ``seq``, from the
    shapes alone (``opcount_granite4h.ssd_scan`` at this configuration's
    keys: chunk 128, the groups and heads held). Operations: the chunked
    form's matmuls at chunk Q, a position: ``C B^T`` once a group (2 Q N G),
    the masked product with ``dt x`` (2 Q P H), a chunk's end state and the
    carried state's part (2 P N H each); a backward is twice a forward.
    Bytes: ``x`` and ``y`` (bf16), ``dt`` (float32), ``B`` and ``C`` (bf16)
    read or written once, and the chunk states (float32) written once and
    read once; a backward twice a forward's."""
    s = sizes(cfg)
    Q, H, P, G, N = s["chunk"], s["Hm"], s["P"], s["G"], s["N"]
    tokens = batch * seq
    chunks = batch * -(-seq // Q)
    flops = tokens * (2.0 * Q * N * G + 2.0 * Q * P * H + 4.0 * P * N * H)
    byts = tokens * (2 * H * P * BF16 + H * F32 + 2 * G * N * BF16) \
        + 2.0 * chunks * H * P * N * F32
    times = forwards + 2 * backwards
    return {"flops": flops * times, "bytes": float(byts) * times}


# ---- the experts ----------------------------------------------------------

def expected_pairs_per_token(cfg: Dict) -> float:
    """(token, expert) pairs a token sends to the held experts under a
    uniform router: k x held / routed."""
    s = sizes(cfg)
    return s["k"] * s["held"] / s["E"]


def grouped_products(cfg: Dict, pairs: float, forwards: int = 1,
                     backwards: int = 0) -> Dict[str, float]:
    """The grouped products of one expert layer over ``pairs`` (token,
    expert) pairs that were computed: a forward is two products (4 Z Fm
    operations a pair), a backward four; the held experts' weights read once
    a product (their gradients written once a backward), the pairs' rows
    read and written once a product (Z in and Fm out, Fm in and Z out)."""
    s = sizes(cfg)
    Z, F = s["Z"], s["Fm"]
    flops = (4.0 * forwards + 8.0 * backwards) * pairs * Z * F
    weights = s["held"] * 2 * Z * F * BF16
    rows_fwd = pairs * (2 * Z + 2 * F) * BF16
    byts = forwards * (weights + rows_fwd) + backwards * (2 * weights
                                                          + 2 * rows_fwd)
    return {"flops": flops, "bytes": float(byts)}


# ---- the whole step -------------------------------------------------------

def layer_forward_flops_per_token(cfg: Dict, seq: int) -> Dict[str, float]:
    """An expert layer's forward operations a token, by part (the cell's
    ``why`` quotes these): the router, the two latent maps, the shared
    expert, the held routed experts at their expectation."""
    s = sizes(cfg)
    return {"router": 2.0 * s["D"] * s["E"],
            "latent": 4.0 * s["D"] * s["Z"],
            "shared": 4.0 * s["D"] * s["Fs"],
            "routed": 2.0 * expected_pairs_per_token(cfg)
            * expert_params(cfg)}


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    """Forward plus backward of one training token on packed sequences of
    ``seq``: 6 x the matrix parameters it visits (each Mamba layer's two
    projections, the attention layer's four, each expert layer's router,
    latent maps, shared expert and its held experts' share at its
    expectation under a uniform router, the head over the vocabulary held),
    plus for each Mamba layer three times the scan's and the convolution's
    forward operations a token, plus attention's 12 x H x d x mean context
    for the attention layer. Recomputation is not counted."""
    s, letters = sizes(cfg), kinds(cfg)
    mamba = s["D"] * (s["inner"] + s["conv"] + s["Hm"]) + s["inner"] * s["D"]
    attn = 2 * s["D"] * s["H"] * s["d"] + 2 * s["D"] * s["K"] * s["d"]
    experts = sum(layer_forward_flops_per_token(cfg, seq).values()) / 2.0
    mat = (letters.count("M") * mamba + letters.count("*") * attn
           + letters.count("E") * experts + s["D"] * s["V"])
    scan = ssd_scan(cfg, seq)["flops"] / seq
    conv = 2.0 * s["taps"] * s["conv"]
    ctx = 12.0 * s["H"] * s["d"] * causal_pairs(seq, seq, None) / seq
    return 6.0 * mat + 3.0 * letters.count("M") * (scan + conv) \
        + letters.count("*") * ctx
