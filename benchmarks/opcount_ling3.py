"""Operations and bytes Ling-3.0-flash's layers need, from shapes alone.

Like ``opcount.py``: nothing here imports the program. A "config" is the dict
of ``configs/ling3_flash_train_d7h16e8v8.json`` (the published key names; the
head count and ``num_experts`` are those held here, ``router_width`` the
experts the router scores, ``first_layer`` the published index of the first
layer kept). FLOPs count a multiply-add as 2. Recomputation is never counted
in ``train_flops_per_token``; the rooflines take the number of times the
program runs a forward as an argument. ``whole`` is the published model's
dict from the file's ``reduced`` (the count of its parameters is how the
shapes were checked against the publisher's "about 125B-A5.5B").
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from benchmarks.opcount import BF16, causal_pairs

__all__ = ["sizes", "kinds", "whole", "kda_params", "mla_params",
           "expert_params", "layer_params", "total_params", "active_params",
           "kda_rule", "flash_forward", "flash_backward",
           "expected_pairs_per_token", "grouped_products",
           "layer_forward_flops_per_token", "train_flops_per_token"]

F32 = 4
#: positions a chunk of the rule holds (``ops/delta_rule.py``'s, the
#: family's kernels' choice; the file's ``deployment.kda_chunk``)
CHUNK = 64


def sizes(cfg: Dict) -> Dict[str, int]:
    held = int(cfg["num_experts"])
    return {"D": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
            "d": int(cfg["head_dim"]), "r": int(cfg["kv_lora_rank"]),
            "dn": int(cfg["qk_nope_head_dim"]),
            "dr": int(cfg["qk_rope_head_dim"]), "dv": int(cfg["v_head_dim"]),
            "F": int(cfg["intermediate_size"]),
            "Fm": int(cfg["moe_intermediate_size"]),
            "Fs": int(cfg["moe_shared_expert_intermediate_size"]),
            "V": int(cfg["vocab_size"]), "L": int(cfg["num_hidden_layers"]),
            "taps": int(cfg["short_conv_kernel_size"]), "held": held,
            "E": int(cfg.get("router_width") or held),
            "k": int(cfg["num_experts_per_tok"]),
            "chunk": int(cfg.get("deployment", {}).get("kda_chunk", CHUNK))}


def kinds(cfg: Dict) -> List[Tuple[str, str]]:
    """``(mixer, ffn)`` of each layer kept: published layers ``first_layer``
    on, latent attention where ``(i + 1) % layer_group_size == 0``, the
    first ``first_k_dense_replace`` of the kept ones dense."""
    first, period = int(cfg.get("first_layer", 0)), \
        int(cfg["layer_group_size"])
    dense = int(cfg["first_k_dense_replace"])
    return [("mla" if (first + i + 1) % period == 0 else "kda",
             "dense" if i < dense else "moe")
            for i in range(int(cfg["num_hidden_layers"]))]


def whole(cfg: Dict) -> Dict:
    """The published model's dict: every key of ``reduced`` at its published
    value, every expert held, from layer 0."""
    out = {k: v for k, v in cfg.items() if k != "first_layer"}
    out.update({k: v["published"] for k, v in cfg["reduced"].items()})
    out["router_width"] = out["num_experts"]
    return out


def kda_params(cfg: Dict) -> Dict[str, int]:
    """A KDA mixer's stored parameters: the projections (q, k, v, the
    decay's at full rank, the step's and the gate's, the output's), and
    everything else (three convolutions, A_log, dt_bias, the output norm's
    scale)."""
    s = sizes(cfg)
    width = s["H"] * s["d"]
    return {"matrices": s["D"] * (4 * width + 2 * s["H"]) + width * s["D"],
            "other": 3 * s["taps"] * width + s["H"] + width + s["d"]}


def mla_params(cfg: Dict) -> Dict[str, int]:
    """``wq``, ``wkv_a``, ``wkv_b``, the gate's, ``wo``; the latent's norm."""
    s = sizes(cfg)
    return {"matrices": s["D"] * s["H"] * (s["dn"] + s["dr"])
            + s["D"] * (s["r"] + s["dr"])
            + s["r"] * s["H"] * (s["dn"] + s["dv"]) + s["D"] * s["H"]
            + s["H"] * s["dv"] * s["D"],
            "other": s["r"]}


def expert_params(cfg: Dict) -> int:
    s = sizes(cfg)
    return 3 * s["D"] * s["Fm"]


def _mixer(cfg: Dict, mixer: str) -> Dict[str, int]:
    return kda_params(cfg) if mixer == "kda" else mla_params(cfg)


def layer_params(cfg: Dict, kind: Tuple[str, str]) -> int:
    """Stored parameters of one layer of ``kind`` here: its mixer, two
    RMSNorm scales, and the dense SwiGLU or the shared expert, the router
    with its selection bias and the held experts."""
    s = sizes(cfg)
    ffn = 3 * s["D"] * s["F"] if kind[1] == "dense" else (
        3 * s["D"] * s["Fs"] + s["D"] * s["E"] + s["E"]
        + s["held"] * expert_params(cfg))
    return sum(_mixer(cfg, kind[0]).values()) + 2 * s["D"] + ffn


def total_params(cfg: Dict) -> int:
    """Every stored parameter: the kept layers, the table, the untied head,
    the final norm."""
    s = sizes(cfg)
    return sum(layer_params(cfg, k) for k in kinds(cfg)) \
        + 2 * s["V"] * s["D"] + s["D"]


def active_params(cfg: Dict) -> int:
    """The parameters a token's forward reads, as publishers count them:
    :func:`total_params` with ``num_experts_per_tok`` experts a routed layer
    (the table whole)."""
    s = sizes(cfg)
    routed = sum(ffn == "moe" for _, ffn in kinds(cfg))
    return total_params(cfg) - routed * (s["held"] - s["k"]) \
        * expert_params(cfg)


# ---- the rule -------------------------------------------------------------

def kda_rule(cfg: Dict, seq: int, batch: int = 1, forwards: int = 1,
             backwards: int = 0) -> Dict[str, float]:
    """One KDA layer's rule over ``batch`` sequences of ``seq``, from the
    shapes alone, whatever implements it. Operations, as
    ``opcount_olmo_hybrid.delta_rule``'s (the decays a channel move the
    chunked form's decays into its operands, not its matmuls): a position of
    a head ``K K^T`` and ``Q K^T`` (2 C dk each), the triangular inverse by
    substitution (2 C^2 / 3), its products with ``beta V`` and ``beta K``
    (2 C (dk + dv)), the masked product with the written values (2 C dv),
    and the state's three (2 dk dv each); a backward is twice a forward.
    Bytes: ``q``, ``k``, ``v`` and ``o`` (bf16), ``g`` at ``[T, H, dk]`` and
    ``beta`` (float32) read or written once, and the chunk states (float32)
    written once and read once; a backward twice a forward's."""
    s = sizes(cfg)
    C, H, dk, dv = s["chunk"], s["H"], s["d"], s["d"]
    tokens = batch * seq
    chunks = batch * -(-seq // C)
    flops = tokens * H * (C * (6.0 * dk + 4.0 * dv) + 2.0 * C * C / 3.0
                          + 6.0 * dk * dv)
    byts = tokens * H * (2 * dk * BF16 + 2 * dv * BF16 + dk * F32 + F32) \
        + 2.0 * chunks * H * dk * dv * F32
    times = forwards + 2 * backwards
    return {"flops": flops * times, "bytes": float(byts) * times}


# ---- attention: keys dn + dr wide over values dv wide ---------------------

def _flash(cfg: Dict, seq: int, batch: int, products, reads_writes
           ) -> Dict[str, float]:
    """``opcount_kanana2._flash`` at this configuration's held heads."""
    s = sizes(cfg)
    dk, dv = s["dn"] + s["dr"], s["dv"]
    flops = 2.0 * causal_pairs(seq, seq, None) * s["H"] * batch \
        * (products[0] * dk + products[1] * dv)
    byts = batch * seq * s["H"] * (reads_writes[0] * dk
                                   + reads_writes[1] * dv) * BF16
    return {"flops": flops, "bytes": float(byts)}


def flash_forward(cfg: Dict, seq: int, batch: int = 1) -> Dict[str, float]:
    """The latent-attention layer's forward over ``batch`` sequences of
    ``seq``: QK^T over 192 and PV over 128 for every causal pair of the held
    heads; q, k read, v read and o written once, unpadded."""
    return _flash(cfg, seq, batch, (1, 1), (2, 2))


def flash_backward(cfg: Dict, seq: int, batch: int = 1) -> Dict[str, float]:
    """Its backward: dV and dP over 128, dQ and dK over 192; the kernel's
    recomputation of QK^T is not counted. Reads q, k, v, o, do; writes dq,
    dk, dv."""
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
    expert) pairs that were computed (``opcount_kanana2.grouped_products``
    at this configuration's widths): a forward is three products (6 D Fm
    operations a pair), a backward six; the held experts' weights read once
    a product, the pairs' rows read and written once a product."""
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
    """A routed KDA layer's forward operations a token, by part (the cell's
    ``why`` quotes these as multiply-adds: halve them): the mixer's seven
    products, the rule and the convolutions, the shared expert, the held
    routed experts at their expectation, the router."""
    s = sizes(cfg)
    return {"kda_proj": 2.0 * kda_params(cfg)["matrices"],
            "kda_rule": kda_rule(cfg, seq)["flops"] / seq,
            "kda_conv": 2.0 * 3 * s["taps"] * s["H"] * s["d"],
            "shared": 2.0 * 3 * s["D"] * s["Fs"],
            "routed": 2.0 * expected_pairs_per_token(cfg)
            * expert_params(cfg),
            "router": 2.0 * s["D"] * s["E"]}


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    """Forward plus backward of one training token on packed sequences of
    ``seq``: 6 x the matrix parameters it visits (each mixer's products, the
    dense layer's FFN, each routed layer's shared expert, router and its held
    experts' share at its expectation under a uniform router, the head over
    the vocabulary held), plus for each KDA layer three times the rule's and
    the convolutions' forward operations a token, plus attention's 6 x H x
    (dk + dv) x mean context for each latent-attention layer. Recomputation
    is not counted."""
    s = sizes(cfg)
    parts = layer_forward_flops_per_token(cfg, seq)
    routed = (3 * s["D"] * s["Fs"] + s["D"] * s["E"]
              + expected_pairs_per_token(cfg) * expert_params(cfg))
    mat = s["D"] * s["V"]
    extra = 0.0
    for mixer, ffn in kinds(cfg):
        mat += _mixer(cfg, mixer)["matrices"] \
            + (3 * s["D"] * s["F"] if ffn == "dense" else routed)
        if mixer == "kda":
            extra += 3.0 * (parts["kda_rule"] + parts["kda_conv"])
        else:
            extra += 6.0 * s["H"] * (s["dn"] + s["dr"] + s["dv"]) \
                * causal_pairs(seq, seq, None) / seq
    return 6.0 * mat + extra
