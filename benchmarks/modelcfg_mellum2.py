"""From the Mellum2 configuration's published sizes to the program's model,
and from the program's parameter tree to the names ``reference_mellum2``
reads.

The file keeps the publisher's key names (Hugging Face ``config.json``,
``model_type: "mellum"``); this is the one place in the benchmark that maps
them onto ``TransformerConfig``. No preset of the program is read. What the
file adds to the publisher's keys: ``router_width`` (the experts the router
scores; ``num_experts`` is how many are held here) and ``first_expert``.
"""

from __future__ import annotations

from typing import Dict, Tuple

KINDS = {"sliding_attention": "window", "full_attention": "full"}


def attn_pattern(cfg: Dict) -> Tuple[str, ...]:
    """The kept layers' kinds (the program keeps the shortest period)."""
    return tuple(KINDS[k] for k in
                 cfg["layer_types"][:int(cfg["num_hidden_layers"])])


def transformer_config(cfg: Dict, *, max_seq_len: int, param_dtype: str,
                       **extra):
    from deepspeed_tpu.models import TransformerConfig

    L = int(cfg["num_hidden_layers"])
    if set(cfg["mlp_layer_types"][:L]) != {"sparse"}:
        raise ValueError("a dense FFN layer among the kept ones is not "
                         "mapped here")
    if not cfg["norm_topk_prob"] or cfg.get("attention_bias"):
        raise ValueError("the program's router renormalises its top k and "
                         "its attention has no biases")
    dep = cfg["deployment"]
    held, routed = int(cfg["num_experts"]), int(cfg["router_width"])
    kw = dict(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        num_layers=L,
        num_heads=int(cfg["num_attention_heads"]),
        num_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim_override=int(cfg["head_dim"]),
        intermediate_size=int(cfg["intermediate_size"]),   # no layer uses it
        max_seq_len=int(max_seq_len),
        arch="llama",                       # RMSNorm, RoPE, SwiGLU, no biases
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        dtype="bfloat16", param_dtype=param_dtype,
        attention_impl="auto",
        # window and full layers in turn, each kind with its own rope
        # (a rehearsal nulls the window: one as long as the sequence is none)
        sliding_window=int(cfg["sliding_window"] or max_seq_len),
        attn_pattern=attn_pattern(cfg),
        rope_by_kind={KINDS[k]: dict(v)
                      for k, v in cfg["rope_parameters"].items()},
        # the experts: the router scores all `routed`, `held` live here
        num_experts=routed,
        top_k=int(cfg["num_experts_per_tok"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        moe_dispatch="grouped",
        moe_experts_held=None if held == routed else held,
        moe_first_expert=int(cfg.get("first_expert", 0)),
        moe_ep_capacity_factor=float(dep["local_pairs_factor"]),
        moe_aux_loss_coef=float(dep["load_balance_coef"]),
        remat_policy=str(dep["remat_policy"]),
        embed_init_std=float(dep["embed_init_std"]),
    )
    kw.update(extra)
    return TransformerConfig(**kw)


def weights_getter(params, convert=lambda t: t):
    """``get(name, layer=None)`` over the program's parameter tree, as
    ``reference_mellum2`` wants it: the one place that knows where the
    program keeps each tensor. ``convert`` is applied to what is returned (a
    cast, a move to another device)."""
    layers = params["layers"]

    def get(name, layer=None):
        if name == "embed":
            t = params["embed"]["tokens"]
        elif name == "final_norm":
            t = params["final_norm"]["scale"]
        elif name == "head":
            t = params["lm_head"]
        elif name.startswith("ln"):
            t = layers[name]["scale"][layer]
        elif name in ("wq", "wk", "wv", "wo"):
            t = layers["attn"][name][layer]
        else:
            t = layers["mlp"][name][layer]
        return convert(t)

    return get
