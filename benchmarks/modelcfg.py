"""From a configuration file's published sizes to the program's model.

The file keeps the publisher's key names (Hugging Face ``config.json``); this
is the one place that maps them onto ``TransformerConfig``. No preset of the
program is read, so a change to ``models/presets.py`` cannot move a cell.
"""

from __future__ import annotations

from typing import Dict


def transformer_config(cfg: Dict, *, max_seq_len: int, param_dtype: str,
                       **extra):
    from deepspeed_tpu.models import TransformerConfig

    experts = int(cfg.get("num_local_experts", 1) or 1)
    kw = dict(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        num_layers=int(cfg["num_hidden_layers"]),
        num_heads=int(cfg["num_attention_heads"]),
        num_kv_heads=int(cfg["num_key_value_heads"]),
        intermediate_size=int(cfg["intermediate_size"]),
        max_seq_len=int(max_seq_len),
        arch="llama",                       # RMSNorm, RoPE, SwiGLU, no biases
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        rope_theta=float(cfg["rope_theta"]),
        sliding_window=cfg.get("sliding_window"),
        norm_eps=float(cfg["rms_norm_eps"]),
        dtype="bfloat16", param_dtype=param_dtype,
        attention_impl="auto",
    )
    if cfg.get("head_dim") and \
            int(cfg["head_dim"]) != kw["hidden_size"] // kw["num_heads"]:
        kw["head_dim_override"] = int(cfg["head_dim"])
    if experts > 1:
        kw.update(num_experts=experts,
                  top_k=int(cfg["num_experts_per_tok"]))
    kw.update(extra)
    return TransformerConfig(**kw)


def weights_getter(params, convert=lambda t: t):
    """``get(name, layer=None, expert=None)`` over the program's parameter
    tree, as ``reference.forward`` wants it: the one place that knows where
    the program keeps each tensor. ``convert`` is applied to what is
    returned (a cast, a move to another device)."""
    layers = params["layers"]

    def get(name, layer=None, expert=None):
        if name == "embed":
            t = params["embed"]["tokens"]
        elif name == "final_norm":
            t = params["final_norm"]["scale"]
        elif name == "head":
            t = params["lm_head"]
        elif name in ("ln1", "ln2"):
            t = layers[name]["scale"][layer]
        elif name in ("wq", "wk", "wv", "wo"):
            t = layers["attn"][name][layer]
        else:
            t = layers["mlp"][name]
            t = t[layer] if expert is None else t[layer, expert]
        return convert(t)

    return get
