"""From the looped configuration's published sizes to the program's model,
and from the program's parameter tree to the names ``reference_ouro`` reads.

The file keeps the publisher's key names (Hugging Face ``config.json``,
``model_type: "ouro"``); this is the one place that maps them onto
``TransformerConfig``. No preset of the program is read.
"""

from __future__ import annotations

from typing import Dict


def transformer_config(cfg: Dict, *, max_seq_len: int, param_dtype: str,
                       **extra):
    from deepspeed_tpu.models import TransformerConfig

    if cfg.get("sliding_window") is not None or cfg.get("use_sliding_window"):
        raise ValueError("the looped configuration attends fully; a window "
                         "is not mapped here")
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
        norm_eps=float(cfg["rms_norm_eps"]),
        dtype="bfloat16", param_dtype=param_dtype,
        attention_impl="auto",
        # the mechanism: R passes over shared weights, a second norm on each
        # branch, the exit gate and its loss; beta and the recomputation
        # policy are the deployment's (the published file gives neither)
        num_passes=int(cfg["total_ut_steps"]),
        sandwich_norm=True,
        exit_loss_beta=float(cfg["deployment"]["exit_loss_beta"]),
        remat_policy=str(cfg["deployment"]["remat_policy"]),
    )
    if int(cfg["head_dim"]) != kw["hidden_size"] // kw["num_heads"]:
        kw["head_dim_override"] = int(cfg["head_dim"])
    kw.update(extra)
    return TransformerConfig(**kw)


def weights_getter(params, convert=lambda t: t):
    """``get(name, layer=None, step=None)`` over the program's parameter
    tree, as ``reference_ouro`` wants it: the one place that knows where the
    program keeps each tensor. The weights are shared, so the pass that asks
    (``step``) is ignored. ``convert`` is applied to what is returned (a
    cast, a move to another device)."""
    layers = params["layers"]

    def get(name, layer=None, step=None):
        if name == "embed":
            t = params["embed"]["tokens"]
        elif name == "final_norm":
            t = params["final_norm"]["scale"]
        elif name == "head":
            t = params["lm_head"]
        elif name in ("gate_w", "gate_b"):
            t = params["exit_gate"][name[-1]]
        elif name.startswith("ln"):
            t = layers[name]["scale"][layer]
        elif name in ("wq", "wk", "wv", "wo"):
            t = layers["attn"][name][layer]
        else:
            t = layers["mlp"][name][layer]
        return convert(t)

    return get
