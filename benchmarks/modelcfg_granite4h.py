"""From the Granite-4.0-H configuration's published sizes to the program's
model, and from the program's parameter tree to the names
``reference_granite4h`` reads.

The file keeps the publisher's key names (Hugging Face ``config.json``,
``model_type: "granitemoehybrid"``); this is the one place in the benchmark
that maps them onto ``TransformerConfig``. No preset of the program is read.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

KINDS = {"mamba": "ssm", "attention": "full"}
#: what the program holds in float32 in its compute copy of the weights
#: (``assumed.fp32_leaves``): the reference reads them unrounded
FP32_LEAVES = ("A_log", "dt_bias", "D")
_ATTN = ("wq", "wk", "wv", "wo")
_MLP = ("w_gate", "w_up", "w_down")


def layer_kinds(cfg: Dict) -> Tuple[str, ...]:
    """The kept layers' kinds, in the program's names."""
    return tuple(KINDS[k] for k in
                 cfg["layer_types"][:int(cfg["num_hidden_layers"])])


def transformer_config(cfg: Dict, *, max_seq_len: int, param_dtype: str,
                       **extra):
    from deepspeed_tpu.models import TransformerConfig

    if int(cfg.get("num_local_experts") or 0) > 0:
        raise ValueError("a routed expert layer is not mapped here")
    if cfg.get("attention_bias") or cfg.get("mamba_proj_bias") \
            or not cfg.get("mamba_conv_bias") \
            or cfg["position_embedding_type"] != "nope" \
            or cfg["normalization_function"] != "rmsnorm":
        raise ValueError("only: no attention or projection bias, a "
                         "convolution bias, no positions, RMSNorm")
    dep = cfg["deployment"]
    heads, hidden = int(cfg["num_attention_heads"]), int(cfg["hidden_size"])
    kw = dict(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=hidden,
        num_layers=int(cfg["num_hidden_layers"]),
        num_heads=heads,
        num_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim_override=int(cfg.get("head_dim") or hidden // heads),
        intermediate_size=int(cfg["shared_intermediate_size"]),
        max_seq_len=int(max_seq_len),
        arch="llama",                       # RMSNorm, SwiGLU, no biases
        use_rope=False,                     # position_embedding_type: nope
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        dtype="bfloat16", param_dtype=param_dtype,
        attention_impl="auto",
        attn_pattern=layer_kinds(cfg),
        ssm_heads=int(cfg["mamba_n_heads"]),
        ssm_head_dim=int(cfg["mamba_d_head"]),
        ssm_state=int(cfg["mamba_d_state"]),
        ssm_groups=int(cfg["mamba_n_groups"]),
        ssm_conv=int(cfg["mamba_d_conv"]),
        ssm_chunk=int(cfg["mamba_chunk_size"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        remat_policy=str(dep["remat_policy"]),
        embed_init_std=float(dep["embed_init_std"]),
    )
    kw.update(extra)
    return TransformerConfig(**kw)


def weights_getter(params, cfg: Dict, convert: Callable = lambda t: t,
                   exact: Optional[Callable] = None) -> Callable:
    """``get(name, layer=None)`` over the program's parameter tree, as
    ``reference_granite4h`` wants it: the one place that knows where the
    program keeps each tensor (the norms and the MLP a row a layer; a
    mixer's leaves a row for each layer of its kind, in layer order).
    ``convert`` is applied to what is returned (a cast, a move to another
    device), ``exact`` (default: ``convert``) to the leaves the program
    itself keeps in float32."""
    layers, kinds = params["layers"], layer_kinds(cfg)
    exact = exact or convert

    def get(name, layer=None):
        if name == "embed":
            return convert(params["embed"]["tokens"])
        if name == "final_norm":
            return convert(params["final_norm"]["scale"])
        if name in ("ln1", "ln2"):
            return convert(layers[name]["scale"][layer])
        if name in _MLP:
            return convert(layers["mlp"][name][layer])
        group = "attn" if name in _ATTN else "ssm"
        row = sum((k == "ssm") == (group == "ssm") for k in kinds[:layer])
        t = layers[group][name][row]
        return exact(t) if name in FP32_LEAVES else convert(t)

    return get
