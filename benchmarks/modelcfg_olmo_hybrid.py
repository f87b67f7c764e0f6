"""From the Olmo-Hybrid configuration's published sizes to the program's
model, and from the program's parameter tree to the names
``reference_olmo_hybrid`` reads.

The file keeps the publisher's key names (Hugging Face ``config.json``,
``model_type: "olmo_hybrid"``); this is the one place in the benchmark that
maps them onto ``TransformerConfig``. No preset of the program is read.

The heads: the file's ``num_attention_heads``, ``num_key_value_heads`` and
``linear_num_*_heads`` count the heads **held here**; ``reduced`` gives the
published counts beside them, and ``head_dim`` (a key of the file's own)
the width 3840 / 30 that the published file leaves to be derived. The
program is told the whole model's heads and the share held
(``heads_held``), as it is told a share of the experts.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

KINDS = {"linear_attention": "delta", "full_attention": "full"}
#: what the program holds in float32 in its compute copy of the weights
#: (``assumed.fp32_leaves``): the reference reads them unrounded
FP32_LEAVES = ("A_log", "dt_bias")
_NORMS = ("ln1_post", "ln2_post")
_MLP = ("w_gate", "w_up", "w_down")
_LINEAR = ("linear_num_key_heads", "linear_num_value_heads",
           "linear_key_head_dim", "linear_value_head_dim")


def at_widths(cfg: Dict) -> Dict:
    """``cfg`` as it is run. ``rehearsal.json`` substitutes a hidden size of
    64 and 4 heads of 16 and does not know the ``linear_*`` keys: a hidden
    state narrower than one published value head is a toy, and the delta
    layers are then given the toy's head count, keys of half a head and
    values of a whole one. At the published widths nothing changes."""
    if int(cfg["hidden_size"]) >= int(cfg["linear_value_head_dim"]):
        return cfg
    heads, d = int(cfg["num_attention_heads"]), int(cfg["head_dim"])
    return {**cfg, "linear_num_key_heads": heads,
            "linear_num_value_heads": heads, "linear_key_head_dim": d // 2,
            "linear_value_head_dim": d}


def share(cfg: Dict) -> int:
    """Over how many chips a layer's mixer is divided by heads: the
    published head count over the count held (``reduced``)."""
    cut = cfg.get("reduced", {}).get("num_attention_heads")
    return 1 if cut is None else int(cut["published"]) // int(cut["here"])


def layer_kinds(cfg: Dict) -> Tuple[str, ...]:
    """The kept layers' kinds, in the program's names."""
    return tuple(KINDS[k] for k in
                 cfg["layer_types"][:int(cfg["num_hidden_layers"])])


def transformer_config(cfg: Dict, *, max_seq_len: int, param_dtype: str,
                       **extra):
    from deepspeed_tpu.models import TransformerConfig

    if cfg.get("attention_bias") or cfg["hidden_act"] != "silu" \
            or (cfg.get("rope_parameters") or {}).get("rope_theta") \
            is not None \
            or cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
        raise ValueError("only: no attention bias, SwiGLU, no rope (a null "
                         "rope_theta), a key head a value head")
    cfg, dep, n = at_widths(cfg), cfg["deployment"], share(cfg)
    held = int(cfg["num_attention_heads"])
    kw = dict(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        num_layers=int(cfg["num_hidden_layers"]),
        num_heads=held * n,
        num_kv_heads=int(cfg["num_key_value_heads"]) * n,
        head_dim_override=int(cfg["head_dim"]),
        heads_held=held if n > 1 else None,
        intermediate_size=int(cfg["intermediate_size"]),
        max_seq_len=int(max_seq_len),
        arch="llama",                       # RMSNorm, SwiGLU, no biases
        use_rope=False,                     # rope_theta: null
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        dtype="bfloat16", param_dtype=param_dtype,
        attention_impl="auto",
        attn_pattern=layer_kinds(cfg),
        norm_placement="post", qk_norm="width",
        delta_heads=int(cfg["linear_num_value_heads"]) * n,
        delta_key_dim=int(cfg["linear_key_head_dim"]),
        delta_value_dim=int(cfg["linear_value_head_dim"]),
        delta_conv=int(cfg["linear_conv_kernel_dim"]),
        delta_neg_eigval=bool(cfg["linear_allow_neg_eigval"]),
        remat_policy=str(dep["remat_policy"]),
        embed_init_std=float(dep["embed_init_std"]),
    )
    kw.update(extra)
    return TransformerConfig(**kw)


def weights_getter(params, cfg: Dict, convert: Callable = lambda t: t,
                   exact: Optional[Callable] = None) -> Callable:
    """``get(name, layer=None)`` over the program's parameter tree, as
    ``reference_olmo_hybrid`` wants it: the one place that knows where the
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
        if name == "lm_head":
            return convert(params["lm_head"])
        if name == "final_norm":
            return convert(params["final_norm"]["scale"])
        if name in _NORMS:
            return convert(layers[name]["scale"][layer])
        if name in _MLP:
            return convert(layers["mlp"][name][layer])
        group = "delta" if kinds[layer] == "delta" else "attn"
        t = layers[group][name][kinds[:layer].count(kinds[layer])]
        return exact(t) if name in FP32_LEAVES else convert(t)

    return get
