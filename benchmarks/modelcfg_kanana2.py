"""The mapping from kanana-2-30b-a3b's ``config.json`` keys
(``model_type: "deepseek_v3"``) to the program's TransformerConfig and
parameter tree, for ``runners/train_mla_moe.py``. Kept apart from
``reference_kanana2.py`` (which imports nothing of the program) and from
``opcount_kanana2.py``.

The published keys and what the program's config calls them:
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``q_lora_rank`` (null) and ``rope_interleave`` by the same names;
``first_k_dense_replace`` -> ``first_k_dense`` (FFN kinds by layer: a dense
stack and a routed one); ``scoring_func`` -> ``moe_scoring``,
``routed_scaling_factor`` -> ``moe_routed_scale``, ``n_shared_experts`` ->
``moe_shared_experts``, ``n_routed_experts`` the experts held here of the
``router_width`` the router scores (``moe_experts_held`` of ``num_experts``).
What training adds (``deployment``: ``bias_update_rate``, ``bias_init``,
``balance_coef``, ``embed_init_std``) is listed under the file's ``assumed``.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

#: the leaves the program keeps in float32 in its compute copy
FP32_LEAVES = ("router_bias",)
_MLA = ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")
_SHARED = {"shared_gate": "w_gate", "shared_up": "w_up",
           "shared_down": "w_down"}


def transformer_config(cfg: Dict, *, max_seq_len: int, param_dtype: str,
                       **extra):
    from deepspeed_tpu.models import TransformerConfig

    if (cfg.get("q_lora_rank") is not None or cfg.get("rope_scaling")
            or int(cfg.get("n_group", 1)) > 1 or cfg.get("attention_bias")
            or cfg["scoring_func"] != "sigmoid" or not cfg["norm_topk_prob"]):
        raise ValueError(
            "mapped here: one query matrix (q_lora_rank null), a plain rope, "
            "one routing group, no attention biases, sigmoid scores whose "
            "top k is normalised")
    dep = cfg["deployment"]
    held, routed = int(cfg["n_routed_experts"]), int(cfg["router_width"])
    kw = dict(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        num_layers=int(cfg["num_hidden_layers"]),
        num_heads=int(cfg["num_attention_heads"]),
        intermediate_size=int(cfg["intermediate_size"]),   # the dense layer's
        max_seq_len=int(max_seq_len),
        arch="llama",                       # RMSNorm, RoPE, SwiGLU, no biases
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        dtype="bfloat16", param_dtype=param_dtype,
        attention_impl="auto",
        # latent attention: keys dn + dr wide over values dv wide
        kv_lora_rank=int(cfg["kv_lora_rank"]),
        q_lora_rank=None,
        qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
        qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
        v_head_dim=int(cfg["v_head_dim"]),
        rope_interleave=bool(cfg["rope_interleave"]),
        # a leading dense run, then routed layers with shared experts
        first_k_dense=int(cfg["first_k_dense_replace"]),
        num_experts=routed,
        top_k=int(cfg["num_experts_per_tok"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        moe_dispatch="grouped",
        moe_experts_held=None if held == routed else held,
        moe_first_expert=int(cfg.get("first_expert", 0)),
        moe_ep_capacity_factor=float(dep["local_pairs_factor"]),
        moe_scoring="sigmoid",
        moe_routed_scale=float(cfg["routed_scaling_factor"]),
        moe_shared_experts=int(cfg["n_shared_experts"]),
        moe_bias_rate=float(dep["bias_update_rate"]),
        moe_bias_init=float(dep["bias_init"]),
        moe_aux_loss_coef=float(dep["balance_coef"]),
        remat_policy=str(dep["remat_policy"]),
        embed_init_std=float(dep["embed_init_std"]),
    )
    kw.update(extra)
    return TransformerConfig(**kw)


def weights_getter(params, cfg: Dict, convert: Callable = lambda t: t,
                   exact: Optional[Callable] = None) -> Callable:
    """``get(name, layer=None)`` over the program's parameter tree, as
    ``reference_kanana2`` wants it: the one place that knows where the
    program keeps each tensor (the norms and the mixer a row a layer; the
    dense FFNs a row for each dense layer, the routed ones a row for each
    routed layer). ``convert`` is applied to what is returned (a cast, a move
    to another device), ``exact`` (default: ``convert``) to the leaves the
    program itself keeps in float32."""
    layers = params["layers"]
    dense = int(cfg["first_k_dense_replace"])
    exact = exact or convert

    def get(name, layer=None):
        if name == "embed":
            return convert(params["embed"]["tokens"])
        if name == "final_norm":
            return convert(params["final_norm"]["scale"])
        if name == "head":
            return convert(params["lm_head"])
        if name in ("ln1", "ln2"):
            return convert(layers[name]["scale"][layer])
        if name in _MLA:
            return convert(layers["mla"][name][layer])
        if layer < dense:
            return convert(layers["mlp_dense"][name][layer])
        group = layers["mlp_moe"]
        if name in _SHARED:
            return convert(group["shared"][_SHARED[name]][layer - dense])
        t = group[name][layer - dense]
        return exact(t) if name in FP32_LEAVES else convert(t)

    return get


def biases(params):
    """The selection biases [routed layers, E] in the program's tree."""
    return params["layers"]["mlp_moe"]["router_bias"]
