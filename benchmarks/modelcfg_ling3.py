"""The mapping from Ling-3.0-flash's ``config.json`` keys (this repo's label
``model_type: "bailing_hybrid"``) to the program's TransformerConfig and
parameter tree, for ``runners/train_kda_moe.py``. Kept apart from
``reference_ling3.py`` (which imports nothing of the program) and from
``opcount_ling3.py``.

The published keys and what the program's config calls them
(``deepspeed_tpu/models/hf.py:config_from_hf``, which this file calls):
``layer_group_size`` -> ``attn_pattern`` of "kda" and "mla" (here the kept
layers' kinds listed whole, from published layer ``first_layer`` on);
``head_dim`` -> ``delta_key_dim`` = ``delta_value_dim``;
``short_conv_kernel_size`` -> ``delta_conv``; ``kda_lower_bound`` by its
name; ``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``rope_interleave`` by their names, with ``mla_head_gate``;
``first_k_dense_replace`` -> ``first_k_dense``; ``num_experts`` the experts
held here of the ``router_width`` the router scores (``moe_experts_held`` of
``num_experts``); ``n_group``, ``topk_group`` -> ``moe_n_group``,
``moe_topk_group``; ``moe_shared_expert_intermediate_size`` ->
``moe_shared_experts``; ``routed_scaling_factor`` -> ``moe_routed_scale``.
``num_attention_heads`` is the heads held of ``heads``'s published count
(``heads_held`` of ``num_heads``). What training adds (``deployment``:
``bias_update_rate``, ``bias_init``, ``balance_coef``, ``embed_init_std``) is
listed under the file's ``assumed``.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

#: the leaves the program keeps in float32 in its compute copy
FP32_LEAVES = ("A_log", "dt_bias", "router_bias")
_MOE = {"router": ("mlp_moe", "router"),
        "router_bias": ("mlp_moe", "router_bias"),
        "w_gate": ("mlp_moe", "w_gate"), "w_up": ("mlp_moe", "w_up"),
        "w_down": ("mlp_moe", "w_down"),
        "shared_gate": ("mlp_moe", "shared", "w_gate"),
        "shared_up": ("mlp_moe", "shared", "w_up"),
        "shared_down": ("mlp_moe", "shared", "w_down")}
#: the reference's names of a layer's tensors -> (group, leaf path) in the
#: program's tree, by the layer's mixer and FFN
_WHERE = {
    "kda": {n: ("kda", n) for n in (
        "wq", "wk", "wv", "wf", "wb", "wg", "conv_q", "conv_k", "conv_v",
        "A_log", "dt_bias", "o_norm", "wo")},
    "mla": {n: ("mla", n) for n in ("wq", "wkv_a", "kv_norm", "wkv_b", "wg",
                                    "wo")},
    "dense": {n: ("mlp_dense", n) for n in ("w_gate", "w_up", "w_down")},
    "moe": _MOE}
_NORMS = ("ln1", "ln2")


def kinds(cfg: Dict):
    """``(mixer, ffn)`` of each layer kept (``opcount_ling3.kinds``)."""
    from benchmarks.opcount_ling3 import kinds as of

    return of(cfg)


def transformer_config(cfg: Dict, *, max_seq_len: int, param_dtype: str,
                       **extra):
    """The program's config of the file ``cfg``: the published keys through
    the program's own mapping (``models/hf.py:config_from_hf``, which
    refuses what it does not map by name), then what the file adds: the kept
    layers' kinds, the heads held of the published count, the experts held of
    the ``router_width`` scored, the buffer of held pairs, and training's
    parts."""
    from deepspeed_tpu.models.hf import config_from_hf

    dep = cfg["deployment"]
    held, routed = int(cfg["num_experts"]), int(cfg["router_width"])
    heads, all_heads = int(cfg["num_attention_heads"]), int(cfg["heads"])
    kw = dict(
        max_seq_len=int(max_seq_len), dtype="bfloat16",
        param_dtype=param_dtype, attention_impl="auto",
        attn_pattern=tuple(mixer for mixer, _ in kinds(cfg)),
        num_heads=all_heads,
        heads_held=None if heads == all_heads else heads,
        num_experts=routed,
        moe_experts_held=None if held == routed else held,
        moe_first_expert=int(cfg.get("first_expert", 0)),
        moe_ep_capacity_factor=float(dep["local_pairs_factor"]),
        moe_bias_rate=float(dep["bias_update_rate"]),
        moe_bias_init=float(dep["bias_init"]),
        moe_aux_loss_coef=float(dep["balance_coef"]),
        remat_policy=str(dep["remat_policy"]),
        embed_init_std=float(dep["embed_init_std"]))
    kw.update(extra)
    return config_from_hf(cfg, **kw)


def weights_getter(params, cfg: Dict, convert: Callable = lambda t: t,
                   exact: Optional[Callable] = None) -> Callable:
    """``get(name, layer=None)`` over the program's parameter tree, as
    ``reference_ling3`` wants it: the one place that knows where the program
    keeps each tensor (the two norms a row a layer; each mixer's and each
    FFN's leaves a row for each layer of that kind). ``convert`` is applied
    to what is returned (a cast, a move to another device), ``exact``
    (default: ``convert``) to the leaves the program itself keeps in
    float32."""
    layers, ks = params["layers"], kinds(cfg)
    exact = exact or convert
    top = {"embed": lambda: params["embed"]["tokens"],
           "final_norm": lambda: params["final_norm"]["scale"],
           "lm_head": lambda: params["lm_head"]}

    def get(name, layer=None):
        if layer is None:
            return convert(top[name]())
        if name in _NORMS:
            return convert(layers[name]["scale"][layer])
        for slot, kind in enumerate(ks[layer]):
            if name in _WHERE[kind]:
                leaf = layers
                for key in _WHERE[kind][name]:
                    leaf = leaf[key]
                t = leaf[sum(k[slot] == kind for k in ks[:layer])]
                return exact(t) if _WHERE[kind][name][-1] in FP32_LEAVES \
                    else convert(t)
        raise KeyError(f"layer {layer} ({ks[layer]}) has no {name!r}")

    return get


def biases(params):
    """The selection biases [routed layers, E] in the program's tree."""
    return params["layers"]["mlp_moe"]["router_bias"]
