"""The mapping from Laguna-S-2.1's ``config.json`` keys (``model_type:
"laguna"``) to the program's TransformerConfig and parameter tree, for
``runners/train_heads_moe.py``. Kept apart from ``reference_laguna.py``
(which imports nothing of the program) and from ``opcount_laguna.py``.

The published keys and what the program's config calls them
(``deepspeed_tpu/models/hf.py:config_from_hf``, which this file calls):
``layer_types`` -> ``attn_pattern`` of "full" and "window" (the kept layers'
kinds listed whole); ``num_attention_heads_per_layer`` -> ``heads_by_kind``;
``rope_parameters`` -> ``rope_by_kind`` with each kind's
``partial_rotary_factor``; ``gating`` -> ``mla_head_gate``;
``mlp_only_layers`` -> ``first_k_dense``; ``shared_expert_intermediate_size``
-> ``moe_shared_experts``; ``moe_routed_scaling_factor`` ->
``moe_routed_scale``; ``num_experts`` the experts held here of the
``router_width`` the router scores (``moe_experts_held`` of ``num_experts``).
``num_attention_heads``, ``num_attention_heads_per_layer`` and
``num_key_value_heads`` count the heads held of ``heads`` and ``kv_heads``,
the published counts (``heads_held`` of ``num_heads``, the same share of each
kind). What training adds (``deployment``: ``bias_update_rate``,
``bias_init``, ``balance_coef``, ``embed_init_std``) is listed under the
file's ``assumed``.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

#: the leaves the program keeps in float32 in its compute copy
FP32_LEAVES = ("router_bias",)
_ATTN = ("wq", "wk", "wv", "wg", "wo")
_GROUP = {"sliding_attention": "attn_window", "full_attention": "attn_full"}
_SHARED = {"shared_gate": "w_gate", "shared_up": "w_up",
           "shared_down": "w_down"}


def published_heads(cfg: Dict) -> Dict:
    """``cfg`` with its three head counts as the publisher's file has them:
    the held counts times ``heads / num_attention_heads``."""
    held, heads = int(cfg["num_attention_heads"]), int(cfg["heads"])
    return {**cfg, "num_attention_heads": heads,
            "num_key_value_heads": int(cfg["kv_heads"]),
            "num_attention_heads_per_layer": [
                int(n) * heads // held
                for n in cfg["num_attention_heads_per_layer"]]}


def transformer_config(cfg: Dict, *, max_seq_len: int, param_dtype: str,
                       **extra):
    """The program's config of the file ``cfg``: the published keys through
    the program's own mapping (``models/hf.py:config_from_hf``, which
    refuses what it does not map by name), then what the file adds: the
    heads held of the published counts, the experts held of the
    ``router_width`` scored, the buffer of held pairs, and training's
    parts."""
    from deepspeed_tpu.models.hf import config_from_hf

    dep = cfg["deployment"]
    held, routed = int(cfg["num_experts"]), int(cfg["router_width"])
    heads, all_heads = int(cfg["num_attention_heads"]), int(cfg["heads"])
    kw = dict(
        max_seq_len=int(max_seq_len), dtype="bfloat16",
        param_dtype=param_dtype, attention_impl="auto",
        # (a rehearsal nulls the window: one as long as the sequence is none)
        sliding_window=int(cfg["sliding_window"] or max_seq_len),
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
    return config_from_hf(published_heads(cfg), **kw)


def weights_getter(params, cfg: Dict, convert: Callable = lambda t: t,
                   exact: Optional[Callable] = None) -> Callable:
    """``get(name, layer=None)`` over the program's parameter tree, as
    ``reference_laguna`` wants it: the one place that knows where the program
    keeps each tensor (the norms a row a layer; the attention leaves a row
    for each layer of the kind, in the kind's own stack where the kinds'
    heads differ; the dense FFNs a row for each dense layer, the routed ones
    a row for each routed layer). ``convert`` is applied to what is returned
    (a cast, a move to another device), ``exact`` (default: ``convert``) to
    the leaves the program itself keeps in float32."""
    layers = params["layers"]
    types = list(cfg["layer_types"])
    dense = sorted(cfg["mlp_only_layers"])
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
        if name in _ATTN:
            grp = _GROUP[types[layer]]
            if grp in layers:
                return convert(layers[grp][name][
                    types[:layer].count(types[layer])])
            return convert(layers["attn"][name][layer])
        if layer in dense:
            return convert(layers["mlp_dense"][name][dense.index(layer)])
        row = layer - sum(d < layer for d in dense)
        group = layers["mlp_moe"]
        if name in _SHARED:
            return convert(group["shared"][_SHARED[name]][row])
        t = group[name][row]
        return exact(t) if name in FP32_LEAVES else convert(t)

    return get


def biases(params):
    """The selection biases [routed layers, E] in the program's tree."""
    return params["layers"]["mlp_moe"]["router_bias"]
