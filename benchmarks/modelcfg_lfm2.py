"""The mapping from LFM2-MoE's ``config.json`` keys (``model_type:
"lfm2_moe"``) to the program's TransformerConfig and parameter tree, for
``runners/train_conv_moe.py``. Kept apart from ``reference_lfm2.py`` (which
imports nothing of the program) and from ``opcount_lfm2.py``.

The published keys and what the program's config calls them
(``deepspeed_tpu/models/hf.py:config_from_hf``, which this file calls):
``layer_types`` -> ``attn_pattern`` ("conv" a gated short convolution,
"full_attention" "full"), of which the file runs ``num_hidden_layers`` from
``first_layer`` on; ``conv_L_cache`` -> ``conv_taps``; the per-head norm of q
and k -> ``qk_norm="head"``; ``num_dense_layers`` -> ``first_k_dense`` (FFN
kinds by layer: a dense stack and a routed one); ``num_experts`` the experts
held here of the ``router_width`` the router scores (``moe_experts_held`` of
``num_experts``); ``use_expert_bias`` -> ``moe_scoring="sigmoid"`` with its
selection bias; ``routed_scaling_factor`` -> ``moe_routed_scale``. What
training adds (``deployment``: ``bias_update_rate``, ``bias_init``,
``balance_coef``, ``embed_init_std``) is listed under the file's ``assumed``.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

#: the leaves the program keeps in float32 in its compute copy
FP32_LEAVES = ("router_bias",)
#: the reference's names of a layer's tensors -> (group, leaf) in the
#: program's tree, by the kind of mixer or FFN that owns them
_WHERE = {
    "conv": {n: ("conv", n) for n in ("in_proj", "conv_w", "out_proj")},
    "full": {n: ("attn", n) for n in ("wq", "wk", "wv", "wo", "q_norm",
                                      "k_norm")},
    "dense": {"w1": ("mlp_dense", "w_gate"), "w3": ("mlp_dense", "w_up"),
              "w2": ("mlp_dense", "w_down")},
    "moe": {"router": ("mlp_moe", "router"),
            "router_bias": ("mlp_moe", "router_bias"),
            "w1": ("mlp_moe", "w_gate"), "w3": ("mlp_moe", "w_up"),
            "w2": ("mlp_moe", "w_down")}}
_NORMS = {"operator_norm": "ln1", "ffn_norm": "ln2"}
_LAYER_TYPES = {"conv": "conv", "full_attention": "full"}


def kinds(cfg: Dict):
    """``(mixer, ffn)`` of each layer kept: ``layer_types`` from
    ``first_layer`` on, the first ``num_dense_layers`` of them dense."""
    first, L = int(cfg.get("first_layer", 0)), int(cfg["num_hidden_layers"])
    dense = int(cfg["num_dense_layers"])
    return [(_LAYER_TYPES[t], "dense" if i < dense else "moe")
            for i, t in enumerate(cfg["layer_types"][first:first + L])]


def transformer_config(cfg: Dict, *, max_seq_len: int, param_dtype: str,
                       **extra):
    """The program's config of the file ``cfg``: the published keys through
    the program's own mapping (``models/hf.py:config_from_hf``, which
    refuses what it does not map by name) on the layers kept, then what the
    file adds: the experts held of the ``router_width`` scored, the buffer of
    held pairs, and training's parts."""
    from deepspeed_tpu.models.hf import config_from_hf

    dep = cfg["deployment"]
    first, L = int(cfg.get("first_layer", 0)), int(cfg["num_hidden_layers"])
    held = int(cfg["num_experts"])
    routed = int(cfg.get("router_width") or held)
    kw = dict(
        max_seq_len=int(max_seq_len), dtype="bfloat16",
        param_dtype=param_dtype, attention_impl="auto",
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
    return config_from_hf(
        {**cfg, "layer_types": list(cfg["layer_types"])[first:first + L]},
        **kw)


def weights_getter(params, cfg: Dict, convert: Callable = lambda t: t,
                   exact: Optional[Callable] = None) -> Callable:
    """``get(name, layer=None)`` over the program's parameter tree, as
    ``reference_lfm2`` wants it: the one place that knows where the program
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
            return convert(layers[_NORMS[name]]["scale"][layer])
        for slot, kind in enumerate(ks[layer]):
            if name in _WHERE[kind]:
                group, leaf = _WHERE[kind][name]
                t = layers[group][leaf][
                    sum(k[slot] == kind for k in ks[:layer])]
                return exact(t) if leaf in FP32_LEAVES else convert(t)
        raise KeyError(f"layer {layer} ({ks[layer]}) has no {name!r}")

    return get


def biases(params):
    """The selection biases [routed layers, E] in the program's tree."""
    return params["layers"]["mlp_moe"]["router_bias"]
