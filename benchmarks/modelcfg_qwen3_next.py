"""The mapping from Qwen3-Next-80B-A3B-Instruct's ``config.json`` keys
(``model_type: "qwen3_next"``) to the program's TransformerConfig and
parameter tree, for ``runners/train_gdn_moe.py``. Kept apart from
``reference_qwen3_next.py`` (which imports nothing of the program) and from
``opcount_qwen3_next.py``.

The published keys go through the program's own mapping
(``deepspeed_tpu/models/hf.py:config_from_hf``, which refuses what it does not
map by name): ``full_attention_interval`` -> ``attn_pattern``, the
``linear_*`` keys -> the ``delta_*`` fields (``linear_num_key_heads`` ->
``delta_key_heads``), ``head_dim`` with ``qk_norm="head"``,
``partial_rotary_factor`` -> ``rope_pct``, and what the modelling code fixes
without a key: ``norm_zero_centred``, ``attn_channel_gate``,
``moe_shared_gate``. ``num_experts`` is the experts held here of the
``router_width`` the router scores (``moe_experts_held`` of ``num_experts``).
What training adds comes from ``deployment``: ``balance_coef``,
``local_pairs_factor``, ``embed_init_std``, ``remat_policy``.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

#: the reference's names of a layer's tensors -> (group, leaf...) in the
#: program's tree, for what every layer has
_EVERY = {
    "ln1": ("ln1", "scale"), "ln2": ("ln2", "scale"),
    **{n: ("mlp", n) for n in ("router", "w_gate", "w_up", "w_down")},
    "s_gate": ("mlp", "shared", "w_gate"), "s_up": ("mlp", "shared", "w_up"),
    "s_down": ("mlp", "shared", "w_down"), "s_sg": ("mlp", "shared", "w_sg")}
#: leaves the program keeps in float32 in its compute copy of the weights
FP32_LEAVES = ("A_log", "dt_bias")
#: the keys of the file that are this benchmark's own, not the publisher's
OWN_KEYS = ("router_width", "first_expert")


def layer_kinds(cfg: Dict):
    """The program's kind of each layer kept ("delta" / "full")."""
    every = int(cfg["full_attention_interval"])
    return tuple("full" if (i + 1) % every == 0 else "delta"
                 for i in range(int(cfg["num_hidden_layers"])))


def transformer_config(cfg: Dict, *, max_seq_len: int, param_dtype: str,
                       **extra):
    """The program's config of the file ``cfg``: the published keys through
    the program's own mapping, then what the file adds: the experts held of
    the ``router_width`` scored, the buffer of held pairs, and training's
    parts."""
    from deepspeed_tpu.models.hf import config_from_hf

    dep = cfg["deployment"]
    held, routed = int(cfg["num_experts"]), int(cfg["router_width"])
    kw = dict(
        max_seq_len=int(max_seq_len), dtype="bfloat16",
        param_dtype=param_dtype, attention_impl="auto",
        num_experts=routed,
        moe_experts_held=None if held == routed else held,
        moe_first_expert=int(cfg.get("first_expert", 0)),
        moe_ep_capacity_factor=float(dep["local_pairs_factor"]),
        moe_aux_loss_coef=float(dep["balance_coef"]),
        remat_policy=str(dep["remat_policy"]),
        embed_init_std=float(dep["embed_init_std"]))
    kw.update(extra)
    return config_from_hf({k: v for k, v in cfg.items()
                           if k not in OWN_KEYS}, **kw)


def weights_getter(params, cfg: Dict, convert: Callable = lambda t: t,
                   exact: Optional[Callable] = None) -> Callable:
    """``get(name, layer=None)`` over the program's parameter tree, as
    ``reference_qwen3_next`` wants it: the one place that knows where the
    program keeps each tensor (the norms and the FFN a row a layer; a mixer's
    leaves a row for each layer of its kind, in layer order). ``convert`` is
    applied to what is returned (a cast, a move to another device), ``exact``
    (default: ``convert``) to the leaves the program itself keeps in
    float32."""
    layers, kinds = params["layers"], layer_kinds(cfg)
    exact = exact or convert
    top = {"embed": lambda: params["embed"]["tokens"],
           "final_norm": lambda: params["final_norm"]["scale"],
           "lm_head": lambda: params["lm_head"]}

    def get(name, layer=None):
        if layer is None:
            return convert(top[name]())
        if name in _EVERY:
            t = layers
            for key in _EVERY[name]:
                t = t[key]
            return convert(t[layer])
        group = "delta" if kinds[layer] == "delta" else "attn"
        t = layers[group][name][kinds[:layer].count(kinds[layer])]
        return exact(t) if name in FP32_LEAVES else convert(t)

    return get
