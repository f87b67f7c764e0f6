"""The mapping from Nemotron-H's ``config.json`` keys (``model_type:
"nemotron_h"``) to the program's TransformerConfig and parameter tree, for
``runners/train_latent_moe.py``. Kept apart from ``reference_nemotron_h.py``
(which imports nothing of the program) and from ``opcount_nemotron_h.py``.

The published keys and what the program's config calls them
(``deepspeed_tpu/models/hf.py:config_from_hf``, which this file calls):
``hybrid_override_pattern`` -> ``one_branch`` with ``attn_pattern`` ("M"
"ssm", "*" "full", "E" "moe"); ``mamba_num_heads``, ``mamba_head_dim``,
``ssm_state_size``, ``n_groups``, ``conv_kernel``, ``chunk_size`` ->
``ssm_heads``, ``ssm_head_dim``, ``ssm_state``, ``ssm_groups``, ``ssm_conv``,
``ssm_chunk``, with ``ssm_group_norm`` (the gated norm by group);
``mlp_hidden_act`` relu2 -> ``activation``; ``n_routed_experts`` the experts
held here of the ``router_width`` the router scores (``moe_experts_held`` of
``num_experts``); ``moe_latent_size`` by its name;
``moe_shared_expert_intermediate_size`` -> ``moe_shared_experts`` (how many
times an expert's width the one shared FFN is); ``routed_scaling_factor`` ->
``moe_routed_scale``. The heads held are the file's own head counts: nothing
of the program's ``heads_held`` is used. What training adds (``deployment``:
``bias_update_rate``, ``bias_init``, ``balance_coef``, ``embed_init_std``) is
listed under the file's ``assumed``. Multi-token prediction is not
implemented: ``num_nextn_predict_layers`` above 0 is refused by name.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

#: the leaves the program keeps in float32 in its compute copy
FP32_LEAVES = ("A_log", "dt_bias", "D", "router_bias")
#: the reference's names of a layer's tensors -> (group, leaf path) in the
#: program's tree, by the layer's letter
_WHERE = {
    "M": {"in_proj": ("ssm", "in_proj"), "conv_w": ("ssm", "conv_w"),
          "conv_b": ("ssm", "conv_b"), "dt_bias": ("ssm", "dt_bias"),
          "A_log": ("ssm", "A_log"), "D": ("ssm", "D"),
          "gate_norm": ("ssm", "norm"), "out_proj": ("ssm", "out_proj")},
    "*": {n: ("attn", n) for n in ("wq", "wk", "wv", "wo")},
    "E": {"router": ("mlp_moe", "router"),
          "router_bias": ("mlp_moe", "router_bias"),
          "latent_down": ("mlp_moe", "latent_down"),
          "latent_up": ("mlp_moe", "latent_up"),
          "w1": ("mlp_moe", "w_up"), "w2": ("mlp_moe", "w_down"),
          "shared_w1": ("mlp_moe", "shared", "w_up"),
          "shared_w2": ("mlp_moe", "shared", "w_down")}}


def pattern(cfg: Dict) -> str:
    """The letters of the layers kept."""
    return str(cfg["hybrid_override_pattern"])[:int(cfg["num_hidden_layers"])]


def transformer_config(cfg: Dict, *, max_seq_len: int, param_dtype: str,
                       **extra):
    """The program's config of the file ``cfg``: the published keys through
    the program's own mapping (``models/hf.py:config_from_hf``, which
    refuses what it does not map by name), then what the file adds: the
    experts held of the ``router_width`` scored, the buffer of held pairs,
    and training's parts."""
    from deepspeed_tpu.models.hf import config_from_hf

    if int(cfg.get("num_nextn_predict_layers", 0) or 0) > 0:
        raise NotImplementedError(
            f"num_nextn_predict_layers={cfg['num_nextn_predict_layers']}: "
            f"the multi-token prediction module (mtp_hybrid_override_pattern"
            f"={cfg.get('mtp_hybrid_override_pattern')!r}) is not "
            f"implemented; the configuration run here states 0 under "
            f"`reduced`")
    dep = cfg["deployment"]
    held, routed = int(cfg["n_routed_experts"]), int(cfg["router_width"])
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
    return config_from_hf(cfg, **kw)


def weights_getter(params, cfg: Dict, convert: Callable = lambda t: t,
                   exact: Optional[Callable] = None) -> Callable:
    """``get(name, layer=None)`` over the program's parameter tree, as
    ``reference_nemotron_h`` wants it: the one place that knows where the
    program keeps each tensor (a norm a layer; each kind's leaves a row for
    each layer of that kind). ``convert`` is applied to what is returned (a
    cast, a move to another device), ``exact`` (default: ``convert``) to the
    leaves the program itself keeps in float32."""
    layers, letters = params["layers"], pattern(cfg)
    exact = exact or convert
    top = {"embed": lambda: params["embed"]["tokens"],
           "final_norm": lambda: params["final_norm"]["scale"],
           "lm_head": lambda: params["lm_head"]}

    def get(name, layer=None):
        if layer is None:
            return convert(top[name]())
        if name == "norm":
            return convert(layers["ln1"]["scale"][layer])
        kind = letters[layer]
        leaf = layers
        for key in _WHERE[kind][name]:
            leaf = leaf[key]
        t = leaf[letters[:layer].count(kind)]
        return exact(t) if _WHERE[kind][name][-1] in FP32_LEAVES \
            else convert(t)

    return get


def biases(params):
    """The selection biases [expert layers, E] in the program's tree."""
    return params["layers"]["mlp_moe"]["router_bias"]
