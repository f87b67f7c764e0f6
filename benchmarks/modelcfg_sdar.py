"""The mapping from SDAR-30B-A3B-Chat's ``config.json`` keys (``model_type:
"sdar_moe"``) to the program's TransformerConfig and parameter tree, for
``runners/train_bd_moe.py``. Kept apart from ``reference_sdar.py`` (which
imports nothing of the program) and from ``opcount_sdar.py``.

The published keys and what the program's config calls them
(``deepspeed_tpu/models/hf.py:config_from_hf``, which this file calls and
which refuses what it does not map by name): ``head_dim`` with
``qk_norm="head"``; ``num_experts`` the experts held here of the
``router_width`` the router scores (``moe_experts_held`` of ``num_experts``).
What the published file does not have and the file gives under ``assumed``:
``block_length`` -> ``diffusion_block``, ``mask_token_id``. What training adds
(``deployment``: ``load_balance_coef``, ``local_pairs_factor``,
``embed_init_std``, ``remat_policy``).
"""

from __future__ import annotations

from typing import Callable, Dict

#: the reference's names of a layer's tensors -> (group, leaf) in the
#: program's tree
_WHERE = {
    **{n: ("attn", n) for n in ("wq", "wk", "wv", "wo", "q_norm", "k_norm")},
    **{n: ("mlp", n) for n in ("router", "w_gate", "w_up", "w_down")}}
_NORMS = ("ln1", "ln2")
#: the keys of the file that are this benchmark's own, not the publisher's
OWN_KEYS = ("block_length", "mask_token_id", "router_width", "first_expert")


def transformer_config(cfg: Dict, *, max_seq_len: int, param_dtype: str,
                       **extra):
    """The program's config of the file ``cfg``: the published keys through
    the program's own mapping, then what the file adds: the block length and
    the mask token, the experts held of the ``router_width`` scored, the
    buffer of held pairs, and training's parts."""
    from deepspeed_tpu.models.hf import config_from_hf

    dep = cfg["deployment"]
    held, routed = int(cfg["num_experts"]), int(cfg["router_width"])
    kw = dict(
        max_seq_len=int(max_seq_len), dtype="bfloat16",
        param_dtype=param_dtype, attention_impl="auto",
        diffusion_block=int(cfg["block_length"]),
        mask_token_id=int(cfg["mask_token_id"]),
        num_experts=routed,
        moe_experts_held=None if held == routed else held,
        moe_first_expert=int(cfg.get("first_expert", 0)),
        moe_ep_capacity_factor=float(dep["local_pairs_factor"]),
        moe_aux_loss_coef=float(dep["load_balance_coef"]),
        remat_policy=str(dep["remat_policy"]),
        embed_init_std=float(dep["embed_init_std"]))
    kw.update(extra)
    return config_from_hf({k: v for k, v in cfg.items()
                           if k not in OWN_KEYS}, **kw)


def weights_getter(params, cfg: Dict = None,
                   convert: Callable = lambda t: t, exact=None) -> Callable:
    """``get(name, layer=None)`` over the program's parameter tree, as
    ``reference_sdar`` wants it: the one place that knows where the program
    keeps each tensor. ``convert`` is applied to what is returned (a cast, a
    move to another device); the program keeps no leaf of this model in
    float32 in its compute copy, so ``exact`` is not read."""
    layers = params["layers"]
    top = {"embed": lambda: params["embed"]["tokens"],
           "final_norm": lambda: params["final_norm"]["scale"],
           "lm_head": lambda: params["lm_head"]}

    def get(name, layer=None):
        if layer is None:
            return convert(top[name]())
        if name in _NORMS:
            return convert(layers[name]["scale"][layer])
        group, leaf = _WHERE[name]
        return convert(layers[group][leaf][layer])

    return get
