"""The mapping from Keye-VL-2.0-30B-A3B's ``config.json`` keys (``model_type:
"KeyeVL2"``) to the program's TransformerConfig and parameter tree, for
``runners/train_dsa_moe.py``. Kept apart from ``reference_keye_vl2.py``
(which imports nothing of the program) and from ``opcount_keye_vl2.py``.

The published keys and what the program's config calls them
(``deepspeed_tpu/models/hf.py:config_from_hf``, which this file calls and
which refuses what it does not map by name): ``sa_config``'s
``indexer_num_heads``, ``indexer_head_dim``, ``indexer_num_kv_heads``,
``topk``, ``q_chunk_size``, ``kv_chunk_size`` -> ``dsa_index_heads``,
``dsa_index_head_dim``, ``dsa_index_kv_heads``, ``dsa_topk``, ``dsa_q_chunk``,
``dsa_kv_chunk``, every layer of kind "dsa"; ``rope_scaling.mrope_section`` ->
``mrope_section``; ``head_dim`` with ``qk_norm="head"``; ``num_experts`` the
experts held here of the ``router_width`` the router scores
(``moe_experts_held`` of ``num_experts``). What training adds
(``deployment``: ``load_balance_coef``, ``indexer_loss_coef``,
``local_pairs_factor``, ``embed_init_std``, ``remat_policy``) is listed under
the file's ``assumed``.
"""

from __future__ import annotations

from typing import Callable, Dict

#: the reference's names of a layer's tensors -> (group, leaf) in the
#: program's tree
_WHERE = {
    **{n: ("attn", n) for n in ("wq", "wk", "wv", "wo", "q_norm", "k_norm")},
    "idx_wq": ("indexer", "wq"), "idx_wk": ("indexer", "wk"),
    "idx_ww": ("indexer", "ww"), "idx_k_norm": ("indexer", "k_norm"),
    "idx_k_bias": ("indexer", "k_bias"),
    **{n: ("mlp", n) for n in ("router", "w_gate", "w_up", "w_down")}}
_NORMS = ("ln1", "ln2")


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
        moe_aux_loss_coef=float(dep["load_balance_coef"]),
        indexer_loss_coef=float(dep["indexer_loss_coef"]),
        remat_policy=str(dep["remat_policy"]),
        embed_init_std=float(dep["embed_init_std"]))
    kw.update(extra)
    return config_from_hf(cfg, **kw)


def weights_getter(params, cfg: Dict = None,
                   convert: Callable = lambda t: t, exact=None) -> Callable:
    """``get(name, layer=None)`` over the program's parameter tree, as
    ``reference_keye_vl2`` wants it: the one place that knows where the
    program keeps each tensor. ``convert`` is applied to what is returned (a
    cast, a move to another device); the program keeps no leaf of this model
    in float32 in its compute copy, so ``exact`` is not read."""
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
