"""HuggingFace model interop: checkpoint import + AutoTP/AutoEP spec inference.

Parity target: ``deepspeed/module_inject/auto_tp.py:194`` (name-pattern
row/column tensor-parallel policy for external models), ``auto_ep.py:273``
(MoE expert conversion), and the HF-checkpoint loading paths the reference's
inference engines consume. TPU-native design: instead of rewriting live torch
modules, we map an HF safetensors checkpoint into the ``TransformerLM`` param
tree (stacked-layer layout) once, and infer ``PartitionSpec`` trees for
arbitrary external pytrees by the same name-pattern table AutoTP uses.

Supported families (the reference's inference-v2 model_implementations/ set):
Llama/Llama-2/3, Mistral, Qwen2, Phi-3, Mixtral, Falcon (rotary variants),
GPT-NeoX/Pythia, GPT-2, OPT; and ``qwen3_next`` (gated delta-rule layers whose
key heads serve several value heads beside gated full-attention layers of
256-wide heads, zero-centred norms, routed experts beside a gated shared
one: config and tensors, :func:`_build_qwen3_next` and back,
:func:`qwen3_next_state_dict`). Weight-layout notes:
  * torch ``nn.Linear`` stores ``[out, in]``; our matmuls are ``x @ w`` with
    ``w [in, out]`` → every projection transposes on import.
  * per-layer tensors stack on a leading layer axis (the ``lax.scan`` layout).
  * RoPE uses the same two-half rotation as HF's ``rotate_half``; RMSNorm
    matches HF's fp32-compute-then-cast.
  * Mixtral experts import into the EP layout ``[L, E, in, out]``. NOTE: our
    MoE forward is GShard-style expert-choice with a capacity factor
    (``moe/sharded_moe.py``), not Mixtral's dropless token-choice — weights
    import exactly, routing semantics differ under load (documented, tested
    for shape/finiteness rather than bitwise logits).
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, Optional, Tuple

import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.transformer import TransformerConfig, TransformerLM
from deepspeed_tpu.utils.logging import log_dist

__all__ = ["config_from_hf", "load_hf_checkpoint", "from_pretrained",
           "infer_tp_specs", "TP_PATTERNS", "OURO_TENSORS",
           "qwen3_next_state_dict"]


_LLAMA_FAMILY = ("llama", "mistral", "qwen2", "phi3", "mixtral", "ouro")

#: ``model_type: "ouro"`` (looped stack, sandwich norms, exit gate): where
#: the tensors it has beyond the llama family's live in the parameter tree,
#: by checkpoint name. The one table both directions read: the importer
#: below, and whoever writes a checkpoint from a tree.
OURO_TENSORS = {
    "model.layers.{}.input_layernorm_2.weight": ("layers", "ln1_post", "scale"),
    "model.layers.{}.post_attention_layernorm_2.weight":
        ("layers", "ln2_post", "scale"),
    "model.early_exit_gate.weight": ("exit_gate", "w"),     # [1, D] there
    "model.early_exit_gate.bias": ("exit_gate", "b"),       # [1] there
}
_SUPPORTED = _LLAMA_FAMILY + ("falcon", "gpt_neox", "gpt2", "opt", "mellum",
                              "granitemoehybrid", "deepseek_v3",
                              "olmo_hybrid", "nemotron_h", "lfm2_moe",
                              "bailing_hybrid", "KeyeVL2", "laguna",
                              "sdar_moe", "qwen3_next")
#: HF ``layer_types`` / ``rope_parameters`` names -> layer kinds here
_HF_KINDS = {"sliding_attention": "window", "full_attention": "full",
             "attention": "full", "mamba": "ssm",
             "linear_attention": "delta", "conv": "conv"}
#: types whose config maps (config_from_hf) and whose checkpoint does not
#: load: no description of the tensor names was at hand, and none is guessed
_CONFIG_ONLY = ("mellum", "granitemoehybrid", "deepseek_v3", "olmo_hybrid",
                "nemotron_h", "lfm2_moe", "bailing_hybrid", "KeyeVL2",
                "laguna", "sdar_moe")
#: ``model_type: "KeyeVL2"``: the keys of ``sa_config`` (the indexer and the
#: set a query keeps) -> the fields here
_KEYE_SA = {"indexer_num_heads": "dsa_index_heads",
            "indexer_head_dim": "dsa_index_head_dim",
            "indexer_num_kv_heads": "dsa_index_kv_heads",
            "topk": "dsa_topk", "q_chunk_size": "dsa_q_chunk",
            "kv_chunk_size": "dsa_kv_chunk"}
#: ``model_type: "bailing_hybrid"`` (the Ling-3.0 family): keys that turn on
#: something the mapping does not build, with the value it takes
_BAILING_PLAIN = {"use_nGPT": False, "value_norm": False,
                  "up_proj_norm": False, "scale_router_input": False,
                  "use_kda_lora": False, "mtp_use_kda": False,
                  "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1}
#: ``hybrid_override_pattern`` letters (``model_type: "nemotron_h"``) ->
#: layer kinds of a ``one_branch`` model
_NEMOTRON_KINDS = {"M": "ssm", "*": "full", "E": "moe", "-": "dense"}

_HF_ACT = {"silu": "swiglu", "gelu": "gelu_exact", "gelu_new": "gelu",
           "gelu_pytorch_tanh": "gelu", "gelu_fast": "gelu", "relu": "relu"}


def config_from_hf(hf_cfg: Any, **overrides) -> TransformerConfig:
    """Map an HF config (object or dict) to :class:`TransformerConfig`."""
    get = (hf_cfg.get if isinstance(hf_cfg, dict)
           else lambda k, d=None: getattr(hf_cfg, k, d))
    model_type = get("model_type", "llama")
    if model_type not in _SUPPORTED:
        raise ValueError(
            f"unsupported model_type '{model_type}' — supported: "
            f"{', '.join(_SUPPORTED)} (unknown families would import "
            "silently wrong)")
    if model_type in _LLAMA_FAMILY:
        rope_scaling = get("rope_scaling")
        if rope_scaling is not None and not isinstance(rope_scaling, dict):
            rope_scaling = dict(rope_scaling)
        heads = get("num_attention_heads")
        hidden = get("hidden_size")
        hd = get("head_dim")
        if hd is not None and hd != hidden // heads:
            raise ValueError(
                f"head_dim={hd} != hidden_size/num_heads={hidden // heads} — "
                "decoupled head_dim is not supported")
        kw = dict(
            vocab_size=get("vocab_size"),
            hidden_size=hidden,
            num_layers=get("num_hidden_layers"),
            num_heads=heads,
            num_kv_heads=get("num_key_value_heads") or heads,
            intermediate_size=get("intermediate_size"),
            max_seq_len=get("max_position_embeddings", 2048),
            arch="llama",
            rope_theta=float(get("rope_theta", 10000.0)),
            rope_scaling=rope_scaling,  # llama3/linear scaling, rope_frequencies
            rope_pct=float(get("partial_rotary_factor") or 1.0),  # phi3
            norm_eps=float(get("rms_norm_eps", 1e-5)),
            tie_embeddings=bool(get("tie_word_embeddings", False)),
        )
        if model_type == "mixtral":
            kw["num_experts"] = get("num_local_experts")
            kw["top_k"] = get("num_experts_per_tok", 2)
            # Mixtral routes droplessly with renormalized top-k softmax —
            # exactly the grouped (ragged_dot) dispatch; the capacity path
            # would drop overflow tokens and diverge from transformers
            kw["moe_dispatch"] = "grouped"
        if model_type in ("mistral", "qwen2", "phi3"):
            win = get("sliding_window")
            if model_type == "qwen2":
                # HF qwen2 windows only layers i >= max_window_layers (the
                # FIRST max_window_layers layers attend fully); mwl >=
                # num_layers therefore means NO layer is windowed
                mwl = int(get("max_window_layers", 0) or 0)
                if not get("use_sliding_window", False) \
                        or mwl >= kw["num_layers"]:
                    win = None
                elif mwl > 0:       # mixed-window checkpoint
                    kw["attn_pattern"] = (("full",) * mwl + ("window",)
                                          * (kw["num_layers"] - mwl))
            kw["sliding_window"] = win
        if model_type == "qwen2":
            kw["qkv_bias"] = True
        if model_type == "ouro":
            # the layer stack runs total_ut_steps times over shared weights,
            # each branch's output is normed again, and a gate scores every
            # pass's output. config.json does not give the exit loss's beta:
            # 0.1 is the family's stage-I value as held here; pass
            # exit_loss_beta= to train with another. Serving paths refuse
            # the model (TransformerLM._one_pass_only).
            kw.update(num_passes=int(get("total_ut_steps", 1)),
                      sandwich_norm=True, exit_loss_beta=0.1)
    elif model_type == "mellum":
        # window and full attention layers in turn (``layer_types``), a rope
        # of its own for each kind (``rope_parameters``), every FFN a layer
        # of ``num_experts`` experts of width ``moe_intermediate_size``
        # routed by a renormalised softmax top k. The config side only: no
        # public description of the checkpoint's tensor names was at hand,
        # so load_hf_checkpoint refuses the type rather than guess them.
        L = get("num_hidden_layers")
        kinds = tuple(_HF_KINDS[k] for k in get("layer_types")[:L])
        if set((get("mlp_layer_types") or ["sparse"])[:L]) != {"sparse"}:
            raise ValueError("mellum with dense FFN layers "
                             "(mlp_layer_types) is not mapped")
        if not get("norm_topk_prob", True) or get("attention_bias", False):
            raise ValueError("mellum without norm_topk_prob, or with "
                             "attention biases, is not mapped")
        ropes = {_HF_KINDS[k]: dict(v)
                 for k, v in (get("rope_parameters") or {}).items()}
        kw = dict(
            vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
            num_layers=L, num_heads=get("num_attention_heads"),
            num_kv_heads=get("num_key_value_heads"),
            head_dim_override=get("head_dim"),
            intermediate_size=get("intermediate_size"),
            max_seq_len=get("max_position_embeddings", 2048), arch="llama",
            norm_eps=float(get("rms_norm_eps", 1e-6)),
            tie_embeddings=bool(get("tie_word_embeddings", False)),
            sliding_window=get("sliding_window") if "window" in kinds
            else None,
            attn_pattern=kinds, rope_by_kind=ropes or None,
            num_experts=get("num_experts"),
            top_k=get("num_experts_per_tok"),
            moe_intermediate_size=get("moe_intermediate_size"),
            moe_dispatch="grouped", moe_aux_loss_coef=0.001,
        )
    elif model_type == "granitemoehybrid":
        # Mamba-2 state-space layers and attention layers in turn
        # (``layer_types``), every FFN the dense "shared" SwiGLU MLP, the
        # family's four multipliers. The config side only, and only the
        # siblings without routed experts.
        if int(get("num_local_experts", 0) or 0) > 0:
            raise ValueError(
                "granitemoehybrid with routed experts (num_local_experts="
                f"{get('num_local_experts')}) is not mapped: only the "
                "siblings whose every FFN is the shared MLP")
        if get("attention_bias", False) or get("mamba_proj_bias", False) \
                or not get("mamba_conv_bias", True):
            raise ValueError("granitemoehybrid with attention or projection "
                             "biases, or without the convolution's bias, is "
                             "not mapped")
        L = get("num_hidden_layers")
        pos = get("position_embedding_type", "nope")
        if pos not in ("nope", "rope"):
            raise ValueError(f"position_embedding_type {pos!r} is not mapped")
        heads = get("mamba_n_heads")
        if heads * get("mamba_d_head") \
                != get("mamba_expand") * get("hidden_size"):
            raise ValueError("mamba_n_heads x mamba_d_head is not "
                             "mamba_expand x hidden_size")
        kw = dict(
            vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
            num_layers=L, num_heads=get("num_attention_heads"),
            num_kv_heads=get("num_key_value_heads"),
            intermediate_size=get("shared_intermediate_size"),
            max_seq_len=get("max_position_embeddings", 2048), arch="llama",
            norm_eps=float(get("rms_norm_eps", 1e-5)),
            tie_embeddings=bool(get("tie_word_embeddings", True)),
            use_rope=pos == "rope",
            rope_theta=float(get("rope_theta", 10000.0)),
            attn_pattern=tuple(_HF_KINDS[k] for k in get("layer_types")[:L]),
            ssm_heads=heads, ssm_head_dim=get("mamba_d_head"),
            ssm_state=get("mamba_d_state"), ssm_groups=get("mamba_n_groups"),
            ssm_conv=get("mamba_d_conv"), ssm_chunk=get("mamba_chunk_size"),
            attention_multiplier=float(get("attention_multiplier")),
            embedding_multiplier=float(get("embedding_multiplier")),
            residual_multiplier=float(get("residual_multiplier")),
            logits_scaling=float(get("logits_scaling")),
        )
    elif model_type == "olmo_hybrid":
        # gated delta-rule (linear-attention) layers and full-attention
        # layers in turn (``layer_types``), the Olmo 2 family's block (no
        # norm before a branch, one after it), an RMSNorm on q and on k over
        # the whole projection, dense SwiGLU FFNs, an untied head. The
        # config side only. What the published file does not say is read the
        # family's way: a null ``rope_theta`` is no rope, the delta layers'
        # chunk is the fla kernels' 64 (``ops/delta_rule.py``). A share of
        # the heads is no config key: pass heads_held=.
        if get("attention_bias", False):
            raise ValueError("olmo_hybrid with attention biases is not "
                             "mapped")
        if get("linear_num_key_heads") != get("linear_num_value_heads"):
            raise ValueError(
                f"olmo_hybrid with linear_num_key_heads="
                f"{get('linear_num_key_heads')} shared by "
                f"linear_num_value_heads={get('linear_num_value_heads')} "
                f"(grouped value heads) is not mapped: a key head a value "
                f"head only")
        L = get("num_hidden_layers")
        rope = dict(get("rope_parameters") or {})
        theta = rope.get("rope_theta", get("rope_theta"))
        if theta is not None and rope.get("rope_type", "default") != "default":
            raise ValueError(f"olmo_hybrid with rope_parameters={rope} is "
                             f"not mapped: no rope, or a plain one")
        kw = dict(
            vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
            num_layers=L, num_heads=get("num_attention_heads"),
            num_kv_heads=get("num_key_value_heads")
            or get("num_attention_heads"),
            intermediate_size=get("intermediate_size"),
            max_seq_len=get("max_position_embeddings", 2048), arch="llama",
            norm_eps=float(get("rms_norm_eps", 1e-6)),
            tie_embeddings=bool(get("tie_word_embeddings", False)),
            use_rope=theta is not None,
            rope_theta=float(theta if theta is not None else 10000.0),
            attn_pattern=tuple(_HF_KINDS[k] for k in get("layer_types")[:L]),
            norm_placement="post", qk_norm="width",
            delta_heads=get("linear_num_value_heads"),
            delta_key_dim=get("linear_key_head_dim"),
            delta_value_dim=get("linear_value_head_dim"),
            delta_conv=get("linear_conv_kernel_dim"),
            delta_neg_eigval=bool(get("linear_allow_neg_eigval", False)),
        )
    elif model_type == "deepseek_v3":
        # latent attention (keys of qk_nope + qk_rope over values of
        # v_head_dim through a latent of kv_lora_rank), a leading run of
        # dense FFN layers, then routed layers: sigmoid scores with a
        # selection bias, the top k normalised and scaled, shared experts.
        # The config side only, and only this shape of the family: one query
        # matrix, one routing group, a plain rope. What training adds (the
        # bias rule's rate, the balance term's weight) is no config key:
        # pass moe_bias_rate= and moe_aux_loss_coef=.
        if get("q_lora_rank") is not None:
            raise ValueError(
                f"deepseek_v3 with q_lora_rank={get('q_lora_rank')} (a "
                f"low-rank query path with its norm) is not mapped")
        if int(get("n_group", 1) or 1) > 1:
            raise ValueError(
                f"deepseek_v3 with n_group={get('n_group')} (group-limited "
                f"routing) is not mapped")
        if get("rope_scaling"):
            raise ValueError(
                f"deepseek_v3 with rope_scaling={get('rope_scaling')} (yarn "
                f"with the family's mscale) is not mapped: a plain rope only")
        if (get("scoring_func", "sigmoid") != "sigmoid"
                or not get("norm_topk_prob", True)
                or get("attention_bias", False)
                or int(get("moe_layer_freq", 1)) != 1):
            raise ValueError(
                "deepseek_v3 is mapped with sigmoid scores whose top k is "
                "normalised, no attention biases and every layer after the "
                "dense ones routed (moe_layer_freq 1)")
        kw = dict(
            vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
            num_layers=get("num_hidden_layers"),
            num_heads=get("num_attention_heads"),
            intermediate_size=get("intermediate_size"),
            max_seq_len=get("max_position_embeddings", 2048), arch="llama",
            norm_eps=float(get("rms_norm_eps", 1e-6)),
            tie_embeddings=bool(get("tie_word_embeddings", False)),
            rope_theta=float(get("rope_theta", 10000.0)),
            kv_lora_rank=get("kv_lora_rank"),
            qk_nope_head_dim=get("qk_nope_head_dim"),
            qk_rope_head_dim=get("qk_rope_head_dim"),
            v_head_dim=get("v_head_dim"),
            rope_interleave=bool(get("rope_interleave", True)),
            first_k_dense=int(get("first_k_dense_replace", 0)),
            num_experts=get("n_routed_experts"),
            top_k=get("num_experts_per_tok"),
            moe_intermediate_size=get("moe_intermediate_size"),
            moe_dispatch="grouped", moe_scoring="sigmoid",
            moe_routed_scale=float(get("routed_scaling_factor", 1.0)),
            moe_shared_experts=int(get("n_shared_experts", 0) or 0),
        )
    elif model_type == "nemotron_h":
        # every layer one pre-norm branch, by ``hybrid_override_pattern``:
        # "M" a Mamba-2 mixer whose gated norm is by group, "*" attention
        # without a rope, "E" LatentMoE (sigmoid scores with a selection
        # bias, the top k normalised and scaled; experts of two products
        # round relu^2 in a latent of ``moe_latent_size``; one shared expert
        # at full width), "-" a dense relu^2 MLP. The config side only. A
        # share of the heads or experts is no config key: pass the counts
        # held (num_heads=, ssm_heads=, moe_experts_held=). What training
        # adds (the bias rule's rate, the balance term's weight): pass
        # moe_bias_rate= and moe_aux_loss_coef=.
        if int(get("n_group", 1) or 1) > 1:
            raise ValueError(
                f"nemotron_h with n_group={get('n_group')} (group-limited "
                f"routing) is not mapped")
        if (get("attention_bias", False) or get("mamba_proj_bias", False)
                or get("mlp_bias", False) or get("use_bias", False)
                or not get("use_conv_bias", True)
                or not get("norm_topk_prob", True)
                or get("mlp_hidden_act", "relu2") != "relu2"
                or get("mamba_hidden_act", "silu") != "silu"):
            raise ValueError(
                "nemotron_h is mapped without projection biases, with the "
                "convolution's bias, silu in the mixer, relu2 FFNs and a "
                "normalised top k")
        L = get("num_hidden_layers")
        pattern = str(get("hybrid_override_pattern"))[:L]
        if len(pattern) != L or set(pattern) - set(_NEMOTRON_KINDS):
            raise ValueError(
                f"hybrid_override_pattern {get('hybrid_override_pattern')!r}"
                f" does not name num_hidden_layers={L} layers by "
                f"{sorted(_NEMOTRON_KINDS)}")
        if int(get("num_nextn_predict_layers", 0) or 0):
            log_dist(
                f"nemotron_h: num_nextn_predict_layers="
                f"{get('num_nextn_predict_layers')} and "
                f"mtp_hybrid_override_pattern="
                f"{get('mtp_hybrid_override_pattern')!r} are not read: the "
                f"multi-token prediction module is an auxiliary training "
                f"loss beside the forward pass mapped here, and is not "
                f"implemented")
        kw = dict(
            vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
            num_layers=L, num_heads=get("num_attention_heads"),
            num_kv_heads=get("num_key_value_heads"),
            head_dim_override=get("head_dim"),
            intermediate_size=get("intermediate_size"),
            max_seq_len=get("max_position_embeddings", 2048), arch="llama",
            activation="relu2", use_rope=False, one_branch=True,
            norm_eps=float(get("layer_norm_epsilon", get("norm_eps", 1e-5))),
            tie_embeddings=bool(get("tie_word_embeddings", False)),
            attn_pattern=tuple(_NEMOTRON_KINDS[c] for c in pattern),
            ssm_heads=get("mamba_num_heads"),
            ssm_head_dim=get("mamba_head_dim"),
            ssm_state=get("ssm_state_size"), ssm_groups=get("n_groups"),
            ssm_conv=get("conv_kernel"), ssm_chunk=get("chunk_size"),
            ssm_group_norm=True,
        )
        if "E" in pattern:
            width = get("moe_intermediate_size")
            shared = int(get("moe_shared_expert_intermediate_size", 0) or 0)
            if shared % width:
                raise ValueError(
                    f"moe_shared_expert_intermediate_size={shared} is no "
                    f"multiple of moe_intermediate_size={width}: the shared "
                    f"expert is built as that many times an expert's width")
            kw.update(
                num_experts=get("n_routed_experts"),
                top_k=get("num_experts_per_tok"),
                moe_intermediate_size=width, moe_dispatch="grouped",
                moe_scoring="sigmoid",
                moe_routed_scale=float(get("routed_scaling_factor", 1.0)),
                moe_shared_experts=shared // width,
                moe_latent_size=get("moe_latent_size"))
    elif model_type == "lfm2_moe":
        # gated short convolutions and GQA layers in turn (``layer_types``),
        # the attention's q and k normed per head before a plain rope; the
        # first ``num_dense_layers`` FFNs dense SwiGLU, the others routed:
        # sigmoid scores with a selection bias (``use_expert_bias``), the top
        # k normalised and scaled, no shared expert; a tied head (the
        # family's convention where the file is silent). The config side
        # only. A share of the experts is no config key: pass
        # moe_experts_held=. What training adds (the bias rule's rate, the
        # balance term's weight): pass moe_bias_rate= and moe_aux_loss_coef=.
        # The published router divides by the chosen scores' sum + 1e-6; the
        # program's divides by the sum.
        if get("conv_bias", False):
            raise ValueError("lfm2_moe with conv_bias=true is not mapped: "
                             "the short convolution is built without a bias")
        rope = dict(get("rope_parameters") or {})
        if rope.get("rope_type", "default") != "default" \
                or get("rope_scaling"):
            raise ValueError(
                f"lfm2_moe with rope_parameters={rope} / rope_scaling="
                f"{get('rope_scaling')} is not mapped: a plain rope only")
        if not get("norm_topk_prob", True):
            raise ValueError("lfm2_moe is mapped with a normalised top k "
                             "(norm_topk_prob true)")
        L = get("num_hidden_layers")
        types = list(get("layer_types") or ())[:L]
        if len(types) != L or set(types) - {"conv", "full_attention"}:
            raise ValueError(
                f"layer_types {get('layer_types')!r} do not name "
                f"num_hidden_layers={L} layers by 'conv' / 'full_attention'")
        kw = dict(
            vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
            num_layers=L, num_heads=get("num_attention_heads"),
            num_kv_heads=get("num_key_value_heads")
            or get("num_attention_heads"),
            intermediate_size=get("intermediate_size"),
            max_seq_len=get("max_position_embeddings", 2048), arch="llama",
            norm_eps=float(get("norm_eps", 1e-5)),
            tie_embeddings=bool(get("tie_word_embeddings",
                                    get("tie_embedding", True))),
            rope_theta=float(rope.get("rope_theta",
                                      get("rope_theta", 1000000.0))),
            attn_pattern=tuple(_HF_KINDS[k] for k in types),
            qk_norm="head", conv_taps=int(get("conv_L_cache", 3)),
            first_k_dense=int(get("num_dense_layers", 0) or 0),
            num_experts=get("num_experts"),
            top_k=get("num_experts_per_tok"),
            moe_intermediate_size=get("moe_intermediate_size"),
            moe_dispatch="grouped", moe_scoring="sigmoid",
            moe_routed_scale=float(get("routed_scaling_factor", 1.0)),
        )
    elif model_type == "bailing_hybrid":
        # the Ling-3.0 family: KDA layers (the delta rule with a decay a key
        # channel, a lower-bounded gate, a head-wise output gate) with every
        # ``layer_group_size``-th layer latent attention under the same
        # head-wise gate; the first ``first_k_dense_replace`` FFNs dense
        # SwiGLU, the others routed: sigmoid scores with a selection bias,
        # group-limited selection, the top k normalised and scaled, one
        # shared expert. ``use_qk_norm`` is read as the latent's norm in a
        # latent-attention layer and the L2 norms of q and k in a KDA layer.
        # The config side only. A share of the heads or experts is no config
        # key: pass heads_held= and moe_experts_held=. What training adds
        # (the bias rule's rate, the balance term's weight): pass
        # moe_bias_rate= and moe_aux_loss_coef=.
        for key, plain in _BAILING_PLAIN.items():
            if (get(key, plain) or plain) != plain:
                raise ValueError(
                    f"bailing_hybrid with {key}={get(key)!r} is not mapped "
                    f"(only {key}={plain!r})")
        L, period = int(get("num_hidden_layers")), int(get("layer_group_size"))
        for key in ("expert_swiglu_limit_list",
                    "share_expert_swiglu_limit_list"):
            if any(list(get(key) or ())[:L]):
                raise ValueError(
                    f"bailing_hybrid with a non-zero {key} among its "
                    f"{L} layers (a clamp inside the experts' SwiGLU whose "
                    f"form the config does not give) is not mapped")
        if get("q_lora_rank") is not None or get("rope_scaling"):
            raise ValueError(
                f"bailing_hybrid with q_lora_rank={get('q_lora_rank')} / "
                f"rope_scaling={get('rope_scaling')} is not mapped: one "
                f"query matrix and a plain rope only")
        if (get("score_function", "sigmoid") != "sigmoid"
                or not get("norm_topk_prob", True)
                or not get("moe_router_enable_expert_bias", True)
                or not get("kda_safe_gate", True)
                or not get("no_kda_lora", True)
                or not get("linear_silu", True)
                or not get("use_qk_norm", True)
                or get("gated_attention_proj_granularity_type",
                       "head_wise") != "head_wise"):
            raise ValueError(
                "bailing_hybrid is mapped with sigmoid scores and a "
                "selection bias, a normalised top k, the lower-bounded KDA "
                "gate at full rank (kda_safe_gate, no_kda_lora), silu after "
                "the convolutions (linear_silu), use_qk_norm and a "
                "head-wise output gate")
        shared = int(get("moe_shared_expert_intermediate_size", 0) or 0)
        width = int(get("moe_intermediate_size"))
        if shared % width:
            raise ValueError(
                f"moe_shared_expert_intermediate_size={shared} is no "
                f"multiple of moe_intermediate_size={width}")
        if int(get("rotary_dim", get("qk_rope_head_dim"))) \
                != int(get("qk_rope_head_dim")):
            raise ValueError(
                f"rotary_dim={get('rotary_dim')} is not qk_rope_head_dim="
                f"{get('qk_rope_head_dim')}: the rope is on the keys' rope "
                f"columns")
        kw = dict(
            vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
            num_layers=L, num_heads=get("num_attention_heads"),
            intermediate_size=get("intermediate_size"),
            max_seq_len=get("max_position_embeddings", 2048), arch="llama",
            norm_eps=float(get("rms_norm_eps", 1e-6)),
            tie_embeddings=bool(get("tie_word_embeddings", False)),
            rope_theta=float(get("rope_theta", 10000.0)),
            # layer i is latent attention where (i + 1) % period == 0 (a cut
            # that does not start at layer 0 passes its own attn_pattern=)
            attn_pattern=tuple("mla" if (i + 1) % period == 0 else "kda"
                               for i in range(L)),
            delta_key_dim=get("head_dim"), delta_value_dim=get("head_dim"),
            delta_conv=int(get("short_conv_kernel_size", 4)),
            kda_lower_bound=float(get("kda_lower_bound", -5.0)),
            kv_lora_rank=get("kv_lora_rank"),
            qk_nope_head_dim=get("qk_nope_head_dim"),
            qk_rope_head_dim=get("qk_rope_head_dim"),
            v_head_dim=get("v_head_dim"),
            rope_interleave=bool(get("rope_interleave", True)),
            mla_head_gate=True,
            first_k_dense=int(get("first_k_dense_replace", 0)),
            num_experts=get("num_experts"),
            top_k=get("num_experts_per_tok"),
            moe_intermediate_size=width,
            moe_dispatch="grouped", moe_scoring="sigmoid",
            moe_routed_scale=float(get("routed_scaling_factor", 1.0)),
            moe_shared_experts=shared // width,
            moe_n_group=int(get("n_group", 1) or 1),
            moe_topk_group=int(get("topk_group", 1) or 1),
        )
    elif model_type == "KeyeVL2":
        # the language model of Keye-VL-2.0: every layer grouped-query
        # attention under a per-head RMSNorm on q and k, over the ``topk``
        # keys a 16-head indexer picks for each query (``sa_config``; kind
        # "dsa"), a rope over three position axes
        # (``rope_scaling.mrope_section``), every FFN ``num_experts``
        # softmax-routed experts, the top k renormalised. The vision tower
        # has no key here and is not built. The config side only
        sa = dict(get("sa_config") or {})
        unknown = sorted(set(sa) - set(_KEYE_SA))
        if unknown or not sa:
            raise ValueError(
                f"KeyeVL2 with sa_config keys {unknown or 'absent'}: the "
                f"mapping knows {sorted(_KEYE_SA)} and guesses at no other")
        rs = dict(get("rope_scaling") or {})
        if (rs.get("rope_type", rs.get("type", "default")) != "default"
                or set(rs) - {"mrope_section", "rope_type", "type"}
                or "mrope_section" not in rs):
            raise ValueError(
                f"KeyeVL2 with rope_scaling={rs}: mapped is the default rope "
                f"over the three axes of mrope_section")
        if (get("mlp_only_layers") or get("decoder_sparse_step", 1) != 1
                or not get("norm_topk_prob", True)
                or get("attention_bias", False)
                or get("use_sliding_window", False)
                or get("sliding_window") is not None):
            raise ValueError(
                "KeyeVL2 with dense FFN layers (mlp_only_layers, "
                "decoder_sparse_step), without norm_topk_prob, with "
                "attention biases or with a sliding window is not mapped")
        kw = dict(
            vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
            num_layers=get("num_hidden_layers"),
            num_heads=get("num_attention_heads"),
            num_kv_heads=get("num_key_value_heads"),
            head_dim_override=get("head_dim"),
            intermediate_size=get("intermediate_size"),   # no layer uses it
            max_seq_len=get("max_position_embeddings", 2048), arch="llama",
            rope_theta=float(get("rope_theta", 10000.0)),
            mrope_section=tuple(rs["mrope_section"]),
            norm_eps=float(get("rms_norm_eps", 1e-6)),
            tie_embeddings=bool(get("tie_word_embeddings", False)),
            qk_norm="head", attn_pattern=("dsa",),
            num_experts=get("num_experts"),
            top_k=get("num_experts_per_tok"),
            moe_intermediate_size=get("moe_intermediate_size"),
            moe_dispatch="grouped", moe_aux_loss_coef=0.001,
            **{field: int(sa[key]) for key, field in _KEYE_SA.items()
               if key in sa})
    elif model_type == "sdar_moe":
        # SDAR's mixture of experts: the Qwen3-MoE block (grouped-query
        # attention under a per-head RMSNorm on q and k, a plain rope, every
        # FFN ``num_experts`` softmax-routed experts, the top k
        # renormalised), trained and decoded by diffusion over blocks. The
        # block's length and the mask token are no keys of ``config.json``:
        # ``diffusion_block`` and ``mask_token_id`` come as overrides, and
        # without them the config is the next-token model of the same block.
        # The config side only
        if (get("mlp_only_layers") or get("decoder_sparse_step", 1) != 1
                or not get("norm_topk_prob", True)
                or get("attention_bias", False)
                or get("use_sliding_window", False)
                or get("sliding_window") is not None
                or get("rope_scaling")):
            raise ValueError(
                "sdar_moe with dense FFN layers (mlp_only_layers, "
                "decoder_sparse_step), without norm_topk_prob, with "
                "attention biases, a sliding window or rope_scaling is not "
                "mapped")
        kw = dict(
            vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
            num_layers=get("num_hidden_layers"),
            num_heads=get("num_attention_heads"),
            num_kv_heads=get("num_key_value_heads"),
            head_dim_override=get("head_dim"),
            intermediate_size=get("intermediate_size"),   # no layer uses it
            max_seq_len=get("max_position_embeddings", 2048), arch="llama",
            rope_theta=float(get("rope_theta", 10000.0)),
            norm_eps=float(get("rms_norm_eps", 1e-6)),
            tie_embeddings=bool(get("tie_word_embeddings", False)),
            qk_norm="head",
            num_experts=get("num_experts"),
            top_k=get("num_experts_per_tok"),
            moe_intermediate_size=get("moe_intermediate_size"),
            moe_dispatch="grouped", moe_aux_loss_coef=0.001)
    elif model_type == "qwen3_next":
        # gated delta-rule layers and full-attention layers in turn (layer i
        # full where (i + 1) % full_attention_interval == 0, or as
        # ``layer_types`` says), pre-norm; the delta layers'
        # ``linear_num_key_heads`` serve ``linear_num_value_heads`` value
        # heads; the full layers have heads of ``head_dim`` under a per-head
        # RMSNorm on q and k, a rope on ``partial_rotary_factor`` of a head
        # and a gate a channel from ``q_proj``'s second half; every RMSNorm
        # but the delta layer's gated one multiplies by 1 + weight; every
        # FFN is softmax-routed experts, the top k renormalised, beside one
        # shared expert under a sigmoid gate a token. The last two and the
        # gate a channel are fixed in ``modeling_qwen3_next.py``, no key
        # declares them. The multi-token module (``mtp.*`` tensors) is no
        # key of the config and is not built. A share of the heads or of the
        # experts is no config key: pass heads_held= / moe_experts_held=.
        L = get("num_hidden_layers")
        interval = get("full_attention_interval", 4)
        types = list(get("layer_types") or (
            "linear_attention" if (i + 1) % interval else "full_attention"
            for i in range(L)))[:L]
        F, Fs = get("moe_intermediate_size"), get(
            "shared_expert_intermediate_size")
        if (get("mlp_only_layers") or get("decoder_sparse_step", 1) != 1
                or not get("norm_topk_prob", True)
                or get("attention_bias", False) or get("rope_scaling")
                or not Fs or Fs % F
                or set(types) - {"linear_attention", "full_attention"}):
            raise ValueError(
                "qwen3_next with dense FFN layers (mlp_only_layers, "
                "decoder_sparse_step), without norm_topk_prob, with "
                "attention biases, rope_scaling, a shared expert whose "
                "width is no multiple of the experts' or another kind of "
                "layer than linear_attention / full_attention is not "
                "mapped")
        kw = dict(
            vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
            num_layers=L, num_heads=get("num_attention_heads"),
            num_kv_heads=get("num_key_value_heads"),
            head_dim_override=get("head_dim"),
            intermediate_size=get("intermediate_size"),   # no layer uses it
            max_seq_len=get("max_position_embeddings", 2048), arch="llama",
            rope_theta=float(get("rope_theta", 10000.0)),
            rope_pct=float(get("partial_rotary_factor", 1.0)),
            norm_eps=float(get("rms_norm_eps", 1e-6)),
            tie_embeddings=bool(get("tie_word_embeddings", False)),
            attn_pattern=tuple(_HF_KINDS[k] for k in types),
            qk_norm="head", norm_zero_centred=True, attn_channel_gate=True,
            delta_heads=get("linear_num_value_heads"),
            delta_key_heads=get("linear_num_key_heads"),
            delta_key_dim=get("linear_key_head_dim"),
            delta_value_dim=get("linear_value_head_dim"),
            delta_conv=get("linear_conv_kernel_dim"),
            num_experts=get("num_experts"),
            top_k=get("num_experts_per_tok"),
            moe_intermediate_size=F, moe_shared_experts=Fs // F,
            moe_shared_gate=True, moe_dispatch="grouped",
            moe_aux_loss_coef=float(get("router_aux_loss_coef", 0.001)))
    elif model_type == "laguna":
        # window and full attention layers in turn (``layer_types``), each
        # kind with its own number of query heads over the same key-value
        # heads (``num_attention_heads_per_layer``), its own rope and the
        # share of a head that rope turns (``rope_parameters``), every head
        # under a sigmoid gate (``gating``); the FFN of ``mlp_only_layers``
        # dense, the others' ``num_experts`` experts beside a shared one. The
        # router's score function is no key of the family's file: sigmoid
        # scores with a selection bias, the top k normalised and scaled
        # (``norm_topk_prob`` beside ``moe_routed_scaling_factor``, the
        # DeepSeek-V3 family's pair), is what this mapping builds. What
        # training adds (the bias rule's rate, the balance term's weight) is
        # no config key: pass moe_bias_rate= and moe_aux_loss_coef=; a share
        # of the heads or of the experts: heads_held=, moe_experts_held=. The
        # config side only
        L = get("num_hidden_layers")
        kinds = tuple(_HF_KINDS[k] for k in get("layer_types")[:L])
        per_layer = list(get("num_attention_heads_per_layer")
                         or [get("num_attention_heads")] * L)[:L]
        heads = {k: sorted({n for kk, n in zip(kinds, per_layer) if kk == k})
                 for k in dict.fromkeys(kinds)}
        if set(kinds) - {"window", "full"} or any(
                len(ns) != 1 for ns in heads.values()):
            raise ValueError(
                f"laguna with num_attention_heads_per_layer {heads} by kind "
                f"of layer: mapped is one head count for the window layers "
                f"and one for the full layers")
        gating = {str(g).replace("_", "-") for g in
                  [get("gating", "per-head")] + list(get("gating_types")
                                                     or [])[:L]}
        if gating != {"per-head"}:
            raise ValueError(
                f"laguna with gating {sorted(gating)}: mapped is the gate of "
                f"one scalar a head ('per-head') on every layer")
        dense = sorted(get("mlp_only_layers") or [])
        mlp_types = list(get("mlp_layer_types") or [])[:L]
        if (dense != list(range(len(dense))) or len(dense) >= L
                or int(get("decoder_sparse_step", 1)) != 1
                or (mlp_types and mlp_types != [
                    "dense" if i < len(dense) else "sparse"
                    for i in range(L)])):
            raise ValueError(
                f"laguna with mlp_only_layers={dense}, decoder_sparse_step="
                f"{get('decoder_sparse_step', 1)}: mapped is a leading run of "
                f"dense FFN layers before routed ones")
        if get("moe_router_logit_softcapping", 0):
            raise ValueError(
                f"laguna with moe_router_logit_softcapping="
                f"{get('moe_router_logit_softcapping')}: the router's logits "
                f"are not capped here")
        if get("moe_apply_router_weight_on_input", False):
            raise ValueError(
                "laguna with moe_apply_router_weight_on_input: the router's "
                "weight is applied to an expert's output here")
        if not get("norm_topk_prob", True) or get("attention_bias", False):
            raise ValueError("laguna without norm_topk_prob, or with "
                             "attention biases, is not mapped")
        Fm = get("moe_intermediate_size")
        Fs = int(get("shared_expert_intermediate_size", 0) or 0)
        if Fs % Fm:
            raise ValueError(
                f"laguna with shared_expert_intermediate_size={Fs}: the "
                f"shared expert is built as a whole number of experts' "
                f"widths (moe_intermediate_size={Fm})")
        ropes = {_HF_KINDS[k]: dict(v)
                 for k, v in (get("rope_parameters") or {}).items()
                 if _HF_KINDS[k] in kinds}
        kw = dict(
            vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
            num_layers=L, num_heads=get("num_attention_heads"),
            num_kv_heads=get("num_key_value_heads"),
            head_dim_override=get("head_dim"),
            heads_by_kind={k: ns[0] for k, ns in heads.items()},
            intermediate_size=get("intermediate_size"),
            max_seq_len=get("max_position_embeddings", 2048), arch="llama",
            norm_eps=float(get("rms_norm_eps", 1e-6)),
            tie_embeddings=bool(get("tie_word_embeddings", False)),
            sliding_window=get("sliding_window") if "window" in kinds
            else None,
            attn_pattern=kinds, rope_by_kind=ropes or None,
            mla_head_gate=True,
            first_k_dense=len(dense),
            num_experts=get("num_experts"),
            top_k=get("num_experts_per_tok"),
            moe_intermediate_size=Fm,
            moe_dispatch="grouped", moe_scoring="sigmoid",
            moe_routed_scale=float(get("moe_routed_scaling_factor", 1.0)),
            moe_shared_experts=Fs // Fm,
        )
    elif model_type == "falcon":
        if get("alibi", False):
            raise ValueError("falcon alibi variants are not supported "
                             "(rotary falcon only)")
        heads = get("num_attention_heads") or get("n_head")
        new_arch = bool(get("new_decoder_architecture", False))
        parallel = bool(get("parallel_attn", True))
        if new_arch:
            num_kv = get("num_kv_heads") or heads
        else:
            num_kv = 1 if get("multi_query", True) else heads
        num_ln = get("num_ln_in_parallel_attn") or (2 if new_arch else 1)
        kw = dict(
            vocab_size=get("vocab_size"),
            hidden_size=get("hidden_size"),
            num_layers=get("num_hidden_layers") or get("n_layer"),
            num_heads=heads,
            num_kv_heads=num_kv,
            intermediate_size=get("ffn_hidden_size") or 4 * get("hidden_size"),
            max_seq_len=get("max_position_embeddings", 2048),
            arch="gpt2", norm="layernorm",
            activation=_HF_ACT.get(get("activation", "gelu"), "gelu_exact"),
            use_rope=True, learned_pos=False,
            rope_theta=float(get("rope_theta", 10000.0)),
            norm_eps=float(get("layer_norm_epsilon", 1e-5)),
            tie_embeddings=bool(get("tie_word_embeddings", True)),
            qkv_bias=bool(get("bias", False)),
            proj_bias=bool(get("bias", False)),
            parallel_block=parallel,
            parallel_shared_norm=parallel and num_ln == 1,
        )
    elif model_type == "gpt_neox":
        kw = dict(
            vocab_size=get("vocab_size"),
            hidden_size=get("hidden_size"),
            num_layers=get("num_hidden_layers"),
            num_heads=get("num_attention_heads"),
            intermediate_size=get("intermediate_size"),
            max_seq_len=get("max_position_embeddings", 2048),
            arch="gpt2", norm="layernorm",
            activation=_HF_ACT.get(get("hidden_act", "gelu"), "gelu_exact"),
            use_rope=True, learned_pos=False,
            rope_pct=float(get("rotary_pct", 1.0)),
            rope_theta=float(get("rope_theta")
                             or get("rotary_emb_base", 10000.0)),
            norm_eps=float(get("layer_norm_eps", 1e-5)),
            tie_embeddings=bool(get("tie_word_embeddings", False)),
            qkv_bias=True, proj_bias=True,
            parallel_block=bool(get("use_parallel_residual", True)),
        )
    elif model_type == "gpt2":
        kw = dict(
            vocab_size=get("vocab_size"),
            hidden_size=get("n_embd"),
            num_layers=get("n_layer"),
            num_heads=get("n_head"),
            intermediate_size=get("n_inner") or 4 * get("n_embd"),
            max_seq_len=get("n_positions", 1024),
            arch="gpt2",
            activation=_HF_ACT.get(get("activation_function", "gelu_new"),
                                   "gelu"),
            norm_eps=float(get("layer_norm_epsilon", 1e-5)),
            tie_embeddings=True, qkv_bias=True, proj_bias=True,
        )
    else:  # opt
        if not get("do_layer_norm_before", True):
            raise ValueError("OPT with do_layer_norm_before=False (350m) is "
                             "not supported (post-norm layout)")
        if get("word_embed_proj_dim", get("hidden_size")) != get("hidden_size"):
            raise ValueError("OPT word_embed_proj_dim != hidden_size is not "
                             "supported")
        kw = dict(
            vocab_size=get("vocab_size"),
            hidden_size=get("hidden_size"),
            num_layers=get("num_hidden_layers"),
            num_heads=get("num_attention_heads"),
            intermediate_size=get("ffn_dim"),
            max_seq_len=get("max_position_embeddings", 2048),
            arch="gpt2",
            activation=_HF_ACT.get(get("activation_function", "relu"), "relu"),
            norm_eps=1e-5,
            tie_embeddings=bool(get("tie_word_embeddings", True)),
            qkv_bias=True, proj_bias=True,
        )
    kw.update(overrides)
    return TransformerConfig(**kw)


def _load_state_dict(path: str, dtype: np.dtype) -> Dict[str, np.ndarray]:
    """Read (possibly sharded) safetensors into ``dtype`` numpy via torch
    (torch handles bf16 payloads that numpy cannot represent). Casting at load
    time keeps peak host RAM near 1x the target-dtype model size."""
    import torch  # cpu torch is baked into the image
    from safetensors.torch import load_file

    tdt = {np.dtype(np.float32): torch.float32,
           np.dtype(np.float16): torch.float16}.get(np.dtype(dtype),
                                                    torch.float32)
    index = os.path.join(path, "model.safetensors.index.json")
    if os.path.exists(index):
        shards = sorted(set(json.load(open(index))["weight_map"].values()))
        files = [os.path.join(path, s) for s in shards]
    else:
        files = [os.path.join(path, "model.safetensors")]
    sd: Dict[str, np.ndarray] = {}
    for f in files:
        for k, v in load_file(f).items():
            sd[k] = np.asarray(v.to(tdt).numpy(), dtype)
    return sd


def _stack(sd: Dict[str, np.ndarray], fmt: str, L: int,
           transpose: bool = False) -> np.ndarray:
    # pop: consumed entries free immediately AND leftovers are detectable
    arrs = [sd.pop(fmt.format(i)) for i in range(L)]
    if transpose:
        arrs = [np.ascontiguousarray(a.T) for a in arrs]
    return np.stack(arrs)


def _stack_experts(sd, layer_fmt: str, L: int, E: int) -> np.ndarray:
    """[L, E, in, out] from per-layer per-expert torch [out, in] weights."""
    return np.stack([np.stack([np.ascontiguousarray(
        sd.pop(layer_fmt.format(i, j)).T) for j in range(E)])
        for i in range(L)])


def _ln(sd, fmt: str, L: int) -> Dict[str, np.ndarray]:
    """Stacked layernorm {scale, bias} from ``fmt`` (without .weight/.bias)."""
    return {"scale": _stack(sd, fmt + ".weight", L),
            "bias": _stack(sd, fmt + ".bias", L)}


def _build_llama_family(sd, cfg: TransformerConfig, model_type: str):
    L = cfg.num_layers
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    F = cfg.intermediate_size
    if model_type == "phi3":
        # phi3 fuses qkv_proj [(H+2K)*hd, out-major q|k|v] and gate_up [2F]
        qs, ks, vs, gs, us = [], [], [], [], []
        for i in range(L):
            w = sd.pop(f"model.layers.{i}.self_attn.qkv_proj.weight")
            q, k, v = np.split(w, [H * hd, (H + K) * hd])
            qs.append(q.T), ks.append(k.T), vs.append(v.T)
            gu = sd.pop(f"model.layers.{i}.mlp.gate_up_proj.weight")
            gs.append(gu[:F].T), us.append(gu[F:].T)
        attn = {"wq": np.stack(qs), "wk": np.stack(ks), "wv": np.stack(vs),
                "wo": _stack(sd, "model.layers.{}.self_attn.o_proj.weight",
                             L, True)}
        mlp = {"w_gate": np.stack(gs), "w_up": np.stack(us),
               "w_down": _stack(sd, "model.layers.{}.mlp.down_proj.weight",
                                L, True)}
    else:
        attn = {
            "wq": _stack(sd, "model.layers.{}.self_attn.q_proj.weight", L, True),
            "wk": _stack(sd, "model.layers.{}.self_attn.k_proj.weight", L, True),
            "wv": _stack(sd, "model.layers.{}.self_attn.v_proj.weight", L, True),
            "wo": _stack(sd, "model.layers.{}.self_attn.o_proj.weight", L, True),
        }
        if cfg.qkv_bias:  # qwen2
            attn["bq"] = _stack(sd, "model.layers.{}.self_attn.q_proj.bias", L)
            attn["bk"] = _stack(sd, "model.layers.{}.self_attn.k_proj.bias", L)
            attn["bv"] = _stack(sd, "model.layers.{}.self_attn.v_proj.bias", L)
        if cfg.num_experts > 1:
            E = cfg.num_experts
            mlp = {
                "router": _stack(
                    sd, "model.layers.{}.block_sparse_moe.gate.weight", L, True),
                # mixtral expert naming: w1=gate, w3=up, w2=down
                "w_gate": _stack_experts(
                    sd, "model.layers.{0}.block_sparse_moe.experts.{1}.w1.weight", L, E),
                "w_up": _stack_experts(
                    sd, "model.layers.{0}.block_sparse_moe.experts.{1}.w3.weight", L, E),
                "w_down": _stack_experts(
                    sd, "model.layers.{0}.block_sparse_moe.experts.{1}.w2.weight", L, E),
            }
        else:
            mlp = {
                "w_gate": _stack(sd, "model.layers.{}.mlp.gate_proj.weight", L, True),
                "w_up": _stack(sd, "model.layers.{}.mlp.up_proj.weight", L, True),
                "w_down": _stack(sd, "model.layers.{}.mlp.down_proj.weight", L, True),
            }
    params = {
        "embed": {"tokens": sd.pop("model.embed_tokens.weight")},
        "layers": {
            "ln1": {"scale": _stack(
                sd, "model.layers.{}.input_layernorm.weight", L)},
            "ln2": {"scale": _stack(
                sd, "model.layers.{}.post_attention_layernorm.weight", L)},
            "attn": attn,
            "mlp": mlp,
        },
        "final_norm": {"scale": sd.pop("model.norm.weight")},
    }
    if model_type == "ouro":
        for name, path in OURO_TENSORS.items():
            # the gate is a Linear(D, 1): [1, D] and [1] there, [D] and [] here
            t = (_stack(sd, name, L) if "{}" in name
                 else np.squeeze(sd.pop(name)))
            node = params
            for k in path[:-1]:
                node = node.setdefault(k, {})
            node[path[-1]] = t
    return params, "lm_head.weight"


def _build_falcon(sd, cfg: TransformerConfig, model_type: str):
    L, D = cfg.num_layers, cfg.hidden_size
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pre = "transformer.h.{}"
    qs, ks, vs, bqs, bks, bvs = [], [], [], [], [], []
    for i in range(L):
        # fused layout: K groups of (H/K q-heads | 1 k | 1 v) rows
        w = sd.pop(f"transformer.h.{i}.self_attention.query_key_value.weight")
        w = w.reshape(K, H // K + 2, hd, D)
        qs.append(w[:, :-2].reshape(H * hd, D).T)
        ks.append(w[:, -2].reshape(K * hd, D).T)
        vs.append(w[:, -1].reshape(K * hd, D).T)
        if cfg.qkv_bias:
            b = sd.pop(f"transformer.h.{i}.self_attention.query_key_value.bias")
            b = b.reshape(K, H // K + 2, hd)
            bqs.append(b[:, :-2].reshape(H * hd))
            bks.append(b[:, -2].reshape(K * hd))
            bvs.append(b[:, -1].reshape(K * hd))
    attn = {"wq": np.stack(qs), "wk": np.stack(ks), "wv": np.stack(vs),
            "wo": _stack(sd, pre + ".self_attention.dense.weight", L, True)}
    if cfg.qkv_bias:
        attn.update(bq=np.stack(bqs), bk=np.stack(bks), bv=np.stack(bvs))
    if cfg.proj_bias:
        attn["bo"] = _stack(sd, pre + ".self_attention.dense.bias", L)
    mlp = {"w_up": _stack(sd, pre + ".mlp.dense_h_to_4h.weight", L, True),
           "w_down": _stack(sd, pre + ".mlp.dense_4h_to_h.weight", L, True)}
    if cfg.proj_bias:
        mlp["b_up"] = _stack(sd, pre + ".mlp.dense_h_to_4h.bias", L)
        mlp["b_down"] = _stack(sd, pre + ".mlp.dense_4h_to_h.bias", L)
    layers = {"attn": attn, "mlp": mlp}
    if cfg.parallel_shared_norm:       # 7b-style: one shared input_layernorm
        layers["ln1"] = _ln(sd, pre + ".input_layernorm", L)
    elif cfg.parallel_block:           # 40b-style: ln_attn + ln_mlp
        layers["ln1"] = _ln(sd, pre + ".ln_attn", L)
        layers["ln2"] = _ln(sd, pre + ".ln_mlp", L)
    else:
        layers["ln1"] = _ln(sd, pre + ".input_layernorm", L)
        layers["ln2"] = _ln(sd, pre + ".post_attention_layernorm", L)
    return {
        "embed": {"tokens": sd.pop("transformer.word_embeddings.weight")},
        "layers": layers,
        "final_norm": {"scale": sd.pop("transformer.ln_f.weight"),
                       "bias": sd.pop("transformer.ln_f.bias")},
    }, "lm_head.weight"


def _build_gpt_neox(sd, cfg: TransformerConfig, model_type: str):
    L, D = cfg.num_layers, cfg.hidden_size
    H, hd = cfg.num_heads, cfg.head_dim
    pre = "gpt_neox.layers.{}"
    qs, ks, vs, bqs, bks, bvs = [], [], [], [], [], []
    for i in range(L):
        # fused layout: rows interleaved per head [H, (q|k|v), hd]
        w = sd.pop(f"gpt_neox.layers.{i}.attention.query_key_value.weight")
        w = w.reshape(H, 3, hd, D)
        qs.append(w[:, 0].reshape(H * hd, D).T)
        ks.append(w[:, 1].reshape(H * hd, D).T)
        vs.append(w[:, 2].reshape(H * hd, D).T)
        b = sd.pop(f"gpt_neox.layers.{i}.attention.query_key_value.bias")
        b = b.reshape(H, 3, hd)
        bqs.append(b[:, 0].reshape(H * hd))
        bks.append(b[:, 1].reshape(H * hd))
        bvs.append(b[:, 2].reshape(H * hd))
    attn = {"wq": np.stack(qs), "wk": np.stack(ks), "wv": np.stack(vs),
            "bq": np.stack(bqs), "bk": np.stack(bks), "bv": np.stack(bvs),
            "wo": _stack(sd, pre + ".attention.dense.weight", L, True),
            "bo": _stack(sd, pre + ".attention.dense.bias", L)}
    mlp = {"w_up": _stack(sd, pre + ".mlp.dense_h_to_4h.weight", L, True),
           "b_up": _stack(sd, pre + ".mlp.dense_h_to_4h.bias", L),
           "w_down": _stack(sd, pre + ".mlp.dense_4h_to_h.weight", L, True),
           "b_down": _stack(sd, pre + ".mlp.dense_4h_to_h.bias", L)}
    params = {
        "embed": {"tokens": sd.pop("gpt_neox.embed_in.weight")},
        "layers": {"ln1": _ln(sd, pre + ".input_layernorm", L),
                   "ln2": _ln(sd, pre + ".post_attention_layernorm", L),
                   "attn": attn, "mlp": mlp},
        "final_norm": {"scale": sd.pop("gpt_neox.final_layer_norm.weight"),
                       "bias": sd.pop("gpt_neox.final_layer_norm.bias")},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = np.ascontiguousarray(sd.pop("embed_out.weight").T)
    return params, "embed_out.weight"


def _build_gpt2(sd, cfg: TransformerConfig, model_type: str):
    L, D = cfg.num_layers, cfg.hidden_size
    # GPT2LMHeadModel exports prefix with "transformer.", the original gpt2
    # release doesn't — normalize in place (callers hold this dict)
    for k in list(sd):
        if k.startswith("transformer."):
            sd[k[len("transformer."):]] = sd.pop(k)
    # gpt2 Conv1D stores [in, out] — no transpose anywhere
    qs, ks, vs, bqs, bks, bvs = [], [], [], [], [], []
    for i in range(L):
        w = sd.pop(f"h.{i}.attn.c_attn.weight")  # [D, 3D], cols q|k|v
        q, k, v = np.split(w, 3, axis=1)
        qs.append(q), ks.append(k), vs.append(v)
        b = sd.pop(f"h.{i}.attn.c_attn.bias")
        bq, bk, bv = np.split(b, 3)
        bqs.append(bq), bks.append(bk), bvs.append(bv)
    attn = {"wq": np.stack(qs), "wk": np.stack(ks), "wv": np.stack(vs),
            "bq": np.stack(bqs), "bk": np.stack(bks), "bv": np.stack(bvs),
            "wo": _stack(sd, "h.{}.attn.c_proj.weight", L),
            "bo": _stack(sd, "h.{}.attn.c_proj.bias", L)}
    mlp = {"w_up": _stack(sd, "h.{}.mlp.c_fc.weight", L),
           "b_up": _stack(sd, "h.{}.mlp.c_fc.bias", L),
           "w_down": _stack(sd, "h.{}.mlp.c_proj.weight", L),
           "b_down": _stack(sd, "h.{}.mlp.c_proj.bias", L)}
    return {
        "embed": {"tokens": sd.pop("wte.weight"), "pos": sd.pop("wpe.weight")},
        "layers": {"ln1": _ln(sd, "h.{}.ln_1", L),
                   "ln2": _ln(sd, "h.{}.ln_2", L),
                   "attn": attn, "mlp": mlp},
        "final_norm": {"scale": sd.pop("ln_f.weight"),
                       "bias": sd.pop("ln_f.bias")},
    }, "lm_head.weight"


def _build_opt(sd, cfg: TransformerConfig, model_type: str):
    L = cfg.num_layers
    pre = "model.decoder.layers.{}"
    attn = {
        "wq": _stack(sd, pre + ".self_attn.q_proj.weight", L, True),
        "bq": _stack(sd, pre + ".self_attn.q_proj.bias", L),
        "wk": _stack(sd, pre + ".self_attn.k_proj.weight", L, True),
        "bk": _stack(sd, pre + ".self_attn.k_proj.bias", L),
        "wv": _stack(sd, pre + ".self_attn.v_proj.weight", L, True),
        "bv": _stack(sd, pre + ".self_attn.v_proj.bias", L),
        "wo": _stack(sd, pre + ".self_attn.out_proj.weight", L, True),
        "bo": _stack(sd, pre + ".self_attn.out_proj.bias", L),
    }
    mlp = {"w_up": _stack(sd, pre + ".fc1.weight", L, True),
           "b_up": _stack(sd, pre + ".fc1.bias", L),
           "w_down": _stack(sd, pre + ".fc2.weight", L, True),
           "b_down": _stack(sd, pre + ".fc2.bias", L)}
    # OPT's learned positions live at offset 2 (rows 0-1 are pad relics);
    # slicing here makes our arange-positions lookup exact
    pos = sd.pop("model.decoder.embed_positions.weight")[2:]
    return {
        "embed": {"tokens": sd.pop("model.decoder.embed_tokens.weight"),
                  "pos": pos},
        "layers": {"ln1": _ln(sd, pre + ".self_attn_layer_norm", L),
                   "ln2": _ln(sd, pre + ".final_layer_norm", L),
                   "attn": attn, "mlp": mlp},
        "final_norm": {
            "scale": sd.pop("model.decoder.final_layer_norm.weight"),
            "bias": sd.pop("model.decoder.final_layer_norm.bias")},
    }, "lm_head.weight"


def _qwen3_next_kinds(cfg: TransformerConfig):
    if (cfg.heads_held is not None or not cfg.has_delta
            or set(cfg.layer_kinds) - {"delta", "full"}):
        raise NotImplementedError(
            "the qwen3_next tensors map to a whole stack of 'delta' and "
            "'full' layers (every head held; a share of the experts is cut "
            "out, moe_experts_held)")
    return cfg.layer_kinds


def _build_qwen3_next(sd, cfg: TransformerConfig, model_type: str):
    """The tree from a state dict as ``modeling_qwen3_next.py`` lays it out.
    A delta layer's ``in_proj_qkvz`` rows are a key head's ``[q dk, k dk,
    v r dv, z r dv]`` after each other (``r`` value heads a key head),
    ``in_proj_ba``'s a key head's ``[b r, a r]``, ``conv1d``'s the joined
    ``[q, k, v]`` channels; a full layer's ``q_proj`` rows are a head's
    query then its gate, which is ``wq``'s layout here. Every norm weight is
    the program's scale as it is (both keep the distance from one; the delta
    layer's gated norm is plain in both). With ``cfg.moe_experts_held`` the
    held experts are cut out. ``mtp.*`` (the multi-token module) is dropped:
    the program has no such module."""
    kinds = _qwen3_next_kinds(cfg)
    for k in [k for k in sd if k.startswith("mtp.")]:
        del sd[k]
    D = cfg.hidden_size
    Hk, Hv = cfg.delta_key_heads or cfg.delta_heads, cfg.delta_heads
    dk, dv, r = cfg.delta_key_dim, cfg.delta_value_dim, Hv // Hk
    pre = "model.layers.{}."
    T = np.ascontiguousarray
    delta = {n: [] for n in ("wq", "wk", "wv", "wz", "wb", "wa", "conv_q",
                             "conv_k", "conv_v", "A_log", "dt_bias",
                             "o_norm", "wo")}
    attn = {n: [] for n in ("wq", "wk", "wv", "wo", "q_norm", "k_norm")}
    for i, kind in enumerate(kinds):
        p = pre.format(i)
        if kind == "delta":
            la = p + "linear_attn."
            qkvz = sd.pop(la + "in_proj_qkvz.weight").reshape(
                Hk, 2 * dk + 2 * r * dv, D)
            q, k, v, z = np.split(qkvz, [dk, 2 * dk, 2 * dk + r * dv], axis=1)
            ba = sd.pop(la + "in_proj_ba.weight").reshape(Hk, 2 * r, D)
            conv = sd.pop(la + "conv1d.weight")[:, 0, :]    # [C, K]
            cq, ck, cv = np.split(conv, [Hk * dk, 2 * Hk * dk], axis=0)
            for n, a in (("wq", q), ("wk", k), ("wv", v), ("wz", z),
                         ("wb", ba[:, :r]), ("wa", ba[:, r:])):
                delta[n].append(T(a.reshape(-1, D).T))
            for n, a in (("conv_q", cq), ("conv_k", ck), ("conv_v", cv)):
                delta[n].append(T(a.T))
            delta["A_log"].append(sd.pop(la + "A_log"))
            delta["dt_bias"].append(sd.pop(la + "dt_bias"))
            delta["o_norm"].append(sd.pop(la + "norm.weight"))
            delta["wo"].append(T(sd.pop(la + "out_proj.weight").T))
        else:
            sa = p + "self_attn."
            for n, t in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"),
                         ("wo", "o_proj")):
                attn[n].append(T(sd.pop(sa + t + ".weight").T))
            attn["q_norm"].append(sd.pop(sa + "q_norm.weight"))
            attn["k_norm"].append(sd.pop(sa + "k_norm.weight"))
    L, E = cfg.num_layers, cfg.num_experts
    lo = cfg.moe_first_expert if cfg.moe_experts_held else 0
    held = range(lo, lo + (cfg.moe_experts_held or E))
    mlp = {n: np.stack([np.stack([T(sd.pop(
        f"{pre.format(i)}mlp.experts.{e}.{t}.weight").T) for e in held])
        for i in range(L)])
        for n, t in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                     ("w_down", "down_proj"))}
    for k in [k for k in sd if ".mlp.experts." in k]:
        del sd[k]                                   # the experts not held
    mlp["router"] = _stack(sd, pre + "mlp.gate.weight", L, transpose=True)
    mlp["shared"] = {
        n: _stack(sd, pre + f"mlp.shared_expert.{t}.weight", L,
                  transpose=True)
        for n, t in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                     ("w_down", "down_proj"))}
    mlp["shared"]["w_sg"] = _stack(
        sd, pre + "mlp.shared_expert_gate.weight", L, transpose=True)
    return {
        "embed": {"tokens": sd.pop("model.embed_tokens.weight")},
        "layers": {
            "ln1": {"scale": _stack(sd, pre + "input_layernorm.weight", L)},
            "ln2": {"scale": _stack(
                sd, pre + "post_attention_layernorm.weight", L)},
            "delta": {n: np.stack(a) for n, a in delta.items()},
            "attn": {n: np.stack(a) for n, a in attn.items()},
            "mlp": mlp},
        "final_norm": {"scale": sd.pop("model.norm.weight")},
    }, "lm_head.weight"


def qwen3_next_state_dict(params, cfg: TransformerConfig
                          ) -> Dict[str, np.ndarray]:
    """:func:`_build_qwen3_next` backwards: the tree's leaves under the
    names and in the layouts of ``modeling_qwen3_next.py`` (torch ``[out,
    in]``, the interleaved ``in_proj_qkvz`` / ``in_proj_ba``, the joined
    ``conv1d`` [C, 1, K], ``q_proj`` a head's query then its gate), every
    expert held."""
    kinds = _qwen3_next_kinds(cfg)
    if cfg.moe_experts_held:
        raise NotImplementedError("a state dict holds every expert: this "
                                  "tree holds a share (moe_experts_held)")
    D = cfg.hidden_size
    Hk, Hv = cfg.delta_key_heads or cfg.delta_heads, cfg.delta_heads
    r = Hv // Hk
    mlp = params["layers"]["mlp"]
    lay = {g: {n: np.asarray(a) for n, a in w.items()}
           for g, w in params["layers"].items() if g != "mlp"}
    sd = {"model.embed_tokens.weight": np.asarray(params["embed"]["tokens"]),
          "model.norm.weight": np.asarray(params["final_norm"]["scale"]),
          "lm_head.weight": np.asarray(params["lm_head"]).T}
    seen = {"delta": 0, "full": 0}
    for i, kind in enumerate(kinds):
        p, j = f"model.layers.{i}.", seen[kind]
        seen[kind] += 1
        sd[p + "input_layernorm.weight"] = lay["ln1"]["scale"][i]
        sd[p + "post_attention_layernorm.weight"] = lay["ln2"]["scale"][i]
        if kind == "delta":
            w, la = lay["delta"], p + "linear_attn."
            heads = [w[n][j].T.reshape(Hk, -1, D)
                     for n in ("wq", "wk", "wv", "wz")]
            sd[la + "in_proj_qkvz.weight"] = np.concatenate(
                heads, axis=1).reshape(-1, D)
            sd[la + "in_proj_ba.weight"] = np.concatenate(
                [w[n][j].T.reshape(Hk, r, D) for n in ("wb", "wa")],
                axis=1).reshape(-1, D)
            sd[la + "conv1d.weight"] = np.concatenate(
                [w[n][j].T for n in ("conv_q", "conv_k", "conv_v")],
                axis=0)[:, None, :]
            sd[la + "A_log"], sd[la + "dt_bias"] = w["A_log"][j], \
                w["dt_bias"][j]
            sd[la + "norm.weight"] = w["o_norm"][j]
            sd[la + "out_proj.weight"] = w["wo"][j].T
        else:
            w, sa = lay["attn"], p + "self_attn."
            for n, t in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"),
                         ("wo", "o_proj")):
                sd[sa + t + ".weight"] = w[n][j].T
            sd[sa + "q_norm.weight"] = w["q_norm"][j]
            sd[sa + "k_norm.weight"] = w["k_norm"][j]
        for n, t in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                     ("w_down", "down_proj")):
            for e in range(cfg.num_experts):
                sd[f"{p}mlp.experts.{e}.{t}.weight"] = np.asarray(
                    mlp[n][i, e]).T
            sd[f"{p}mlp.shared_expert.{t}.weight"] = np.asarray(
                mlp["shared"][n][i]).T
        sd[p + "mlp.gate.weight"] = np.asarray(mlp["router"][i]).T
        sd[p + "mlp.shared_expert_gate.weight"] = np.asarray(
            mlp["shared"]["w_sg"][i]).T
    return sd


_PARAM_BUILDERS = {
    **{m: _build_llama_family for m in _LLAMA_FAMILY},
    "qwen3_next": _build_qwen3_next,
    "falcon": _build_falcon,
    "gpt_neox": _build_gpt_neox,
    "gpt2": _build_gpt2,
    "opt": _build_opt,
}

# non-parameter buffers that older exports materialize — safe to drop
_IGNORABLE_SUFFIXES = ("rotary_emb.inv_freq", "attn.bias", "attn.masked_bias",
                       "attention.bias", "attention.masked_bias")


def load_hf_checkpoint(path: str, cfg: Optional[TransformerConfig] = None,
                       dtype: str = "float32") -> Tuple[TransformerLM, Any]:
    """Import an HF checkpoint directory → (model, params).

    Families: llama/mistral/qwen2/phi3/mixtral/falcon/gpt_neox/gpt2/opt
    (the reference's v2 ``model_implementations/`` coverage).
    ``cfg`` overrides the auto-derived config (e.g. to change dtype/remat).
    """
    with open(os.path.join(path, "config.json")) as f:
        hf_cfg = json.load(f)
    if hf_cfg.get("model_type") in _CONFIG_ONLY:
        raise NotImplementedError(
            f"model_type {hf_cfg['model_type']!r}: the config maps onto "
            "TransformerConfig "
            "(config_from_hf), but no description of the checkpoint's tensor "
            "names was at hand when this was written, and none is guessed: "
            "importing its weights is not supported")
    if cfg is None:
        cfg = config_from_hf(hf_cfg, param_dtype="float32", dtype=dtype)
    sd = _load_state_dict(path, np.dtype(cfg.param_dtype))
    model_type = hf_cfg.get("model_type", "llama")
    params, lm_head_key = _PARAM_BUILDERS[model_type](sd, cfg, model_type)
    if not cfg.tie_embeddings and "lm_head" not in params:
        params["lm_head"] = np.ascontiguousarray(sd.pop(lm_head_key).T)
    else:
        sd.pop(lm_head_key, None)  # some tied exports still materialize it
    # anything left means the architecture has weights we did not map —
    # importing would be silently wrong (e.g. qkv biases, extra norms)
    leftovers = [k for k in sd
                 if not any(k.endswith(s) for s in _IGNORABLE_SUFFIXES)]
    if leftovers:
        raise ValueError(
            f"unmapped tensors in checkpoint (first 5): {leftovers[:5]} — "
            "this architecture is not fully supported")
    L = cfg.num_layers
    import jax

    # TransformerLM derives the MoE dispatch from cfg.moe_dispatch itself
    model = TransformerLM(cfg)
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    log_dist(f"imported HF checkpoint {path}: {hf_cfg.get('model_type')} "
             f"{n/1e6:.1f}M params, L={L}")
    return model, params


def from_pretrained(path: str, **kw) -> Tuple[TransformerLM, Any]:
    """Reference-flavored alias of :func:`load_hf_checkpoint`."""
    return load_hf_checkpoint(path, **kw)


# ---------------------------------------------------------------------------
# AutoTP: name-pattern spec inference for external param trees
# ---------------------------------------------------------------------------

# (regex on the leaf path) -> which dim carries 'tp'. Column-parallel shards
# the OUTPUT dim (last), row-parallel the INPUT dim (second-to-last) — the
# auto_tp.py row/col policy, expressed on names instead of module classes.
TP_PATTERNS: Tuple[Tuple[str, str], ...] = (
    # our family
    (r"(^|/)(wq|wk|wv|w_gate|w_up)$", "col"),
    (r"(^|/)(wo|w_down)$", "row"),
    (r"(^|/)embed/tokens$", "vocab"),
    (r"(^|/)lm_head$", "col"),
    # HF torch names ([out, in] layout → col shards dim -2, row shards dim -1)
    (r"(q|k|v)_proj\.weight$", "hf_col"),
    (r"(gate|up)_proj\.weight$", "hf_col"),
    (r"(o|down|out)_proj\.weight$", "hf_row"),
    (r"(fc1|dense_h_to_4h)\.weight$", "hf_col"),
    (r"(fc2|dense_4h_to_h)\.weight$", "hf_row"),
    (r"(attention|self_attention)\.dense\.weight$", "hf_row"),
    # fused qkv: neox rows are per-head [H, 3, hd] and falcon rows are
    # per-kv-group — both contiguous per head(-group), so col-sharding the
    # fused out dim keeps whole heads per rank (valid when tp divides K)
    (r"query_key_value\.weight$", "hf_col"),
    # gpt2 Conv1D stores [in, out] → native col/row orientation. NOTE:
    # c_attn is q|k|v concatenated on the out dim — col-sharding would split
    # q from k/v, so it intentionally falls through to replication.
    (r"c_fc\.weight$", "col"),
    (r"c_proj\.weight$", "row"),
    (r"(embed_tokens|word_embeddings|embed_in|wte)\.weight$", "vocab"),
    (r"(lm_head|embed_out)\.weight$", "hf_col"),
    # MoE experts (ep on the expert dim is added separately)
    (r"experts.*w[13]\.weight$", "hf_col"),
    (r"experts.*w2\.weight$", "hf_row"),
    (r"(^|/)router$", "none"),
)


def _spec_for(kind: str, ndim: int) -> Optional[P]:
    lead = [None] * max(0, ndim - 2)
    if kind == "col":
        return P(*lead, None, "tp")
    if kind == "row":
        return P(*lead, "tp", None)
    if kind == "hf_col":   # torch [out, in]
        return P(*lead, "tp", None)
    if kind == "hf_row":
        return P(*lead, None, "tp")
    if kind == "vocab":
        return P("tp", *([None] * (ndim - 1)))
    if kind == "none":
        return P(*([None] * ndim))
    return None


def infer_tp_specs(params: Any, patterns=TP_PATTERNS) -> Any:
    """AutoTP for arbitrary pytrees: infer a PartitionSpec tree by leaf-path
    name patterns (auto_tp.py:194 policy). Unmatched leaves are replicated.
    Leaves whose path mentions experts additionally carry ``ep`` on the
    leading expert dim when they are >= 3-D (AutoEP conversion, auto_ep.py)."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    out = []
    for keypath, leaf in flat:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in keypath)
        ndim = np.ndim(leaf) if not hasattr(leaf, "ndim") else leaf.ndim
        spec = None
        for pat, kind in patterns:
            if re.search(pat, name):
                spec = _spec_for(kind, ndim)
                break
        if spec is None:
            spec = P(*([None] * ndim))
        # AutoEP: stacked-MoE leaves [L, E, in, out] carry 'ep' on the expert
        # dim (our import layout; a raw HF tree keeps one 2-D leaf per expert,
        # where the expert axis is python structure, not a tensor dim)
        if ndim == 4 and re.search(r"(^|/)w_(gate|up|down)$", name):
            entries = list(spec) + [None] * (ndim - len(spec))
            if entries[1] is None:
                entries[1] = "ep"
            spec = P(*entries)
        out.append(spec)
    return jax.tree_util.tree_unflatten(treedef, out)
