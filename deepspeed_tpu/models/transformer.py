"""Decoder-only transformer LM family (GPT-2-style and Llama-style in one impl).

Parity target: the reference's in-tree model implementations
(``deepspeed/model_implementations/transformers/ds_{gpt,llama2,bert}.py``) and the HF
models its AutoTP/kernel-injection paths consume. TPU-first design:

* parameters for all layers are **stacked** on a leading layer axis so the forward is a
  single ``lax.scan`` — one compiled block regardless of depth, ZeRO-3/remat friendly;
* activations carry explicit sharding constraints (batch over dp/fsdp, sequence over
  sp, heads/ffn over tp) so XLA SPMD inserts megatron-style collectives — replacing
  ``module_inject/auto_tp.py:194``'s module rewriting;
* the attention core is pluggable (``set_attention_impl``) so the Pallas flash /
  ring-attention kernels (``deepspeed_tpu/ops``) drop in without touching the model;
* compute dtype is bf16 by default with fp32 params (master-weight parity with
  ``runtime/bf16_optimizer.py:37``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import operator
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops import lowerings
from deepspeed_tpu.parallel.sharding import constrain

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    hidden_size: int = 512
    num_layers: int = 4
    num_heads: int = 8
    num_kv_heads: Optional[int] = None  # None = MHA; < num_heads = GQA
    intermediate_size: Optional[int] = None  # None → 4*D (gpt2) or 8/3*D (llama)
    max_seq_len: int = 1024

    arch: str = "llama"  # "llama" | "gpt2"
    # derived-from-arch defaults (overridable)
    norm: Optional[str] = None        # rmsnorm | layernorm
    activation: Optional[str] = None  # swiglu | gelu | gelu_exact | relu | relu2
    use_rope: Optional[bool] = None
    learned_pos: Optional[bool] = None
    tie_embeddings: bool = True
    # standard deviation the token embedding is drawn with (init)
    embed_init_std: float = 0.02
    rope_theta: float = 10000.0
    # --- family knobs (reference: inference v2 model_implementations/ for
    # llama/mistral/qwen2/phi3/falcon/opt; each maps to one switch here) ---
    qkv_bias: bool = False        # qwen/qwen2 (bias on q/k/v only)
    proj_bias: bool = False       # gpt2/opt/gpt-neox/falcon(bias=True): wo + mlp
    parallel_block: bool = False  # falcon/gpt-neox: x + attn(ln(x)) + mlp(ln(x))
    parallel_shared_norm: bool = False  # falcon-7b: one ln feeds both branches
    rope_pct: float = 1.0         # gpt-neox partial rotary (rotary_pct)
    sliding_window: Optional[int] = None  # mistral/qwen2 windowed attention
    # the period of layer kinds over the layers: "window" (attention over the
    # last ``sliding_window`` keys), "full" (attention over every key) or
    # "ssm" (the mixer is no attention but a Mamba-2 state-space layer of the
    # ``ssm_*`` sizes below, models/mamba.py) or "delta" (a gated delta-rule
    # layer of the ``delta_*`` sizes, models/gated_delta.py) or "conv" (a
    # gated short convolution over ``conv_taps`` positions at the model's
    # width, models/short_conv.py) or "kda" (the delta rule with a decay a
    # key channel, models/kda.py) or, beside "kda" layers, "mla" (latent
    # attention, models/mla.py) or "dsa" (attention over the ``dsa_topk``
    # keys a learned indexer picks for each query, models/dsa.py; the kind's
    # fields are below, with ``mrope_section``): layer i is of
    # kind attn_pattern[i % len]. None = every layer the one attention kind
    # (windowed where sliding_window is set). HF qwen2's leading run of n
    # full layers is ("full",) * n + ("window",) * (L - n): a period of the
    # whole stack
    attn_pattern: Optional[Tuple[str, ...]] = None
    # every layer is one pre-norm branch, x + f(N(x)), f a mixer alone or an
    # FFN alone (the Nemotron-H family's stack): ``attn_pattern`` then also
    # names the FFN layers, "moe" (the routed experts) or "dense", each kind
    # with a stack of its own leaves and one norm a layer
    one_branch: bool = False
    # a state-space layer: heads of ``ssm_head_dim`` channels (inner width =
    # heads x head_dim), a state of ``ssm_state`` a channel, B and C shared
    # by the heads of each of ``ssm_groups`` groups, a causal depthwise
    # convolution over ``ssm_conv`` positions, the scan in chunks of
    # ``ssm_chunk`` (ops/ssd_scan.py: Pallas kernels, forward and backward,
    # where the backend, dtype and shapes allow, einsums elsewhere)
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # the gated norm over each of the ``ssm_groups`` groups' channels on its
    # own (Nemotron-H), not over all inner channels at once (Granite)
    ssm_group_norm: bool = False
    # a gated delta-rule (linear-attention) layer: ``delta_heads`` heads with
    # keys of ``delta_key_dim`` and values of ``delta_value_dim`` (the state
    # of a head is key x value), a causal depthwise convolution over
    # ``delta_conv`` positions on q, k and v, the rule in chunks
    # (ops/delta_rule.py: ``CHUNK``); ``delta_neg_eigval``: the step is
    # 2 sigmoid, so that a head's transition may have eigenvalues below 0
    delta_heads: int = 0
    # the heads of a delta layer's q and k where they are fewer than its
    # ``delta_heads`` (then the heads of v, z, the step, the decay and the
    # state): a divisor of them, value head i reading key head
    # i // (delta_heads / delta_key_heads) (Qwen3-Next: 16 for 32). None =
    # as many
    delta_key_heads: Optional[int] = None
    delta_key_dim: int = 128
    delta_value_dim: int = 128
    delta_conv: int = 4
    delta_neg_eigval: bool = False
    # a KDA layer (Kimi Delta Attention: the delta rule with a decay a key
    # channel): ``num_heads`` heads (``heads_held`` of them where set) with
    # keys of ``delta_key_dim`` and values of ``delta_value_dim``, the
    # convolutions over ``delta_conv`` positions, a head-wise sigmoid output
    # gate; the decay's logarithm is ``kda_lower_bound x sigmoid(.)``, in
    # (``kda_lower_bound``, 0): the bound the chunked rule's operands need
    # (ops/kda_rule.py)
    kda_lower_bound: float = -5.0
    # a gated short-convolution layer (the LFM2 family's): ``in_proj`` to
    # three times the width, a gate before and a gate after a causal
    # depthwise convolution over ``conv_taps`` positions (no bias, no
    # activation), ``out_proj``
    conv_taps: int = 3
    # the softmax scale of attention (None = 1/sqrt(head_dim)), what the
    # embedding's rows and each branch's output are multiplied by, and what
    # the logits are divided by (the Granite family's four multipliers)
    attention_multiplier: Optional[float] = None
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # HF-style rope_scaling dict ({"rope_type": "llama3"|"linear"|"yarn",
    # ...}); None = unscaled
    rope_scaling: Optional[Dict[str, Any]] = None
    # a rope of its own for a kind of layer: {"full": {"rope_theta": ...,
    # "rope_type": "yarn", ..., "partial_rotary_factor": 0.5}}; a kind that is
    # not named here takes rope_theta and rope_scaling, a kind without
    # "partial_rotary_factor" takes rope_pct
    rope_by_kind: Optional[Dict[str, Dict[str, Any]]] = None
    norm_eps: float = 1e-5
    # an RMSNorm's scale is kept as its distance from one: every norm of the
    # block, the q / k norms (``qk_norm``) and the final norm multiply by
    # ``1 + scale`` and the scale is drawn at 0 (the Qwen3-Next family's
    # norms, Gemma's: weight decay then pulls the factor to 1, not to 0); a
    # delta layer's gated output norm stays plain
    norm_zero_centred: bool = False

    dtype: str = "bfloat16"        # compute dtype
    param_dtype: str = "float32"   # storage dtype (master weights)
    remat_policy: str = "none"     # runtime.activation_checkpointing.POLICIES
    scan_layers: bool = True
    attention_impl: str = "auto"   # auto|xla|flash|ring|fpdt
    # FPDT q/kv chunk length for attention_impl="fpdt" (None → the
    # sequence.fpdt default); both fpdt tiers read it
    fpdt_chunk: Optional[int] = None
    # compression_training activation_quantization: fake-quantize MLP block
    # inputs with straight-through gradients when set (e.g. 8)
    act_quant_bits: Optional[int] = None
    z_loss: float = 0.0
    # >1: compute the CE loss in T/loss_tiling sequence chunks without ever
    # materializing the [B, T, V] fp32 logits (ALST TiledFusedLogitsLoss,
    # ulysses_sp.py:1065) — required for 100k+ contexts where dense logits
    # alone exceed HBM (128k x 32000 vocab fp32 = 16.8 GB)
    loss_tiling: int = 0

    # --- looped (weight-shared) stack: the L blocks run ``num_passes`` times
    # over the same weights, the final norm closing every pass; each pass has
    # its own logits through the one head (Ouro / LoopLM) ---
    num_passes: int = 1
    # x + norm(attn(norm(x))), then a + norm(ffn(norm(a))): a second norm
    # scale on each branch's output (``ln1_post``, ``ln2_post``)
    sandwich_norm: bool = False
    # where a block's one norm a branch stands: "pre" (x + Mix(N(x))) or
    # "post" (x + N(Mix(x)), then h + N(FFN(h)): no norm before a branch,
    # ``ln1_post`` / ``ln2_post`` after it; the Olmo 2 family's block)
    norm_placement: str = "pre"
    # "width": an RMSNorm on q and one on k over the whole projection (all
    # the heads held), before the heads are split and before any rope
    # (``q_norm``, ``k_norm`` in the attention group); "head": the norm over
    # each head's ``head_dim`` channels on their own, one scale of
    # ``head_dim`` for q and one for k, shared by the heads, after the heads
    # are split and before any rope; None = none
    qk_norm: Optional[str] = None
    # a share of a mixer's heads: this model holds ``heads_held`` of an
    # attention, latent-attention or KDA layer's ``num_heads`` (with the
    # key-value heads that serve them; latent attention's ``wkv_a`` and its
    # norm stay whole) and of a delta layer's ``delta_heads``, the first of
    # them (what
    # one chip of several that divide a layer's mixer by heads holds).
    # The projections are built for the heads held, the output projection has
    # their rows, and the mixer's output is the partial sum those heads give.
    # None = all of them
    heads_held: Optional[int] = None
    # query heads by kind of attention layer, {"window": 72}: a "window" or
    # "full" kind that is not named has ``num_heads``, every kind
    # ``num_kv_heads`` key-value heads of ``head_dim_override`` channels.
    # Where a count differs from ``num_heads`` each kind keeps a stack of its
    # own attention leaves (``attn_window``, ``attn_full``) and
    # ``heads_held`` is a share of each kind's heads (24 of 48: 36 of 72);
    # where none does, this is None and the model the one it was
    heads_by_kind: Optional[Dict[str, int]] = None
    # not None: a per-token exit gate sigmoid(w_g . h_t + b_g) after every
    # pass and the expected-exit loss sum_t p_t CE_t - beta H(p) (``loss_fn``)
    exit_loss_beta: Optional[float] = None

    # MoE (wired by deepspeed_tpu.moe; dense when num_experts <= 1)
    num_experts: int = 1
    top_k: int = 2
    capacity_factor: float = 1.25
    moe_aux_loss_coef: float = 0.01
    # "capacity" (GShard einsum, the EP form) | "grouped" (dropless
    # ragged_dot grouped GEMM; under ep>1 routes through a padded a2a over
    # the ep axis to per-shard grouped GEMMs)
    moe_dispatch: str = "capacity"
    # a2a capacity for grouped-under-ep: 0 → worst-case dropless
    # (cap = S_local*top_k); f>0 → cap ≈ S_local*top_k*f/ep (may drop
    # overflow pairs under extreme router imbalance)
    moe_ep_capacity_factor: float = 0.0
    # grouped-dispatch FFN kernel: "ragged" (grouped GEMMs over the sorted
    # rows: Pallas or lax.ragged_dot by shape, ops/grouped_matmul.py;
    # auto-fallback) | "padded" (capacity-einsum reference twin)
    moe_kernel: str = "ragged"
    # a2a dispatch wire (comm/quantized.py): 0 = dense, 4/8 = blockwise
    # quantized payload; moe_a2a_slice > 1 = hierarchical two-hop a2a
    # (quantized across DCN, dense inside a slice of that many shards)
    moe_a2a_bits: int = 0
    moe_a2a_slice: int = 0
    moe_a2a_block: int = 512
    # the experts' own width (None = intermediate_size)
    moe_intermediate_size: Optional[int] = None
    # a share of the experts: this model holds ``moe_experts_held`` of them,
    # from ``moe_first_expert`` on (what one chip of an expert-parallel host
    # holds). The router keeps its num_experts outputs, the top_k and their
    # weights are the whole model's, and the layer's output is the partial
    # sum the held experts give (moe/sharded_moe.py:grouped_moe_mlp_block;
    # the buffer of local pairs is bounded by moe_ep_capacity_factor).
    # None = all of them
    moe_experts_held: Optional[int] = None
    moe_first_expert: int = 0
    # how the router scores: "softmax" (the top k of the softmax, weights
    # renormalised, GShard's balance term) | "sigmoid" (the DeepSeek-V3
    # family: the top k of ``sigmoid + router_bias``, weights the sigmoids
    # without the bias, normalised, times ``moe_routed_scale``; the
    # sequence-wise balance term; grouped dispatch only). ``router_bias``
    # gets no gradient: after every step the engine moves it by
    # ``moe_bias_rate x sign(mean count - count)`` (:meth:`TransformerLM.
    # rule_updates`); it is drawn uniform in +-``moe_bias_init``
    moe_scoring: str = "softmax"
    moe_routed_scale: float = 1.0
    moe_bias_rate: float = 0.0
    moe_bias_init: float = 0.0
    # group-limited selection of a sigmoid router (DeepSeek-V3's): the
    # experts in ``moe_n_group`` groups of equal size, a group's score the
    # sum of its two largest ``sigmoid + router_bias``, the ``moe_topk_group``
    # best groups kept and the top k taken among their experts. 1 group: no
    # limit
    moe_n_group: int = 1
    moe_topk_group: int = 1
    # this many shared experts, every token's: one FFN of the experts' kind
    # (SwiGLU, or two products round relu^2) of that many times the experts'
    # width beside the routed ones (grouped dispatch only)
    moe_shared_experts: int = 0
    # the shared experts' output times ``sigmoid(x w_sg)``, ``w_sg`` [D, 1]
    # (``shared/w_sg``), one scalar a token (Qwen2-MoE's and Qwen3-Next's
    # ``shared_expert_gate``)
    moe_shared_gate: bool = False
    # LatentMoE: the tokens go through dispatch, the routed experts and
    # combine in a latent of this width (a plain linear map down before the
    # dispatch, one up after the combine; ``latent_down``, ``latent_up``);
    # the router and the shared experts read the full width. None = none
    moe_latent_size: Optional[int] = None
    # FFN kinds by layer: the first ``first_k_dense`` layers' FFN is dense at
    # ``intermediate_size``, the others' routed (num_experts > 1); each kind
    # has a stack of its own (``mlp_dense``, ``mlp_moe``)
    first_k_dense: int = 0
    # latent attention (models/mla.py), every layer's mixer where
    # ``kv_lora_rank`` is set and no ``attn_pattern`` names the "mla" layers
    # among "kda" ones: keys and values through a latent of that rank
    # with a norm in the middle, keys ``qk_nope_head_dim + qk_rope_head_dim``
    # wide (the rope part one vector a position, shared by the heads), values
    # ``v_head_dim``; ``rope_interleave``: the published weights pair the
    # rope columns (2i, 2i + 1). A low-rank query path (``q_lora_rank``) is
    # not implemented
    kv_lora_rank: Optional[int] = None
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_interleave: bool = False
    # each head's output times ``sigmoid(x wg)[h]`` before ``wo``, ``x`` the
    # layer's normed input, ``wg`` [D, H] (arXiv:2505.06708's head-wise
    # form): in a latent-attention layer and in a "window" or "full" layer,
    # there under the scope ``attn_gate`` (a KDA layer always has the gate)
    mla_head_gate: bool = False
    # a gate a channel on a "window" or "full" layer's heads
    # (arXiv:2505.06708's element-wise form, Qwen3-Next's): ``wq`` is twice
    # as wide, a head's ``2 head_dim`` columns its query then its gate, and
    # each head's output is multiplied by ``sigmoid(gate)`` before ``wo``,
    # under the scope ``attn_gate`` (``mla_head_gate`` beside it is the gate
    # a head from ``wg``; a model has one or the other)
    attn_channel_gate: bool = False
    # a rope whose frequency pairs follow three position axes (time, height,
    # width; HF ``rope_scaling.mrope_section``): consecutive sections of the
    # head's rope_dim / 2 pairs, the first turning by the first axis's
    # position and so on. The batch then may carry ``position_ids`` [3, B, T]
    # (``loss_fn``); without them the three are the token's index and the
    # rope the plain one. None = one axis
    mrope_section: Optional[Tuple[int, ...]] = None
    # a "dsa" layer (attention over the keys a learned indexer picks,
    # models/dsa.py, ops/dsa.py): the indexer's ``dsa_index_heads`` heads of
    # ``dsa_index_head_dim`` channels over ``dsa_index_kv_heads`` key heads
    # (one), the ``dsa_topk`` keys a query keeps, the query and key tiles its
    # scores and the attention are evaluated in (equal; they change no
    # result), and what the indexer's own loss (the KL from the heads' mean
    # attention over the set to the indexer's softmax over it, which trains
    # the indexer alone) is multiplied by in the step's loss
    dsa_index_heads: int = 0
    dsa_index_head_dim: int = 64
    dsa_index_kv_heads: int = 1
    dsa_topk: int = 2048
    dsa_q_chunk: int = 512
    dsa_kv_chunk: int = 512
    indexer_loss_coef: float = 1.0
    # block-diffusion training (models/block_diffusion.py): a row of L
    # tokens is trained as ``[noised ; clean]``, 2L positions at positions
    # ``0..L-1`` twice, under the block-diffusion mask over blocks of
    # ``diffusion_block`` tokens (a power of two), the loss over the noised
    # half alone at the positions the noise hid, each weighted by the batch's
    # ``loss_weights``; the batch carries ``noised_ids`` and ``loss_weights``
    # beside ``input_ids`` (runtime/data_pipeline/block_noise.py), whose ids
    # never are ``mask_token_id``. None = next-token training
    diffusion_block: Optional[int] = None
    mask_token_id: Optional[int] = None

    def __post_init__(self):
        is_llama = self.arch == "llama"
        object.__setattr__(self, "norm", self.norm or ("rmsnorm" if is_llama else "layernorm"))
        object.__setattr__(self, "activation",
                           self.activation or ("swiglu" if is_llama else "gelu"))
        if self.use_rope is None:
            object.__setattr__(self, "use_rope", is_llama)
        if self.learned_pos is None:
            object.__setattr__(self, "learned_pos", not is_llama)
        if self.num_kv_heads is None:
            object.__setattr__(self, "num_kv_heads", self.num_heads)
        if self.intermediate_size is None:
            inter = (int(8 * self.hidden_size / 3) if self.activation == "swiglu"
                     else 4 * self.hidden_size)
            # round to MXU-friendly multiple of 128
            inter = max(128, ((inter + 127) // 128) * 128)
            object.__setattr__(self, "intermediate_size", inter)
        if self.head_dim_override is None:
            assert self.hidden_size % self.num_heads == 0
        assert self.num_heads % self.num_kv_heads == 0
        if self.parallel_shared_norm:
            assert self.parallel_block, "shared norm requires parallel_block"
        if self.num_passes < 1:
            raise ValueError(f"num_passes={self.num_passes} must be >= 1")
        if self.sandwich_norm and self.parallel_block:
            raise ValueError("sandwich_norm norms each branch's output before "
                             "its own residual add; parallel_block has one add")
        if self.exit_loss_beta is not None and self.num_passes < 2:
            raise ValueError("exit_loss_beta (the exit gate and expected-exit "
                             "loss) needs num_passes >= 2")
        if self.attn_pattern is not None:
            pat = tuple(self.attn_pattern)
            if (self.kv_lora_rank is not None) != ("mla" in pat) or (
                    "mla" in pat and set(pat) != {"kda", "mla"}):
                raise NotImplementedError(
                    "latent attention (kv_lora_rank) is every layer's mixer, "
                    "or the 'mla' layers of an attn_pattern of 'kda' and "
                    "'mla' layers: not with another attn_pattern")
            ffns = {"moe", "dense"} if self.one_branch else set()
            if not pat or set(pat) - {"window", "full", "ssm", "delta",
                                      "conv", "kda", "mla", "dsa"} \
                    - ffns or self.num_layers % len(pat):
                raise ValueError(
                    f"attn_pattern={pat}: a period of 'window' / 'full' / "
                    f"'ssm' / 'delta' / 'conv' / 'kda' / 'dsa' (beside 'kda' "
                    f"also 'mla'; with one_branch also 'moe' / "
                    f"'dense') whose length divides num_layers="
                    f"{self.num_layers}")
            if "window" in pat and self.sliding_window is None:
                raise ValueError("attn_pattern has window layers and "
                                 "sliding_window is not set")
            # kept as its shortest period: a whole stack's list of kinds
            # (HF ``layer_types``) and its period are the same model
            p = next(p for p in range(1, len(pat) + 1) if len(pat) % p == 0
                     and pat == pat[:p] * (len(pat) // p))
            object.__setattr__(self, "attn_pattern", pat[:p])
        if self.has_ssm:
            if self.ssm_heads < 1 or self.ssm_heads % self.ssm_groups:
                raise ValueError(
                    f"a state-space layer needs ssm_heads={self.ssm_heads} "
                    f"> 0, a multiple of ssm_groups={self.ssm_groups}")
            if (self.looped or self.parallel_block
                    or self.loss_tiling > 1 or self.attention_impl == "fpdt"):
                raise NotImplementedError(
                    "a model with state-space layers (attn_pattern holds "
                    "'ssm') runs one pre-norm pass with whole logits and "
                    "whole-sequence attention: not num_passes > 1, "
                    "sandwich_norm, the exit gate, parallel_block, "
                    "loss_tiling > 1 or attention_impl='fpdt'")
        if self.has_delta:
            if self.delta_heads < 1 or self.delta_conv < 1:
                raise ValueError(
                    f"a delta layer needs delta_heads={self.delta_heads} "
                    f"and delta_conv={self.delta_conv} above 0")
            kh = self.delta_key_heads
            if kh is not None and (kh < 1 or self.delta_heads % kh):
                raise ValueError(
                    f"delta_key_heads={kh} does not divide delta_heads="
                    f"{self.delta_heads}: a key head serves a whole number "
                    f"of value heads")
            if self.num_experts > 1 and self.moe_dispatch != "grouped":
                raise NotImplementedError(
                    "a delta layer beside routed experts (num_experts > 1) "
                    "runs the grouped dispatch (moe_dispatch='grouped'), "
                    "whose step record it has been run with; not the "
                    "capacity form")
            if self.looped:
                raise NotImplementedError(
                    "a delta layer in a looped stack (num_passes > 1, "
                    "sandwich_norm or the exit gate): a pass would have to "
                    "say what state the next one starts from")
            if self.parallel_block:
                raise NotImplementedError(
                    "a delta layer under parallel_block: its block is "
                    "written for one branch after the other")
            if self.loss_tiling > 1:
                raise NotImplementedError(
                    "a delta layer with the tiled loss (loss_tiling > 1): "
                    "the step record's mixer outputs come from the whole-"
                    "logits path")
            if self.attention_impl == "fpdt":
                raise NotImplementedError(
                    "a delta layer with attention_impl='fpdt': the chunked "
                    "sequence path carries key-value chunks, not a "
                    "recurrent state")
        if self.has_kda:
            if self.delta_conv < 1 or not self.kda_lower_bound < 0:
                raise ValueError(
                    f"a KDA layer needs delta_conv={self.delta_conv} above "
                    f"0 and kda_lower_bound={self.kda_lower_bound} below 0")
            if (self.looped or self.parallel_block or self.loss_tiling > 1
                    or self.attention_impl == "fpdt" or self.one_branch
                    or set(self.attn_pattern) - {"kda", "mla"}
                    or self.norm_placement != "pre"):
                raise NotImplementedError(
                    "a model with KDA layers (attn_pattern holds 'kda') "
                    "runs one pre-norm pass of two-branch layers with whole "
                    "logits and whole sequences, beside latent-attention "
                    "layers alone: not a looped stack (num_passes > 1, "
                    "sandwich_norm or the exit gate: a pass would have to "
                    "say what state the next one starts from), "
                    "parallel_block, the tiled loss (loss_tiling > 1), "
                    "attention_impl='fpdt' (its chunks carry keys and "
                    "values, not a recurrent state), one_branch, "
                    "norm_placement='post' or another kind of mixer in the "
                    "pattern")
        if self.mrope_section is not None:
            sec = tuple(int(n) for n in self.mrope_section)
            object.__setattr__(self, "mrope_section", sec)
            if (len(sec) != 3 or min(sec) < 0 or not self.use_rope
                    or 2 * sum(sec) != self.rope_dim):
                raise ValueError(
                    f"mrope_section={sec}: three sections (time, height, "
                    f"width) that add up to the rope's {self.rope_dim // 2} "
                    f"frequency pairs")
            if (self.has_mla or self.rope_scaling or self.rope_by_kind
                    or self.one_branch
                    or self.attention_impl in ("fpdt", "ring")):
                raise NotImplementedError(
                    "a rope over three position axes (mrope_section) is "
                    "applied by plain and 'dsa' attention layers of "
                    "two-branch blocks over whole sequences: not latent "
                    "attention (its rope kernel takes one axis), "
                    "rope_scaling, rope_by_kind, one_branch or "
                    "attention_impl='fpdt' / 'ring' (which rotate chunk by "
                    "chunk from one axis)")
        if self.has_dsa:
            J, c = self.dsa_index_heads, self.dsa_index_head_dim
            if J < 1 or c < 2 or c % 2 or self.dsa_topk < 1:
                raise ValueError(
                    f"a 'dsa' layer needs dsa_index_heads={J} above 0, an "
                    f"even dsa_index_head_dim={c} and dsa_topk="
                    f"{self.dsa_topk} above 0")
            if self.dsa_index_kv_heads != 1:
                raise NotImplementedError(
                    f"dsa_index_kv_heads={self.dsa_index_kv_heads}: the "
                    f"indexer scores every query head against one key head")
            if self.dsa_q_chunk != self.dsa_kv_chunk:
                raise NotImplementedError(
                    f"dsa_q_chunk={self.dsa_q_chunk} and dsa_kv_chunk="
                    f"{self.dsa_kv_chunk}: the selected-key attention runs "
                    f"in one tile for queries and keys")
            if self.mrope_section is not None and any(
                    s * c % self.head_dim for s in self.mrope_section):
                raise ValueError(
                    f"mrope_section={self.mrope_section} does not scale to "
                    f"the indexer's {c // 2} frequency pairs")
            if (self.looped or self.parallel_block or self.one_branch
                    or self.heads_held is not None
                    or self.sliding_window is not None
                    or self.qkv_bias or self.proj_bias
                    or self.rope_scaling or self.rope_by_kind
                    or self.rope_pct != 1.0
                    or self.attention_multiplier is not None
                    or self.norm_placement != "pre"
                    or self.attention_impl in ("fpdt", "ring")):
                raise NotImplementedError(
                    "a model with 'dsa' layers (attention over the keys a "
                    "learned indexer picks) runs one pre-norm pass of "
                    "two-branch layers over whole sequences, every head "
                    "held, its set the only limit on the keys: not a looped "
                    "stack (num_passes > 1, sandwich_norm or the exit gate: "
                    "each pass would select again and add an indexer loss "
                    "of its own), parallel_block, one_branch, heads_held "
                    "(the indexer's target is the mean over all heads), "
                    "sliding_window (a window beside the set), biases, "
                    "rope_scaling, rope_by_kind, rope_pct, "
                    "attention_multiplier, norm_placement='post' or "
                    "attention_impl='fpdt' / 'ring' (their chunks carry "
                    "keys and values, not the indexer's keys and each "
                    "query's set)")
        if self.has_conv:
            if self.conv_taps < 1:
                raise ValueError(f"a conv layer needs conv_taps="
                                 f"{self.conv_taps} above 0")
            if (self.looped or self.parallel_block or self.loss_tiling > 1
                    or self.attention_impl == "fpdt"
                    or self.heads_held is not None):
                raise NotImplementedError(
                    "a model with short-convolution layers (attn_pattern "
                    "holds 'conv') runs one pass, one branch after the "
                    "other, with whole logits, whole sequences and whole "
                    "mixers: not a looped stack (num_passes > 1, "
                    "sandwich_norm or the exit gate: a pass would have to "
                    "say what positions the next one's convolution starts "
                    "from), parallel_block, the tiled loss (loss_tiling > "
                    "1), attention_impl='fpdt' (its chunks carry keys and "
                    "values, not the positions before a chunk) or "
                    "heads_held (the gates and taps are by channel: there "
                    "are no heads to hold a share of)")
        if self.one_branch:
            # (``kind_cfg``'s copy for one kind of layer has no pattern)
            pat = self.attn_pattern
            if pat is not None and (
                    ("moe" in pat) != (self.num_experts > 1)
                    or not set(pat) & {"moe", "dense"}):
                raise ValueError(
                    f"one_branch with attn_pattern={pat}: the pattern names "
                    f"the FFN layers ('moe' / 'dense'), 'moe' where and only "
                    f"where num_experts > 1")
            if (self.looped or self.parallel_block or self.loss_tiling > 1
                    or self.norm_placement != "pre" or self.has_delta
                    or self.has_conv
                    or self.first_k_dense or self.heads_held is not None
                    or self.residual_multiplier != 1.0
                    or self.attention_impl == "fpdt"):
                raise NotImplementedError(
                    "one_branch (a layer is a mixer alone or an FFN alone) "
                    "runs one pass of pre-norm attention, state-space and "
                    "FFN layers with whole logits: not num_passes > 1, "
                    "sandwich_norm, the exit gate, parallel_block, "
                    "loss_tiling > 1, norm_placement='post', delta or conv "
                    "layers, first_k_dense (the pattern names the dense "
                    "layers), "
                    "heads_held, residual_multiplier or attention_impl="
                    "'fpdt'")
        if self.has_bd:
            n, mask = self.diffusion_block, self.mask_token_id
            if n < 1 or n & (n - 1):
                raise NotImplementedError(
                    f"diffusion_block={n}: the flash kernels round the "
                    f"diagonal to blocks whose length is a power of two")
            if mask is None or not 0 <= mask < self.vocab_size:
                raise ValueError(
                    f"block diffusion (diffusion_block={n}) needs "
                    f"mask_token_id={mask} among the vocab_size="
                    f"{self.vocab_size} rows the model holds")
            if (self.looped or self.parallel_block or self.one_branch
                    or self.loss_tiling > 1 or self.attn_pattern is not None
                    or self.sliding_window is not None or self.has_mla
                    or self.mrope_section is not None or self.mla_head_gate
                    or not self.use_rope or self.learned_pos
                    or self.exit_loss_beta is not None
                    or self.attention_impl not in ("auto", "xla", "flash",
                                                   "flash_pallas")):
                raise NotImplementedError(
                    f"block diffusion (diffusion_block={n}) trains one "
                    f"pre-norm pass of plain attention layers of one kind "
                    f"under a rope, over whole rows with whole logits: not "
                    f"a looped stack (num_passes > 1, sandwich_norm or the "
                    f"exit gate: each pass would need its own noised row), "
                    f"parallel_block, one_branch, the tiled loss "
                    f"(loss_tiling > 1: it shifts the labels and weighs no "
                    f"position), an attn_pattern, sliding_window (a window "
                    f"beside the rounded diagonal), latent attention, "
                    f"mrope_section, mla_head_gate (the halves' results are "
                    f"projected apart), use_rope=False / learned positions "
                    f"(the two halves repeat their positions through the "
                    f"rope), or attention_impl='fpdt' / 'ring' / 'ulysses' "
                    f"(their chunks know the causal mask only)")
        if self.norm_placement not in ("pre", "post"):
            raise ValueError(f"norm_placement={self.norm_placement!r}: "
                             f"'pre' or 'post'")
        if self.norm_placement == "post" and (
                self.sandwich_norm or self.parallel_block):
            raise ValueError(
                "norm_placement='post' is one norm after each branch: not "
                "with sandwich_norm (a norm before it too) or parallel_block "
                "(one residual add)")
        if self.qk_norm not in (None, "width", "head"):
            raise ValueError(f"qk_norm={self.qk_norm!r}: None, 'width' (one "
                             f"RMSNorm over the whole projection) or 'head' "
                             f"(over each head's channels)")
        if self.qk_norm and (self.has_mla or self.norm != "rmsnorm"
                             or self.attention_impl == "fpdt"):
            raise NotImplementedError(
                "qk_norm is an RMSNorm on the q and k projections of plain "
                "attention layers: not with latent attention (its norm is "
                "the latent's), norm='layernorm' or attention_impl='fpdt' "
                "(which projects chunk by chunk)")
        if self.heads_held is not None:
            n = self.heads_held
            for what, total in (("num_heads", self.num_heads),
                                ("delta_heads", self.delta_heads
                                 if self.has_delta else None)):
                if total is not None and not 1 <= n <= total:
                    raise ValueError(
                        f"heads_held={n} are not among the {what}={total}")
            group = self.num_heads // self.num_kv_heads
            if n % group:
                raise ValueError(
                    f"heads_held={n} cut a group of {group} query heads "
                    f"from the key-value head that serves it")
            if self.has_delta and n % (self.delta_heads // (
                    self.delta_key_heads or self.delta_heads)):
                raise ValueError(
                    f"heads_held={n} cut a group of a delta layer's value "
                    f"heads (delta_heads={self.delta_heads}) from the key "
                    f"head that serves it (delta_key_heads="
                    f"{self.delta_key_heads})")
            if (self.has_ssm or self.looped
                    or self.parallel_block or self.qkv_bias or self.proj_bias
                    or self.attention_impl == "fpdt"):
                raise NotImplementedError(
                    "a held share of the heads (heads_held) is built for "
                    "plain attention, latent attention, delta and KDA "
                    "layers without biases: not "
                    "a state-space layer, a looped stack, "
                    "parallel_block, qkv_bias / proj_bias or "
                    "attention_impl='fpdt'")
        if self.has_mla:
            if self.q_lora_rank is not None:
                raise NotImplementedError(
                    f"q_lora_rank={self.q_lora_rank}: the low-rank query "
                    f"path with its norm is not implemented; latent "
                    f"attention takes one query matrix (q_lora_rank None)")
            if (self.looped or self.parallel_block or self.qkv_bias
                    or self.proj_bias or self.sliding_window is not None
                    or self.rope_scaling or self.rope_by_kind
                    or not self.use_rope or self.norm != "rmsnorm"
                    or self.attention_multiplier is not None
                    or self.loss_tiling > 1 or self.attention_impl == "fpdt"):
                raise NotImplementedError(
                    "latent attention (kv_lora_rank) runs one pre-norm "
                    "RMSNorm pass with a plain rope over whole sequences and "
                    "whole logits: not num_passes > 1, sandwich_norm, the "
                    "exit gate, parallel_block, biases, sliding_window, "
                    "rope_scaling, rope_by_kind, use_rope=False, "
                    "attention_multiplier, loss_tiling > 1 or "
                    "attention_impl='fpdt'")
        if self.mla_head_gate and not (self.has_mla
                                       or self.gates_plain_heads):
            raise ValueError("mla_head_gate (the gate a head, from wg "
                             "[D, H]) gates the heads of latent "
                             "attention (kv_lora_rank) or of 'window' / "
                             "'full' attention layers: this model has "
                             "neither (the gate a channel, from wq's second "
                             "half, is attn_channel_gate)")
        if self.attn_channel_gate:
            if self.mla_head_gate or not self.has_plain_attention:
                raise ValueError(
                    "attn_channel_gate (the gate a channel, from wq's "
                    "second half) gates the heads of 'window' / 'full' "
                    "attention layers, which this model lacks, and not "
                    "beside mla_head_gate (the gate a head, from wg): one "
                    "gate or the other")
            if self.qk_norm == "width" or self.has_dsa or self.has_bd:
                raise NotImplementedError(
                    "attn_channel_gate with qk_norm='width' (the norm over "
                    "the whole projection would take the gates' columns "
                    "in), 'dsa' layers (whose block reads no gate) or block "
                    "diffusion (the halves' results are projected apart)")
        if self.norm_zero_centred:
            if self.norm != "rmsnorm":
                raise ValueError(
                    f"norm_zero_centred is an RMSNorm's scale kept as its "
                    f"distance from one: not norm={self.norm!r}")
            if (self.looped or self.parallel_block or self.one_branch
                    or self.norm_placement != "pre" or self.has_mla
                    or self.has_kda or self.has_dsa or self.has_ssm
                    or self.has_conv or self.has_bd or self.loss_tiling > 1
                    or self.attention_impl in ("fpdt", "ring")):
                raise NotImplementedError(
                    "zero-centred norms (norm_zero_centred) are applied by "
                    "the train step's two-branch pre-norm block of plain "
                    "attention and delta layers, its q / k norms and the "
                    "final norm: not a looped stack (num_passes > 1, "
                    "sandwich_norm or the exit gate), parallel_block, "
                    "one_branch, norm_placement='post', latent attention, "
                    "KDA, 'dsa', state-space or short-convolution layers "
                    "(whose own norms are written plain), block diffusion, "
                    "the tiled loss (loss_tiling > 1) or attention_impl="
                    "'fpdt' / 'ring' (which norm chunk by chunk)")
        if self.moe_shared_gate and not self.moe_shared_experts:
            raise ValueError("moe_shared_gate gates the shared experts' "
                             "output: this model has none "
                             "(moe_shared_experts=0)")
        if self.heads_by_kind is not None:
            by = {k: int(n) for k, n in self.heads_by_kind.items()}
            if (set(by) - {"window", "full"}
                    or set(by) - set(self.attn_pattern or ())
                    or any(n < 1 or n % self.num_kv_heads
                           for n in by.values())
                    or self.head_dim_override is None):
                raise ValueError(
                    f"heads_by_kind={by}: query heads for the 'window' / "
                    f"'full' kinds of attn_pattern={self.attn_pattern}, each "
                    f"count a multiple of num_kv_heads={self.num_kv_heads}, "
                    f"the head's width given (head_dim_override)")
            by = {k: n for k, n in by.items() if n != self.num_heads}
            object.__setattr__(self, "heads_by_kind", by or None)
            held = self.heads_held
            for kind, n in by.items():
                if held is not None and (
                        held * n % self.num_heads
                        or held * n // self.num_heads
                        % (n // self.num_kv_heads)):
                    raise ValueError(
                        f"heads_held={held} of num_heads={self.num_heads} is "
                        f"no whole number of groups of "
                        f"{n // self.num_kv_heads} of a {kind!r} layer's "
                        f"{n} query heads")
        if self.attn_differs_by_kind and (
                self.looped or self.parallel_block or self.one_branch
                or self.has_dsa or self.qkv_bias or self.proj_bias
                or self.loss_tiling > 1
                or self.attention_impl in ("fpdt", "ring")):
            raise NotImplementedError(
                "query heads by kind (heads_by_kind), a rope width by kind "
                "(rope_by_kind's partial_rotary_factor) and a gate on "
                "'window' / 'full' layers' heads (mla_head_gate: a head; "
                "attn_channel_gate: a channel) run one pre-norm "
                "pass of two-branch layers without biases over whole "
                "sequences with whole logits: not a looped stack "
                "(num_passes > 1, sandwich_norm or the exit gate), "
                "parallel_block, one_branch, 'dsa' layers (whose block reads "
                "one attention stack and no gate), qkv_bias / proj_bias, the "
                "tiled loss (loss_tiling > 1: the step record's mixer "
                "outputs come from the whole-logits path) or "
                "attention_impl='fpdt' / 'ring' (which project and rotate "
                "chunk by chunk from one head count and one rope width)")
        if self.moe_scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"moe_scoring={self.moe_scoring!r}: 'softmax' "
                             f"or 'sigmoid'")
        if self.moe_n_group != 1 or self.moe_topk_group != 1:
            n, kept = self.moe_n_group, self.moe_topk_group
            if (self.moe_scoring != "sigmoid" or n < 1
                    or self.num_experts % n or not 1 <= kept <= n
                    or self.num_experts // n < 2
                    or kept * (self.num_experts // n) < self.top_k):
                raise ValueError(
                    f"moe_n_group={n}, moe_topk_group={kept}: group-limited "
                    f"selection is a sigmoid router's (moe_scoring="
                    f"'sigmoid'), over groups of equal size of at least two "
                    f"of the num_experts={self.num_experts}, the kept "
                    f"groups holding at least top_k={self.top_k} experts")
        if (self.moe_scoring == "sigmoid" or self.moe_shared_experts
                or self.first_k_dense or self.moe_latent_size) and (
                    self.num_experts <= 1 or self.moe_dispatch != "grouped"
                    or self.activation not in ("swiglu", "relu2")):
            raise ValueError(
                "moe_scoring='sigmoid', moe_shared_experts, moe_latent_size "
                "and first_k_dense belong to a model with routed SwiGLU or "
                "relu2 experts under the grouped dispatch (num_experts > 1, "
                "moe_dispatch='grouped')")
        if (self.activation == "relu2" and self.num_experts > 1
                and self.moe_dispatch != "grouped"):
            raise ValueError("experts of two products round relu^2 "
                             "(activation='relu2') run the grouped dispatch "
                             "only (moe_dispatch='grouped')")
        if not 0 <= self.first_k_dense < self.num_layers:
            raise ValueError(f"first_k_dense={self.first_k_dense} of "
                             f"num_layers={self.num_layers}")
        if self.has_ffn_kinds and (self.looped or self.parallel_block
                                   or self.loss_tiling > 1):
            raise NotImplementedError(
                "FFN kinds by layer (first_k_dense) run one pre-norm pass "
                "with whole logits: not num_passes > 1, sandwich_norm, the "
                "exit gate, parallel_block or loss_tiling > 1")
        if self.moe_experts_held is not None:
            lo, n = self.moe_first_expert, self.moe_experts_held
            if not (n >= 1 and lo >= 0 and lo + n <= self.num_experts):
                raise ValueError(
                    f"experts [{lo}, {lo + n}) are not among the "
                    f"{self.num_experts} the router scores")
            if self.moe_dispatch != "grouped":
                raise ValueError("a held share of the experts "
                                 "(moe_experts_held) runs the grouped "
                                 "dispatch only (moe_dispatch='grouped')")

    # set when structured head pruning shrinks num_heads (head_dim is
    # otherwise derived as hidden_size // num_heads, which would silently
    # change under a reduced head count)
    head_dim_override: Optional[int] = None

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or self.hidden_size // self.num_heads

    @property
    def looped(self) -> bool:
        """Whether anything of the looped family is on: several passes, the
        sandwich norm or the exit gate. Paths written for one pass over a
        pre-norm stack ask this and refuse."""
        return (self.num_passes > 1 or self.sandwich_norm
                or self.exit_loss_beta is not None)

    @property
    def has_ssm(self) -> bool:
        """Whether any layer's mixer is a state-space layer."""
        return "ssm" in (self.attn_pattern or ())

    @property
    def has_delta(self) -> bool:
        """Whether any layer's mixer is a gated delta-rule layer."""
        return "delta" in (self.attn_pattern or ())

    @property
    def has_kda(self) -> bool:
        """Whether any layer's mixer is a KDA layer (the delta rule with a
        decay a key channel)."""
        return "kda" in (self.attn_pattern or ())

    @property
    def has_conv(self) -> bool:
        """Whether any layer's mixer is a gated short convolution."""
        return "conv" in (self.attn_pattern or ())

    @property
    def has_dsa(self) -> bool:
        """Whether any layer's mixer is attention over the keys a learned
        indexer picks."""
        return "dsa" in (self.attn_pattern or ())

    @property
    def has_bd(self) -> bool:
        """Whether the model trains by block diffusion
        (``diffusion_block``): a ``[noised ; clean]`` row under the
        block-diffusion mask."""
        return self.diffusion_block is not None

    @property
    def heads_here(self) -> int:
        """The attention heads this model holds (``heads_held``, else all)."""
        return self.heads_held or self.num_heads

    @property
    def kv_heads_here(self) -> int:
        """The key-value heads that serve :attr:`heads_here`."""
        return self.heads_here * self.num_kv_heads // self.num_heads

    @property
    def has_plain_attention(self) -> bool:
        """Whether any layer's mixer is plain attention ("window" or
        "full")."""
        return any(k.partition(":")[0] in ("window", "full")
                   for k in self.layer_kinds)

    @property
    def gates_plain_heads(self) -> bool:
        """Whether a "window" or "full" layer's heads are under the head gate
        (``mla_head_gate`` in a model with such layers)."""
        return self.mla_head_gate and self.has_plain_attention

    @property
    def attn_differs_by_kind(self) -> bool:
        """Whether "window" and "full" layers carry what only the train
        step's block applies: query heads by kind, a rope width by kind
        (``rope_by_kind``'s "partial_rotary_factor"), the gate a head or the
        gate a channel."""
        return bool(self.heads_by_kind) or self.gates_plain_heads \
            or self.attn_channel_gate or any(
            "partial_rotary_factor" in r
            for r in (self.rope_by_kind or {}).values())

    @property
    def has_mla(self) -> bool:
        """Whether a layer's mixer is latent attention: every layer's, or
        the "mla" layers' of an ``attn_pattern`` beside "kda" ones."""
        return self.kv_lora_rank is not None

    @property
    def reports_mixer_outputs(self) -> bool:
        """Whether the step record carries each layer's mixer-output mean
        square (``mix_out_ms``): a model with a mixer that is no plain
        attention, one whose attention kinds differ in their heads or gate
        them, or one whose layers are one branch each (then every layer's
        branch output, an FFN layer's too)."""
        return (self.has_ssm or self.has_mla or self.has_delta
                or self.has_conv or self.has_kda or self.has_dsa
                or self.one_branch or bool(self.heads_by_kind)
                or self.gates_plain_heads or self.attn_channel_gate
                or self.has_bd)

    @property
    def has_ffn_kinds(self) -> bool:
        """Whether each FFN kind keeps a stack of its own (``mlp_dense``,
        ``mlp_moe``): a leading run of dense layers before the routed ones,
        or layers of one branch each."""
        return self.first_k_dense > 0 or self.one_branch

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """The kind of every layer: its mixer's, "window", "full", "ssm",
        "delta", "conv", "kda", "mla" or "dsa"; in a model whose FFNs differ by
        layer, then
        ":" and its FFN's, "dense" or "moe". A layer of one branch (``one_branch``)
        names what it lacks "none": "ssm:none", "none:moe"."""
        pat = self.attn_pattern or (
            ("mla",) if self.has_mla else
            ("full",) if self.sliding_window is None else ("window",))
        kinds = pat * (self.num_layers // len(pat))
        if self.one_branch:
            return tuple("none:" + k if k in ("moe", "dense")
                         else k + ":none" for k in kinds)
        if self.has_ffn_kinds:
            kinds = tuple(
                k + (":dense" if i < self.first_k_dense else ":moe")
                for i, k in enumerate(kinds))
        return kinds

    @property
    def patterned(self) -> bool:
        """Whether the layers are of more than one kind, or of one that is no
        plain attention: the layer loop then runs by kind
        (``_run_periods``)."""
        return (len(set(self.layer_kinds)) > 1 or self.has_ssm
                or self.has_mla or self.has_delta or self.has_conv
                or self.has_kda or self.has_dsa or self.one_branch
                or self.has_bd)

    def kind_cfg(self, kind: str) -> "TransformerConfig":
        """The configuration a block of ``kind`` runs under: no window on a
        full layer, the kind's own rope (its theta, its scaling, the share of
        a head it turns), the kind's own query heads and ``heads_held`` as
        the same share of them. The same object where nothing differs."""
        kind = kind.partition(":")[0]
        rope = (self.rope_by_kind or {}).get(kind)
        window = self.sliding_window if kind == "window" else None
        if (rope is None and window == self.sliding_window
                and self.attn_pattern is None):
            return self
        kw: Dict[str, Any] = dict(sliding_window=window, attn_pattern=None,
                                  rope_by_kind=None, heads_by_kind=None)
        if rope is not None:
            scaling = {k: v for k, v in rope.items()
                       if k not in ("rope_theta", "partial_rotary_factor")}
            kw["rope_theta"] = float(rope.get("rope_theta", self.rope_theta))
            kw["rope_scaling"] = (scaling if scaling.get(
                "rope_type", "default") != "default" else None)
            if "partial_rotary_factor" in rope:
                kw["rope_pct"] = float(rope["partial_rotary_factor"])
        heads = (self.heads_by_kind or {}).get(kind)
        if heads is not None:
            kw["num_heads"] = heads
            if self.heads_held is not None:
                kw["heads_held"] = self.heads_held * heads // self.num_heads
        return dataclasses.replace(self, **kw)

    @property
    def rope_dim(self) -> int:
        """Rotary dims per head (gpt-neox style partial rotary when < head_dim;
        under latent attention the keys' rope part)."""
        if self.has_mla:
            return self.qk_rope_head_dim
        return 2 * (int(self.head_dim * self.rope_pct) // 2)

    def num_params_estimate(self) -> int:
        """The leaves ``TransformerLM.init`` makes, counted from the sizes:
        each layer's mixer and FFN by its kind, the norms once each."""
        D, F, V, L = self.hidden_size, self.intermediate_size, self.vocab_size, self.num_layers
        gated = self.activation == "swiglu"
        norm = D * (2 if self.norm == "layernorm" else 1)
        norms = norm * ((1 if self.parallel_shared_norm or self.one_branch
                         else 2) + (2 if self.sandwich_norm else 0))

        def attn_of(ck: "TransformerConfig") -> int:
            hd, nh, nkv = ck.head_dim, ck.heads_here, ck.kv_heads_here
            attn = D * nh * hd + 2 * D * nkv * hd + nh * hd * D
            if self.attn_channel_gate:
                attn += D * nh * hd
            if ck.qk_norm:
                attn += 2 * hd if ck.qk_norm == "head" else (nh + nkv) * hd
            if ck.qkv_bias:
                attn += (nh + 2 * nkv) * hd
            if ck.proj_bias:
                attn += D
            if self.gates_plain_heads:
                attn += D * nh
            return attn

        dense = (3 if gated else 2) * D * F
        if self.proj_bias and not gated:
            dense += F + D
        routed = dense
        if self.num_experts > 1:
            Fm, Z = self.moe_intermediate_size or F, self.moe_latent_size
            n = 3 if gated else 2
            routed = ((self.moe_experts_held or self.num_experts)
                      * n * (Z or D) * Fm + D * self.num_experts
                      + n * D * Fm * self.moe_shared_experts
                      + (D if self.moe_shared_gate else 0)
                      + 2 * D * (Z or 0))
            if self.moe_scoring == "sigmoid":
                routed += self.num_experts
        mixers = {"attn": attn_of(self)}
        for kind in ("window", "full") if self.heads_by_kind else ():
            mixers["attn_" + kind] = attn_of(self.kind_cfg(kind))
        if self.has_ssm:
            from deepspeed_tpu.models import mamba

            mixers["ssm"] = mamba.num_params(self)
        if self.has_mla:
            from deepspeed_tpu.models import mla

            mixers["mla"] = mla.num_params(self)
        if self.has_delta:
            from deepspeed_tpu.models import gated_delta

            mixers["delta"] = gated_delta.num_params(self)
        if self.has_conv:
            from deepspeed_tpu.models import short_conv

            mixers["conv"] = short_conv.num_params(self)
        if self.has_kda:
            from deepspeed_tpu.models import kda

            mixers["kda"] = kda.num_params(self)
        if self.has_dsa:
            from deepspeed_tpu.models import dsa

            mixers["indexer"] = dsa.num_params(self)
        layers = 0
        for kind in self.layer_kinds:
            mixer, _, ffn = kind.partition(":")
            layers += norms
            if mixer != "none":
                layers += sum(mixers[g] for g in _groups_of(
                    mixer, bool(self.heads_by_kind)))
            if ffn != "none":
                layers += dense if ffn == "dense" else routed
        embed = V * D + (self.max_seq_len * D if self.learned_pos else 0)
        head = 0 if self.tie_embeddings else D * V
        gate = D + 1 if self.exit_loss_beta is not None else 0
        # passes share their weights: the count does not grow with them
        # (models/spec.py:model_flops_per_token multiplies the work)
        return layers + embed + head + norm + gate


# ---------------------------------------------------------------------------
# Attention core registry — ops/ kernels override the default XLA path.
# ---------------------------------------------------------------------------

_ATTENTION_IMPLS: Dict[str, Callable] = {}


def register_attention_impl(name: str, fn: Callable) -> None:
    _ATTENTION_IMPLS[name] = fn


def get_attention_impl(name: str) -> Callable:
    if name in ("auto", "xla"):
        impl = _ATTENTION_IMPLS.get("flash") if name == "auto" else None
        return impl or xla_attention
    if name not in _ATTENTION_IMPLS:
        raise ValueError(f"unknown attention impl '{name}' "
                         f"(have {sorted(_ATTENTION_IMPLS)} + xla)")
    return _ATTENTION_IMPLS[name]


def repeat_kv(k: jax.Array, v: jax.Array, num_heads: int):
    """GQA: tile kv heads up to ``num_heads`` (no-op for MHA). The single
    source of the head-repeat convention — every attention path uses it."""
    K = k.shape[2]
    if K != num_heads:
        rep = num_heads // K
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


def xla_attention(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
                  segment_ids: Optional[jax.Array] = None,
                  window: Optional[int] = None) -> jax.Array:
    """Reference attention: q[B,T,H,d], k/v[B,S,K,d] → [B,T,H,d]. GQA via head repeat.

    ``window`` masks keys more than ``window-1`` positions behind each query
    (mistral/qwen2 sliding-window attention)."""
    B, T, H, d = q.shape
    S = k.shape[1]
    k, v = repeat_kv(k, v, H)
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(d)
    mask = None
    if causal:
        mask = jnp.tril(jnp.ones((T, S), dtype=bool), k=S - T)[None, None]
    if window is not None:
        tpos = jnp.arange(T)[:, None] + (S - T)
        in_win = jnp.arange(S)[None, :] > tpos - window
        mask = in_win[None, None] if mask is None else (mask & in_win[None, None])
    if segment_ids is not None:
        seg = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        mask = seg if mask is None else (mask & seg)
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhts,bshd->bthd", probs, v)
    # same remat tag as the pallas kernel so attn_saveable policies also pin
    # the XLA fallback's output instead of silently recomputing it
    return checkpoint_name(out, "flash_attn_out")


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------

def _norm(x: jax.Array, w: Params, kind: str, eps: float,
          zero_centred: bool = False) -> jax.Array:
    """``zero_centred`` (``cfg.norm_zero_centred``): the RMSNorm multiplies
    by ``1 + scale``, float32."""
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        scale = w["scale"].astype(jnp.float32)
        out = xf * (1.0 + scale if zero_centred else scale)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * w["scale"] + w["bias"]
    return out.astype(x.dtype)


def rope_frequencies(head_dim: int, max_seq: int, theta: float,
                     scaling: Optional[Dict[str, Any]] = None) -> jax.Array:
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if scaling:
        rt = scaling.get("rope_type", scaling.get("type", "linear"))
        if rt == "linear":
            inv = inv / float(scaling["factor"])
        elif rt == "llama3":
            # HF Llama-3.1 frequency-band scaling: low-frequency bands divide
            # by `factor`, high-frequency bands pass through, bands between
            # interpolate smoothly (transformers modeling_rope_utils).
            factor = float(scaling["factor"])
            lo = float(scaling.get("low_freq_factor", 1.0))
            hi = float(scaling.get("high_freq_factor", 4.0))
            orig = float(scaling.get("original_max_position_embeddings", 8192))
            wavelen = 2.0 * math.pi / inv
            smooth = (orig / wavelen - lo) / (hi - lo)
            interp = (1 - smooth) * inv / factor + smooth * inv
            inv = jnp.where(wavelen > orig / lo, inv / factor,
                            jnp.where(wavelen < orig / hi, inv, interp))
        elif rt == "yarn":
            # HF yarn (transformers modeling_rope_utils): bands that turn
            # more than beta_fast times over the original length keep their
            # frequency, bands that turn less than beta_slow times divide it
            # by `factor`, a linear ramp over the band index between. The
            # attention factor scales cos and sin (rope_attention_factor).
            factor = float(scaling["factor"])
            orig = float(scaling["original_max_position_embeddings"])

            def band(turns):
                return (head_dim * math.log(orig / (turns * 2.0 * math.pi))
                        / (2.0 * math.log(theta)))

            low = max(math.floor(band(float(scaling.get("beta_fast", 32)))), 0)
            high = min(math.ceil(band(float(scaling.get("beta_slow", 1)))),
                       head_dim - 1)
            if low == high:
                high += 0.001
            ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32)
                             - low) / (high - low), 0.0, 1.0)
            inv = inv / factor * ramp + inv * (1.0 - ramp)
        else:
            raise ValueError(f"unsupported rope_scaling type '{rt}' "
                             "(have: linear, llama3, yarn)")
    t = jnp.arange(max_seq, dtype=jnp.float32)
    return jnp.outer(t, inv)  # [max_seq, head_dim//2]


def rope_attention_factor(scaling: Optional[Dict[str, Any]]) -> float:
    """What yarn multiplies cos and sin by (so the scores by its square):
    the config's ``attention_factor``, else ``0.1 ln(factor) + 1``; 1 for
    every other rope."""
    if not scaling or scaling.get("rope_type", scaling.get("type")) != "yarn":
        return 1.0
    given = scaling.get("attention_factor")
    return float(given) if given is not None \
        else 0.1 * math.log(float(scaling["factor"])) + 1.0


def apply_rope(x: jax.Array, freqs: jax.Array, positions: Optional[jax.Array] = None,
               scale: float = 1.0,
               sections: Optional[Tuple[int, ...]] = None) -> jax.Array:
    """x: [B, T, H, d]; freqs: [max_seq, rd//2]; positions: [B, T] (default arange).

    When ``2*freqs.shape[-1] < d`` only the leading rotary dims rotate and the
    tail passes through (gpt-neox/phi partial rotary, ``rotary_pct``).
    ``scale`` multiplies cos and sin (yarn's attention factor). With
    ``positions`` [3, B, T] (time, height, width) and ``sections`` that add up
    to the rd/2 frequency pairs, pair i turns by the position of the axis
    whose section holds it (consecutive sections; three equal axes are the
    plain rope)."""
    B, T = x.shape[0], x.shape[1]
    rd = 2 * freqs.shape[-1]
    tail = None
    if rd < x.shape[-1]:
        x, tail = x[..., :rd], x[..., rd:]
    if positions is None:
        f = freqs[:T][None, :, None, :]  # [1, T, 1, rd/2]
    elif positions.ndim == 3:
        if sections is None or sum(sections) != freqs.shape[-1]:
            raise ValueError(
                f"positions {positions.shape} over {positions.shape[0]} axes "
                f"need sections that add up to the rope's {freqs.shape[-1]} "
                f"frequency pairs (mrope_section), not {sections}")
        edges = [sum(sections[:a]) for a in range(len(sections) + 1)]
        f = jnp.concatenate(
            [freqs[positions[a]][..., lo:hi]
             for a, (lo, hi) in enumerate(zip(edges, edges[1:]))],
            axis=-1)[:, :, None, :]  # [B, T, 1, rd/2]
    else:
        f = freqs[positions][:, :, None, :]  # [B, T, 1, rd/2]
    cos, sin = jnp.cos(f), jnp.sin(f)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    out = out.astype(x.dtype)
    return out if tail is None else jnp.concatenate([out, tail], axis=-1)


class QuantizedWeight:
    """Packed int4/int8 matmul weight usable anywhere a dense [Din, F]
    array sits in the param tree (``ops/quant_matmul`` layout — reference
    ``inference/v2/kernels/cutlass_ops/mixed_gemm``): :func:`linear`
    dispatches it to the fused dequant-matmul Pallas kernel, so the serving
    engines cut decode weight-bandwidth 2x/4x by swapping leaves without
    touching any forward code. A pytree node whose children (packed,
    scales) stack/slice/shard exactly like the dense leaf they replace."""

    __slots__ = ("packed", "scales", "bits", "din")

    def __init__(self, packed: jax.Array, scales: jax.Array, bits: int,
                 din: int):
        self.packed, self.scales = packed, scales
        self.bits, self.din = bits, din

    def tree_flatten(self):
        return (self.packed, self.scales), (self.bits, self.din)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    @property
    def nbytes(self) -> int:
        return self.packed.nbytes + self.scales.nbytes


jax.tree_util.register_pytree_node(
    QuantizedWeight, QuantizedWeight.tree_flatten,
    QuantizedWeight.tree_unflatten)


def split_quant_leaves(layers: Params):
    """Split a stacked layer tree into (dense-only tree, [(group, name,
    stacked QuantizedWeight)]). Layer-scanned callers put only the dense
    tree in scan xs and rebind the quant stacks per iteration as
    :class:`QuantLayerRef` (see its docstring for why)."""
    dense, quant = {}, []
    for grp, sub in layers.items():
        if isinstance(sub, dict):
            dsub = {}
            for name, leaf in sub.items():
                if isinstance(leaf, QuantizedWeight):
                    quant.append((grp, name, leaf))
                else:
                    dsub[name] = leaf
            dense[grp] = dsub
        else:
            dense[grp] = sub
    return dense, quant


class QuantLayerRef(NamedTuple):
    """(stacked :class:`QuantizedWeight`, traced layer index): ``linear``
    runs the fused kernel over the FULL weight stack with the layer picked
    by a scalar-prefetched BlockSpec index map. Layer-scanned decode paths
    must use this instead of putting quant leaves in the scan xs — the
    per-iteration dynamic-slice of an xs leaf cannot fuse into a Pallas
    operand, so XLA materializes a copy of every packed layer every step
    (measured ~13 ms/step on the 464M serving proxy, erasing the
    quantization's bandwidth win)."""

    qw: "QuantizedWeight"
    layer: Any


def linear(x: jax.Array, w) -> jax.Array:
    """``x [..., Din] @ w`` where ``w`` is a dense array, a
    :class:`QuantizedWeight`, or a :class:`QuantLayerRef` (fused
    dequant-matmul kernel; stacked form for layer-scanned callers)."""
    if isinstance(w, QuantLayerRef):
        from deepspeed_tpu.ops.quant_matmul import quantized_matmul

        lead = x.shape[:-1]
        out = quantized_matmul(x.reshape(-1, w.qw.din), w.qw.packed,
                               w.qw.scales, bits=w.qw.bits, layer=w.layer)
        return out.reshape(*lead, out.shape[-1])
    if isinstance(w, QuantizedWeight):
        from deepspeed_tpu.ops.quant_matmul import quantized_matmul

        lead = x.shape[:-1]
        out = quantized_matmul(x.reshape(-1, w.din), w.packed, w.scales,
                               bits=w.bits)
        return out.reshape(*lead, out.shape[-1])
    return x @ w


def qkv_proj(x: jax.Array, w: Params, cfg: TransformerConfig,
             with_gate: bool = False):
    """Shared q/k/v projection (+ optional qwen-style biases) for every
    forward path (train, dense decode, paged decode). Serving engines may
    install a fused ``wqkv`` [D, (H+2K)*hd] leaf (one kernel launch instead
    of three — decode is a chain of small kernels). ``with_gate``: a fourth
    value, the gate a channel [B, T, H, hd] that ``wq``'s second half of each
    head's columns gives under ``cfg.attn_channel_gate`` (None without it);
    a caller that does not ask is one that applies no gate, and is refused."""
    if cfg.attn_channel_gate and not with_gate:
        raise NotImplementedError(
            "attn_channel_gate: wq holds each head's query and its gate; "
            "only the train step's attention block applies the gate")
    B, T = x.shape[0], x.shape[1]
    hd, H, K = cfg.head_dim, cfg.heads_here, cfg.kv_heads_here
    if "wqkv" in w:
        qkv = linear(x, w["wqkv"])
        if "bqkv" in w:
            qkv = qkv + w["bqkv"]
        q, k, v = jnp.split(qkv, [H * hd, (H + K) * hd], axis=-1)
    else:
        q, k, v = linear(x, w["wq"]), linear(x, w["wk"]), linear(x, w["wv"])
        if "bq" in w:
            q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    if cfg.qk_norm == "width":
        # over the whole projection (every head held), before the split
        q = _norm(q, {"scale": w["q_norm"]}, "rmsnorm", cfg.norm_eps)
        k = _norm(k, {"scale": w["k_norm"]}, "rmsnorm", cfg.norm_eps)
    gate = None
    if cfg.attn_channel_gate:
        # a head's columns: its query, then its gate
        q, gate = jnp.split(q.reshape(B, T, H, 2 * hd), 2, axis=-1)
    q, k = q.reshape(B, T, H, hd), k.reshape(B, T, K, hd)
    if cfg.qk_norm == "head":
        # over each head's channels, one scale for all heads; the caller's
        # rope comes after it
        q = _norm(q, {"scale": w["q_norm"]}, "rmsnorm", cfg.norm_eps,
                  cfg.norm_zero_centred)
        k = _norm(k, {"scale": w["k_norm"]}, "rmsnorm", cfg.norm_eps,
                  cfg.norm_zero_centred)
    v = v.reshape(B, T, K, hd)
    return (q, k, v, gate) if with_gate else (q, k, v)


def attn_out_proj(attn: jax.Array, w: Params, cfg: TransformerConfig) -> jax.Array:
    """[B, T, H, hd] attention output → [B, T, D] (+ optional bias)."""
    B, T = attn.shape[0], attn.shape[1]
    o = linear(attn.reshape(B, T, cfg.heads_here * cfg.head_dim), w["wo"])
    return o + w["bo"] if "bo" in w else o


def _attn_takes(attn_fn: Callable, kwarg: str) -> bool:
    """Whether a registered attention impl accepts ``kwarg``: ``window``
    (impls without it — e.g. ring/ulysses SP wrappers — get the masked XLA
    fallback instead) or latent attention's ``q_rope`` (``models/mla.py``)."""
    import inspect

    params = inspect.signature(attn_fn).parameters
    return (kwarg in params or any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()))


def attention_block(x: jax.Array, w: Params, cfg: TransformerConfig,
                    freqs: Optional[jax.Array],
                    attn_fn: Callable,
                    positions: Optional[jax.Array] = None) -> jax.Array:
    hd = cfg.head_dim
    rope_scale = rope_attention_factor(cfg.rope_scaling)
    if cfg.attention_impl == "fpdt" and rope_scale != 1.0:
        raise NotImplementedError("attention_impl='fpdt' applies a plain "
                                  "rope; yarn's attention factor is not "
                                  "carried into its chunk loop")
    if cfg.attention_impl == "fpdt" and positions is None:
        # fused per-chunk-projection tier: q/k/v never materialize full-T
        # (sequence/fpdt.py module docstring), incl. windowed families
        # (mistral/qwen2 — static-chunk-distance pair loop). Falls through
        # to the seam path (full-T projection + chunked fpdt_attention)
        # only when T is too short to chunk.
        from deepspeed_tpu.sequence.fpdt import fpdt_block_attention

        o = fpdt_block_attention(x, w, cfg, freqs)
        if o is not None:
            return constrain(o, P(("dp", "fsdp"), "sp", None))
    q, k, v, gate = qkv_proj(x, w, cfg, with_gate=True)
    q = constrain(q, P(("dp", "fsdp"), "sp", "tp", None))
    k = constrain(k, P(("dp", "fsdp"), "sp", "tp", None))
    if cfg.use_rope and cfg.mrope_section is not None:
        q = apply_rope(q, freqs, positions, rope_scale, cfg.mrope_section)
        k = apply_rope(k, freqs, positions, rope_scale, cfg.mrope_section)
    elif cfg.use_rope:
        q = apply_rope(q, freqs, positions, rope_scale)
        k = apply_rope(k, freqs, positions, rope_scale)
    if cfg.attention_multiplier is not None:
        # the kernels scale the scores by 1/sqrt(d); q carries the rest
        # (Granite's 1/64 at d = 64: a factor of 1/8, exact in bf16)
        q = _times(q, cfg.attention_multiplier * math.sqrt(hd))
    if cfg.has_bd:
        # the ``[noised ; clean]`` row under the block-diffusion mask, a
        # result a half: each projected on its own, the halves joined at the
        # model's width (models/block_diffusion.py:attention says why)
        o = jnp.concatenate([attn_out_proj(half, w, cfg)
                             for half in _bd_attend(q, k, v, cfg)], axis=1)
        return constrain(o, P(("dp", "fsdp"), "sp", None))
    if cfg.sliding_window is not None:
        # windowed families (mistral/qwen2): the flash kernel takes the
        # window natively (block-skipping); impls without window support
        # (ring/ulysses SP wrappers) fall back to the masked XLA path
        if _attn_takes(attn_fn, "window"):
            out = attn_fn(q, k, v, causal=True, window=cfg.sliding_window)
        else:
            out = xla_attention(q, k, v, causal=True,
                                window=cfg.sliding_window)
    elif cfg.attention_impl == "fpdt" and cfg.fpdt_chunk:
        # the seam tier must honor the configured chunk too (the fused tier
        # reads it inside fpdt_block_attention)
        out = attn_fn(q, k, v, causal=True, chunk=cfg.fpdt_chunk)
    else:
        out = attn_fn(q, k, v, causal=True)
    if "wg" in w or gate is not None:
        with jax.named_scope("attn_gate"):
            # on the heads' outputs: one scalar a head and position from
            # ``wg``, or one a channel from ``wq``'s second half
            if gate is None:
                gate = (x @ w["wg"])[..., None]
            out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(x.dtype)
    o = attn_out_proj(out, w, cfg)
    return constrain(o, P(("dp", "fsdp"), "sp", None))


def _bd_attend(q: jax.Array, k: jax.Array, v: jax.Array,
               cfg: TransformerConfig) -> Tuple[jax.Array, jax.Array]:
    """Attention of a block-diffusion row (models/block_diffusion.py), the
    noised half's result and the clean half's: the flash kernels under the
    rounded diagonal where a Mosaic call runs whole (the TPU, no mesh axis to
    partition over; ``attention_impl="flash_pallas"`` forces them,
    interpreted off the TPU), else the same mask as a dense softmax, with a
    warning on the TPU."""
    from deepspeed_tpu import ops
    from deepspeed_tpu.models import block_diffusion as bd

    block = cfg.diffusion_block
    if cfg.attention_impl == "flash_pallas" or (
            cfg.attention_impl != "xla" and ops.mosaic_runs_whole()):
        return bd.attention(q, k, v, block)
    if cfg.attention_impl != "xla" and ops.on_tpu():
        from deepspeed_tpu.utils.logging import logger

        logger.warning(
            f"block diffusion: the flash kernels cannot run per shard on "
            f"this mesh — a dense softmax over q{q.shape}'s "
            f"{q.shape[1]} x {q.shape[1]} scores runs instead")
    with jax.named_scope(bd.CROSS_SCOPE):
        out = bd.dense_attention(q, k, v, block)
    return out[:, :q.shape[1] // 2], out[:, q.shape[1] // 2:]


def _cached_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      valid: jax.Array) -> jax.Array:
    """Attention over a padded KV cache; valid: [B, t, S] bool per query row."""
    k, v = repeat_kv(k, v, q.shape[2])
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(q.shape[-1])
    scores = jnp.where(valid[:, None], scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


def _decode_block(h: jax.Array, wc: Params, cfg: TransformerConfig,
                  freqs: Optional[jax.Array], positions: jax.Array,
                  attend: Callable,
                  moe_fn: Optional[Callable] = None,
                  moe_valid: Optional[jax.Array] = None) -> Any:
    """One decoder block on the decode path. ``attend(q, k, v)`` owns the
    cache read + attention and returns ``(out [B, t, H, hd], *left)``; the
    block returns ``(h, left)``, handing what the layer leaves behind back
    to the layer loop. Mirrors :func:`transformer_block` (parallel residual,
    shared norm, biases, MoE).
    ``moe_valid`` [B, t] marks real (non-padding/idle) lanes: without it the
    batch's no-op rows would compete for expert capacity and skew routing."""
    if cfg.gates_plain_heads or cfg.attn_channel_gate:
        raise NotImplementedError(
            "the decode block multiplies no head's output by a gate "
            "(mla_head_gate, a head, or attn_channel_gate, a channel, on "
            "'window' / 'full' layers); only the train step's block does")
    if cfg.norm_zero_centred or cfg.moe_shared_gate:
        raise NotImplementedError(
            "the decode block's norms multiply by the scale itself and its "
            "shared experts have no gate (norm_zero_centred, "
            "moe_shared_gate); only the train step's block applies them")

    def _mlp(hn):
        if moe_fn is not None:
            try:
                return moe_fn(hn, wc["mlp"], cfg, valid=moe_valid)[0]
            except TypeError:  # custom moe_fn without valid support
                return moe_fn(hn, wc["mlp"], cfg)[0]  # aux unused at decode
        return mlp_block(hn, wc["mlp"], cfg)

    hn1 = _norm(h, wc["ln1"], cfg.norm, cfg.norm_eps)
    q, k, v = qkv_proj(hn1, wc["attn"], cfg)
    if cfg.use_rope:
        rope_scale = rope_attention_factor(cfg.rope_scaling)
        q = apply_rope(q, freqs, positions, rope_scale)
        k = apply_rope(k, freqs, positions, rope_scale)
    attn, *left = attend(q, k, v)
    attn_out = attn_out_proj(attn, wc["attn"], cfg)
    if cfg.parallel_block:
        hn2 = (hn1 if cfg.parallel_shared_norm
               else _norm(h, wc["ln2"], cfg.norm, cfg.norm_eps))
        return h + attn_out + _mlp(hn2), left
    h = h + attn_out
    hn2 = _norm(h, wc["ln2"], cfg.norm, cfg.norm_eps)
    return h + _mlp(hn2), left


def mlp_block(x: jax.Array, w: Params, cfg: TransformerConfig) -> jax.Array:
    if cfg.act_quant_bits:
        # activation quantization (compression_training
        # activation_quantization parity): fake-quantize the block input
        # with straight-through gradients
        from deepspeed_tpu.compression.compress import ste_quantize

        x = ste_quantize(x, bits=cfg.act_quant_bits)
    if cfg.activation == "swiglu":
        if "w_gateup" in w:  # serving-fused gate|up (one kernel launch)
            gu = linear(x, w["w_gateup"])
            g_half, u_half = jnp.split(gu, 2, axis=-1)
            h = jax.nn.silu(g_half) * u_half
        else:
            h = jax.nn.silu(linear(x, w["w_gate"])) * linear(x, w["w_up"])
    else:
        # gelu = tanh-approx (HF gelu_new/gelu_pytorch_tanh, gpt2 family);
        # gelu_exact = erf gelu (HF "gelu": falcon/gpt-neox); relu = opt
        act = {"gelu": partial(jax.nn.gelu, approximate=True),
               "gelu_exact": partial(jax.nn.gelu, approximate=False),
               "relu": jax.nn.relu,
               "relu2": lambda v: jnp.square(jax.nn.relu(v))}[cfg.activation]
        up = linear(x, w["w_up"])
        h = act(up + w["b_up"] if "b_up" in w else up)
    h = constrain(h, P(("dp", "fsdp"), "sp", "tp"))
    out = linear(h, w["w_down"])
    return out + w["b_down"] if "b_down" in w else out


#: the scopes of the fused train step: every operation of the step program
#: lies under one of them (``grad_accum`` and ``optimizer`` are the engine's,
#: ``layers`` is the layer loop's own slicing and stacking; inside it a block's
#: scope, the innermost, is the one that counts). tests/unit/test_step_scopes.py
#: holds the lowered program to this; benchmarks/readers/program.py sums
#: device time by them.
STEP_SCOPES = ("embed", "layers", "attn", "mlp", "moe", "final_norm",
               "lm_head", "loss", "exit_gate", "grad_accum", "optimizer",
               # nested: the layer's kind inside attn (a model whose layers
               # are of more than one) and inside that the head gate of a
               # "window" or "full" layer, the grouped expert layer's parts
               # inside moe (moe/sharded_moe.py)
               "attn_window", "attn_full", "attn_gate",
               "moe_router", "moe_dispatch", "moe_experts", "moe_latent",
               # a state-space layer's parts inside attn, the token mixer's
               # slot (models/mamba.py)
               "ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate",
               # latent attention's parts inside attn/attn_mla
               # (models/mla.py); the shared experts inside moe
               "attn_mla", "mla_proj", "mla_rope", "moe_shared",
               # a delta layer's parts inside attn (models/gated_delta.py)
               "delta_proj", "delta_conv", "delta_scan", "delta_gate",
               # a short-convolution layer's inside attn
               # (models/short_conv.py)
               "sconv_proj", "sconv_conv",
               # a KDA layer's inside attn (models/kda.py)
               "kda_proj", "kda_conv", "kda_scan", "kda_gate",
               # a layer that attends to the keys its indexer picks, inside
               # attn/attn_dsa (models/dsa.py): the indexer's projections and
               # scores, the threshold and the set, the attention over the
               # set, the indexer's loss with its gradient
               "attn_dsa", "dsa_indexer", "dsa_select", "dsa_attend",
               "dsa_loss",
               # block diffusion's attention inside attn
               # (models/block_diffusion.py): the two flash calls under the
               # rounded diagonal (the noised half's with its own block as
               # a second key source)
               "bd_cross")
#: a period of up to this many blocks is the body of one scan over periods;
#: a longer list of kinds is cut into runs of one kind. Layers of one branch
#: each (``one_branch``) are half a block: a period of up to twice as many,
#: and a body that traces one block a kind, whatever the period's length
#: (Nemotron-H's eleven layers, alternating: three)
_MAX_PERIOD = 8
#: leaves that stay float32 in the compute copy of the weights, by name,
#: whatever group holds them: a state-space or a delta layer's, which enter
#: an exponential or a softplus and never a matmul, and the router's
#: selection bias, whose steps of ``moe_bias_rate`` bf16 would round away
_KEEP_FP32 = ("A_log", "dt_bias", "D", "router_bias")
#: the groups of ``params["layers"]`` that hold a kind's own leaves: a layer's
#: kind is its mixer's, and in a model whose FFNs differ by layer
#: (``first_k_dense``) then ":" and its FFN's ("mla:dense", "mla:moe"). Such
#: a group's stack has one row for each layer of its kinds, in layer order;
#: every other group (norms; the FFN where all layers' are alike, "mlp") has
#: a row for every layer. A layer of one branch names what it lacks "none"
#: ("ssm:none", "none:moe") and keeps leaves in the one group it has
_MIXER_GROUP = {"window": "attn", "full": "attn", "ssm": "ssm", "mla": "mla",
                "delta": "delta", "conv": "conv", "kda": "kda", "dsa": "attn"}
_FFN_GROUP = {"dense": "mlp_dense", "moe": "mlp_moe"}
#: what a kind keeps beside its mixer's group: a "dsa" layer the attention
#: leaves every attention kind has and, in a group of its own, its indexer's
_MIXER_EXTRA = {"dsa": "indexer"}
#: in a model whose attention kinds differ in their query heads
#: (``cfg.heads_by_kind``) each keeps a stack of its own in place of "attn"
_SPLIT_GROUP = {"window": "attn_window", "full": "attn_full"}
_KIND_GROUPS = frozenset(_MIXER_GROUP.values()) | frozenset(
    _FFN_GROUP.values()) | frozenset(_MIXER_EXTRA.values()) | frozenset(
    _SPLIT_GROUP.values())
#: the scope an FFN kind's own group is cast and run under
_FFN_SCOPE = {"mlp_dense": "mlp", "mlp_moe": "moe"}


def _groups_of(kind: str, split: bool = False) -> Tuple[str, ...]:
    """The groups that hold the own leaves of a layer of ``kind``; with
    ``split`` a "window" or a "full" layer's mixer in its kind's own."""
    mixer, _, ffn = kind.partition(":")
    mixers = {**_MIXER_GROUP, **_SPLIT_GROUP} if split else _MIXER_GROUP
    return tuple(group[k] for k, group in ((mixer, mixers),
                                           (mixer, _MIXER_EXTRA),
                                           (ffn, _FFN_GROUP))
                 if k and k != "none" and k in group)


def _split(layers: Params) -> bool:
    """Whether the stacks ``layers`` keep a "window" or "full" layer's
    attention leaves by kind (:data:`_SPLIT_GROUP`)."""
    return any(g in layers for g in _SPLIT_GROUP.values())


def _times(x: jax.Array, factor: float) -> jax.Array:
    """``x * factor`` with the factor at full precision (0.22 is no bf16
    number): the product in float32, rounded once to ``x``'s dtype."""
    return (x.astype(jnp.float32) * factor).astype(x.dtype)


def _compute(p: jax.Array, dt, count: bool = False) -> jax.Array:
    """One weight leaf as the forward reads it: a float32 master cast to the
    compute dtype ``dt``, a leaf that is not float32 (the engine's carried
    copy, :meth:`TransformerLM.working_copy`; a block's own stack, cast
    already) handed on as it is. With ``count`` (the forward's own first read
    of the leaf) the registry's ``weight_cast`` says which it was: "in_step"
    or "carried"; a float32 compute dtype casts nothing and counts nothing."""
    if p.dtype != jnp.float32 or dt == jnp.float32:
        if count and p.dtype == dt != jnp.float32:
            lowerings.count("weight_cast", "carried")
        return p
    if count:
        lowerings.count("weight_cast", "in_step")
    return p.astype(dt)


def _cast_layers(w: Params, dt, ffn: Optional[str],
                 count: bool = False) -> Params:
    """fp32 master weights of one block (or the whole stack) to the compute
    dtype, each under the scope of the block that reads it; with ``ffn`` None
    under no scope of their own (the working copy, which the optimizer
    writes under its own)."""
    cast = partial(_compute, dt=dt, count=count)

    out = {}
    for k, v in w.items():
        with (contextlib.nullcontext() if ffn is None else jax.named_scope(
                "attn" if k in ("ln1", "attn", "ssm", "mla", "delta", "conv",
                                "kda", "indexer", "ln1_post", "attn_window",
                                "attn_full")
                else _FFN_SCOPE.get(k, ffn))):
            if any(n in _KEEP_FP32 for n in v):
                out[k] = {n: p if n in _KEEP_FP32
                          else jax.tree_util.tree_map(cast, p)
                          for n, p in v.items()}
            else:
                out[k] = jax.tree_util.tree_map(cast, v)
    return out


def _cast_alone(cast: Any, master: Any) -> Any:
    """The sub-tree of ``cast`` (nested dicts, shaped like ``master``) that
    holds only the leaves a cast made: a leaf that is ``master``'s own object
    was handed on as it was and is left out, a dict left empty with it."""
    if not isinstance(cast, dict):
        return None if cast is master else cast
    out = {k: c for k, v in cast.items()
           if (c := _cast_alone(v, master[k])) is not None}
    return out or None


def transformer_block(x: jax.Array, w: Params, cfg: TransformerConfig,
                      freqs: Optional[jax.Array], attn_fn: Callable,
                      moe_fn: Optional[Callable] = None,
                      positions: Optional[jax.Array] = None,
                      kind: Optional[str] = None,
                      mix_ms: bool = False) -> Any:
    """One decoder block, pre-norm unless the config says otherwise. Returns
    (x, aux_loss). ``positions`` [B, T] overrides RoPE positions (random-LTD
    token subsets). With ``cfg.sandwich_norm`` each branch's output is normed
    again before its residual add: ``a = x + N2(Attn(N1(x)))``, ``y = a +
    N4(FFN(N3(a)))``; with ``cfg.norm_placement == "post"`` that second norm
    is a branch's only one: ``a = x + N2(Mix(x))``, ``y = a + N4(FFN(a))``.
    ``kind`` names the layer's kind in a model that has several: an
    attention layer's operations then lie under ``attn/attn_<kind>``; a
    layer of kind "ssm" mixes its tokens with ``w["ssm"]``
    (models/mamba.py:ssm_block) under ``attn/ssm_*``, one of kind "delta"
    with ``w["delta"]`` (models/gated_delta.py:delta_block) under
    ``attn/delta_*``, one of kind "conv" with ``w["conv"]``
    (models/short_conv.py:conv_block) under ``attn/sconv_*``, one of kind
    "kda" with ``w["kda"]`` (models/kda.py:kda_block) under ``attn/kda_*``,
    one of kind "mla" with ``w["mla"]``
    (models/mla.py:mla_block), one of kind "dsa" with the attention leaves
    ``w["attn"]`` and its indexer's ``w["indexer"]``
    (models/dsa.py:dsa_block, under ``attn/attn_dsa/dsa_*``; its aux then
    also holds the layer's ``indexer_loss`` and ``dsa_probe_sets``); a kind
    that names its FFN
    ("mla:dense") runs ``moe_fn`` only where that is "moe". With ``mix_ms``
    the aux value is a dict that also holds the mean square of the mixer's
    output (``mix_out_ms``), taken before a norm on the branch's output,
    which would pin it."""
    # named scopes land in HLO op metadata — the per-module profiler
    # (profiling/flops_profiler.per_module_profile) and the benchmark's
    # device-time-by-scope reader group cost by them. Every operation of the
    # block lies under one of STEP_SCOPES: the first norm with attention, the
    # residual adds and the second norm with the FFN.
    kind, _, ffn_kind = (kind or "").partition(":")
    if ffn_kind == "dense":
        moe_fn = None
    ffn = "moe" if moe_fn is not None else "mlp"
    wc = _cast_layers(w, jnp.dtype(cfg.dtype), ffn)
    res = cfg.residual_multiplier
    post = cfg.norm_placement == "post"
    with jax.named_scope("attn"), (jax.named_scope("attn_" + kind)
                                   if kind and kind not in ("ssm", "delta",
                                                            "conv", "kda")
                                   else contextlib.nullcontext()):
        hn1 = x if post else _norm(x, wc["ln1"], cfg.norm, cfg.norm_eps,
                                   cfg.norm_zero_centred)
        if kind == "delta":
            from deepspeed_tpu.models.gated_delta import delta_block

            attn_out = constrain(delta_block(hn1, wc["delta"], cfg),
                                 P(("dp", "fsdp"), "sp", None))
        elif kind == "ssm":
            from deepspeed_tpu.models.mamba import ssm_block

            attn_out = constrain(ssm_block(hn1, wc["ssm"], cfg),
                                 P(("dp", "fsdp"), "sp", None))
        elif kind == "conv":
            from deepspeed_tpu.models.short_conv import conv_block

            attn_out = constrain(conv_block(hn1, wc["conv"], cfg),
                                 P(("dp", "fsdp"), "sp", None))
        elif kind == "kda":
            from deepspeed_tpu.models.kda import kda_block

            attn_out = constrain(kda_block(hn1, wc["kda"], cfg),
                                 P(("dp", "fsdp"), "sp", None))
        elif kind == "mla":
            from deepspeed_tpu.models.mla import mla_block

            attn_out = mla_block(hn1, wc["mla"], cfg, freqs, attn_fn)
        elif kind == "dsa":
            from deepspeed_tpu.models.dsa import dsa_block

            attn_out, *of_set = dsa_block(
                hn1, wc["attn"], wc["indexer"], cfg, freqs, positions)
        else:
            attn_out = attention_block(hn1, wc["attn"], cfg, freqs, attn_fn,
                                       positions=positions)
        if mix_ms:
            ms = {"mix_out_ms": jnp.mean(jnp.square(
                attn_out.astype(jnp.float32)))}
            if cfg.has_bd:
                from deepspeed_tpu.models.block_diffusion import early_ms

                ms["bd_early_ms"] = early_ms(attn_out)
        if cfg.sandwich_norm or post:
            attn_out = _norm(attn_out, wc["ln1_post"], cfg.norm, cfg.norm_eps)
        if res != 1.0:
            attn_out = _times(attn_out, res)
    with jax.named_scope(ffn):
        if cfg.parallel_block:
            # falcon/gpt-neox: attn and mlp branch from the SAME residual
            # input
            h = hn1 if cfg.parallel_shared_norm else _norm(
                x, wc["ln2"], cfg.norm, cfg.norm_eps)
        else:
            x = x + attn_out
            h = x if post else _norm(x, wc["ln2"], cfg.norm, cfg.norm_eps,
                                     cfg.norm_zero_centred)
        if moe_fn is not None:
            mlp_out, aux = moe_fn(h, wc["mlp"], cfg)
        else:
            mlp_out = mlp_block(h, wc["mlp"], cfg)
            aux = jnp.zeros((), jnp.float32)
        if cfg.sandwich_norm or post:
            mlp_out = _norm(mlp_out, wc["ln2_post"], cfg.norm, cfg.norm_eps)
        if res != 1.0:
            mlp_out = _times(mlp_out, res)
        if mix_ms:
            # a layer without a router (a dense one among routed ones)
            # reports no balance term
            if not isinstance(aux, dict):
                aux = {} if ffn_kind == "dense" else {"lb": aux}
            aux = {**aux, **ms}
        if kind == "dsa":
            aux = {**(aux if isinstance(aux, dict) else {"lb": aux}),
                   "indexer_loss": of_set[0], "dsa_probe_sets": of_set[1]}
        x = x + mlp_out + attn_out if cfg.parallel_block else x + mlp_out
        return constrain(x, P(("dp", "fsdp"), "sp", None)), aux


def branch_block(x: jax.Array, w: Params, cfg: TransformerConfig,
                 freqs: Optional[jax.Array], attn_fn: Callable,
                 moe_fn: Optional[Callable], kind: str) -> Any:
    """One layer of a ``cfg.one_branch`` model: ``x + f(N(x))``, ``f`` the
    one branch ``kind`` names, a mixer alone ("ssm:none", "full:none",
    "window:none": ``w["ssm"]`` / ``w["attn"]`` under ``attn``, as
    :func:`transformer_block` runs them) or an FFN alone ("none:moe",
    "none:dense": ``w["mlp"]`` under ``moe`` / ``mlp``), the layer's one norm
    ``w["ln1"]`` with it. Returns ``(x, aux)``, ``aux`` a dict: the mean
    square of the branch's output (``mix_out_ms``) and, of a routed layer,
    what its experts report (the balance term under ``lb``, the router's
    counts)."""
    mixer, _, ffn = kind.partition(":")
    scope = "attn" if mixer != "none" else "moe" if ffn == "moe" else "mlp"
    wc = _cast_layers(w, jnp.dtype(cfg.dtype), scope)
    aux: Dict[str, jax.Array] = {}
    with jax.named_scope(scope), (jax.named_scope("attn_" + mixer)
                                  if mixer in ("window", "full")
                                  else contextlib.nullcontext()):
        h = _norm(x, wc["ln1"], cfg.norm, cfg.norm_eps)
        if mixer == "ssm":
            from deepspeed_tpu.models.mamba import ssm_block

            out = ssm_block(h, wc["ssm"], cfg)
        elif mixer != "none":
            out = attention_block(h, wc["attn"], cfg, freqs, attn_fn)
        elif ffn == "moe":
            out, aux = moe_fn(h, wc["mlp"], cfg)
            if not isinstance(aux, dict):
                aux = {"lb": aux}
        else:
            out = mlp_block(h, wc["mlp"], cfg)
        out = constrain(out, P(("dp", "fsdp"), "sp", None))
        aux = {**aux, "mix_out_ms": jnp.mean(jnp.square(
            out.astype(jnp.float32)))}
        return constrain(x + out, P(("dp", "fsdp"), "sp", None)), aux


def _maybe_remat(fn: Callable, policy: str) -> Callable:
    """Map the activation-checkpointing config to ``jax.checkpoint``
    (reference: ``runtime/activation_checkpointing/checkpointing.py:948``);
    policy names resolve through the shared
    ``runtime.activation_checkpointing.resolve_policy``."""
    from deepspeed_tpu.runtime.activation_checkpointing import checkpoint_wrapper

    return checkpoint_wrapper(fn, policy=policy)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def token_cross_entropy(logits: jax.Array, labels: jax.Array,
                        z_loss: float = 0.0) -> jax.Array:
    """Per-token ``logsumexp(logits) - logits[label]`` in f32, plus
    ``z_loss * logsumexp^2`` when ``z_loss > 0``: logits [..., V] in any float
    dtype, labels [...] in [0, V).

    It has its own derivative rule so that the vocabulary-sized arrays are the
    logits as they arrive and their gradient in the same dtype, each read or
    written once a pass: the casts, reductions and the exponential happen in
    f32 inside the fused passes, and only ``logz`` [...] is kept beside the
    logits for the backward. Plain ``jnp``, so GSPMD partitions the
    reductions when the vocabulary is sharded."""
    return _token_ce_fwd(logits, labels, z_loss)[0]


def _token_ce_fwd(logits, labels, z_loss):
    lg = logits.astype(jnp.float32)
    top = jax.lax.stop_gradient(lg.max(axis=-1))
    logz = top + jnp.log(jnp.exp(lg - top[..., None]).sum(axis=-1))
    # the label's logit by a comparison against an iota, which fuses into the
    # pass that reads the logits (a gather or scatter is a pass of its own)
    is_label = jax.nn.one_hot(labels, logits.shape[-1], dtype=bool)
    gold = jnp.where(is_label, lg, 0.0).sum(axis=-1)
    nll = logz - gold
    if z_loss > 0.0:
        nll = nll + z_loss * jnp.square(logz)
    return nll, (logits, labels, logz)


def _token_ce_bwd(z_loss, res, g):
    logits, labels, logz = res
    scale = g * (1.0 + 2.0 * z_loss * logz) if z_loss > 0.0 else g
    probs = jnp.exp(logits.astype(jnp.float32) - logz[..., None])
    is_label = jax.nn.one_hot(labels, logits.shape[-1], dtype=bool)
    d = scale[..., None] * probs - jnp.where(is_label, g[..., None], 0.0)
    # written once, here: left to itself the TPU compiler fuses this formula
    # into both of the head's backward matmuls as their operand, and the
    # exponential then holds the MXU back by more than the pass costs
    # (PERF.md, PR 27: the head's backward took 17.8 ms that way, 13.6 so)
    return jax.lax.optimization_barrier(d.astype(logits.dtype)), None


token_cross_entropy.defvjp(_token_ce_fwd, _token_ce_bwd)


def _lm_targets(batch: Dict[str, jax.Array]):
    """(labels [B, T] >= 0, target mask [B, T]) of a batch: its ``labels``
    (negative = no target) or its ``input_ids`` shifted by one."""
    if "labels" in batch:
        labels = batch["labels"]
        lmask = labels >= 0
    else:
        # next-token LM loss: shift the labels, not the logits, so that every
        # array over the vocabulary keeps T rows (T - 1 is aligned to nothing)
        ids = batch["input_ids"]
        labels = jnp.roll(ids, -1, axis=1)
        mask = (batch["attention_mask"].astype(bool)
                if "attention_mask" in batch else jnp.ones_like(ids, bool))
        lmask = jnp.roll(mask, -1, axis=1).at[:, -1].set(False)
    return jnp.maximum(labels, 0), lmask


def lm_loss(cfg: TransformerConfig, logits: jax.Array,
            batch: Dict[str, jax.Array]) -> jax.Array:
    """Next-token / labeled cross-entropy with masking and optional z-loss;
    under block diffusion the noised half's logits [B, L, V] against the
    clean ids at the same positions (no shift), each position times the
    batch's ``loss_weights``, over the B L tokens."""
    if cfg.has_bd:
        nll = token_cross_entropy(logits, batch["input_ids"], cfg.z_loss)
        w = batch["loss_weights"].astype(jnp.float32)
        return (w * nll).sum() / w.size
    labels, lmask = _lm_targets(batch)
    nll = token_cross_entropy(logits, labels, cfg.z_loss)
    denom = jnp.maximum(lmask.sum(), 1)
    return jnp.where(lmask, nll, 0.0).sum() / denom


def _share_parts(aux: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """What the step record carries of a held share of the experts, from the
    layers' counts (``moe/sharded_moe.py``): ``lb_loss``, the load-balance
    term summed over the layers; by layer ``expert_pairs`` [L, held] (the
    (token, expert) pairs each held expert received), ``pairs_here`` (those
    computed), ``pairs_dropped`` (those the buffer of local pairs had no
    room for) and ``load_max_over_mean`` (the busiest held expert's pairs
    over the mean)."""
    pairs = aux["expert_pairs"]
    total = pairs.sum(axis=-1)
    parts = {"lb_loss": aux["lb"], "expert_pairs": pairs,
             "pairs_here": total - aux["pairs_dropped"],
             "pairs_dropped": aux["pairs_dropped"],
             "load_max_over_mean": pairs.max(axis=-1) * pairs.shape[-1]
             / jnp.maximum(total, 1).astype(jnp.float32)}
    if "router_counts" in aux:
        # a sigmoid router's: the pairs every expert the router scores
        # received, held here or not (what the bias rule reads)
        parts["router_counts"] = aux["router_counts"]
    if "groups_kept" in aux:
        # group-limited selection's: the tokens that kept each group
        parts["groups_kept"] = aux["groups_kept"]
    return parts


def _by_period(tree, lo: int, hi: int, p: int):
    """Layers ``[lo, hi)`` of stacked leaves ``[L, ...]`` as the scan input
    of a loop over periods of ``p`` blocks, ``[(hi - lo) / p, p, ...]``; a
    period of one block keeps the layer axis as it is."""
    def cut(a):
        a = a if (lo, hi) == (0, a.shape[0]) else a[lo:hi]
        return a if p == 1 else a.reshape(((hi - lo) // p, p) + a.shape[1:])
    return jax.tree_util.tree_map(cut, tree)


def _block_of(xs, j: int, p: int):
    """Block ``j``'s slice of one period's scan input (:func:`_by_period`)."""
    return xs if p == 1 else jax.tree_util.tree_map(lambda a: a[j], xs)


def _in_group(kinds, grp: str, split: bool = False) -> int:
    """How many of ``kinds`` keep leaves of their own in the group ``grp``."""
    return sum(grp in _groups_of(k, split) for k in kinds)


def _segment(layers: Params, kinds, lo: int, hi: int, period) -> Params:
    """Layers ``[lo, hi)`` of the stacks as the scan input of a loop over
    periods of the kinds ``period``, group by group (:func:`_by_period`): a
    kind's group is cut to the rows of its own layers among them (a group
    none of the period's kinds reads is left out), every other group to the
    layers themselves."""
    out, split = {}, _split(layers)
    for grp in sorted(layers):
        if grp in _KIND_GROUPS:
            n = _in_group(period, grp, split)
            if n:
                first = _in_group(kinds[:lo], grp, split)
                out[grp] = _by_period(
                    layers[grp], first,
                    first + _in_group(kinds[lo:hi], grp, split), n)
        else:
            out[grp] = _by_period(layers[grp], lo, hi, len(period))
    return out


def _block_weights(xs: Params, j: int, period) -> Params:
    """Block ``j``'s weights out of one period's scan input
    (:func:`_segment`): its kind's own groups (its FFN's under ``mlp`` and
    its kind's attention stack under ``attn``, where the block reads them)
    and every shared group."""
    split = _split(xs)
    mine = _groups_of(period[j], split)
    out = {}
    for grp in sorted(xs):
        if grp not in _KIND_GROUPS:
            out[grp] = _block_of(xs[grp], j, len(period))
        elif grp in mine:
            out["mlp" if grp in _FFN_GROUP.values()
                else "attn" if grp in _SPLIT_GROUP.values() else grp] = \
                _block_of(xs[grp], _in_group(period[:j], grp, split),
                          _in_group(period, grp, split))
    return out


def _stacked(trees: list):
    """A list of like pytrees as one, its leaves stacked on a new axis."""
    return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *trees)


def _by_part(trees: list, join: Callable):
    """A list of aux values as one, ``join`` over each leaf's list. Layers of
    different kinds report different parts (a routed layer its router's
    counts, a dense or a mixer layer none): each part then holds, in order,
    the layers that report it."""
    if (all(isinstance(a, dict) for a in trees)
            and len({tuple(a) for a in trees}) > 1):
        return {k: join([a[k] for a in trees if k in a])
                for k in dict.fromkeys(k for a in trees for k in a)}
    return jax.tree_util.tree_map(lambda *a: join(a), *trees)


def _stack_blocks(ys: list, p: int):
    """One period's per-block outputs as the scan's output."""
    return ys[0] if p == 1 else _by_part(ys, jnp.stack)


def _by_layer(ys, p: int):
    """A scan over periods' stacked outputs ``[periods, blocks, ...]`` back
    to ``[layers, ...]``."""
    return ys if p == 1 else jax.tree_util.tree_map(
        lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:]), ys)


def _join_runs(per_layer: list):
    """The runs' per-layer aux values as one, run after run."""
    return _by_part(per_layer, jnp.concatenate)


def _layer_aux(auxes):
    """What a pass hands on of its layers' aux values ``[L, ...]``: their sum
    (the load-balance term) for a scalar a layer; for a layer that reports
    its router's counts (a dict, a held share of the experts) that sum under
    ``lb`` and the rest by layer."""
    if isinstance(auxes, dict):
        return {**auxes, "lb": jnp.sum(auxes["lb"])} if "lb" in auxes \
            else auxes
    return jnp.sum(auxes)


class TransformerLM:
    """ModelSpec implementation for the decoder-only LM family."""

    def __init__(self, cfg: TransformerConfig, moe_fn: Optional[Callable] = None):
        self.cfg = cfg
        if moe_fn is None and cfg.num_experts > 1:
            # derive the dispatch algebra from cfg.moe_dispatch so every
            # construction path (direct, HF import, presets) honors it
            from deepspeed_tpu.moe import moe_block_for

            moe_fn = moe_block_for(cfg)
        self.moe_fn = moe_fn
        # what a block of each attention kind runs under: (its config, its
        # rope table)
        self._kinds = {}
        for kind in dict.fromkeys(cfg.layer_kinds):
            ck = cfg.kind_cfg(kind)
            self._kinds[kind] = (ck, rope_frequencies(
                ck.rope_dim, ck.max_seq_len, ck.rope_theta, ck.rope_scaling)
                if cfg.use_rope else None)
        # the first layer's: the paths written for layers of one kind read it
        self._freqs = self._kinds[cfg.layer_kinds[0]][1]
        # the jitted block of each kind (:meth:`_jitted_block`)
        self._blocks: Dict[Any, Callable] = {}
        # random-LTD (data_routing/basic_layer.py parity): when set, layers in
        # [start, end) process only `keep` randomly chosen tokens per step;
        # dropped tokens ride the residual stream untouched. The engine owns
        # the keep schedule and rebuilds its jits when the bucket changes.
        self._ltd_keep: Optional[int] = None
        self._ltd_layers: Optional[tuple] = None
        # progressive-layer-drop static-depth mode: when set (< num_layers)
        # the TRAIN forward runs only the first k layers — the engine owns
        # the theta->depth tier schedule and rebuilds its jits on change
        # (one recompile per tier; the reference's actual wall-clock saving)
        self._pld_depth: Optional[int] = None

    def _one_pass_only(self, what: str) -> None:
        """Raise on a path written for one pass over a pre-norm stack of
        attention layers: for a looped model (``cfg.looped``: nothing falls
        back to one pass), for one with a state-space or a delta layer (no
        path but the train step keeps a recurrent state), for one whose block
        has its norm after the branch, norms q and k or holds a share of the
        heads, and for one with the Granite multipliers (only
        ``transformer_block`` and the train forward apply them)."""
        cfg = self.cfg
        if cfg.has_bd:
            raise NotImplementedError(
                f"{what} runs a row of tokens under the causal mask and the "
                f"next-token loss: this model trains by block diffusion "
                f"(diffusion_block={cfg.diffusion_block}: a [noised ; "
                f"clean] row under the block-diffusion mask, decoded a "
                f"block at a time); only the train step runs it")
        if cfg.norm_zero_centred or cfg.moe_shared_gate:
            raise NotImplementedError(
                f"{what} norms by the scale itself and adds the shared "
                f"experts ungated: this model has zero-centred norms "
                f"(norm_zero_centred={cfg.norm_zero_centred}: 1 + scale) or "
                f"a gate on its shared experts (moe_shared_gate="
                f"{cfg.moe_shared_gate}); only the train step's block "
                f"applies them")
        if cfg.attn_differs_by_kind:
            raise NotImplementedError(
                f"{what} reads one stack of attention leaves with one head "
                f"count, one rope width and no gate: this model has "
                f"heads_by_kind={cfg.heads_by_kind} (a stack of leaves and a "
                f"cache shape for each kind), a rope width by kind "
                f"(rope_by_kind={cfg.rope_by_kind}) or a gate on its "
                f"attention layers' heads (mla_head_gate="
                f"{cfg.mla_head_gate}, a head; attn_channel_gate="
                f"{cfg.attn_channel_gate}, a channel); only "
                f"the train step's block applies them")
        if cfg.has_delta:
            raise NotImplementedError(
                f"{what} is written for attention layers: this model has "
                f"gated delta-rule layers (attn_pattern={cfg.attn_pattern}), "
                f"whose recurrent and convolution state it would have to "
                f"keep beside the key-value cache; only the train step runs "
                f"them")
        if cfg.has_kda:
            raise NotImplementedError(
                f"{what} is written for attention layers: this model has "
                f"KDA layers (attn_pattern={cfg.attn_pattern}), whose "
                f"recurrent and convolution state it would have to keep "
                f"beside the latent cache; only the train step runs them")
        if cfg.has_dsa:
            raise NotImplementedError(
                f"{what} is written for attention over every key a query may "
                f"see: this model has 'dsa' layers (attn_pattern="
                f"{cfg.attn_pattern}), whose indexer's keys it would have to "
                f"cache beside the key-value cache and whose top "
                f"{cfg.dsa_topk} keys a query it would have to select at "
                f"every step; only the train step runs them")
        if cfg.mrope_section is not None:
            raise NotImplementedError(
                f"{what} rotates by one position a token: this model's rope "
                f"follows three position axes (mrope_section="
                f"{cfg.mrope_section}); only the train step applies them")
        if cfg.has_conv:
            raise NotImplementedError(
                f"{what} is written for attention layers: this model has "
                f"short-convolution layers (attn_pattern={cfg.attn_pattern}"
                f"), whose last conv_taps - 1 = {cfg.conv_taps - 1} "
                f"positions it would have to keep beside the key-value "
                f"cache; only the train step runs them")
        if (cfg.norm_placement != "pre" or cfg.qk_norm
                or cfg.heads_held is not None):
            raise NotImplementedError(
                f"{what} runs pre-norm blocks whose attention holds every "
                f"head and norms neither q nor k: this model has "
                f"norm_placement={cfg.norm_placement!r}, qk_norm="
                f"{cfg.qk_norm!r}, heads_held={cfg.heads_held}; only the "
                f"train step's block applies them")
        if cfg.has_mla or cfg.has_ffn_kinds:
            raise NotImplementedError(
                f"{what} is written for layers of one FFN kind whose "
                f"attention has one head width for q, k and v: this model "
                f"has latent attention (kv_lora_rank={cfg.kv_lora_rank}: "
                f"keys wider than values through a latent it would have to "
                f"cache) or FFN kinds by layer (first_k_dense="
                f"{cfg.first_k_dense}: a stack for each kind); only the "
                f"train step runs them")
        if cfg.has_ssm:
            raise NotImplementedError(
                f"{what} is written for attention layers: this model has "
                f"state-space layers (attn_pattern={cfg.attn_pattern}), "
                f"whose recurrent and convolution state it would have to "
                f"keep beside the key-value cache; only the train step runs "
                f"them")
        if (cfg.attention_multiplier is not None
                or (cfg.embedding_multiplier, cfg.residual_multiplier,
                    cfg.logits_scaling) != (1.0, 1.0, 1.0)):
            raise NotImplementedError(
                f"{what} does not apply attention_multiplier, "
                f"embedding_multiplier, residual_multiplier or "
                f"logits_scaling; only the train step does")
        if cfg.looped:
            raise NotImplementedError(
                f"{what} runs the layer stack once, pre-norm, with one set of "
                f"logits; this model is looped (num_passes={cfg.num_passes}, "
                f"sandwich_norm={cfg.sandwich_norm}, exit gate "
                f"{'on' if cfg.exit_loss_beta is not None else 'off'}) and "
                f"would need every pass (and, with a cache, num_passes "
                f"key-value caches a layer)")

    def set_random_ltd(self, keep: Optional[int],
                       layers: Optional[tuple] = None) -> None:
        L = self.cfg.num_layers
        if keep is not None:
            self._one_pass_only("random layerwise token dropping")
            start, end = layers if layers is not None else (1, L - 1)
            self._ltd_layers = (max(0, start), end if end > 0 else L - 1)
        self._ltd_keep = keep

    def set_pld_depth(self, k: Optional[int]) -> None:
        if k is not None:
            self._one_pass_only("progressive layer drop")
            if not 1 <= k <= self.cfg.num_layers:
                raise ValueError(f"pld depth {k} out of [1, "
                                 f"{self.cfg.num_layers}]")
        self._pld_depth = k

    @property
    def layer_applications(self) -> int:
        """Block applications one micro-batch's forward holds: the layers
        that run, times the passes over them (the step-program table's
        ``layer_applications``)."""
        return (self._pld_depth or self.cfg.num_layers) * self.cfg.num_passes

    def step_program_facts(self, batch_shape=None) -> Dict[str, Any]:
        """What a step program's row of the step-program table says of its
        model (``observability/steplog.py:StepProgram.facts``; a fact that
        does not apply is left out and the row answers None), and what a
        batch of ``batch_shape`` [rows, T] adds once one is known."""
        cfg = self.cfg
        facts: Dict[str, Any] = {
            # block applications one micro-batch's forward holds
            "layer_applications": self.layer_applications,
            # the period of layer kinds the layer loop scans ("window" /
            # "full" attention, "ssm" a state-space layer, "delta" a gated
            # delta-rule layer, "conv" a short convolution; a stack with a
            # leading run of dense FFNs: its runs' kinds, "conv:dense",
            # "full:moe")
            "layer_pattern": tuple(
                cfg.attn_pattern if cfg.attn_pattern and not cfg.first_k_dense
                else dict.fromkeys(cfg.layer_kinds))}
        chunks = {}
        if cfg.has_ssm:
            chunks["ssm"] = cfg.ssm_chunk
        if cfg.has_delta or cfg.has_kda:
            # (the KDA kind's rule runs in the delta rule's chunks)
            from deepspeed_tpu.ops import delta_rule
            chunks["delta" if cfg.has_delta else "kda"] = delta_rule.CHUNK
        if cfg.has_delta:
            from deepspeed_tpu.models import gated_delta

            # (key heads, value heads) a delta layer holds and, once the
            # rows' length is known, the lowering the rule's picker gives
            # each delta layer, by layer index (``ops/delta_rule.py:
            # rule_lowering``, the function the layer itself asks; the row's
            # ``delta_qk_rows`` counts the rows of q and k the rules traced
            # read: a repeat of q and k to the value heads doubles it)
            sz = gated_delta.sizes(cfg)
            facts["delta_heads"] = (sz["key_heads"], sz["heads"])
            if batch_shape is not None:
                took = delta_rule.rule_lowering(
                    int(batch_shape[1]), sz["heads"], cfg.delta_key_dim,
                    cfg.delta_value_dim, cfg.dtype,
                    key_heads=sz["key_heads"])[0]
                facts["delta_rule_lowering"] = {
                    i: took for i, k in enumerate(cfg.layer_kinds)
                    if k.partition(":")[0] == "delta"}
        for kind, chunk in chunks.items():
            # the chunk length of the kind's scan, and the chunks one step's
            # forward scans: layers x rows x ceil(T / chunk)
            facts[f"{kind}_chunk"] = chunk
            if batch_shape is not None:
                rows, T = batch_shape
                facts[f"{kind}_chunks_per_step"] = (
                    sum(k.partition(":")[0] == kind for k in cfg.layer_kinds)
                    * int(rows) * -(-int(T) // chunk))
        if cfg.heads_held is not None:
            # (count, all) of the heads a mixer holds, where a share of them;
            # by kind where the kinds' counts differ
            facts["heads_held"] = (
                {kind.partition(":")[0]: (ck.heads_here, ck.num_heads)
                 for kind, (ck, _) in self._kinds.items()}
                if cfg.heads_by_kind else (cfg.heads_held, cfg.num_heads))
        plain = [self._kinds[k][0].heads_here for k in cfg.layer_kinds
                 if k.partition(":")[0] in ("window", "full")]
        if plain and cfg.attn_pattern is not None:
            # the query heads the "window" and "full" layers of one
            # micro-batch's forward run, summed over those layers (a model
            # of more than one kind of layer)
            facts["attn_heads_per_step"] = sum(plain) * cfg.num_passes
        if cfg.mrope_section is not None:
            # the position axes the rope's frequency pairs follow
            facts["mrope_axes"] = len(cfg.mrope_section)
        if cfg.has_dsa:
            # the keys a query of a "dsa" layer keeps and, once the rows'
            # length is known, the (query, key) pairs kept over the causal
            # pairs of a step
            facts["dsa_topk"] = cfg.dsa_topk
            if batch_shape is not None:
                from deepspeed_tpu.ops.dsa import selected_share

                facts["dsa_selected_share"] = selected_share(
                    int(batch_shape[1]), cfg.dsa_topk)
        if cfg.has_bd:
            # block diffusion: the block's length, the positions the layers
            # run for each token of a row (the noised copy and the clean
            # one) and, once the rows' length is known, the positions of a
            # row the head reads (the noised half) and what the flash
            # kernels do with a head of a row in the two calls
            # (``block_diffusion.kernel_tiles``: tiles by arm under the two
            # rounded diagonals, the noised call's own tiles under the
            # band's label, their sub-blocks, the pairs worked and kept)
            facts["diffusion_block"] = cfg.diffusion_block
            facts["positions_per_token"] = 2
            if batch_shape is not None:
                from deepspeed_tpu.models import block_diffusion as bd

                facts["head_rows"] = int(batch_shape[1])
                facts["bd_mask_tiles"] = bd.kernel_tiles(
                    int(batch_shape[1]), cfg.diffusion_block)
        if cfg.has_mla:
            # (key width, value width) of a head where they differ (latent
            # attention: the flash kernels take both)
            facts["attn_widths"] = (
                cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.v_head_dim)
        elif plain and cfg.head_dim > 128:
            # and where a plain head is wider than the kernels' usual 128
            # (the row's ``flash_bwd_arm`` says which backward such a head
            # took, fused or split, by width, and ``flash_bwd_segments`` in
            # how many q-segments the fused one worked it)
            facts["attn_widths"] = (cfg.head_dim, cfg.head_dim)
        if cfg.num_experts > 1:
            if cfg.moe_scoring != "softmax":
                # how the router scores where it is not the softmax
                # ("sigmoid": a selection bias the step moves by rule,
                # ``router_counts`` in the step record's parts)
                facts["moe_scoring"] = cfg.moe_scoring
            # (first, count, routed) of the experts a layer holds
            facts["experts_held"] = (
                cfg.moe_first_expert if cfg.moe_experts_held else 0,
                cfg.moe_experts_held or cfg.num_experts, cfg.num_experts)
            if cfg.moe_n_group > 1:
                # (groups, groups a token keeps) of group-limited selection
                facts["moe_groups"] = (cfg.moe_n_group, cfg.moe_topk_group)
            if cfg.moe_dispatch == "grouped":
                from deepspeed_tpu.moe.sharded_moe import resolve_moe_kernel

                # the grouped expert product the program is traced with:
                # "ragged" (every pair over the sorted rows: the Pallas
                # kernels or ``lax.ragged_dot``, the row's ``moe_grouped``
                # count says which) or "padded" (its einsum twin, also what
                # ``resolve_moe_kernel`` falls to where ragged_dot does not
                # lower)
                facts["moe_kernel_resolved"] = resolve_moe_kernel(
                    cfg.moe_kernel)[0]
        return facts

    def rule_leaves(self) -> Tuple[Tuple[str, ...], ...]:
        """The paths of the parameter leaves that no gradient moves: a step
        moves them by a rule of the model's own (:meth:`rule_updates`), and
        the engine keeps them out of the gradient norm and the optimizer's
        update (weight decay with it). Here a sigmoid router's selection
        bias, which only picks experts."""
        cfg = self.cfg
        if cfg.num_experts > 1 and cfg.moe_scoring == "sigmoid":
            return (("layers", "mlp_moe" if cfg.has_ffn_kinds else "mlp",
                     "router_bias"),)
        return ()

    def rule_updates(self, params: Params, parts: Dict[str, jax.Array]
                     ) -> Dict[Tuple[str, ...], jax.Array]:
        """Each of :meth:`rule_leaves` after a step whose loss had the parts
        ``parts`` (``loss_and_parts``): the selection bias of an expert that
        received fewer pairs than the layer's mean rises by
        ``moe_bias_rate``, one that received more falls by it (DeepSeek-V3's
        balancing without an auxiliary loss), from the counts of this
        device's tokens."""
        out = {}
        for path in self.rule_leaves():
            counts = parts["router_counts"].astype(jnp.float32)   # [L, E]
            bias = functools.reduce(operator.getitem, path, params)
            out[path] = bias + self.cfg.moe_bias_rate * jnp.sign(
                counts.mean(axis=-1, keepdims=True) - counts).astype(
                    bias.dtype)
        return out

    def working_copy(self, params: Params) -> Params:
        """The weights' working copy: every leaf of ``params`` that the
        forward casts whole to the compute dtype at one site (the stacks of
        ``params["layers"]`` but their ``_KEEP_FP32`` leaves, an untied
        table, the learned positions, the head), cast by the function that
        site casts with, as nested dicts that hold those leaves alone; ``{}``
        where the compute dtype is the masters' own. A forward handed the
        copy's leaves in the masters' places (the engine's plain fused step
        does that, and has AdamW write the next copy beside the master it has
        just made) casts nothing: it reads the same bf16 values either way.

        Left with the master, and cast in the step as before: a leaf the
        forward reads as float32, and one it casts at more than one site,
        whose cotangents are summed in float32 behind the casts (a tied
        table, gathered and projected with; the head under the exit gate,
        projected with once a pass): summed in the copy's dtype they would
        round once more."""
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        cast = {"layers": _cast_layers(params["layers"], dt, None)}
        embed = [] if cfg.tie_embeddings else ["tokens"]
        embed += ["pos"] if cfg.learned_pos else []
        cast["embed"] = {k: _compute(params["embed"][k], dt) for k in embed}
        if "lm_head" in params and cfg.exit_loss_beta is None:
            cast["lm_head"] = _compute(params["lm_head"], dt)
        return _cast_alone(cast, params) or {}

    def check_topology(self, axis_sizes: Dict[str, int]) -> None:
        """Raise where the mesh has an axis this model cannot be laid over:
        a ``tp`` axis divides an attention layer's heads, which a delta
        or a conv layer's projections (replicated, ``gated_delta.param_specs``,
        ``short_conv.param_specs``) and a held share of the heads
        (``heads_held``, already one chip's part of them) do not follow."""
        cfg = self.cfg
        if cfg.has_dsa and (axis_sizes.get("tp", 1) > 1
                            or axis_sizes.get("sp", 1) > 1):
            raise NotImplementedError(
                f"a tp or sp axis (tp={axis_sizes.get('tp', 1)}, sp="
                f"{axis_sizes.get('sp', 1)}) with 'dsa' layers: the "
                f"indexer's target is the mean over all of a query's heads "
                f"and its set is chosen among all of a row's keys, so the "
                f"layer keeps heads and rows whole on a chip")
        if axis_sizes.get("tp", 1) > 1 and (cfg.heads_by_kind
                                            or cfg.gates_plain_heads
                                            or cfg.attn_channel_gate):
            raise NotImplementedError(
                f"a tp axis of {axis_sizes['tp']} with query heads by kind "
                f"(heads_by_kind={cfg.heads_by_kind}) or a gate on "
                f"attention layers' heads (mla_head_gate, attn_channel_gate"
                f"): the kinds' stacks, the gate's [D, H] columns and wq's "
                f"query-then-gate columns are laid out whole on a chip")
        if axis_sizes.get("tp", 1) > 1 and (cfg.has_delta or cfg.has_conv
                                            or cfg.has_kda
                                            or cfg.heads_held is not None):
            raise NotImplementedError(
                f"a tp axis of {axis_sizes['tp']} with gated delta-rule or "
                f"KDA layers, short-convolution layers or a held share of the "
                f"heads (heads_held={cfg.heads_held}): tensor parallelism "
                f"would divide heads that the delta layer keeps whole and "
                f"that heads_held already divides, and a conv layer's fused "
                f"in_proj holds three projections side by side")

    # ---- init -------------------------------------------------------------
    def init(self, rng: jax.Array) -> Params:
        cfg = self.cfg
        pd = jnp.dtype(cfg.param_dtype)
        D, F, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
        L = cfg.num_layers
        keys = jax.random.split(rng, 12)

        def dense(key, fan_in, shape):
            return (jax.random.normal(key, shape, pd) / math.sqrt(fan_in))

        def layer_stack(key, fan_in, shape, n=L):
            return dense(key, fan_in, (n,) + shape)

        kinds = cfg.layer_kinds
        # a norm's scale as drawn: 1, or 0 where it is its distance from one
        unit_scale = jnp.zeros if cfg.norm_zero_centred else jnp.ones
        norm_w = {"scale": unit_scale((L, D), pd)}
        if cfg.norm == "layernorm":
            norm_w["bias"] = jnp.zeros((L, D), pd)
        # one stack of mixer leaves for each kind of mixer, a row for each
        # layer of that kind (every layer's, where all are attention; a
        # stack for "window" and one for "full" where their heads differ)
        split = bool(cfg.heads_by_kind)

        def attn_stack(ck: TransformerConfig, La: int, ks) -> Params:
            hd, H, K = ck.head_dim, ck.heads_here, ck.kv_heads_here
            attn_w = {
                # (under the gate a channel a head's query, then its gate)
                "wq": layer_stack(ks[0], D, (D, H * hd * (
                    2 if cfg.attn_channel_gate else 1)), La),
                "wk": layer_stack(ks[1], D, (D, K * hd), La),
                "wv": layer_stack(ks[2], D, (D, K * hd), La),
                "wo": layer_stack(ks[3], H * hd, (H * hd, D), La),
            }
            if cfg.qkv_bias:
                attn_w["bq"] = jnp.zeros((La, H * hd), pd)
                attn_w["bk"] = jnp.zeros((La, K * hd), pd)
                attn_w["bv"] = jnp.zeros((La, K * hd), pd)
            if cfg.proj_bias:
                attn_w["bo"] = jnp.zeros((La, D), pd)
            if cfg.qk_norm == "head":
                attn_w["q_norm"] = unit_scale((La, hd), pd)
                attn_w["k_norm"] = unit_scale((La, hd), pd)
            elif cfg.qk_norm:
                attn_w["q_norm"] = unit_scale((La, H * hd), pd)
                attn_w["k_norm"] = unit_scale((La, K * hd), pd)
            if cfg.gates_plain_heads:
                attn_w["wg"] = layer_stack(ks[4], D, (D, H), La)
            return attn_w

        La = _in_group(kinds, "attn", split)
        attn_w = attn_stack(cfg, La, (
            keys[1], keys[2], keys[10], keys[3],
            jax.random.fold_in(rng, 20) if cfg.gates_plain_heads else None))
        # the FFNs: one stack with a row a layer, or (first_k_dense,
        # one_branch) a dense stack and a routed one, a row for each layer
        # of the kind
        Ld = _in_group(kinds, "mlp_dense") if cfg.has_ffn_kinds else L
        Lm = _in_group(kinds, "mlp_moe") if cfg.has_ffn_kinds else L
        mlp = ({"w_gate": layer_stack(keys[4], D, (D, F), Ld),
                "w_up": layer_stack(keys[5], D, (D, F), Ld),
                "w_down": layer_stack(keys[6], F, (F, D), Ld)}
               if cfg.activation == "swiglu" else
               {"w_up": layer_stack(keys[5], D, (D, F), Ld),
                "w_down": layer_stack(keys[6], F, (F, D), Ld)})
        if cfg.proj_bias and cfg.activation != "swiglu":
            mlp["b_up"] = jnp.zeros((Ld, F), pd)
            mlp["b_down"] = jnp.zeros((Ld, D), pd)
        layers: Params = {"ln1": dict(norm_w), "attn": attn_w}
        if cfg.has_ffn_kinds and Ld:
            layers["mlp_dense"] = mlp
        if cfg.num_experts > 1:
            # the experts held here at their own width, reading the latent
            # where there is one; the router scores all of them
            E, Eh = cfg.num_experts, cfg.moe_experts_held or cfg.num_experts
            F, Z = cfg.moe_intermediate_size or F, cfg.moe_latent_size or D
            mlp = ({"w_gate": layer_stack(keys[4], Z, (Eh, Z, F), Lm),
                    "w_up": layer_stack(keys[5], Z, (Eh, Z, F), Lm),
                    "w_down": layer_stack(keys[6], F, (Eh, F, Z), Lm)}
                   if cfg.activation == "swiglu" else
                   {"w_up": layer_stack(keys[5], Z, (Eh, Z, F), Lm),
                    "w_down": layer_stack(keys[6], F, (Eh, F, Z), Lm)})
            mlp["router"] = layer_stack(keys[7], D, (D, E), Lm)
            if cfg.moe_scoring == "sigmoid" or cfg.moe_shared_experts:
                more = jax.random.split(jax.random.fold_in(rng, 13), 4)
            if cfg.moe_scoring == "sigmoid":
                mlp["router_bias"] = jax.random.uniform(
                    more[0], (Lm, E), pd, -1.0, 1.0) * cfg.moe_bias_init
            if cfg.moe_shared_experts:
                Fs = cfg.moe_shared_experts * F
                mlp["shared"] = {
                    "w_gate": layer_stack(more[1], D, (D, Fs), Lm),
                    "w_up": layer_stack(more[2], D, (D, Fs), Lm),
                    "w_down": layer_stack(more[3], Fs, (Fs, D), Lm)}
                if cfg.activation != "swiglu":
                    del mlp["shared"]["w_gate"]
                if cfg.moe_shared_gate:
                    mlp["shared"]["w_sg"] = layer_stack(
                        jax.random.fold_in(rng, 23), D, (D, 1), Lm)
            if cfg.moe_latent_size:
                lat = jax.random.split(jax.random.fold_in(rng, 16), 2)
                mlp["latent_down"] = layer_stack(lat[0], D, (D, Z), Lm)
                mlp["latent_up"] = layer_stack(lat[1], Z, (Z, D), Lm)
        if cfg.num_experts > 1 or not cfg.has_ffn_kinds:
            layers["mlp_moe" if cfg.has_ffn_kinds else "mlp"] = mlp
        if cfg.has_ssm:
            from deepspeed_tpu.models import mamba

            layers["ssm"] = mamba.init(jax.random.fold_in(rng, 12), cfg,
                                       _in_group(kinds, "ssm"), pd)
        if cfg.has_delta:
            from deepspeed_tpu.models import gated_delta

            layers["delta"] = gated_delta.init(
                jax.random.fold_in(rng, 15), cfg,
                _in_group(kinds, "delta"), pd)
        if cfg.has_conv:
            from deepspeed_tpu.models import short_conv

            layers["conv"] = short_conv.init(
                jax.random.fold_in(rng, 17), cfg, _in_group(kinds, "conv"),
                pd)
        if cfg.has_mla:
            from deepspeed_tpu.models import mla

            layers["mla"] = mla.init(jax.random.fold_in(rng, 14), cfg,
                                     _in_group(kinds, "mla"), pd)
        if cfg.has_kda:
            from deepspeed_tpu.models import kda

            layers["kda"] = kda.init(jax.random.fold_in(rng, 18), cfg,
                                     _in_group(kinds, "kda"), pd)
        if cfg.has_dsa:
            from deepspeed_tpu.models import dsa

            layers["indexer"] = dsa.init(jax.random.fold_in(rng, 19), cfg,
                                         _in_group(kinds, "indexer"), pd)
        if not La:
            del layers["attn"]
        for n, (kind, grp) in enumerate(_SPLIT_GROUP.items() if split
                                        else ()):
            layers[grp] = attn_stack(
                cfg.kind_cfg(kind), _in_group(kinds, grp, split),
                jax.random.split(jax.random.fold_in(rng, 21 + n), 5))
        if not (cfg.parallel_shared_norm or cfg.one_branch):
            layers["ln2"] = jax.tree_util.tree_map(jnp.copy, norm_w)
        if cfg.sandwich_norm or cfg.norm_placement == "post":
            layers["ln1_post"] = jax.tree_util.tree_map(jnp.copy, norm_w)
            layers["ln2_post"] = jax.tree_util.tree_map(jnp.copy, norm_w)
        if cfg.norm_placement == "post":
            del layers["ln1"], layers["ln2"]
        params: Params = {
            "embed": {"tokens": dense(keys[0], 1, (V, D))
                      * cfg.embed_init_std},
            "layers": layers,
            "final_norm": {"scale": unit_scale((D,), pd)},
        }
        if cfg.norm == "layernorm":
            params["final_norm"]["bias"] = jnp.zeros((D,), pd)
        if cfg.learned_pos:
            params["embed"]["pos"] = dense(keys[8], 1, (cfg.max_seq_len, D)) * 0.01
        if not cfg.tie_embeddings:
            params["lm_head"] = dense(keys[9], D, (D, V))
        if cfg.exit_loss_beta is not None:
            params["exit_gate"] = {"w": dense(keys[11], D, (D,)),
                                   "b": jnp.zeros((), pd)}
        return params

    # ---- forward ----------------------------------------------------------
    def _head(self, params: Params):
        """[D, V] output projection (tied or separate). Serving engines may
        install a quantized copy under ``lm_head_q`` (the head matmul reads
        the whole [D, V] table every decode step; the embedding GATHER keeps
        the bf16 table)."""
        if "lm_head_q" in params:
            return params["lm_head_q"]
        return (params["embed"]["tokens"].T if self.cfg.tie_embeddings
                else params["lm_head"])

    def _head_proj(self, params: Params, x: jax.Array) -> jax.Array:
        """``x [..., D] @ head`` for every logits site (dense or quantized)."""
        head = self._head(params)
        if isinstance(head, QuantizedWeight):
            return linear(x, head)
        return x @ _compute(head, jnp.dtype(self.cfg.dtype), count=True)

    def _project(self, params: Params, hidden: jax.Array) -> jax.Array:
        """hidden [B, T, D] → logits [B, T, V] with the canonical sharding."""
        with jax.named_scope("lm_head"):
            logits = self._head_proj(params, hidden)
            if self.cfg.logits_scaling != 1.0:
                logits = _times(logits, 1.0 / self.cfg.logits_scaling)
            return constrain(logits, P(("dp", "fsdp"), "sp", "tp"))

    def logits(self, params: Params, input_ids: jax.Array,
               positions: Optional[jax.Array] = None,
               ltd_seed: Optional[jax.Array] = None,
               pld_theta: Optional[jax.Array] = None) -> jax.Array:
        return self._project(params, self.hidden_states(
            params, input_ids, positions=positions, ltd_seed=ltd_seed,
            pld_theta=pld_theta))

    def _layer_plan(self):
        """How the layer loop runs the stack: ``[(lo, hi, period)]``, layers
        ``[lo, hi)`` as a scan over periods whose body is the period's
        blocks, ``period`` the kinds of one. A stack whose kinds repeat with
        a period of up to ``_MAX_PERIOD`` blocks is one such scan (one kind:
        a period of one block; three window layers and a full one: four
        blocks traced whatever the depth); any other list (HF qwen2's leading
        run of full layers before the windowed ones) is cut into runs of one
        kind. Layers of one branch each (``cfg.one_branch``) are one scan
        over periods of up to ``2 * _MAX_PERIOD`` of them whose body traces
        one block a kind (:meth:`_run_periods`): Nemotron-H's eleven
        alternating layers are three bodies. Each block runs under its
        kind's own static config
        (``self._kinds``), so a window layer keeps the tile-skipping kernels
        and a full layer pays no window mask. A run reads its kind's own
        stack of mixer leaves (:func:`_segment`): nine state-space layers to
        one attention layer, a period of ten, are at 40 layers nine runs
        (nine block bodies traced, where a period body would trace ten); so
        is a stack whose FFNs differ by layer (a dense run, then a routed
        one: two bodies)."""
        kinds = self.cfg.layer_kinds
        L = len(kinds)
        period = self.cfg.attn_pattern or kinds[:1]
        if self.cfg.one_branch and len(period) <= 2 * _MAX_PERIOD:
            # (the kinds as ``layer_kinds`` spells them: what a layer lacks)
            return [(0, L, kinds[:len(period)])]
        if len(period) <= _MAX_PERIOD and not self.cfg.has_ffn_kinds:
            return [(0, L, period)]
        cuts = [0] + [i for i in range(1, L) if kinds[i] != kinds[i - 1]] + [L]
        return [(lo, hi, (kinds[lo],)) for lo, hi in zip(cuts, cuts[1:])]

    def hidden_states(self, params: Params, input_ids: jax.Array,
                      positions: Optional[jax.Array] = None,
                      ltd_seed: Optional[jax.Array] = None,
                      pld_theta: Optional[jax.Array] = None) -> jax.Array:
        """Final-norm hidden states [B, T, D] (everything before the LM
        head) — the input of the tiled logits loss; of a looped model, the
        last pass's."""
        if self.cfg.has_bd:
            self._one_pass_only("a forward over a plain row (hidden_states, "
                                "logits)")
        return self._hidden_passes(params, input_ids, positions, ltd_seed,
                                   pld_theta)[0][-1]

    def _hidden_passes(self, params: Params, input_ids: jax.Array,
                       positions: Optional[jax.Array] = None,
                       ltd_seed: Optional[jax.Array] = None,
                       pld_theta: Optional[jax.Array] = None,
                       rope_positions: Optional[jax.Array] = None,
                       head_rows: Optional[int] = None):
        """``([h_1 .. h_R], aux)``: the final-norm hidden states after each
        of the ``cfg.num_passes`` passes of the layer stack, and the MoE aux
        loss summed over layers and passes (with a held share of the experts
        a dict: that sum under ``lb`` and the router's counts by layer,
        :func:`_layer_aux`). Every pass reads the same stacked
        weights, cast once; the final norm closes a pass and its output is
        what the next pass reads, so one backward sums each weight's gradient
        over its uses. ``head_rows``: the final norm and what follows read
        the first that many positions of a row only (block diffusion's
        noised half)."""
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        if pld_theta is not None:
            self._one_pass_only("progressive layer drop")
        with jax.named_scope("embed"):
            x = _compute(params["embed"]["tokens"], dt, count=True)[input_ids]
            if cfg.embedding_multiplier != 1.0:
                x = _times(x, cfg.embedding_multiplier)
            if cfg.learned_pos:
                T = input_ids.shape[1]
                pos_emb = (params["embed"]["pos"][:T] if positions is None
                           else params["embed"]["pos"][positions])
                x = x + _compute(pos_emb, dt, count=True)
            x = constrain(x, P(("dp", "fsdp"), "sp", None))
        attn_fn = get_attention_impl(cfg.attention_impl)
        with jax.named_scope("layers"):
            # Cast the whole layer stack to compute dtype ONCE, outside the
            # layer scan and the pass loop: the per-layer cast inside
            # transformer_block then no-ops. Done per layer (and re-done under
            # remat) this was a full extra pass over the fp32 master weights
            # every micro-batch.
            layers = _cast_layers(params["layers"], dt,
                                  "moe" if self.moe_fn is not None else "mlp",
                                  count=True)
        hs, aux = [], None
        for _ in range(cfg.num_passes):
            with jax.named_scope("layers"):
                x, a = self._run_layers(layers, x, input_ids, attn_fn,
                                        ltd_seed, pld_theta, rope_positions)
            with jax.named_scope("final_norm"):
                if head_rows is not None:
                    x = x[:, :head_rows]
                x = _norm(x, params["final_norm"], cfg.norm, cfg.norm_eps,
                          cfg.norm_zero_centred)
                x = constrain(x, P(("dp", "fsdp"), "sp", None))
            hs.append(x)
            aux = a if aux is None else jax.tree_util.tree_map(jnp.add, aux, a)
        return hs, aux

    def _run_layers(self, layers: Params, x: jax.Array, input_ids: jax.Array,
                    attn_fn: Callable, ltd_seed, pld_theta,
                    rope_positions: Optional[jax.Array] = None):
        """One pass of the (already cast) layer stack on ``x``: ``(x, the
        layers' MoE aux values as :func:`_layer_aux` hands them on)``.
        ``rope_positions`` [3, B, T]: the batch's positions over the rope's
        three axes (``cfg.mrope_section``)."""
        cfg = self.cfg
        patterned = cfg.patterned
        T = input_ids.shape[1]
        ltd_keep = self._ltd_keep
        ltd = ltd_keep is not None and ltd_keep < T
        kpld = self._pld_depth
        if (kpld is not None and kpld < cfg.num_layers and not patterned
                and not ltd):
            # static-depth PLD: run only the first k layers (real compute
            # saving — the gated-residual mode below computes every layer)
            layers = jax.tree_util.tree_map(lambda p: p[:kpld], layers)
            n_layers_run = kpld
        else:
            n_layers_run = cfg.num_layers
        if patterned:
            if ltd or pld_theta is not None:
                raise NotImplementedError(
                    "layers of more than one attention kind (attn_pattern) "
                    "cannot combine with random-LTD or progressive layer "
                    "drop")
            return self._run_periods(self._layer_plan(), layers, x, attn_fn,
                                     rope_positions)
        cfg, freqs = self._kinds[cfg.layer_kinds[0]]
        if ltd or pld_theta is not None:
            # shared routing key for LTD/PLD: step seed (engine-provided,
            # fresh per step/epoch) folded with batch content (fresh per
            # microbatch)
            seed = jnp.uint32(0) if ltd_seed is None else ltd_seed
            key0 = jax.random.fold_in(jax.random.PRNGKey(seed),
                                      jnp.sum(input_ids).astype(jnp.uint32))
        if ltd:
            # random layerwise token dropping: per-LTD-layer random sorted
            # token subset; the subset runs the block (causal order and RoPE
            # positions preserved), dropped tokens skip via the residual
            start_l, end_l = self._ltd_layers

            def ltd_block(h, layer_w, li):
                key = jax.random.fold_in(key0, li)
                pos = jnp.sort(jax.random.permutation(key, T)[:ltd_keep])
                h_sub = h[:, pos]
                posb = jnp.broadcast_to(pos[None], (h.shape[0], ltd_keep))
                y, aux = transformer_block(h_sub, layer_w, cfg, freqs, attn_fn,
                                           self.moe_fn, positions=posb)
                return h.at[:, pos].set(y), aux

            def body(carry, xs):
                layer_w, li = xs
                is_ltd = jnp.logical_and(li >= start_l, li < end_l)
                return jax.lax.cond(
                    is_ltd,
                    lambda c, w, i: ltd_block(c, w, i),
                    lambda c, w, i: transformer_block(c, w, cfg, freqs,
                                                      attn_fn, self.moe_fn),
                    carry, layer_w, li)

            xs = (layers, jnp.arange(cfg.num_layers))
        elif pld_theta is not None:
            # progressive layer drop (runtime/progressive_layer_drop.py):
            # deeper layers are dropped with growing probability. Implemented
            # as a gated residual (compute-and-mask) rather than lax.cond:
            # differentiating a data-dependent cond around the Pallas flash
            # kernel is unsupported, so PLD here keeps the stochastic-depth
            # REGULARIZATION but not the reference's wall-clock saving.
            L = cfg.num_layers

            def body(carry, xs):
                layer_w, li = xs
                keep_p = 1.0 - ((li.astype(jnp.float32) + 1.0) / L) \
                    * (1.0 - pld_theta)
                keep = jax.random.bernoulli(jax.random.fold_in(key0, li),
                                            keep_p)
                y, aux = transformer_block(carry, layer_w, cfg, freqs,
                                           attn_fn, self.moe_fn)
                x_new = jnp.where(keep, y, carry)
                return x_new, jax.tree_util.tree_map(
                    lambda a: jnp.where(keep, a, jnp.zeros_like(a)), aux)

            xs = (layers, jnp.arange(cfg.num_layers))
        else:
            def body(carry, xs):
                y, aux = transformer_block(carry, xs, cfg, freqs, attn_fn,
                                           self.moe_fn,
                                           positions=rope_positions)
                return y, aux

            xs = layers

        body = _maybe_remat(body, cfg.remat_policy)
        wrapped = ltd or pld_theta is not None
        if cfg.scan_layers:
            x, auxes = jax.lax.scan(body, x, xs)
        else:
            auxes = []
            for i in range(n_layers_run):
                xi = jax.tree_util.tree_map(lambda p: p[i], layers)
                x, aux = body(x, (xi, jnp.int32(i)) if wrapped else xi)
                auxes.append(aux)
            auxes = _stacked(auxes)
        return x, _layer_aux(auxes)

    def _run_periods(self, plan, layers: Params, x: jax.Array,
                     attn_fn: Callable,
                     rope_positions: Optional[jax.Array] = None):
        """The layer loop of a stack whose layers are of more than one
        attention kind: each entry of ``plan`` (:meth:`_layer_plan`) is a scan
        over its periods, the body the period's blocks, each under its kind's
        config and rope table and recomputed on its own."""
        cfg = self.cfg
        per_layer = []
        for lo, hi, period in plan:
            # (layers of one branch each: one jitted block a kind, so that a
            # kind's later layers in the period reuse the first one's trace,
            # forward and backward)
            blocks = [self._jitted_block(kind, attn_fn) if cfg.one_branch
                      else _maybe_remat(
                          partial(self._kind_block, kind, attn_fn),
                          cfg.remat_policy)
                      for kind in period]
            if rope_positions is not None:
                blocks = [partial(blk, positions=rope_positions)
                          for blk in blocks]
            p = len(period)
            seg = _segment(layers, cfg.layer_kinds, lo, hi, period)

            def body(carry, xs, _blocks=blocks, period=period, p=p):
                auxes = []
                for j, blk in enumerate(_blocks):
                    carry, aux = blk(carry, _block_weights(xs, j, period))
                    auxes.append(aux)
                return carry, _stack_blocks(auxes, p)

            if cfg.scan_layers:
                x, auxes = jax.lax.scan(body, x, seg)
            else:
                auxes = []
                for i in range((hi - lo) // p):
                    x, aux = body(x, jax.tree_util.tree_map(
                        lambda a: a[i], seg))
                    auxes.append(aux)
                auxes = _stacked(auxes)
            per_layer.append(_by_layer(auxes, p))
        return x, _layer_aux(_join_runs(per_layer))

    def _jitted_block(self, kind: str, attn_fn: Callable) -> Callable:
        """:meth:`_kind_block` of ``kind`` under the config's recomputation
        policy as one jitted function, made once a model: every layer of the
        kind calls the same one."""
        key = (kind, attn_fn)
        if key not in self._blocks:
            self._blocks[key] = jax.jit(_maybe_remat(
                partial(self._kind_block, kind, attn_fn),
                self.cfg.remat_policy))
        return self._blocks[key]

    def _kind_block(self, kind: str, attn_fn: Callable, x: jax.Array,
                    w: Params, positions: Optional[jax.Array] = None):
        ck, freqs = self._kinds[kind]
        if ck.one_branch:
            return branch_block(x, w, ck, freqs, attn_fn, self.moe_fn, kind)
        return transformer_block(
            x, w, ck, freqs, attn_fn, self.moe_fn, positions=positions,
            kind=kind, mix_ms=self.cfg.reports_mixer_outputs)

    def _tiled_loss(self, params: Params, batch: Dict[str, jax.Array],
                    hidden: jax.Array) -> jax.Array:
        """CE over T/loss_tiling chunks — [B, T, V] is never materialized.

        The next-token shift keeps length T by appending one padding label
        instead of slicing hidden to T-1: T-1 is odd for every even T, which
        would silently defeat the power-of-two chunking."""
        from deepspeed_tpu.sequence.tiling import tiled_logits_loss

        cfg = self.cfg
        ids = batch["input_ids"]
        if "labels" in batch:
            labels, h = batch["labels"], hidden
        else:  # next-token LM loss
            pad = jnp.full((ids.shape[0], 1), -100, ids.dtype)
            labels = jnp.concatenate([ids[:, 1:], pad], axis=1)
            if "attention_mask" in batch:
                mask = batch["attention_mask"].astype(bool)
                labels = labels.at[:, :-1].set(
                    jnp.where(mask[:, 1:], labels[:, :-1], -100))
            h = hidden
        head = _compute(self._head(params), jnp.dtype(cfg.dtype), count=True)
        return tiled_logits_loss(h, head, labels,
                                 num_shards=cfg.loss_tiling,
                                 z_loss=cfg.z_loss)

    def loss_fn(self, params: Params, batch: Dict[str, jax.Array],
                rng: Optional[jax.Array] = None) -> jax.Array:
        return self.loss_and_parts(params, batch)[0]

    def loss_and_parts(self, params: Params, batch: Dict[str, jax.Array]):
        """``(loss, parts)``. ``parts`` holds what a step record carries
        beside the loss, as device values: nothing for a model with one set
        of logits; with the exit gate ``pass_loss`` [R] (each pass's mean
        cross-entropy), ``exit_prob`` [R] (the mean exit distribution) and
        ``exit_entropy`` (its mean entropy), over the target positions."""
        cfg = self.cfg
        if cfg.loss_tiling > 1:
            self._one_pass_only("the tiled logits loss (loss_tiling > 1)")
        seed = batch.get("ltd_seed")
        pld = batch.get("pld_theta")
        if cfg.has_bd:
            hs, aux = self._bd_hidden(params, batch)
        else:
            hs, aux = self._hidden_passes(
                params, batch["input_ids"],
                ltd_seed=None if seed is None else seed[0],
                pld_theta=None if pld is None else pld[0],
                rope_positions=self._rope_positions(batch))
        if cfg.exit_loss_beta is not None:
            loss, parts = self._expected_exit_loss(params, batch, hs)
        else:
            # the tiled loss holds the head matmul too, so it has no lm_head
            # scope
            logits = (None if cfg.loss_tiling > 1
                      else self._project(params, hs[-1]))
            with jax.named_scope("loss"):
                loss = (self._tiled_loss(params, batch, hs[-1])
                        if logits is None else lm_loss(cfg, logits, batch))
            parts = {}
        if cfg.has_bd:
            with jax.named_scope("loss"):
                # the positions the noise hid, and their weights' sum
                w = batch["loss_weights"]
                parts = {**parts, "bd_masked_targets": jnp.sum(w > 0),
                         "bd_weight_sum": jnp.sum(w.astype(jnp.float32)),
                         # by layer, the mixer output's mean square over
                         # the first positions of each half
                         "bd_early_ms": aux["bd_early_ms"]}
        if cfg.reports_mixer_outputs:
            # by layer, the mean square of the mixer's output
            parts = {**parts, "mix_out_ms": aux["mix_out_ms"]}
        if cfg.has_dsa:
            with jax.named_scope("loss"):
                # the layers' indexer losses, summed: they train the
                # indexers alone (models/dsa.py)
                parts = {**parts,
                         "indexer_loss": jnp.sum(aux["indexer_loss"]),
                         # by layer, the sets of a few queries of each row
                         # (ops/dsa.py:probe_positions), packed
                         "dsa_probe_sets": aux["dsa_probe_sets"]}
                loss = loss + cfg.indexer_loss_coef * parts["indexer_loss"]
        if cfg.num_experts > 1:
            with jax.named_scope("loss"):
                if isinstance(aux, dict):
                    # a held share of the experts: the load-balance term and
                    # the router's counts go into the step record
                    if "expert_pairs" in aux:
                        parts = {**parts, **_share_parts(aux)}
                    if cfg.moe_bias_rate and "router_counts" in parts:
                        # the biases the step's rule moves: an expert's
                        # whose count is not the layer's mean
                        c = parts["router_counts"]
                        parts["bias_moved"] = jnp.sum(
                            c * c.shape[-1] != c.sum(-1, keepdims=True))
                    aux = aux["lb"]
                loss = loss + cfg.moe_aux_loss_coef * aux
        return loss, parts

    def _bd_hidden(self, params: Params, batch: Dict[str, jax.Array]):
        """:meth:`_hidden_passes` of a block-diffusion batch: the layers run
        the ``[noised ; clean]`` row of ``2L`` positions at positions
        ``0..L-1`` twice (models/block_diffusion.py), the final norm and the
        head read the noised half."""
        from deepspeed_tpu.models import block_diffusion as bd

        cfg = self.cfg
        ids = batch["input_ids"]
        rows, L = ids.shape
        missing = [k for k in ("noised_ids", "loss_weights")
                   if k not in batch]
        if missing or L % cfg.diffusion_block or any(
                k in batch for k in ("segment_ids", "attention_mask",
                                     "labels", "ltd_seed", "pld_theta")):
            raise NotImplementedError(
                f"block diffusion (diffusion_block={cfg.diffusion_block}) "
                f"takes a batch of input_ids, noised_ids and loss_weights "
                f"[rows, L], L a whole number of blocks "
                f"(runtime/data_pipeline/block_noise.py): this one has "
                f"{sorted(batch)} at L={L}; segment_ids, attention_mask and "
                f"labels (document boundaries and padding under the rounded "
                f"diagonal), random-LTD and progressive layer drop are not "
                f"implemented for it")
        with jax.named_scope("embed"):
            row = jnp.concatenate(
                [batch["noised_ids"].astype(ids.dtype), ids], axis=1)
            positions = bd.row_positions(rows, L)
        return self._hidden_passes(params, row, rope_positions=positions,
                                   head_rows=L)

    def bd_logits(self, params: Params,
                  batch: Dict[str, jax.Array]) -> jax.Array:
        """The noised half's logits [rows, L, V] of a block-diffusion
        batch: position ``i`` of block ``b`` predicts ``x0_i`` from the
        noised block ``b`` and the clean blocks before it."""
        return self._project(params, self._bd_hidden(params, batch)[0][-1])

    def _rope_positions(self, batch: Dict[str, jax.Array]
                        ) -> Optional[jax.Array]:
        """The batch's ``position_ids`` [3, B, T] where the model's rope
        follows three position axes (``cfg.mrope_section``) and the batch
        has them; None elsewhere (the token's index on every axis)."""
        pos = batch.get("position_ids")
        if self.cfg.mrope_section is None or pos is None:
            return None
        ids = batch["input_ids"]
        if pos.shape != (3,) + ids.shape:
            raise ValueError(
                f"position_ids {pos.shape}: a rope over three position axes "
                f"takes [3, B, T] beside input_ids {ids.shape}")
        return pos

    def _expected_exit_loss(self, params: Params,
                            batch: Dict[str, jax.Array], hs):
        """The looped family's stage-I objective over the R pass outputs
        ``hs``: logits ``z_t = W_head h_t`` through the one head, a per-token
        gate ``lambda_t = sigmoid(w_g . h_t + b_g)``, the exit distribution
        ``p_t = lambda_t prod_{j<t}(1 - lambda_j)`` (``p_R`` takes what is
        left, so ``lambda_R`` is never read), and the mean over target
        positions of ``sum_t p_t CE(z_t) - beta H(p)``. The distribution is
        carried as its logarithm (``log_sigmoid``), in f32."""
        cfg = self.cfg
        with jax.named_scope("loss"):
            labels, lmask = _lm_targets(batch)
        nll = []
        for h in hs:
            logits = self._project(params, h)
            with jax.named_scope("loss"):
                nll.append(token_cross_entropy(logits, labels, cfg.z_loss))
        with jax.named_scope("loss"), jax.named_scope("exit_gate"):
            w = params["exit_gate"]["w"].astype(jnp.float32)
            b = params["exit_gate"]["b"].astype(jnp.float32)
            log_p, log_stay = [], 0.0
            for h in hs[:-1]:
                # a multiply and a sum, not a dot: f32 on the vector unit
                s = (h.astype(jnp.float32) * w).sum(axis=-1) + b
                log_p.append(log_stay + jax.nn.log_sigmoid(s))
                log_stay = log_stay + jax.nn.log_sigmoid(-s)
            log_p = jnp.stack(log_p + [log_stay])               # [R, B, T]
            p, nll = jnp.exp(log_p), jnp.stack(nll)
            entropy = -(p * log_p).sum(axis=0)
            denom = jnp.maximum(lmask.sum(), 1)

            def mean(a):
                return jnp.where(lmask, a, 0.0).sum(axis=(-2, -1)) / denom

            loss = mean((p * nll).sum(axis=0) - cfg.exit_loss_beta * entropy)
            return loss, {"pass_loss": mean(nll), "exit_prob": mean(p),
                          "exit_entropy": mean(entropy)}

    # ---- decode path (KV cache) ------------------------------------------
    def init_kv_cache(self, batch_size: int, max_seq_len: Optional[int] = None,
                      dtype: Optional[Any] = None) -> Dict[str, jax.Array]:
        """Allocate a dense per-layer KV cache (inference engine decode state)."""
        self._one_pass_only("the dense key-value cache")
        cfg = self.cfg
        S = max_seq_len or cfg.max_seq_len
        dt = jnp.dtype(dtype or cfg.dtype)
        shape = (cfg.num_layers, batch_size, S, cfg.num_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt),
                "pos": jnp.zeros((batch_size,), jnp.int32)}

    def _serve_layers(self, params: Params, token_ids: jax.Array,
                      positions: jax.Array, moe_valid: Optional[jax.Array],
                      attend: Callable, layer_xs: Any = (),
                      carry: Any = ()) -> Any:
        """The layer stack of every serving forward: embed ``token_ids``
        [.., t] at ``positions`` [.., t], scan the periods of each entry of
        :meth:`_layer_plan` through :func:`_decode_block` (each block under
        its kind's config and rope table), final norm.

        ``attend(cseg, li, q, k, v, xs, carry)`` is the caller's own: the
        cache read and the attention of layer ``li`` under its kind's config
        ``cseg``. ``xs`` is that layer's slice of ``layer_xs`` (per-layer
        scan inputs, leading dim L), ``carry`` what the layer before handed
        on (``carry`` here for the first). It returns ``(out [.., t, H, hd],
        ys, carry)``: ``ys`` is stacked over the layers, ``carry`` goes to
        the next layer.

        Returns (normed hidden [.., t, D], stacked ys, final carry)."""
        self._one_pass_only("a serving forward (the cached layer loop)")
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        x = params["embed"]["tokens"].astype(dt)[token_ids]
        if cfg.learned_pos:
            # a bucket-padded row may lie past max_seq_len; pad rows are
            # never gathered or appended
            safe_pos = jnp.minimum(positions, cfg.max_seq_len - 1)
            x = x + params["embed"]["pos"][safe_pos].astype(dt)
        dense_layers, quant_items = split_quant_leaves(params["layers"])

        parts = []
        for lo, hi, period in self._layer_plan():
            p = len(period)

            def body(h_carry, xs, period=period, p=p):
                h, prev = h_carry
                ys = []
                for j, kind in enumerate(period):
                    layer_w, li, lxs = _block_of(xs, j, p)
                    cseg, freqs = self._kinds[kind]
                    wc = jax.tree_util.tree_map(
                        lambda a: a.astype(dt) if a.dtype == jnp.float32
                        else a, layer_w)
                    for grp, name, qw in quant_items:
                        wc[grp] = {**wc[grp], name: QuantLayerRef(qw, li)}
                    h, (y, prev) = _decode_block(
                        h, wc, cseg, freqs, positions,
                        lambda q, k, v, cseg=cseg, li=li, lxs=lxs, prev=prev:
                        attend(cseg, li, q, k, v, lxs, prev),
                        self.moe_fn, moe_valid=moe_valid)
                    ys.append(y)
                return (h, prev), _stack_blocks(ys, p)

            xs = _by_period(
                (dense_layers, jnp.arange(cfg.num_layers, dtype=jnp.int32),
                 layer_xs), lo, hi, p)
            (x, carry), ys = jax.lax.scan(body, (x, carry), xs)
            parts.append(_by_layer(ys, p))
        ys = jax.tree_util.tree_map(
            lambda *a: a[0] if len(a) == 1 else jnp.concatenate(a), *parts)
        return _norm(x, params["final_norm"], cfg.norm, cfg.norm_eps), ys, carry

    def forward_with_cache(self, params: Params, input_ids: jax.Array,
                           cache: Dict[str, jax.Array],
                           valid: Optional[jax.Array] = None) -> Any:
        """Prefill/decode step: append ``input_ids`` [B, t] at each sequence's
        ``cache['pos']`` and return (logits [B, t, V], updated cache).

        Per-sequence positions: slots in the same batch may be at different
        decode depths. The dense-cache forward of ``inference/engine.py``
        (v1) and ``runtime/hybrid_engine.py``, and the tests' float reference.
        """
        B, t = input_ids.shape
        S = cache["k"].shape[2]
        pos = cache["pos"]  # [B]
        positions = pos[:, None] + jnp.arange(t)[None, :]  # [B, t]

        def attend(cseg, li, q, k, v, xs, carry):
            ck, cv = xs
            # per-sequence scatter of the new kv at each position
            bidx = jnp.arange(B)[:, None] + jnp.zeros((1, t), jnp.int32)
            nk = ck.at[bidx, positions].set(k.astype(ck.dtype))
            nv = cv.at[bidx, positions].set(v.astype(cv.dtype))
            sidx = jnp.arange(S)[None, None, :]
            vmask = sidx <= positions[:, :, None]  # [B,t,S]
            if cseg.sliding_window is not None:
                vmask = vmask & (sidx > positions[:, :, None]
                                 - cseg.sliding_window)
            return _cached_attention(q, nk, nv, vmask), (nk, nv), carry

        x, (nk, nv), _ = self._serve_layers(
            params, input_ids, positions, valid, attend,
            layer_xs=(cache["k"], cache["v"]))
        logits = self._head_proj(params, x)
        return logits, {"k": nk, "v": nv, "pos": pos + t}

    # ---- paged decode path (blocked KV pool) ------------------------------
    def init_paged_kv_cache(self, num_blocks: int, block_size: int = 128,
                            dtype: Optional[Any] = None,
                            quantize: bool = False,
                            bits: int = 8) -> Dict[str, jax.Array]:
        """Allocate the global blocked KV pool (inference v2 kv_cache.py parity):
        ``[L, num_blocks+1, block_size, K*d]`` — the last block is scratch for
        padded lanes. HBM is proportional to ``num_blocks``, not
        ``max_sequences × max_seq_len``.

        The (K, d) axes are stored LANE-FOLDED: a ``[.., K, d]`` layout pads
        K up to the sublane tile, so "reshaping" it to ``[.., K*d]`` at the
        kernel boundary is a full relayout copy of the pool — XLA re-issues
        it at every Pallas read (measured ~1.8 ms x layers x steps on v5e).
        Folding at allocation makes the kernels' DMA view the storage view.

        ``quantize=True`` allocates int pools plus a per-token dequant
        scale array ``kv_scale`` [L, nb+1, 1, 2*block_size] (k scales in lanes
        [0, bs), v in [bs, 2bs)) — KV HBM traffic halves (int8) or quarters
        (``bits=4``: lane j paired with j + K*d/2 per byte), which is the
        decode bound on a bandwidth-limited chip."""
        self._one_pass_only("the paged key-value cache")
        cfg = self.cfg
        dt = jnp.dtype(dtype or cfg.dtype)
        lanes = cfg.num_kv_heads * cfg.head_dim
        if quantize and bits == 4:
            if cfg.head_dim % 2:
                raise ValueError("int4 KV needs an even head_dim")
            lanes //= 2
        shape = (cfg.num_layers, num_blocks + 1, block_size, lanes)
        if quantize:
            return {"k": jnp.zeros(shape, jnp.int8),
                    "v": jnp.zeros(shape, jnp.int8),
                    "kv_scale": jnp.zeros(shape[:2] + (1, 2 * block_size),
                                          jnp.float32)}
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    MAX_ATOM = 256   # widest prefill atom (VMEM-bounded); engines chunk longer prompts

    def forward_with_packed_cache(self, params: Params, token_ids: jax.Array,
                                  cache: Dict[str, jax.Array],
                                  block_tables: jax.Array,
                                  tok_slot: jax.Array, tok_pos: jax.Array,
                                  valid: jax.Array,
                                  gather_idx: jax.Array,
                                  decode_rows: Optional[int] = None,
                                  tile_tq: int = 128,
                                  tiles_no_past: bool = False,
                                  decode_kernel: str = "pallas") -> Any:
        """Token-packed continuous-batching step (ragged_wrapper.py parity).

        The batch is ONE packed row of the scheduled tokens, not a dense
        ``[max_sequences, t_max]`` tile: ``token_ids`` [N] with per-token
        ``tok_slot``/``tok_pos`` metadata, laid out in two regions (the atom
        layout of reference ``v2/kernels/ragged_ops/atom_builder``):

        * rows ``[0, decode_rows)`` — 1-token atoms (decode steps);
        * rows ``[decode_rows, N)`` — ``tile_tq``-wide atoms, each holding
          ONE whole chunk (consecutive tokens of one sequence, right-padded;
          chunks longer than :attr:`MAX_ATOM` are chunked across put()s).

        Attention runs in the manual-DMA Pallas kernel: every atom reads its
        own tokens' KV from VMEM and streams only PAST put()s' blocks from
        the pool, so all layers' KV appends hoist into one in-place scatter
        after the layer scan (``packed_kv_append``) instead of a per-layer
        pool copy. ``decode_rows=None`` treats every row as a 1-token atom
        (valid only when every chunk has length 1). Logits are computed only
        at ``gather_idx`` (chunk ends) — reference ``logits_gather``.

        Returns (logits [G, V], updated cache).
        """
        from deepspeed_tpu.ops.paged_attention import (
            packed_kv_append, packed_kv_append_quant,
            ragged_paged_attention_tp)

        kv_scale = cache.get("kv_scale")
        N = token_ids.shape[0]
        dr = N if decode_rows is None else decode_rows
        if (N - dr) % tile_tq:
            raise ValueError(f"prefill region ({N} - {dr} rows) must be a "
                             f"multiple of the {tile_tq}-token atom tile")
        n_tiles = (N - dr) // tile_tq

        # atom metadata (decode rows: 1-token atoms; tiles: first-row
        # slot/pos + count of real rows)
        a_slot_d, a_pos_d = tok_slot[:dr], tok_pos[:dr]
        a_len_d = valid[:dr].astype(jnp.int32)
        if n_tiles:
            a_slot_t = tok_slot[dr::tile_tq]
            a_pos_t = tok_pos[dr::tile_tq]
            a_len_t = valid[dr:].reshape(n_tiles, tile_tq).sum(
                axis=1, dtype=jnp.int32)

        def attend(cseg, li, q, k, v, xs, carry):
            q2, k2, v2 = q[:, 0], k[:, 0], v[:, 0]              # [N, H|K, d]
            # the WHOLE stacked pool rides through the scan closure
            # (ANY-memory operand, layer picked inside the kernel):
            # per-layer pool slices in the scan xs would materialize
            # a full pool copy every layer
            parts = []
            if dr:
                parts.append(ragged_paged_attention_tp(
                    q2[:dr], k2[:dr], v2[:dr], cache["k"], cache["v"],
                    block_tables, a_slot_d, a_pos_d, a_len_d, tq=1,
                    window=cseg.sliding_window, layer=li,
                    kv_scale=kv_scale, kv_bits=self._kv_bits(cache),
                    kernel=decode_kernel))
            if n_tiles:
                parts.append(ragged_paged_attention_tp(
                    q2[dr:], k2[dr:], v2[dr:], cache["k"], cache["v"],
                    block_tables, a_slot_t, a_pos_t, a_len_t,
                    tq=tile_tq, window=cseg.sliding_window, layer=li,
                    no_past=tiles_no_past, kv_scale=kv_scale,
                    kv_bits=self._kv_bits(cache),
                    kernel=decode_kernel))
            out = (parts[0] if len(parts) == 1
                   else jnp.concatenate(parts))
            # [N, 1, H, d]; the K/V rows are appended after the scan
            return out[:, None], (k2, v2), carry

        x, (krows, vrows), _ = self._serve_layers(
            params, token_ids[:, None], tok_pos[:, None], valid[:, None],
            attend)
        if kv_scale is not None:
            kvb = self._kv_bits(cache)
            nk, sc1 = packed_kv_append_quant(cache["k"], kv_scale, krows,
                                             block_tables, tok_slot, tok_pos,
                                             0, valid, bits=kvb)
            nv, sc2 = packed_kv_append_quant(cache["v"], sc1, vrows,
                                             block_tables, tok_slot, tok_pos,
                                             1, valid, bits=kvb)
            new_cache = {"k": nk, "v": nv, "kv_scale": sc2}
        else:
            nk = packed_kv_append(cache["k"], krows, block_tables, tok_slot,
                                  tok_pos, valid)
            nv = packed_kv_append(cache["v"], vrows, block_tables, tok_slot,
                                  tok_pos, valid)
            new_cache = {"k": nk, "v": nv}
        logits = self._head_proj(params, x[:, 0][gather_idx])   # [G, V]
        return logits, new_cache

    PREFILL_MAX = 4096   # widest whole-prompt prefill (longer prompts chunk)

    def forward_prefill(self, params: Params, input_ids: jax.Array,
                        lengths: jax.Array) -> Any:
        """Whole-prompt prefill at the training path's efficiency.

        Fresh prompts (nothing cached) need no pool reads at all — their
        attention is plain causal flash, exactly the training forward. This
        runs the training-grade attention kernel over ``input_ids`` [B, T]
        (right-padded; ``lengths`` [B] real lengths), stashes every layer's
        K/V rows on the way (reference blocked_flash + kv_copy fusion,
        inference/v2/model_implementations/flat_model_helpers.py), and
        returns (last-token logits [B, V], kv {k,v: [L, B, T, K, d]}) for
        the engine to fold into the paged pool with one scatter. Weights
        stream once per PROMPT instead of once per 256-token chunk — on a
        bandwidth-bound chip that alone is ~T/256 x.
        """
        B, T = input_ids.shape
        positions = jnp.arange(T, dtype=jnp.int32)[None, :]
        valid = positions < lengths[:, None]                    # [B, T]
        attn_fn = get_attention_impl(self.cfg.attention_impl)

        def attend(cseg, li, q, k, v, xs, carry):
            window = cseg.sliding_window
            if window is None:
                out = attn_fn(q, k, v, causal=True)
            elif _attn_takes(attn_fn, "window"):
                out = attn_fn(q, k, v, causal=True, window=window)
            else:
                out = xla_attention(q, k, v, causal=True, window=window)
            return out, (k, v), carry

        x, (kr, vr), _ = self._serve_layers(params, input_ids, positions,
                                            valid, attend)
        last = jnp.clip(lengths - 1, 0, T - 1)
        xg = x[jnp.arange(B), last]                              # [B, D]
        logits = self._head_proj(params, xg)
        return logits, {"k": kr, "v": vr}

    def _kv_bits(self, cache) -> int:
        """4 when the paged pool is int4-packed (lane dim K*d/2), else 8."""
        if "kv_scale" not in cache:
            return 8
        half = self.cfg.num_kv_heads * self.cfg.head_dim // 2
        return 4 if cache["k"].shape[-1] == half else 8

    def forward_decode_tail(self, params: Params, toks: jax.Array,
                            cache: Dict[str, jax.Array],
                            tail: Dict[str, jax.Array], t: jax.Array,
                            block_tables: jax.Array, slots: jax.Array,
                            pos_base: jax.Array,
                            valid: Optional[jax.Array] = None,
                            decode_kernel: str = "pallas") -> Any:
        """One fused-loop decode step with the pool READ-ONLY.

        The engine's multi-step decode scan cannot scatter into the paged
        pool every step: a Pallas read of a buffer that is also written
        in-place inside the same loop makes XLA snapshot-copy the whole pool
        per layer per step (measured ~2 ms x 16 x steps on v5e). Instead the
        freshly decoded KV lives in a small dense ``tail``
        ([L, B, steps, K, d], the in-flight tokens of this decode_batch
        call) and the pool is folded once, after the scan
        (``InferenceEngineV2._multi_decode``). Attention is a three-way
        flash-decode split reduction: pool partials (work-list kernel over
        positions < pos_base) ⊕ tail+self (dense XLA over cols <= t).

        ``toks`` [B]; ``t`` traced step index; ``pos_base`` [B] pool
        frontier (tokens already in the pool); row position = pos_base + t.
        Returns (logits [B, V], updated tail).
        """
        from deepspeed_tpu.ops.paged_attention import decode_pool_partials_tp

        cfg = self.cfg
        B = toks.shape[0]
        K = cfg.num_kv_heads
        hd = cfg.head_dim
        rep = cfg.num_heads // K
        S_tail = tail["k"].shape[2]
        if valid is None:
            valid = jnp.ones((B,), bool)
        row_pos = pos_base + t                                   # [B]
        scale = 1.0 / math.sqrt(hd)

        def attend(cseg, li, q, k, v, xs, carry):
            tk, tv = carry
            q2, k2, v2 = q[:, 0], k[:, 0], v[:, 0]    # [B, H|K, d]
            window = cseg.sliding_window
            acc, m_k, l_k = decode_pool_partials_tp(
                q2, cache["k"], cache["v"], li, block_tables, slots,
                pos_base, window=window, row_pos=row_pos,
                kv_scale=cache.get("kv_scale"),
                kv_bits=self._kv_bits(cache),
                kernel=decode_kernel)
            # append self into the tail, then attend tail cols <= t
            tk2 = jax.lax.dynamic_update_slice(
                tk, k2[None, :, None].astype(tk.dtype),
                (li, 0, t, 0, 0))
            tv2 = jax.lax.dynamic_update_slice(
                tv, v2[None, :, None].astype(tv.dtype),
                (li, 0, t, 0, 0))
            tkl = jax.lax.dynamic_index_in_dim(tk2, li, keepdims=False)
            tvl = jax.lax.dynamic_index_in_dim(tv2, li, keepdims=False)
            qg = q2.reshape(B, K, rep, hd).astype(jnp.float32)
            s_t = jnp.einsum("bkrd,bskd->bkrs", qg,
                             tkl.astype(jnp.float32)) * scale
            col = jnp.arange(S_tail)[None, None, None, :]
            keep = col <= t
            if window is not None:
                keep = keep & (col > t - window)
            s_t = jnp.where(keep, s_t, -1e30)
            m_t = jnp.max(s_t, axis=-1)                # [B, K, rep]
            p_t = jnp.where(keep, jnp.exp(s_t - m_t[..., None]), 0.0)
            l_t = jnp.sum(p_t, axis=-1)
            acc_t = jnp.einsum("bkrs,bskd->bkrd", p_t,
                               tvl.astype(jnp.float32))
            H = K * rep
            m_t = m_t.reshape(B, H)
            l_t = l_t.reshape(B, H)
            acc_t = acc_t.reshape(B, H, hd)
            m2 = jnp.maximum(m_k, m_t)
            c_k = jnp.exp(m_k - m2)
            c_t = jnp.exp(m_t - m2)
            denom = jnp.maximum(l_k * c_k + l_t * c_t, 1e-30)
            out = ((acc * c_k[..., None] + acc_t * c_t[..., None])
                   / denom[..., None])
            out = jnp.where(valid[:, None, None], out, 0)
            return out.astype(q.dtype)[:, None], None, (tk2, tv2)  # [B,1,H,d]

        x, _, (tk, tv) = self._serve_layers(
            params, toks[:, None], row_pos[:, None], valid[:, None], attend,
            carry=(tail["k"], tail["v"]))
        logits = self._head_proj(params, x[:, 0])                # [B, V]
        return logits, {"k": tk, "v": tv}

    # ---- sharding ---------------------------------------------------------
    def param_specs(self) -> Params:
        """Megatron-style TP layout (reference: auto_tp.py row/col policy):
        qkv/up column-parallel (shard output dim over tp), o/down row-parallel
        (shard input dim over tp), vocab-parallel embedding."""
        cfg = self.cfg
        norm_spec = {"scale": P(None, None)}
        if cfg.norm == "layernorm":
            norm_spec["bias"] = P(None, None)
        mlp = ({"w_gate": P(None, None, "tp"), "w_up": P(None, None, "tp"),
                "w_down": P(None, "tp", None)}
               if cfg.activation == "swiglu" else
               {"w_up": P(None, None, "tp"), "w_down": P(None, "tp", None)})
        if cfg.proj_bias and cfg.activation != "swiglu" and cfg.num_experts <= 1:
            mlp["b_up"] = P(None, "tp")
            mlp["b_down"] = P(None, None)
        if cfg.num_experts > 1:
            mlp = {"w_gate": P(None, "ep", None, "tp"), "w_up": P(None, "ep", None, "tp"),
                   "w_down": P(None, "ep", "tp", None), "router": P(None, None, None)}
            if cfg.activation != "swiglu":
                mlp.pop("w_gate")
        if cfg.num_experts > 1 and cfg.moe_scoring == "sigmoid":
            mlp["router_bias"] = P(None, None)
        if cfg.num_experts > 1 and cfg.moe_shared_experts:
            mlp["shared"] = {"w_gate": P(None, None, "tp"),
                             "w_up": P(None, None, "tp"),
                             "w_down": P(None, "tp", None)}
            if cfg.activation != "swiglu":
                del mlp["shared"]["w_gate"]
            if cfg.moe_shared_gate:
                mlp["shared"]["w_sg"] = P(None, None, None)
        if cfg.num_experts > 1 and cfg.moe_latent_size:
            mlp["latent_down"] = P(None, None, None)
            mlp["latent_up"] = P(None, None, None)
        attn_spec = {"wq": P(None, None, "tp"), "wk": P(None, None, "tp"),
                     "wv": P(None, None, "tp"), "wo": P(None, "tp", None)}
        if cfg.qkv_bias:
            attn_spec["bq"] = P(None, "tp")
            attn_spec["bk"] = P(None, "tp")
            attn_spec["bv"] = P(None, "tp")
        if cfg.proj_bias:
            attn_spec["bo"] = P(None, None)
        if cfg.qk_norm == "head":
            attn_spec["q_norm"] = P(None, None)
            attn_spec["k_norm"] = P(None, None)
        elif cfg.qk_norm:
            attn_spec["q_norm"] = P(None, "tp")
            attn_spec["k_norm"] = P(None, "tp")
        layer_specs: Params = {"ln1": norm_spec, "attn": attn_spec, "mlp": mlp}
        if cfg.has_ffn_kinds:
            del layer_specs["mlp"]
            layer_specs["mlp_moe"] = mlp
            layer_specs["mlp_dense"] = {
                "w_gate": P(None, None, "tp"), "w_up": P(None, None, "tp"),
                "w_down": P(None, "tp", None)}
            if cfg.activation != "swiglu":
                del layer_specs["mlp_dense"]["w_gate"]
            for grp in ("mlp_moe", "mlp_dense"):
                if not _in_group(cfg.layer_kinds, grp):
                    del layer_specs[grp]
        if cfg.has_ssm:
            from deepspeed_tpu.models import mamba

            layer_specs["ssm"] = mamba.param_specs()
        if cfg.has_mla:
            from deepspeed_tpu.models import mla

            layer_specs["mla"] = mla.param_specs(cfg)
        if cfg.has_delta:
            from deepspeed_tpu.models import gated_delta

            layer_specs["delta"] = gated_delta.param_specs()
        if cfg.has_conv:
            from deepspeed_tpu.models import short_conv

            layer_specs["conv"] = short_conv.param_specs()
        if cfg.has_kda:
            from deepspeed_tpu.models import kda

            layer_specs["kda"] = kda.param_specs()
        if cfg.has_dsa:
            from deepspeed_tpu.models import dsa

            layer_specs["indexer"] = dsa.param_specs()
        split = bool(cfg.heads_by_kind)
        if cfg.gates_plain_heads:
            attn_spec["wg"] = P(None, None, "tp")
        for grp in _SPLIT_GROUP.values() if split else ():
            if _in_group(cfg.layer_kinds, grp, split):
                layer_specs[grp] = dict(attn_spec)
        if not _in_group(cfg.layer_kinds, "attn", split):
            del layer_specs["attn"]
        if not (cfg.parallel_shared_norm or cfg.one_branch):
            layer_specs["ln2"] = dict(norm_spec)
        if cfg.sandwich_norm or cfg.norm_placement == "post":
            layer_specs["ln1_post"] = dict(norm_spec)
            layer_specs["ln2_post"] = dict(norm_spec)
        if cfg.norm_placement == "post":
            del layer_specs["ln1"], layer_specs["ln2"]
        specs: Params = {
            "embed": {"tokens": P("tp", None)},
            "layers": layer_specs,
            "final_norm": {"scale": P(None)},
        }
        if cfg.norm == "layernorm":
            specs["final_norm"]["bias"] = P(None)
        if cfg.learned_pos:
            specs["embed"]["pos"] = P(None, None)
        if not cfg.tie_embeddings:
            specs["lm_head"] = P(None, "tp")
        if cfg.exit_loss_beta is not None:
            specs["exit_gate"] = {"w": P(None), "b": P()}
        return specs
