"""HF interop tests — the analog of the reference's AutoTP/checkpoint-loading
unit tests: a tiny HF Llama checkpoint must import with exact logits parity,
Mixtral must import structurally, and AutoTP spec inference must reproduce the
row/col policy on both naming families."""

import json

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

transformers = pytest.importorskip("transformers")


@pytest.fixture(scope="module")
def tiny_llama_ckpt(tmp_path_factory):
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    torch.manual_seed(0)
    cfg = LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=64,
                      rms_norm_eps=1e-5, tie_word_embeddings=True)
    model = LlamaForCausalLM(cfg)
    d = str(tmp_path_factory.mktemp("hf_llama"))
    model.save_pretrained(d)
    return d, model


def test_llama_import_logits_parity(tiny_llama_ckpt):
    """Imported weights + our forward == HF forward (fp32, atol 1e-4)."""
    import torch

    from deepspeed_tpu.models.hf import load_hf_checkpoint

    path, hf_model = tiny_llama_ckpt
    model, params = load_hf_checkpoint(path, dtype="float32")
    ids = np.random.default_rng(0).integers(0, 256, (2, 16))
    ours = np.asarray(jax.jit(model.logits)(params, ids))
    with torch.no_grad():
        theirs = hf_model(torch.tensor(ids)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=1e-3, rtol=1e-3)


def test_llama_import_trains_under_engine(tiny_llama_ckpt, eight_devices):
    """An imported checkpoint plugs straight into ds.initialize (the reference
    user journey: HF model -> deepspeed engine)."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.hf import load_hf_checkpoint

    path, _ = tiny_llama_ckpt
    model, params = load_hf_checkpoint(path)
    eng, *_ = ds.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 3, "param_persistence_threshold": 0},
        "mesh": {"fsdp": 4, "tp": 2},
        "steps_per_print": 100})
    eng.params = jax.device_put(params, eng.param_sharding)
    batch = {"input_ids": np.random.default_rng(1).integers(0, 256, (8, 16))}
    losses = []
    for _ in range(3):
        loss = eng.forward(batch)
        eng.backward(loss)
        eng.step()
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_llama3_rope_scaling_parity(tmp_path):
    """Llama-3.1-style rope_scaling must reproduce transformers' frequency
    banding — unscaled frequencies would silently diverge at all positions."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    from deepspeed_tpu.models.hf import load_hf_checkpoint

    torch.manual_seed(1)
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=96,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=4, max_position_embeddings=64,
                      rope_scaling={"rope_type": "llama3", "factor": 8.0,
                                    "low_freq_factor": 1.0,
                                    "high_freq_factor": 4.0,
                                    "original_max_position_embeddings": 32},
                      tie_word_embeddings=False)
    hf_model = LlamaForCausalLM(cfg)
    hf_model.save_pretrained(str(tmp_path))
    model, params = load_hf_checkpoint(str(tmp_path), dtype="float32")
    assert model.cfg.rope_scaling["rope_type"] == "llama3"
    ids = np.random.default_rng(2).integers(0, 128, (1, 48))
    ours = np.asarray(jax.jit(model.logits)(params, ids))
    with torch.no_grad():
        import torch as t

        theirs = hf_model(t.tensor(ids)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=1e-3, rtol=1e-3)


def test_mixtral_import_logits_parity(tmp_path):
    """Mixtral imports into the EP layout with the grouped (dropless) dispatch
    — which matches Mixtral's renormalized top-k routing exactly, so logits
    parity against transformers holds."""
    import torch
    from transformers import MixtralConfig, MixtralForCausalLM

    from deepspeed_tpu.models.hf import load_hf_checkpoint

    torch.manual_seed(0)
    cfg = MixtralConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                        num_hidden_layers=2, num_attention_heads=4,
                        num_key_value_heads=2, num_local_experts=4,
                        num_experts_per_tok=2, max_position_embeddings=32)
    hf_model = MixtralForCausalLM(cfg)
    hf_model.save_pretrained(str(tmp_path))
    model, params = load_hf_checkpoint(str(tmp_path), dtype="float32")
    assert model.cfg.num_experts == 4 and model.cfg.top_k == 2
    assert model.cfg.moe_dispatch == "grouped"
    assert params["layers"]["mlp"]["w_gate"].shape == (2, 4, 32, 64)
    assert params["layers"]["mlp"]["router"].shape == (2, 32, 4)
    ids = np.random.default_rng(0).integers(0, 128, (2, 8))
    ours = np.asarray(jax.jit(model.logits)(params, ids))
    with torch.no_grad():
        theirs = hf_model(torch.tensor(ids)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("preset", ["tiny", "tiny-moe"])
def test_infer_tp_specs_matches_hand_policy(preset):
    """Name-pattern inference reproduces the family's hand-written megatron
    policy on the WHOLE tree — dense and stacked-MoE (ep on the expert dim)."""
    from deepspeed_tpu.models import TransformerLM, get_preset
    from deepspeed_tpu.models.hf import infer_tp_specs

    model = TransformerLM(get_preset(preset))
    params = jax.eval_shape(model.init, jax.random.key(0))
    specs = infer_tp_specs(params)
    hand = model.param_specs()

    def norm(tree):
        # compare per-dim entries, padding trailing Nones
        flat = jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda x: x is None or isinstance(x, P))[0]
        return {tuple(str(k) for k in kp): tuple(s or P()) + (None,) * 4
                for kp, s in flat}

    got, want = norm(specs), norm(hand)
    for key in want:
        assert got[key][:4] == want[key][:4], (key, got[key], want[key])
    if preset == "tiny-moe":
        assert specs["layers"]["mlp"]["w_gate"] == P(None, "ep", None, "tp")
        assert specs["layers"]["mlp"]["w_down"] == P(None, "ep", "tp", None)


def test_infer_tp_specs_hf_naming():
    from deepspeed_tpu.models.hf import infer_tp_specs

    tree = {
        "model.layers.0.self_attn.q_proj.weight": np.zeros((64, 32)),
        "model.layers.0.self_attn.o_proj.weight": np.zeros((32, 64)),
        "model.layers.0.mlp.down_proj.weight": np.zeros((32, 128)),
        "model.embed_tokens.weight": np.zeros((256, 32)),
        "model.layers.0.block_sparse_moe.experts.1.w1.weight": np.zeros((128, 32)),
        "model.norm.weight": np.zeros((32,)),
    }
    specs = infer_tp_specs(tree)
    # torch [out, in]: col-parallel shards out (dim -2), row-parallel in (dim -1)
    assert specs["model.layers.0.self_attn.q_proj.weight"] == P("tp", None)
    assert specs["model.layers.0.self_attn.o_proj.weight"] == P(None, "tp")
    assert specs["model.layers.0.mlp.down_proj.weight"] == P(None, "tp")
    assert specs["model.embed_tokens.weight"] == P("tp", None)
    # raw HF expert leaf is 2-D (expert axis = python structure): plain col
    assert specs["model.layers.0.block_sparse_moe.experts.1.w1.weight"] == \
        P("tp", None)
    assert specs["model.norm.weight"] == P(None)


# ---------------------------------------------------------------------------
# Model-family breadth (reference: inference/v2/model_implementations/ covers
# llama/mistral/mixtral/opt/phi3/qwen2/falcon/...): every family imports with
# logits parity against transformers.
# ---------------------------------------------------------------------------

def _tiny_hf(family):
    import torch
    import transformers as tr

    torch.manual_seed(0)
    if family == "mistral":
        # sliding_window=8 < T=16 so the windowed mask path is exercised
        cfg = tr.MistralConfig(vocab_size=128, hidden_size=64,
                               intermediate_size=96, num_hidden_layers=2,
                               num_attention_heads=4, num_key_value_heads=2,
                               max_position_embeddings=64, sliding_window=8,
                               attn_implementation="eager")
        return tr.MistralForCausalLM(cfg)
    if family == "qwen2":
        cfg = tr.Qwen2Config(vocab_size=128, hidden_size=64,
                             intermediate_size=96, num_hidden_layers=2,
                             num_attention_heads=4, num_key_value_heads=2,
                             max_position_embeddings=64)
        return tr.Qwen2ForCausalLM(cfg)
    if family == "phi3":
        cfg = tr.Phi3Config(vocab_size=128, hidden_size=64,
                            intermediate_size=96, num_hidden_layers=2,
                            num_attention_heads=4, num_key_value_heads=2,
                            max_position_embeddings=64, pad_token_id=0)
        return tr.Phi3ForCausalLM(cfg)
    if family == "falcon7b":  # multi-query + parallel attn + shared ln
        cfg = tr.FalconConfig(vocab_size=128, hidden_size=64,
                              num_hidden_layers=2, num_attention_heads=4,
                              ffn_hidden_size=128, multi_query=True,
                              new_decoder_architecture=False,
                              parallel_attn=True, bias=False, alibi=False,
                              max_position_embeddings=64)
        return tr.FalconForCausalLM(cfg)
    if family == "falcon40b":  # GQA + separate ln_attn/ln_mlp + biases
        cfg = tr.FalconConfig(vocab_size=128, hidden_size=64,
                              num_hidden_layers=2, num_attention_heads=4,
                              num_kv_heads=2, ffn_hidden_size=128,
                              new_decoder_architecture=True, bias=True,
                              alibi=False, max_position_embeddings=64)
        return tr.FalconForCausalLM(cfg)
    if family == "gpt_neox":  # partial rotary + parallel residual + biases
        cfg = tr.GPTNeoXConfig(vocab_size=128, hidden_size=64,
                               intermediate_size=128, num_hidden_layers=2,
                               num_attention_heads=4, rotary_pct=0.5,
                               max_position_embeddings=64,
                               attn_implementation="eager")
        return tr.GPTNeoXForCausalLM(cfg)
    if family == "gpt2":
        cfg = tr.GPT2Config(vocab_size=128, n_embd=64, n_layer=2, n_head=4,
                            n_positions=64)
        return tr.GPT2LMHeadModel(cfg)
    if family == "opt":
        cfg = tr.OPTConfig(vocab_size=128, hidden_size=64, ffn_dim=128,
                           num_hidden_layers=2, num_attention_heads=4,
                           max_position_embeddings=64, word_embed_proj_dim=64,
                           do_layer_norm_before=True)
        return tr.OPTForCausalLM(cfg)
    raise ValueError(family)


@pytest.mark.parametrize("family", ["mistral", "qwen2", "phi3", "falcon7b",
                                    "falcon40b", "gpt_neox", "gpt2", "opt"])
def test_family_import_logits_parity(family, tmp_path):
    import torch

    from deepspeed_tpu.models.hf import load_hf_checkpoint

    hf_model = _tiny_hf(family).eval()  # gpt2/opt default dropout > 0
    hf_model.save_pretrained(str(tmp_path))
    model, params = load_hf_checkpoint(str(tmp_path), dtype="float32")
    ids = np.random.default_rng(3).integers(0, 128, (2, 16))
    ours = np.asarray(jax.jit(model.logits)(params, ids))
    with torch.no_grad():
        theirs = hf_model(torch.tensor(ids)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=2e-3, rtol=2e-3)


def test_family_config_mapping():
    """The family switchboard: each HF config maps to the right arch knobs."""
    from deepspeed_tpu.models.hf import config_from_hf

    qwen = config_from_hf({"model_type": "qwen2", "vocab_size": 128,
                           "hidden_size": 64, "num_hidden_layers": 2,
                           "num_attention_heads": 4, "num_key_value_heads": 2,
                           "intermediate_size": 96})
    assert qwen.qkv_bias and qwen.sliding_window is None
    neox = config_from_hf({"model_type": "gpt_neox", "vocab_size": 128,
                           "hidden_size": 64, "num_hidden_layers": 2,
                           "num_attention_heads": 4, "intermediate_size": 128,
                           "rotary_pct": 0.25})
    assert neox.parallel_block and neox.rope_pct == 0.25 and neox.use_rope
    assert neox.rope_dim == 4  # head_dim 16 * 0.25
    f7 = config_from_hf({"model_type": "falcon", "vocab_size": 128,
                         "hidden_size": 64, "num_hidden_layers": 2,
                         "num_attention_heads": 4, "multi_query": True,
                         "parallel_attn": True, "bias": False})
    assert f7.parallel_block and f7.parallel_shared_norm
    assert f7.num_kv_heads == 1 and not f7.qkv_bias
    with pytest.raises(ValueError):
        config_from_hf({"model_type": "falcon", "vocab_size": 128,
                        "hidden_size": 64, "num_hidden_layers": 2,
                        "num_attention_heads": 4, "alibi": True})
    with pytest.raises(ValueError):
        config_from_hf({"model_type": "opt", "vocab_size": 128,
                        "hidden_size": 64, "num_hidden_layers": 2,
                        "num_attention_heads": 4, "ffn_dim": 128,
                        "do_layer_norm_before": False})


def test_qwen2_mixed_window_import_parity(tmp_path):
    """HF qwen2 windows only layers i >= max_window_layers (the first layers
    attend fully). The import writes that as an attn_pattern over the stack; logits must match transformers on a T > window sequence through
    the train path AND the serving engines (round-2 ADVICE: the old gate was
    inverted and applied the window globally)."""
    import torch
    import transformers as tr

    from deepspeed_tpu.inference import InferenceEngine, InferenceEngineV2
    from deepspeed_tpu.models.hf import load_hf_checkpoint

    torch.manual_seed(0)
    cfg = tr.Qwen2Config(vocab_size=128, hidden_size=64, intermediate_size=96,
                         num_hidden_layers=4, num_attention_heads=4,
                         num_key_value_heads=2, max_position_embeddings=64,
                         use_sliding_window=True, sliding_window=8,
                         max_window_layers=2, attn_implementation="eager")
    hf = tr.Qwen2ForCausalLM(cfg).eval()
    hf.save_pretrained(str(tmp_path))
    model, params = load_hf_checkpoint(str(tmp_path), dtype="float32")
    assert model.cfg.sliding_window == 8
    assert model.cfg.attn_pattern == ("full", "full", "window", "window")
    ids = np.random.default_rng(4).integers(0, 128, (2, 16))  # T=16 > win=8
    ours = np.asarray(jax.jit(model.logits)(params, ids))
    with torch.no_grad():
        theirs = hf(torch.tensor(ids)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, atol=2e-3, rtol=2e-3)

    # serving parity: greedy decode through v1 and one packed v2 step
    e1 = InferenceEngine(model, config={"mesh": {}}, params=params)
    out = np.asarray(e1.generate(ids[:1], max_new_tokens=4))
    with torch.no_grad():
        ref = hf.generate(torch.tensor(ids[:1]), max_new_tokens=4,
                          do_sample=False).numpy()
    np.testing.assert_array_equal(out, ref)

    e2 = InferenceEngineV2(model, params=params, max_sequences=2,
                           max_seq_len=32, block_size=8)
    r = e2.put([1], [ids[0]])
    np.testing.assert_allclose(
        np.asarray(r[1], np.float32), np.asarray(ours[0, -1], np.float32),
        atol=3e-2)


def test_qwen2_window_gate_not_inverted():
    """use_sliding_window with max_window_layers >= num_layers means NO layer
    is windowed — the import must clear the window, not apply it globally."""
    from deepspeed_tpu.models.hf import config_from_hf

    base = {"model_type": "qwen2", "vocab_size": 128, "hidden_size": 64,
            "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 2, "intermediate_size": 96,
            "use_sliding_window": True, "sliding_window": 8}
    assert config_from_hf({**base, "max_window_layers": 2}).sliding_window \
        is None
    allwin = config_from_hf({**base, "max_window_layers": 0})
    assert allwin.sliding_window == 8 and allwin.attn_pattern is None
