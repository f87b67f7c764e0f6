"""opcount's arithmetic against the program's own parameter estimate, and
against numbers worked out by hand. Run by hand:
``python -m pytest benchmarks/tests -q`` (CPU, seconds)."""

import glob
import json
import os

import pytest

from benchmarks import modelcfg, opcount

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = sorted(glob.glob(os.path.join(HERE, "..", "configs", "*.json")))
# Mixtral-8x7B-v0.1's published widths at 4 layers: no cell runs them yet,
# the sparse branch of the arithmetic is kept right for the PR that adds one
MIXTRAL = {"hidden_size": 4096, "intermediate_size": 14336,
           "num_attention_heads": 32, "num_key_value_heads": 8,
           "head_dim": 128, "vocab_size": 32000, "num_hidden_layers": 4,
           "num_local_experts": 8, "num_experts_per_tok": 2,
           "rms_norm_eps": 1e-5, "rope_theta": 1e6, "sliding_window": None,
           "tie_word_embeddings": False}


def _cfg(path):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize(
    "cfg", [_cfg(p) for p in CONFIGS] + [MIXTRAL],
    ids=[os.path.basename(p) for p in CONFIGS] + ["mixtral_widths"])
def test_params_match_the_programs_estimate(cfg):
    tcfg = modelcfg.transformer_config(cfg, max_seq_len=4096,
                                       param_dtype="bfloat16")
    import jax

    from deepspeed_tpu.models import TransformerLM

    # exactly the parameters the program's own init makes
    shapes = jax.eval_shape(TransformerLM(tcfg).init, jax.random.key(0))
    made = sum(int(x.size) for x in jax.tree_util.tree_leaves(shapes))
    assert opcount.total_params(cfg) == made
    # and the program's estimate, which counts one dense FFN per layer (add
    # the other experts and the router) and each RMSNorm scale twice
    est = tcfg.num_params_estimate()
    s = opcount.sizes(cfg)
    if s["E"] > 1:
        est += s["L"] * ((s["E"] - 1) * opcount.ffn_params(cfg)
                         + s["D"] * s["E"])
    assert est - opcount.total_params(cfg) == 2 * s["L"] * s["D"]


def test_mistral_by_hand():
    cfg = _cfg(os.path.join(HERE, "..", "configs", "mistral7b_train_d2.json"))
    assert opcount.attn_params(cfg) == 4096 * 4096 * 2 + 2 * 4096 * 1024
    assert opcount.ffn_params(cfg) == 3 * 4096 * 14336
    # 218M a layer, 131M embedding, 131M head
    assert round(opcount.layer_matmul_params(cfg) / 1e6) == 218
    # the issue's 3.6 GFLOP a token at two layers and 4096 tokens
    assert 3.55e9 < opcount.train_flops_per_token(cfg, 4096) < 3.65e9


def test_causal_pairs():
    assert opcount.causal_pairs(4, 4) == 10
    assert opcount.causal_pairs(1, 100) == 100
    assert opcount.causal_pairs(4, 4, window=2) == 1 + 2 + 2 + 2
    assert opcount.causal_pairs(2, 10, window=4096) == 9 + 10
    assert opcount.causal_pairs(4096, 4096, window=4096) \
        == opcount.causal_pairs(4096, 4096)


def test_roofline_says_which_bound():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    r = opcount.roofline_seconds({"flops": 197e12, "bytes": 1.0}, peak)
    assert r == {"seconds": 1.0, "bound": "compute"}
    r = opcount.roofline_seconds({"flops": 1.0, "bytes": 819e9}, peak)
    assert r == {"seconds": 1.0, "bound": "memory"}
