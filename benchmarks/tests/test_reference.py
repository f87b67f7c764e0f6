"""The plain reference against the program's own forward at a toy size in
float32 (where both are exact up to summation order): dense with a sliding
window, and sparse top-2."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import modelcfg, reference

TOY = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
       "num_hidden_layers": 2, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
       "tie_word_embeddings": False}


@pytest.mark.parametrize("extra", [
    {"sliding_window": 8},
    {"sliding_window": None, "num_local_experts": 4,
     "num_experts_per_tok": 2}], ids=["dense_window", "sparse_top2"])
def test_reference_equals_the_program_in_float32(extra):
    from deepspeed_tpu.models import TransformerLM

    cfg = {**TOY, **extra}
    more = {"moe_dispatch": "grouped"} if "num_local_experts" in extra else {}
    tcfg = modelcfg.transformer_config(cfg, max_seq_len=64,
                                       param_dtype="float32", **more)
    import dataclasses
    tcfg = dataclasses.replace(tcfg, dtype="float32", attention_impl="xla")
    model = TransformerLM(tcfg)
    params = model.init(jax.random.key(0))
    toks = np.random.default_rng(0).integers(1, 256, 24).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model.logits(params, jnp.asarray(toks)[None])[0])
    got = np.asarray(reference.forward(cfg, modelcfg.weights_getter(params),
                                       toks))
    assert np.max(np.abs(got - want)) <= 2e-4 * np.max(np.abs(want))
    loss = float(reference.next_token_loss(jnp.asarray(got), toks))
    assert 4.5 < loss < 7.5
