"""The looped reference: its copy with the program's tests is the same file;
at a toy size in float32 it agrees with the program's own forward, loss and
parts (where both are exact up to summation order); its operation count
agrees with the parameters the program's initialiser makes."""

import dataclasses
import filecmp
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import modelcfg_ouro, opcount_ouro, reference_ouro

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
       "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 256,
       "num_hidden_layers": 3, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
       "tie_word_embeddings": False, "total_ut_steps": 4,
       "deployment": {"exit_loss_beta": 0.1, "remat_policy": "full"}}


def test_the_copy_with_the_programs_tests_is_the_same_file():
    assert filecmp.cmp(
        os.path.join(HERE, "..", "reference_ouro.py"),
        os.path.join(HERE, "..", "..", "tests", "unit",
                     "looped_reference.py"), shallow=False)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(HERE, "..", "reference_ouro.py")) as f:
        src = f.read()
    assert "deepspeed_tpu" not in src
    assert "import benchmarks" not in src and "from benchmarks" not in src


def test_reference_equals_the_program_in_float32():
    from deepspeed_tpu.models import TransformerLM

    tcfg = dataclasses.replace(
        modelcfg_ouro.transformer_config(TOY, max_seq_len=64,
                                         param_dtype="float32"),
        dtype="float32", attention_impl="xla")
    assert (tcfg.num_passes, tcfg.sandwich_norm, tcfg.exit_loss_beta,
            tcfg.remat_policy) == (4, True, 0.1, "full")
    model = TransformerLM(tcfg)
    key = jax.random.key(7)
    leaves, tree = jax.tree_util.tree_flatten(model.init(jax.random.key(0)))
    params = jax.tree_util.tree_unflatten(tree, [
        x + 0.1 * jax.random.normal(jax.random.fold_in(key, i), x.shape)
        for i, x in enumerate(leaves)])
    toks = np.random.default_rng(0).integers(0, 256, 48).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        loss, parts = model.loss_and_parts(
            params, {"input_ids": jnp.asarray(toks)[None]})
    want = reference_ouro.expected_exit_loss(
        TOY, modelcfg_ouro.weights_getter(params), toks, 0.1)
    assert abs(float(loss) - float(want["loss"])) <= 1e-5 * float(want["loss"])
    for name in ("pass_loss", "exit_prob", "exit_entropy"):
        np.testing.assert_allclose(np.asarray(parts[name]),
                                   np.asarray(want[name]), rtol=2e-5,
                                   atol=1e-6)


def test_operations_and_parameters_by_hand():
    with open(os.path.join(HERE, "..", "configs",
                           "ouro2_6b_train_d6.json")) as f:
        cfg = json.load(f)
    from deepspeed_tpu.models import TransformerLM

    tcfg = modelcfg_ouro.transformer_config(cfg, max_seq_len=4096,
                                            param_dtype="float32")
    made = sum(int(x.size) for x in jax.tree_util.tree_leaves(
        jax.eval_shape(TransformerLM(tcfg).init, jax.random.key(0))))
    assert opcount_ouro.total_params(cfg) == made
    assert opcount_ouro.layer_matmul_params(cfg) \
        == 4 * 2048 * 2048 + 3 * 2048 * 5632            # 51.38M
    assert opcount_ouro.layer_applications(cfg) == 24
    # the issue's 11.0 GFLOP a token and 45 TFLOP a step at 4096 tokens
    per_token = opcount_ouro.train_flops_per_token(cfg, 4096)
    assert 10.9e9 < per_token < 11.1e9
    assert 44.5e12 < per_token * 4096 < 45.5e12
    # four passes: the layers' and the head's matrices four times, the
    # attention of 24 block applications
    mat = 6 * 4 * (6 * opcount_ouro.layer_matmul_params(cfg) + 2048 * 49152)
    attn = 12 * 24 * 16 * 128 * (4096 * 4097 // 2) / 4096
    assert per_token == mat + attn
