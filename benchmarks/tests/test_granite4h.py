"""What this benchmark holds of Granite-4.0-H-Micro: the configuration keeps
what the publisher's ``config.json`` says (as the catalog beside the
``model-configs`` guide has it) and cuts depth and vocabulary alone; the
manifest lists the cell and its files exist; the reference's copy with the
program's tests is the same file and agrees with the program at a toy size;
the operation and byte counts are the arithmetic ``PERF.md`` states; the
check's rule; the readers return nothing where there is nothing to read."""

import filecmp
import importlib
import json
import os

import jax
import numpy as np
import pytest

from benchmarks import (modelcfg_granite4h, opcount, opcount_granite4h,
                        reference_granite4h)
from benchmarks.readers import hybrid
from benchmarks.runners import train_hybrid

HERE = os.path.dirname(os.path.abspath(__file__))
B = os.path.join(HERE, "..")
ROOT = os.path.join(B, "..")
CELL = "granite4_h_micro_train_1chip"
CONFIG = "granite4_h_micro_train_d10v8"
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": PERIOD * 4, "logits_scaling": 8,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 0, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}
NEW_METRICS = {
    "ssm_scan_device_ms", "ssm_proj_device_ms", "ssm_conv_device_ms",
    "ssm_gate_device_ms", "ssm_scan_roofline", "train_mfu.ssm",
    "ssm_chunks_per_step.train"}


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _json(B, "configs", f"{CONFIG}.json")


def test_the_configuration_keeps_the_published_values(cfg):
    assert sorted(cfg["reduced"]) == ["num_hidden_layers", "vocab_size"]
    for key, val in PUBLISHED.items():
        if key in cfg["reduced"]:
            cut = cfg["reduced"][key]
            assert (cut["published"], cut["here"]) == (val, cfg[key]), key
        else:
            assert cfg[key] == val, key
    # the floors: a whole period, an eighth of the rows
    assert cfg["layer_types"][:cfg["num_hidden_layers"]] == PERIOD
    assert cfg["vocab_size"] * 8 >= 100352
    dep = cfg["deployment"]
    assert (dep["pipeline_stages"], dep["chips_sharing_the_table"]) == (4, 8)
    for part in ("source", "assumed", "deployment", "check", "modules"):
        assert cfg[part], part
    for text in (dep["remat_why"], dep["embed_init_why"],
                 cfg["check"]["tol_why"]):
        assert len(text) > 100


def test_the_manifest_lists_the_cell_and_its_files_exist(cfg):
    m = _json(ROOT, "BENCHMARK.json")
    entry = {w["name"]: w for w in m["workloads"]}[CELL]
    f = _json(B, "workloads", f"{CELL}.json")
    assert (f["config"], f["traffic"], f["chips"], f["why"]) == \
        (entry["config"], entry["traffic"], entry["chips"], entry["why"])
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    conf = {c["name"]: c for c in m["configs"]}[entry["config"]]
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert sorted(conf["reduced"]) == sorted(cfg["reduced"])
    assert cfg["source"] == conf["source"]
    traffic = _json(B, "traffic", f"{entry['traffic']}.json")
    assert (traffic["kind"], traffic["seq_len"], traffic["rows_per_chip"]) \
        == ("train", 4096, 1)
    for mod in cfg["modules"].values():
        importlib.import_module(f"benchmarks.{mod}")
    assert callable(importlib.import_module(
        f"benchmarks.runners.{f['runner']}").run)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert CELL in e2e["train_tok_s_chip"]["workloads"]
    mine = {p["name"] for p in m["per_layer"] if CELL in p["workloads"]}
    assert NEW_METRICS <= mine and len(mine) == len(NEW_METRICS) + 15
    # their readers count attention in every layer, or find the flash
    # kernels under the name %attn (here they are %attn_full)
    assert not mine & {"flash_fwd_roofline", "flash_bwd_roofline",
                       "flash_bwd_fused_roofline", "train_mfu",
                       "train_mfu.looped", "train_mfu.moe"}
    for p in m["per_layer"]:
        if p["name"] in NEW_METRICS:
            assert p["workloads"] == [CELL]
            assert p["moves"] == "train_tok_s_chip"
            spec = _json(B, "metrics", p["name"] + ".json")
            mod, fn = spec["reader"].split(":")
            assert callable(getattr(importlib.import_module(
                f"benchmarks.{mod}"), fn))


def test_the_copy_with_the_programs_tests_is_the_same_file():
    assert filecmp.cmp(
        os.path.join(B, "reference_granite4h.py"),
        os.path.join(ROOT, "tests", "unit", "granite_reference.py"),
        shallow=False)


def test_the_counts_are_the_arithmetic_perf_md_states(cfg):
    oc = opcount_granite4h
    assert oc.layer_params(cfg, "mamba") == 76_182_976
    assert oc.layer_params(cfg, "attention") == 60_821_504
    assert oc.mamba_params(cfg) == {"matrices": 17_432_576 + 8_388_608,
                                    "other": 21_760 + 192 + 4096}
    assert oc.total_params(cfg) == 9 * 76_182_976 + 60_821_504 \
        + 12544 * 2048 + 2048 == 772_160_448
    assert 772_160_448 * 16 == pytest.approx(12.35e9, rel=1e-3)
    # the whole model: 36 + 4 layers and the whole table, the name's "3B"
    whole = {**cfg, "num_hidden_layers": 40, "vocab_size": 100352}
    assert oc.total_params(whole) == pytest.approx(3.19e9, rel=2e-3)
    # the mixer is 34 % of a mamba layer's matrix parameters, the MLP 66 %
    share = oc.mamba_params(cfg)["matrices"] / (
        oc.mamba_params(cfg)["matrices"] + oc.mlp_params(cfg))
    assert share == pytest.approx(0.339, abs=1e-3)
    # a step: 6 x the matrices a token meets, the scans, the attention layer
    mat = oc.matmul_params_per_token(cfg)
    assert mat == 9 * 25_821_184 + 10_485_760 + 10 * 50_331_648 \
        + 2048 * 12544
    per_tok = oc.train_flops_per_token(cfg, 4096)
    assert 6 * mat == pytest.approx(4.63e9, rel=1e-3)
    assert per_tok - 6 * mat == pytest.approx(0.166e9, rel=2e-2)
    assert per_tok * 4096 == pytest.approx(19.65e12, rel=1e-3)


def test_the_scans_work_is_a_function_of_the_shapes_alone(cfg):
    oc = opcount_granite4h
    one = oc.ssd_scan(cfg, 4096)
    # a position: C B^T once a group, the masked product, the end state and
    # the carried state's part
    per_pos = 2 * 256 * 128 * 1 + 2 * 256 * 64 * 64 + 4 * 64 * 128 * 64
    assert one["flops"] == 4096 * per_pos == 17_448_304_640
    # x and y bf16, dt float32, B and C bf16; 16 chunk states written, read
    assert one["bytes"] == 4096 * (2 * 4096 * 2 + 64 * 4 + 2 * 128 * 2) \
        + 2 * 16 * 64 * 64 * 128 * 4 == 137_363_456
    # two forwards (recomputation) and a backward of twice a forward's work
    step = oc.ssd_scan(cfg, 4096, forwards=2, backwards=1)
    assert step == {"flops": 4 * one["flops"], "bytes": 4 * one["bytes"]}
    assert oc.ssd_scan(cfg, 4096, batch=2)["flops"] == 2 * one["flops"]
    # a ragged tail still holds a chunk state
    assert oc.ssd_scan(cfg, 4097)["bytes"] - one["bytes"] \
        == (2 * 4096 * 2 + 64 * 4 + 2 * 128 * 2) + 2 * 64 * 64 * 128 * 4
    # on a v5e the scan is bound by bytes: 0.168 ms a forward, 6.0 ms a step
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    roof = opcount.roofline_seconds(one, peak)
    assert roof["bound"] == "memory"
    assert roof["seconds"] == pytest.approx(0.1677e-3, rel=1e-3)
    # readers/roofline.py:flash_train's count for the attention layer's one
    # full causal call, heads of 64, 32 / 8, is right from this file's keys
    # (were its pattern to find a kernel named %attn_full)
    flash = opcount.flash_forward(cfg, 4096)
    assert flash["flops"] == 4.0 * (4096 * 4097 // 2) * 32 * 64
    assert flash["bytes"] == 4096 * 2 * (32 + 8) * 64 * 2
    assert opcount.flash_backward(cfg, 4096)["flops"] == 2 * flash["flops"]


def test_the_program_agrees_with_the_reference_at_a_toy_size(cfg):
    """Through the benchmark's own mapping and getter: the period's kinds, the
    per-kind stacks, the float32 leaves."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import TransformerLM

    toy = {**cfg, "hidden_size": 64, "num_attention_heads": 4,
           "num_key_value_heads": 2, "shared_intermediate_size": 128,
           "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
           "mamba_chunk_size": 8, "vocab_size": 96}
    tcfg = modelcfg_granite4h.transformer_config(
        toy, max_seq_len=32, param_dtype="float32", dtype="float32",
        attention_impl="xla")
    assert tcfg.attn_pattern == ("ssm",) * 5 + ("full",) + ("ssm",) * 4
    assert tcfg.remat_policy == cfg["deployment"]["remat_policy"]
    model = TransformerLM(tcfg)
    params = model.init(jax.random.key(1))
    rows = np.random.default_rng(1).integers(0, 96, (2, 20)).astype(np.int32)
    seen = []
    get = modelcfg_granite4h.weights_getter(
        params, toy, lambda w: w, lambda w: seen.append(w.shape) or w)
    want = reference_granite4h.batch_loss(toy, get, rows)
    loss, parts = jax.jit(model.loss_and_parts)(params, {"input_ids": rows})
    np.testing.assert_allclose(loss, want["loss"], atol=2e-5)
    np.testing.assert_allclose(parts["mix_out_ms"], want["mix_out_ms"],
                               rtol=5e-5)
    assert set(seen) == {(8,)} and len(seen) == 2 * 9 * 3
    assert get("in_proj", 6).shape == (64, 2 * 128 + 2 * 16 + 8)
    assert jnp.array_equal(get("wq", 5), params["layers"]["attn"]["wq"][0])
    assert jnp.array_equal(get("in_proj", 6),
                           params["layers"]["ssm"]["in_proj"][5])


def test_the_checks_rule(cfg):
    check = {"compared": ["loss", "mix_out_ms"], "loss_abs_tol": 0.001,
             "mix_out_ms_rel_tol": 0.01, "first_loss_range": [9.0, 10.0]}
    want = {"loss": 9.44, "mix_out_ms": np.array([1.0, 0.002])}
    good = {"loss": 9.4405, "mix_out_ms": np.array([1.009, 0.00201])}
    problems, facts = train_hybrid.compare(good, want, check)
    assert problems == []
    assert facts["mix_out_ms"]["max_rel_diff"] == pytest.approx(0.009)
    # relative, so the small layer is held as tightly as the large ones
    bad = {"loss": 9.4405, "mix_out_ms": np.array([1.0, 0.00203])}
    assert len(train_hybrid.compare(bad, want, check)[0]) == 1
    assert len(train_hybrid.compare({**good, "loss": 9.442}, want,
                                    check)[0]) == 1
    assert len(train_hybrid.compare({**good, "loss": np.nan}, want,
                                    check)[0]) == 2
    assert "shape" in train_hybrid.compare(
        {"loss": 9.44, "mix_out_ms": np.ones(3)}, want, check)[0][0]
    # the cell's own: every compared quantity has a limit
    for name in cfg["check"]["compared"]:
        assert f"{name}_abs_tol" in cfg["check"] \
            or f"{name}_rel_tol" in cfg["check"]


def test_the_readers_return_nothing_where_there_is_nothing(cfg):
    mistral = _json(B, "configs", "mistral7b_train_d2.json")
    ctx = {"values": {"train_tok_s_chip": 30000.0, "seq": 4096, "rows": 1,
                      "chips": 1},
           "cfg": mistral, "peak": {"bf16_flops_per_s": 197e12,
                                    "hbm_bytes_per_s": 819e9},
           "cell": {"name": "mistral7b_train_1chip"}, "trace": None,
           "reduced": {}}
    assert hybrid.scan_roofline(ctx) is None
    assert hybrid.train_mfu(ctx) is None
    ctx["cfg"] = cfg
    assert hybrid.scan_roofline(ctx) is None          # no trace
    assert hybrid.train_mfu({**ctx, "peak": None}) is None
    mfu = hybrid.train_mfu({**ctx, "values": {**ctx["values"],
                                              "train_tok_s_chip": 14000.0}})
    assert mfu == pytest.approx(
        100 * 14000 * opcount_granite4h.train_flops_per_token(cfg, 4096)
        / 197e12)
    assert 30 < mfu < 40
    # no step program in this process: no row to read
    assert hybrid.chunks_per_step(ctx) is None or \
        hybrid.chunks_per_step(ctx) >= 0
