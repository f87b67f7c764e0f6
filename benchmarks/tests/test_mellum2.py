"""What this benchmark holds of Mellum2-12B-A2.5B: the configuration keeps
what the publisher's ``config.json`` says (as the catalog beside the
``model-configs`` guide has it) and cuts depth, experts held and vocabulary
alone; the manifest lists the cell and its files exist; the reference's copy
with the program's tests is the same file and agrees with the program at a
toy size; the operation counts are the arithmetic ``PERF.md`` states; the
readers read what they say and return nothing where there is nothing."""

import filecmp
import importlib
import json
import os

import jax
import numpy as np
import pytest

from benchmarks import (modelcfg_mellum2, opcount, opcount_mellum2,
                        reference_mellum2)
from benchmarks.readers import moe_share

HERE = os.path.dirname(os.path.abspath(__file__))
B = os.path.join(HERE, "..")
ROOT = os.path.join(B, "..")
CELL = "mellum2_12b_train_1chip"
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168, "layer_types": PERIOD * 7,
    "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
    "max_window_layers": 0, "model_type": "mellum",
    "moe_intermediate_size": 896, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 28, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}
NEW_METRICS = {
    "flash_fwd_roofline.mixed", "flash_bwd_roofline.mixed",
    "moe_router_device_ms", "moe_dispatch_device_ms", "moe_experts_device_ms",
    "moe_experts_roofline", "train_mfu.moe", "moe_pairs_per_step.train",
    "moe_pairs_dropped.train", "moe_load_max_over_mean.train"}


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _json(B, "configs", "mellum2_12b_train_d4e16.json")


def test_the_configuration_keeps_the_published_values(cfg):
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers",
                                      "vocab_size"]
    for key, val in PUBLISHED.items():
        if key in cfg["reduced"]:
            cut = cfg["reduced"][key]
            assert (cut["published"], cut["here"]) == (val, cfg[key]), key
        else:
            assert cfg[key] == val, key
    # the floors: a whole period, at least 8 experts, an eighth of the rows
    assert cfg["layer_types"][:cfg["num_hidden_layers"]] == PERIOD
    assert cfg["num_experts"] >= 8 and cfg["vocab_size"] * 8 >= 98304
    assert cfg["router_width"] == 64 and cfg["first_expert"] == 0
    assert cfg["deployment"]["chips_sharing_a_layer"] == 4
    for part in ("source", "assumed", "deployment", "check", "modules"):
        assert cfg[part], part
    for text in (cfg["deployment"]["remat_why"], cfg["check"]["tol_why"],
                 cfg["deployment"]["local_pairs_why"]):
        assert len(text) > 100


def test_the_manifest_lists_the_cell_and_its_files_exist(cfg):
    m = _json(ROOT, "BENCHMARK.json")
    entry = {w["name"]: w for w in m["workloads"]}[CELL]
    f = _json(B, "workloads", f"{CELL}.json")
    assert (f["config"], f["traffic"], f["chips"], f["why"]) == \
        (entry["config"], entry["traffic"], entry["chips"], entry["why"])
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    conf = {c["name"]: c for c in m["configs"]}[entry["config"]]
    assert conf["file"] == "benchmarks/configs/mellum2_12b_train_d4e16.json"
    assert sorted(conf["reduced"]) == sorted(cfg["reduced"])
    assert cfg["source"] == conf["source"]
    traffic = _json(B, "traffic", f"{entry['traffic']}.json")
    assert (traffic["kind"], traffic["seq_len"], traffic["rows_per_chip"]) \
        == ("train", 8192, 2)
    for mod in cfg["modules"].values():
        importlib.import_module(f"benchmarks.{mod}")
    assert callable(importlib.import_module(
        f"benchmarks.runners.{f['runner']}").run)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert CELL in e2e["train_tok_s_chip"]["workloads"]
    mine = {p["name"] for p in m["per_layer"] if CELL in p["workloads"]}
    assert NEW_METRICS <= mine
    # their readers give every call one window's work, or count a dense FFN
    assert not mine & {"flash_fwd_roofline", "flash_bwd_roofline",
                       "flash_bwd_fused_roofline", "train_mfu",
                       "train_mfu.looped"}
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
        os.path.join(B, "reference_mellum2.py"),
        os.path.join(ROOT, "tests", "unit", "mellum_reference.py"),
        shallow=False)


def test_the_mapping_gives_the_program_the_published_model(cfg):
    t = modelcfg_mellum2.transformer_config(cfg, max_seq_len=8192,
                                            param_dtype="float32")
    assert (t.hidden_size, t.num_heads, t.num_kv_heads, t.head_dim,
            t.num_layers, t.vocab_size, t.sliding_window) \
        == (2304, 32, 4, 128, 4, 24576, 1024)
    assert t.attn_pattern == ("window", "window", "window", "full")
    assert (t.num_experts, t.top_k, t.moe_experts_held, t.moe_first_expert,
            t.moe_intermediate_size, t.moe_dispatch, t.moe_aux_loss_coef) \
        == (64, 8, 16, 0, 896, "grouped", 0.001)
    assert t.kind_cfg("full").rope_scaling["rope_type"] == "yarn"
    assert t.kind_cfg("window").rope_scaling is None
    assert t.norm_eps == 1e-6 and not t.tie_embeddings
    # the program's initialiser makes the parameters the count says
    from deepspeed_tpu.models import TransformerLM

    shapes = jax.eval_shape(TransformerLM(t).init, jax.random.key(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == opcount_mellum2.total_params(cfg)


def test_the_reference_agrees_with_the_program_at_a_toy_size(cfg):
    from deepspeed_tpu.models import TransformerLM

    toy = {**cfg, "hidden_size": 64, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 128,
           "moe_intermediate_size": 32, "sliding_window": 8,
           "num_experts": 4, "router_width": 16, "first_expert": 4,
           "num_experts_per_tok": 4}
    model = TransformerLM(modelcfg_mellum2.transformer_config(
        toy, max_seq_len=32, param_dtype="float32", dtype="float32",
        attention_impl="xla"))
    params = model.init(jax.random.key(2))
    rows = np.random.default_rng(3).integers(0, 128, (2, 24)).astype(np.int32)
    want = reference_mellum2.batch_loss(
        toy, modelcfg_mellum2.weights_getter(params), rows, 0.001)
    loss, parts = model.loss_and_parts(params, {"input_ids": rows})
    np.testing.assert_allclose(loss, want["loss"], atol=2e-5)
    np.testing.assert_allclose(parts["lb_loss"], want["lb_loss"], rtol=1e-5)
    np.testing.assert_array_equal(parts["expert_pairs"],
                                  want["expert_pairs"])


def test_the_operation_counts(cfg):
    oc = opcount_mellum2
    assert oc.attn_params(cfg) == 2 * 2304 * 4096 + 2 * 2304 * 512
    assert oc.expert_params(cfg) == 3 * 2304 * 896
    assert oc.layer_params(cfg) == 21233664 + 2304 * 64 + 16 * 6193152 \
        + 2 * 2304
    assert oc.total_params(cfg) == 4 * oc.layer_params(cfg) \
        + 2 * 24576 * 2304 + 2304
    assert oc.expected_pairs_per_token(cfg) == 2.0
    T = 8192
    full, win = opcount.causal_pairs(T, T), opcount.causal_pairs(T, T, 1024)
    assert win == 1024 * 1025 // 2 + (T - 1024) * 1024 and full > 4 * win
    assert oc.flash_pairs(cfg, T) == [win, win, win, full]
    fwd, bwd = oc.flash_forward(cfg, T, 2), oc.flash_backward(cfg, T, 2)
    assert fwd["flops"] == 4.0 * (3 * win + full) * 32 * 128 * 2
    assert bwd["flops"] == 2 * fwd["flops"]
    assert fwd["bytes"] == 4 * 2 * T * 2 * 36 * 128 * 2
    assert bwd["bytes"] == 2 * fwd["bytes"]
    # a token: 6 x (attention, router, two experts a layer, the head over
    # the slice) + attention over each layer's mean context
    per_layer = 21233664 + 147456 + 2 * 6193152
    want = 6.0 * (4 * per_layer + 2304 * 24576) \
        + 12.0 * 32 * 128 * (3 * win + full) / T
    assert oc.train_flops_per_token(cfg, T) == want
    g = oc.grouped_products(cfg, 32768, forwards=2, backwards=1)
    assert g["flops"] == (12.0 + 12.0) * 32768 * 2304 * 896
    one = oc.grouped_products(cfg, 32768)
    assert one["bytes"] == 16 * 3 * 2304 * 896 * 2 \
        + 32768 * (3 * 2304 + 4 * 896) * 2


class _K:
    def __init__(self, calls, seconds):
        self.v = {"calls": calls, "seconds": seconds}


def test_the_flash_share_sums_the_work_by_the_layers_kinds(cfg, monkeypatch):
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"cfg": cfg, "peak": peak,
           "values": {"seq": 8192, "rows": 2, "chips": 1}}
    monkeypatch.setattr(moe_share.roofline, "_kernel",
                        lambda ctx, pattern, field: {"calls": 16,
                                                     "seconds": 0.05})
    got = moe_share.flash_mixed(ctx, "x", "label", "forward")
    ops = opcount_mellum2.flash_forward(cfg, 8192, 2)
    want = 100.0 * 4 * ops["flops"] / 197e12 / 0.05
    assert got == pytest.approx(want) and 0 < got < 100
    assert ctx["roofline_notes"][0]["bound"] == "compute"
    # nothing found, or another model's configuration: nothing, no raise
    monkeypatch.setattr(moe_share.roofline, "_kernel", lambda *a: None)
    assert moe_share.flash_mixed(ctx, "x") is None
    monkeypatch.setattr(moe_share.roofline, "_kernel",
                        lambda *a: {"calls": 2, "seconds": 1.0})
    assert moe_share.flash_mixed({**ctx, "cfg": {"hidden_size": 1}},
                                 "x") is None


def test_the_experts_share_and_the_utilisation(cfg, monkeypatch):
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"cfg": cfg, "peak": peak,
           "values": {"seq": 8192, "rows": 2, "chips": 1,
                      "moe_pairs_per_step": 4 * 32768.0,
                      "train_tok_s_chip": 40000.0}}
    monkeypatch.setattr(moe_share, "scope_device_ms",
                        lambda ctx, scope, op_name=None: 100.0)
    got = moe_share.experts_roofline(ctx)
    # a forward once (twice under a recomputation policy) and a backward
    fw = 1 if cfg["deployment"]["remat_policy"] == "none" else 2
    assert moe_share._forwards(cfg) == fw
    flops = 4 * (6.0 * fw + 12.0) * 32768 * 2304 * 896
    assert got == pytest.approx(100.0 * flops / 197e12 / 0.1)
    full = {**cfg, "deployment": {**cfg["deployment"], "remat_policy": "full"}}
    assert moe_share.experts_roofline({**ctx, "cfg": full}) \
        == pytest.approx(100.0 * 4 * 24.0 * 32768 * 2304 * 896 / 197e12 / 0.1)
    assert moe_share.train_mfu(ctx) == pytest.approx(
        100.0 * opcount_mellum2.train_flops_per_token(cfg, 8192) * 40000.0
        / 197e12)
    monkeypatch.setattr(moe_share, "scope_device_ms", lambda *a, **k: None)
    assert moe_share.experts_roofline(ctx) is None
    assert moe_share.train_mfu({**ctx, "peak": None}) is None
    # no trace, no program: the scope reader itself returns nothing
    monkeypatch.undo()
    assert moe_share.scope_device_ms(
        {"cell": {"name": "no_such_cell"}, "values": {}, "program": {}},
        "moe_experts", "^ragged-dot") is None


def test_the_runner_refuses_a_program_without_the_model(monkeypatch):
    """On the parent commit the cell fails at once and says what is
    missing."""
    import dataclasses

    import deepspeed_tpu.models as models
    from benchmarks.runners import train_moe_share

    @dataclasses.dataclass
    class Old:
        hidden_size: int = 1

    monkeypatch.setattr(models, "TransformerConfig", Old)
    with pytest.raises(SystemExit, match="attn_pattern"):
        train_moe_share.run({"name": CELL}, None)
