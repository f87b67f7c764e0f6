"""What this benchmark holds of kanana-2-30b-a3b: the configuration keeps
what the publisher's ``config.json`` says (as the catalog beside the
``model-configs`` guide has it) and cuts depth, the experts held and the
vocabulary alone; the manifest lists the cell and its files exist; the
reference's copy with the program's tests is the same file; the operation
and byte counts are the arithmetic ``PERF.md`` states; the check's rules on
recorded numbers; the readers return nothing where there is nothing to
read."""

import filecmp
import importlib
import json
import os
import re

import numpy as np
import pytest

from benchmarks import opcount, opcount_kanana2 as oc, reference_kanana2
from benchmarks.readers import basic, mla
from benchmarks.runners import train_hybrid, train_mla_moe

HERE = os.path.dirname(os.path.abspath(__file__))
B = os.path.join(HERE, "..")
ROOT = os.path.join(B, "..")
CELL = "kanana2_30b_train_1chip"
CONFIG = "kanana2_30b_train_d5e16"
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 48,
    "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 128256}
NEW_METRICS = {
    "mla_proj_device_ms", "mla_rope_device_ms", "moe_shared_device_ms",
    "flash_fwd_roofline.mla", "flash_bwd_roofline.mla",
    "moe_experts_roofline.mla", "train_mfu.mla",
    "moe_bias_moved_per_step.train"}


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _json(B, "configs", f"{CONFIG}.json")


def test_the_configuration_keeps_the_published_values(cfg):
    assert sorted(cfg["reduced"]) == ["n_routed_experts",
                                      "num_hidden_layers", "vocab_size"]
    for key, val in PUBLISHED.items():
        if key in cfg["reduced"]:
            cut = cfg["reduced"][key]
            assert (cut["published"], cut["here"]) == (val, cfg[key]), key
        else:
            assert cfg[key] == val, key
    # the floors: the dense layer and four that follow, 8 routed experts a
    # layer, an eighth of the rows
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8 and cfg["router_width"] == 128
    assert cfg["vocab_size"] * 8 >= 128256
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] == 8
    assert (dep["bias_update_rate"], dep["balance_coef"], dep["bias_init"],
            dep["embed_init_std"]) == (0.001, 0.0001, 0.1, 1.0)
    for name in ("bias_update_rate", "balance_coef", "bias_init",
                 "embedding_init", "rope_layout"):
        assert len(cfg["assumed"][name]) > 60, name
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
    assert cfg["source"] == conf["source"] and len(conf["why"]) <= 200
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
    # the generic entries, the expert layer's and the eight of set-up
    for name in ("train_step_ms", "train_step_device_ms", "train_host_ms",
                 "attn_device_ms", "mlp_device_ms", "head_loss_device_ms",
                 "optimizer_device_ms", "unscoped_device_ms",
                 "device_idle_share.train", "compiles_in_window.train",
                 "moe_router_device_ms", "moe_dispatch_device_ms",
                 "moe_experts_device_ms", "moe_pairs_per_step.train",
                 "moe_pairs_dropped.train", "moe_load_max_over_mean.train",
                 "setup_import_s", "setup_step_first_call_s.train"):
        assert name in mine, name
    # their readers take another configuration's counts or kernel names
    assert not mine & {"flash_fwd_roofline", "flash_bwd_roofline",
                       "flash_fwd_roofline.mixed", "moe_experts_roofline",
                       "train_mfu", "train_mfu.moe", "train_mfu.ssm"}
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
        os.path.join(B, "reference_kanana2.py"),
        os.path.join(ROOT, "tests", "unit", "kanana_reference.py"),
        shallow=False)


def test_the_counts_are_the_hand_sums(cfg):
    mixer = (2048 * 32 * 192 + 2048 * 576 + 512 + 512 * 32 * 256
             + 4096 * 2048)
    assert oc.mla_params(cfg) == mixer == 26_345_984
    assert oc.dense_layer_params(cfg) == mixer + 3 * 2048 * 6144 + 4096 \
        == 64_098_816
    assert oc.shared_params(cfg) == 9_437_184
    assert oc.routed_layer_params(cfg) == (
        mixer + 9_437_184 + 262_272 + 16 * 4_718_592 + 4096) == 111_547_008
    assert oc.total_params(cfg) == 575_955_968
    assert oc.total_params(cfg) * 16 / 1e9 == pytest.approx(9.22, abs=0.005)
    # the program's own count says the same (toy-free: shapes only)
    from benchmarks import modelcfg_kanana2

    tcfg = modelcfg_kanana2.transformer_config(cfg, max_seq_len=8192,
                                               param_dtype="float32")
    assert tcfg.num_params_estimate() == 575_955_968
    assert tcfg.layer_kinds == ("mla:dense",) + ("mla:moe",) * 4
    # the cell's why: a routed layer's forward a token, MFLOP
    part = oc.layer_forward_flops_per_token(cfg, 8192)
    got = {k: round(v / 1e6, 1) for k, v in part.items()}
    assert got == {"mla_proj": 52.7, "scores_values": 83.9, "shared": 18.9,
                   "routed": 7.1, "router": 0.5}
    whole = sum(part.values())
    assert round(100 * (part["mla_proj"] + part["scores_values"]) / whole) \
        == 84
    assert oc.expected_pairs_per_token(cfg) == 0.75


def test_the_rooflines_work_is_the_shapes(cfg):
    pairs = opcount.causal_pairs(8192, 8192, None)
    assert pairs == 8192 * 8193 // 2
    fwd = oc.flash_forward(cfg, 8192, batch=2)
    assert fwd["flops"] == 2.0 * pairs * 32 * 2 * (192 + 128)
    assert fwd["bytes"] == 2 * 8192 * 32 * (2 * 192 + 2 * 128) * 2
    bwd = oc.flash_backward(cfg, 8192, batch=2)
    assert bwd["flops"] == 2 * fwd["flops"] and bwd["bytes"] == 2 * fwd["bytes"]
    g = oc.grouped_products(cfg, 12288, forwards=2, backwards=1)
    assert g["flops"] == 24.0 * 12288 * 2048 * 768
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    roof = opcount.roofline_seconds(fwd, peak)
    assert roof["bound"] == "compute"
    assert roof["seconds"] == pytest.approx(fwd["flops"] / 197e12)
    assert oc.train_flops_per_token(cfg, 8192) == pytest.approx(2.79e9,
                                                                rel=2e-3)


def test_the_kernel_patterns_find_the_calls_by_their_results():
    fwd = _json(B, "metrics", "flash_fwd_roofline.mla.json")["args"]
    bwd = _json(B, "metrics", "flash_bwd_roofline.mla.json")["args"]
    tile = "{3,2,1,0:T(8,128)(2,1)}"
    forward = ("%attn_mla.42 = (bf16[2,32,8192,128]" + tile + ", f32[2,32,1,"
               "8192]{3,2,1,0:T(1,128)}) custom-call(%a, %b, %c), "
               "custom_call_target=\"tpu_custom_call\"")
    fused = ("%attn_mla.41 = (bf16[2,32,8,1024,192]{4,3,2,1,0:T(8,128)(2,1)}"
             ", bf16[2,32,8192,192]" + tile + ", bf16[2,32,8192,128]" + tile
             + ") custom-call(%a), custom_call_target=\"tpu_custom_call\"")
    split_dq = ("%attn_mla.7 = bf16[2,32,8192,192]" + tile
                + " custom-call(%a), custom_call_target=\"tpu_custom_call\"")
    other = forward.replace("attn_mla", "attn_full")
    assert re.search(fwd["pattern"], forward)
    assert not re.search(fwd["pattern"], fused)
    assert not re.search(fwd["pattern"], other)
    assert re.search(bwd["pattern"], fused) and re.search(bwd["pattern"],
                                                          split_dq)
    assert not re.search(bwd["pattern"], forward)


RECORDED = {
    "loss": 10.1873, "lb_loss": 4.0312,
    "mix_out_ms": [0.51, 0.62, 0.71, 0.83, 0.9],
    "expert_pairs": [[768.0] * 16] * 4,
    "router_counts": np.full((4, 128), 768.0)}


def test_the_checks_rules_on_recorded_numbers(cfg):
    check = cfg["check"]
    assert check["compared"] == ["loss", "lb_loss", "mix_out_ms",
                                 "expert_pairs"]
    want = {k: np.asarray(v, np.float64) for k, v in RECORDED.items()}
    ok = {**want, "loss": want["loss"] + 0.5 * check["loss_abs_tol"]}
    assert train_hybrid.compare(ok, want, check)[0] == []
    for name, off in (("loss", 2 * check["loss_abs_tol"]),
                      ("lb_loss", 2 * check["lb_loss_abs_tol"]),
                      ("expert_pairs", 2 * check["expert_pairs_abs_tol"])):
        bad = {**want, name: want[name] + off}
        assert any(name in p for p in train_hybrid.compare(
            bad, want, check)[0]), name
    bad = {**want, "mix_out_ms": want["mix_out_ms"]
           * (1 + 2 * check["mix_out_ms_rel_tol"])}
    assert train_hybrid.compare(bad, want, check)[0]
    out = {**want, "loss": np.float64(check["first_loss_range"][1] + 0.1)}
    assert any("outside" in p for p in train_hybrid.compare(
        out, {**want, "loss": out["loss"]}, check)[0])
    lo, hi = check["first_loss_range"]
    assert lo < np.log(16032) < hi
    # the biases: the rule on the reference's counts, only where a count is
    # clear of the mean by more than the counts' own tolerance
    mods = {"reference": reference_kanana2}
    counts = np.full((4, 128), 768.0)
    counts[:, 0] += 100.0                  # clear of the mean: falls
    counts[:, 1] += 0.5 * check["expert_pairs_abs_tol"]     # too near
    before = np.zeros((4, 128), np.float32)
    rule = np.asarray(reference_kanana2.bias_after(before, counts, 1e-3))
    assert rule[0, 0] == pytest.approx(-1e-3) and rule[0, 5] == \
        pytest.approx(1e-3)
    near = rule.copy()
    near[:, 1] *= -1                       # a flip that is not held against
    problems, facts = train_mla_moe.compare_biases(
        before, near, {"router_counts": counts}, check, 1e-3, mods)
    assert problems == [] and facts["compared"] == 4 and facts["of"] == 512
    far = rule.copy()
    far[:, 0] *= -1
    problems, _ = train_mla_moe.compare_biases(
        before, far, {"router_counts": counts}, check, 1e-3, mods)
    assert len(problems) == 1 and "selection biases" in problems[0]
    problems, _ = train_mla_moe.compare_biases(
        before, before, {"router_counts": counts}, check, 1e-3, mods)
    assert any("moved no selection bias" in p for p in problems)


def test_the_readers_return_nothing_where_there_is_nothing_to_read(cfg):
    empty = {"cell": {"name": CELL}, "cfg": cfg, "peak": None, "trace": None,
             "reduced": {}, "values": {}}
    assert mla.flash_mla(empty, "x") is None
    assert mla.experts_roofline(empty) is None
    assert mla.train_mfu(empty) is None
    assert basic.value(empty, "moe_bias_moved_per_step") is None
    other = {**empty, "cfg": {"hidden_size": 1}, "peak": {
        "bf16_flops_per_s": 197e12}, "values": {"train_tok_s_chip": 1.0,
                                                "seq": 8192}}
    assert mla.train_mfu(other) is None and mla.flash_mla(other, "x") is None
    # the bias counter and the utilisation from what a run holds
    full = {**empty, "peak": {"bf16_flops_per_s": 197e12},
            "values": {"moe_bias_moved_per_step": 508.5,
                       "train_tok_s_chip": 28000.0, "seq": 8192}}
    assert basic.value(full, "moe_bias_moved_per_step") == 508.5
    assert mla.train_mfu(full) == pytest.approx(
        100 * 28000.0 * oc.train_flops_per_token(cfg, 8192) / 197e12)
