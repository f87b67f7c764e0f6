"""What this benchmark holds of Laguna-S-2.1: the configuration keeps what
the publisher's ``config.json`` says (as the catalog beside the
``model-configs`` guide has it) and cuts depth, the heads, the experts held
and the vocabulary alone; the manifest lists the cell, its metrics and their
readers; the operation and byte counts are the arithmetic ``PERF.md`` states
and the program's own; the check's rules on recorded numbers; the readers on
a trace recorded on the chip, and nothing where there is nothing to read."""

import gzip
import importlib
import json
import os
import re
import shutil

import numpy as np
import pytest

from benchmarks import opcount, opcount_laguna as oc, reference_laguna
from benchmarks.readers import heads
from benchmarks.runners import train_heads_moe, train_hybrid, train_mla_moe

HERE = os.path.dirname(os.path.abspath(__file__))
B = os.path.join(HERE, "..")
ROOT = os.path.join(B, "..")
CELL = "laguna_s21_train_1chip"
CONFIG = "laguna_s21_train_d5h24e8v8"
PERIOD = ["full_attention"] + ["sliding_attention"] * 3
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
    "intermediate_size": 12288, "num_hidden_layers": 48,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head",
    "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": PERIOD * 12,
    "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "gating_types": ["per_head"] * 48, "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
    "moe_router_logit_softcapping": 0}
NEW_METRICS = {
    "attn_window_device_ms", "attn_full_device_ms", "attn_gate_device_ms",
    "attn_heads_per_step.train", "flash_fwd_roofline.heads",
    "flash_bwd_roofline.heads", "moe_experts_roofline.heads",
    "train_mfu.heads"}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _json(B, "configs", f"{CONFIG}.json")


def test_the_configuration_keeps_the_published_values(cfg):
    assert sorted(cfg["reduced"]) == [
        "num_attention_heads", "num_attention_heads_per_layer",
        "num_experts", "num_hidden_layers", "num_key_value_heads",
        "vocab_size"]
    for key, val in PUBLISHED.items():
        if key == "num_attention_heads_per_layer":
            assert cfg[key] == [n // 2 for n in val]
        elif key in cfg["reduced"]:
            cut = cfg["reduced"][key]
            assert (cut["published"], cut["here"]) == (val, cfg[key]), key
        else:
            assert cfg[key] == val, key
    # the floors: the dense layer and the whole period after it, 8 routed
    # experts a layer, an eighth of the rows; every width as published
    assert cfg["num_hidden_layers"] - len(cfg["mlp_only_layers"]) >= 4
    assert cfg["layer_types"][1:5] == PERIOD[1:] + PERIOD[:1]
    assert cfg["num_experts"] >= 8 and cfg["router_width"] == 256
    assert cfg["vocab_size"] * 8 >= 100352
    assert (cfg["heads"], cfg["kv_heads"]) == (48, 8)
    # the same half of each kind's heads, whole groups of 6 and of 9
    L = cfg["num_hidden_layers"]
    assert cfg["num_attention_heads_per_layer"][:L] == [24, 36, 36, 36, 24]
    assert {n // cfg["num_key_value_heads"]
            for n in cfg["num_attention_heads_per_layer"]} == {6, 9}
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] == 32
    assert (dep["bias_update_rate"], dep["balance_coef"], dep["bias_init"],
            dep["embed_init_std"]) == (0.001, 0.0001, 0.1, 1.0)
    assert dep["ds_config"]["optimizer"]["params"]["lr"] == 1e-6
    for name in ("router_scoring", "head_gate", "hidden_act", "qk_norm",
                 "partial_rope", "bias_init", "embedding_init"):
        assert len(cfg["assumed"][name]) > 40, name
    for part in ("source", "assumed", "deployment", "check", "modules"):
        assert cfg[part], part
    for text in (dep["remat_why"], dep["local_pairs_why"],
                 dep["embed_init_why"], cfg["check"]["tol_why"]):
        assert len(text) > 100


def test_the_manifest_lists_the_cell_and_its_files_exist(cfg):
    m = _json(ROOT, "BENCHMARK.json")
    entry = {w["name"]: w for w in m["workloads"]}[CELL]
    f = _json(B, "workloads", f"{CELL}.json")
    assert (f["config"], f["traffic"], f["chips"], f["why"]) == \
        (entry["config"], entry["traffic"], entry["chips"], entry["why"])
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert entry["traffic"] == "packed_8k_1row"
    conf = {c["name"]: c for c in m["configs"]}[entry["config"]]
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert sorted(conf["reduced"]) == sorted(cfg["reduced"])
    assert cfg["source"] == conf["source"] and len(conf["why"]) <= 200
    traffic = _json(B, "traffic", f"{entry['traffic']}.json")
    assert (traffic["kind"], traffic["seq_len"], traffic["rows_per_chip"]) \
        == ("train", 8192, 1)
    for mod in cfg["modules"].values():
        importlib.import_module(f"benchmarks.{mod}")
    assert f["runner"] == "train_heads_moe"
    assert callable(train_heads_moe.run)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert CELL in e2e["train_tok_s_chip"]["workloads"]
    mine = {p["name"] for p in m["per_layer"] if CELL in p["workloads"]}
    assert NEW_METRICS <= mine
    for name in ("train_step_ms", "train_step_device_ms", "train_host_ms",
                 "attn_device_ms", "mlp_device_ms", "head_loss_device_ms",
                 "optimizer_device_ms", "unscoped_device_ms",
                 "device_idle_share.train", "compiles_in_window.train",
                 "moe_router_device_ms", "moe_dispatch_device_ms",
                 "moe_experts_device_ms", "moe_shared_device_ms",
                 "moe_pairs_per_step.train", "moe_pairs_dropped.train",
                 "moe_load_max_over_mean.train",
                 "moe_bias_moved_per_step.train",
                 "layer_applications_per_step.train",
                 "setup_import_s", "setup_step_first_call_s.train"):
        assert name in mine, name
    # their readers take another configuration's counts or kernel names
    assert not mine & {"flash_fwd_roofline", "flash_bwd_roofline",
                       "flash_fwd_roofline.mixed", "moe_experts_roofline",
                       "moe_experts_roofline.mla", "train_mfu",
                       "train_mfu.moe", "train_mfu.mla"}
    for p in m["per_layer"]:
        if p["name"] in NEW_METRICS:
            assert p["workloads"] == [CELL]
            assert p["moves"] == "train_tok_s_chip"
            spec = _json(B, "metrics", p["name"] + ".json")
            assert spec["name"] == p["name"]
            mod, fn = spec["reader"].split(":")
            assert callable(getattr(importlib.import_module(
                f"benchmarks.{mod}"), fn))
            if "roofline" in p["name"] or "mfu" in p["name"]:
                assert p["unit"] == "%"


def test_the_counts_are_the_hand_sums_and_the_programs(cfg):
    D = 3072
    full = 2 * D * 24 * 128 + 2 * D * 4 * 128 + D * 24
    window = 2 * D * 36 * 128 + 2 * D * 4 * 128 + D * 36
    assert (oc.attn_params(cfg, 0), oc.attn_params(cfg, 1)) \
        == (full, window) == (22_093_824, 31_567_872)
    assert oc.expert_params(cfg) == oc.shared_params(cfg) == 9_437_184
    routed = 9_437_184 + D * 256 + 256 + 8 * 9_437_184
    assert routed == 85_721_344
    assert [oc.layer_params(cfg, i) for i in range(5)] == [
        full + 3 * D * 12288 + 2 * D, window + routed + 2 * D,
        window + routed + 2 * D, window + routed + 2 * D,
        full + routed + 2 * D] == [135_346_176, 117_295_360, 117_295_360,
                                   117_295_360, 107_821_312]
    assert oc.total_params(cfg) == 672_126_976 \
        == cfg["deployment"]["parameters"]
    assert cfg["deployment"]["state_bytes_at_18"] == 18 * 672_126_976
    assert oc.total_params(cfg) * 18 / 1e9 == pytest.approx(12.10, abs=0.005)
    # the program's own count says the same (shapes only)
    from benchmarks import modelcfg_laguna

    tcfg = modelcfg_laguna.transformer_config(cfg, max_seq_len=8192,
                                              param_dtype="float32")
    assert tcfg.num_params_estimate() == 672_126_976
    assert tcfg.layer_kinds == ("full:dense", "window:moe", "window:moe",
                                "window:moe", "full:moe")
    assert (tcfg.heads_held, tcfg.num_heads, tcfg.heads_by_kind) == (
        24, 48, {"window": 72})
    assert oc.kinds(cfg)[0] == ("full_attention", "dense")
    assert oc.heads(cfg) == [24, 36, 36, 36, 24]
    assert sum(oc.heads(cfg)) == 156
    assert oc.expected_pairs_per_token(cfg) == 10 * 8 / 256
    # what the runner holds the step program's row to
    assert train_heads_moe.heads_said(cfg) == {
        "heads_held": {"full": (24, 48), "window": (36, 72)},
        "attn_heads_per_step": 156}


def test_the_rooflines_work_is_each_layers_own_shapes(cfg):
    full = opcount.causal_pairs(8192, 8192, None)
    window = opcount.causal_pairs(8192, 8192, 512)
    assert full == 8192 * 8193 // 2
    assert window == 512 * 513 // 2 + (8192 - 512) * 512
    assert oc.flash_pairs(cfg, 8192) == [full, window, window, window, full]
    fwd = oc.flash_forward(cfg, 8192)
    assert fwd["flops"] == 4.0 * 128 * (2 * full * 24 + 3 * window * 36)
    assert fwd["bytes"] == 8192 * 2 * 128 * 2 * (2 * (24 + 4) + 3 * (36 + 4))
    bwd = oc.flash_backward(cfg, 8192)
    assert bwd["flops"] == 2 * fwd["flops"] and bwd["bytes"] == 2 * fwd["bytes"]
    g = oc.grouped_products(cfg, 2560, forwards=2, backwards=1)
    assert g["flops"] == 24.0 * 2560 * 3072 * 1024
    assert opcount.roofline_seconds(fwd, PEAK)["bound"] == "compute"
    flops = oc.train_flops_per_token(cfg, 8192)
    assert flops == pytest.approx(2.444e9, rel=1e-3)
    # the cell's why: about half of the step under attn, a quarter in the
    # dense layer's FFN, a few percent in the routed experts
    attn = 6.0 * sum(oc.attn_params(cfg, i) for i in range(5)) + sum(
        12.0 * H * 128 * p / 8192
        for p, H in zip(oc.flash_pairs(cfg, 8192), oc.heads(cfg)))
    assert attn / flops == pytest.approx(0.50, abs=0.01)
    assert 6.0 * 3 * 3072 * 12288 / flops == pytest.approx(0.278, abs=0.005)
    assert 4 * 6.0 * 0.3125 * 9_437_184 / flops == pytest.approx(0.029,
                                                                 abs=0.002)


def test_the_kernel_patterns_find_both_kinds_calls_by_their_results():
    fwd = _json(B, "metrics", "flash_fwd_roofline.heads.json")["args"]
    bwd = _json(B, "metrics", "flash_bwd_roofline.heads.json")["args"]
    tile = "{3,2,1,0:T(8,128)(2,1)}"
    for kind, H in (("attn_window", 36), ("attn_full", 24)):
        forward = (f"%{kind}.34 = (bf16[1,{H},8192,128]" + tile
                   + f", f32[1,{H},1,8192]" + "{3,2,1,0:T(1,128)}) "
                   "custom-call(%a, %b, %c), "
                   "custom_call_target=\"tpu_custom_call\"")
        fused = (f"%{kind}.33 = (bf16[1,{H},8,1024,128]"
                 "{4,3,2,1,0:T(8,128)(2,1)}, " f"bf16[2,1,{H},8192,128]"
                 "{4,3,2,1,0:T(8,128)(2,1)}) custom-call(%a), "
                 "custom_call_target=\"tpu_custom_call\"")
        assert re.search(fwd["pattern"], forward)
        assert not re.search(fwd["pattern"], fused)
        assert re.search(bwd["pattern"], fused)
        assert not re.search(bwd["pattern"], forward)


RECORDED = {
    "loss": 9.95, "lb_loss": 4.01,
    "mix_out_ms": [0.21, 0.32, 0.41, 0.53, 0.6],
    "expert_pairs": [[320.0] * 8] * 4,
    "router_counts": np.full((4, 256), 320.0)}


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
    lo, hi = check["first_loss_range"]
    assert lo < np.log(12544) < hi
    # the biases: the rule on the reference's counts, only where a count is
    # clear of the mean by more than the counts' own tolerance
    mods = {"reference": reference_laguna}
    counts = np.full((4, 256), 320.0)
    counts[:, 0] += 100.0                  # clear of the mean: falls
    before = np.zeros((4, 256), np.float32)
    rule = np.asarray(reference_laguna.bias_after(before, counts, 1e-3))
    assert rule[0, 0] == pytest.approx(-1e-3) and rule[0, 5] == \
        pytest.approx(1e-3)
    far = rule.copy()
    far[:, 0] *= -1
    problems, facts = train_mla_moe.compare_biases(
        before, far, {"router_counts": counts}, check, 1e-3, mods)
    assert len(problems) == 1 and "selection biases" in problems[0]
    assert facts["of"] == 1024
    problems, _ = train_mla_moe.compare_biases(
        before, before, {"router_counts": counts}, check, 1e-3, mods)
    assert any("moved no selection bias" in p for p in problems)


def test_toy_widths_shrink_what_rehearsal_json_does_not_name(cfg):
    assert train_heads_moe.at_widths(cfg) is cfg
    rehearsal = _json(B, "rehearsal.json")["config"]
    toy = train_heads_moe.at_widths({**cfg, **rehearsal})
    assert {k: toy[k] for k in train_heads_moe.TOY} == train_heads_moe.TOY
    assert not set(train_heads_moe.TOY) & set(rehearsal)
    assert toy["num_attention_heads_per_layer"][:5] == [4, 6, 6, 6, 4]
    assert train_heads_moe.heads_said(toy) == {
        "heads_held": {"full": (4, 8), "window": (6, 12)},
        "attn_heads_per_step": 26}


def test_the_readers_return_nothing_where_there_is_nothing_to_read(cfg):
    empty = {"cell": {"name": CELL}, "cfg": cfg, "peak": None, "trace": None,
             "reduced": {}, "values": {}}
    assert heads.flash_heads(empty, "x") is None
    assert heads.experts_roofline(empty) is None
    assert heads.train_mfu(empty) is None
    # another configuration's file, the parent's program (no such fact)
    other = {**empty, "cfg": {"hidden_size": 1}, "peak": PEAK,
             "values": {"train_tok_s_chip": 1.0, "seq": 8192}}
    assert heads.train_mfu(other) is None
    assert heads.flash_heads(other, "x") is None
    assert heads.experts_roofline(other) is None
    assert heads.attn_heads(empty) is None or heads.attn_heads(empty) > 0
    full = {**empty, "peak": PEAK,
            "values": {"train_tok_s_chip": 30000.0, "seq": 8192}}
    assert heads.train_mfu(full) == pytest.approx(
        100 * 30000.0 * oc.train_flops_per_token(cfg, 8192) / 197e12)


def test_the_readers_read_a_recorded_trace(cfg, tmp_path, monkeypatch):
    """``testdata/heads_tiny.*`` (``testdata/record_heads_trace.py``, on a
    TPU v5e): the cell's five layers at its head size, narrow and short, two
    traced steps. Each kind's scope and the gate's have device time, the
    gate's inside its kinds'; the three shares lie between 0 and 100 %, each
    call's work from its own layer's heads; the row's fact is the toy's
    2 x 2 + 3 x 3 heads."""
    from benchmarks import trace_reduce as tr
    from benchmarks.readers import looped, program

    data = os.path.join(B, "testdata")
    with gzip.open(os.path.join(data, "heads_tiny.json.gz"), "rt") as f:
        facts = json.load(f)
    path = str(tmp_path / "heads_tiny.xplane.pb")
    with gzip.open(os.path.join(data, "heads_tiny.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    monkeypatch.setattr(program, "xplane_path", lambda name: path)
    trace = tr.load_xplane(path)
    toy = {**cfg, **facts["config"],
           "deployment": {**cfg["deployment"],
                          "remat_policy": facts["remat_policy"]}}
    assert facts["facts"]["attn_heads_per_step"] == 13 == sum(oc.heads(toy))
    assert facts["device"] == "TPU v5 lite"

    def ctx():
        return {"cfg": toy, "cell": {"name": "heads_tiny"}, "peak": PEAK,
                "trace": trace, "program": {"hlo_text": facts["hlo_text"]},
                "reduced": {"window_ns": list(tr.window(trace))},
                "values": {"seq": facts["seq"], "rows": 1, "chips": 1,
                           "moe_pairs_per_step": facts["pairs_per_step"]}}

    ms = {s: looped.scope_device_ms(ctx(), s) for s in (
        "attn_window", "attn_full", "attn_gate", "attn")}
    assert all(v and v > 0 for v in ms.values()), ms
    assert ms["attn_gate"] < ms["attn_window"] + ms["attn_full"] <= ms["attn"]
    c = ctx()
    fwd = _json(B, "metrics", "flash_fwd_roofline.heads.json")["args"]
    bwd = _json(B, "metrics", "flash_bwd_roofline.heads.json")["args"]
    shares = {"forward": heads.flash_heads(c, **fwd),
              "backward": heads.flash_heads(c, **bwd),
              "experts": heads.experts_roofline(c)}
    assert all(0 < v < 100 for v in shares.values()), shares
    # the forward's calls: every kept layer once, and once more recomputed
    # under the toy's policy, in each traced step
    k = tr.kernel_seconds(trace, tuple(tr.window(trace)), fwd["pattern"],
                          fwd["field"])
    assert k["calls"] == 5 * 2 * facts["traced_steps"]
    # a program without the scopes or the kernels' names (the parent's, a
    # model of another kind): nothing, and no raise
    other = {**ctx(), "program": {"hlo_text": facts["hlo_text"].replace(
        "attn_gate", "xyz_gate")}}
    assert looped.scope_device_ms(other, "attn_gate") is None
    assert heads.flash_heads(ctx(), "^%no_such_kernel", "label") is None
