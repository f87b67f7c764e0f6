"""What this benchmark holds of Ling-3.0-flash: the configuration keeps what
the catalog beside the ``model-configs`` guide has of the publisher's
``config.json`` and cuts depth, the leading dense layers, the heads and
experts held and the vocabulary alone; the manifest takes the configuration,
cell and metrics by files alone (the traffic file is one the benchmark had);
the parameter count is the program's (648,853,344 at the cut, 124.05 B whole
by the same formulas); the roofline counts at a toy shape by hand; the readers
return nothing where there is nothing to read; and each ``--control`` arm's
recorded readings fail the cell's judgement while the program's pass it."""

import importlib
import json
import os

import numpy as np
import pytest

from benchmarks import opcount, opcount_ling3
from benchmarks.readers import kda
from benchmarks.runners import train_kda_moe
from benchmarks.runners.train_hybrid import compare

HERE = os.path.dirname(os.path.abspath(__file__))
B = os.path.join(HERE, "..")
ROOT = os.path.join(B, "..")
CELL = "ling3_flash_train_1chip"
CONFIG = "ling3_flash_train_d7h16e8v8"
REDUCED = {"num_hidden_layers": (42, 7), "first_k_dense_replace": (2, 1),
           "num_attention_heads": (32, 16), "num_experts": (512, 8),
           "vocab_size": (157184, 19648)}
#: published widths the file may never change
WIDTHS = {"hidden_size": 2560, "head_dim": 128, "intermediate_size": 6144,
          "moe_intermediate_size": 768,
          "moe_shared_expert_intermediate_size": 768, "kv_lora_rank": 512,
          "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
          "num_experts_per_tok": 8, "short_conv_kernel_size": 4,
          "n_group": 8, "topk_group": 4, "routed_scaling_factor": 2.5,
          "rms_norm_eps": 1e-06, "rope_theta": 6000000,
          "kda_lower_bound": -5, "layer_group_size": 6}
NEW_METRICS = {
    "kda_proj_device_ms", "kda_conv_device_ms", "kda_scan_device_ms",
    "kda_gate_device_ms", "kda_chunks_per_step.train", "kda_scan_roofline",
    "flash_fwd_roofline.kda", "flash_bwd_roofline.kda",
    "moe_experts_roofline.kda", "train_mfu.kda"}
TAKEN = {
    "moe_router_device_ms", "moe_dispatch_device_ms", "moe_experts_device_ms",
    "moe_shared_device_ms", "mla_proj_device_ms", "mla_rope_device_ms",
    "moe_pairs_per_step.train", "moe_pairs_dropped.train",
    "moe_load_max_over_mean.train", "moe_bias_moved_per_step.train",
    "layer_applications_per_step.train"}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _json(B, "configs", f"{CONFIG}.json")


def test_the_configuration_keeps_the_published_values(cfg):
    for key, val in WIDTHS.items():
        assert cfg[key] == val, key
    assert sorted(cfg["reduced"]) == sorted(REDUCED)
    for key, (published, here) in REDUCED.items():
        assert (cfg["reduced"][key]["published"], cfg["reduced"][key]["here"],
                cfg[key]) == (published, here, here), key
        assert cfg["reduced"][key]["why"]
    assert (cfg["heads"], cfg["router_width"], cfg["first_layer"],
            cfg["first_expert"]) == (32, 512, 1, 0)
    assert len(cfg["expert_swiglu_limit_list"]) == 42
    assert not any(cfg["expert_swiglu_limit_list"][1:8])
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Ling-3.0-flash-VL")
        assert cfg["source"] == row["source_url"]
        for key, val in row["config"].items():
            assert cfg[key] == (REDUCED[key][1] if key in REDUCED else val), \
                key
    dep = cfg["deployment"]
    assert (dep["chips_sharing_the_experts"], dep["chips_sharing_a_mixer"],
            dep["chips_sharing_the_vocabulary"]) == (64, 2, 8)
    assert dep["ds_config"]["zero_optimization"]["stage"] == 0
    check = cfg["check"]
    # the loss is held to its range and printed, not to a limit (tol_why)
    assert set(check["compared"]) == {"lb_loss", "mix_out_ms",
                                      "expert_pairs", "grad_err",
                                      "param_change_err"}
    assert check["tol_why"] and "TO BE MEASURED" not in json.dumps(cfg)


def test_the_manifest_takes_the_cell_by_files_alone(cfg):
    m = _json(ROOT, "BENCHMARK.json")
    entry = m["workloads"][-1]
    f = _json(B, "workloads", f"{CELL}.json")
    assert entry["name"] == CELL        # appended, nothing moved
    assert (f["config"], f["traffic"], f["chips"], f["why"]) == \
        (entry["config"], entry["traffic"], entry["chips"], entry["why"])
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    conf = m["configs"][-1]
    assert conf["name"] == entry["config"] == CONFIG
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert sorted(conf["reduced"]) == sorted(cfg["reduced"])
    assert cfg["source"] == conf["source"] and len(conf["why"]) <= 200
    traffic = _json(B, "traffic", f"{entry['traffic']}.json")
    assert (entry["traffic"], traffic["kind"], traffic["seq_len"],
            traffic["rows_per_chip"]) == ("packed_8k_1row", "train", 8192, 1)
    for mod in cfg["modules"].values():
        importlib.import_module(f"benchmarks.{mod}")
    assert f["runner"] == "train_kda_moe" and callable(train_kda_moe.run)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert e2e["train_tok_s_chip"]["workloads"][-1] == CELL
    mine = {p["name"] for p in m["per_layer"] if CELL in p["workloads"]}
    everywhere = {p["name"] for p in m["per_layer"]
                  if "granite4_h_micro_train_1chip" in p["workloads"]
                  and "kanana2_30b_train_1chip" in p["workloads"]
                  and "mistral7b_train_1chip" in p["workloads"]}
    assert len(everywhere) == 29
    assert mine == NEW_METRICS | TAKEN | everywhere
    for p in m["per_layer"]:
        assert p["workloads"][-1] == CELL or CELL not in p["workloads"]
        if p["name"] in NEW_METRICS:
            assert p["workloads"] == [CELL]
            assert p["moves"] == "train_tok_s_chip"
            spec = _json(B, "metrics", p["name"] + ".json")
            mod, fn = spec["reader"].split(":")
            assert callable(getattr(importlib.import_module(
                f"benchmarks.{mod}"), fn))
    # the flash pair: the Kanana-2 cell's patterns, by data
    for which in ("fwd", "bwd"):
        mine_, theirs = (_json(B, "metrics", f"flash_{which}_roofline.{x}.json")
                         for x in ("kda", "mla"))
        assert mine_["args"] == theirs["args"]


def test_the_parameter_counts_are_the_issues(cfg):
    """648,853,344 at the cut, layer by layer as the file's ``reduced``
    states them; 124.05 B whole and 5.14 B active by the same formulas, which
    is how the shapes were read right (the publisher says about
    125B-A5.5B)."""
    assert sum(opcount_ling3.kda_params(cfg).values()) == 26_323_088
    assert sum(opcount_ling3.mla_params(cfg).values()) == 16_720_384
    kinds = opcount_ling3.kinds(cfg)
    assert kinds == [("kda", "dense")] + [("kda", "moe")] * 3 \
        + [("mla", "moe")] + [("kda", "moe")] * 2
    assert [opcount_ling3.layer_params(cfg, k) for k in kinds[:2]
            + kinds[4:5]] == [73_514_128, 80_723_600, 71_120_896]
    assert opcount_ling3.total_params(cfg) == 648_853_344 \
        == cfg["deployment"]["parameters"]
    assert cfg["deployment"]["state_bytes_at_18"] == 18 * 648_853_344
    whole = opcount_ling3.whole(cfg)
    assert sum(opcount_ling3.kda_params(whole).values()) == 52_646_048
    assert sum(opcount_ling3.mla_params(whole).values()) == 31_965_696
    wk = opcount_ling3.kinds(whole)
    assert len(wk) == 42 and wk.count(("mla", "moe")) == 7
    assert wk[:2] == [("kda", "dense")] * 2 and wk[5] == ("mla", "moe")
    assert round(opcount_ling3.total_params(whole) / 1e9, 2) == 124.05
    assert round(opcount_ling3.active_params(whole) / 1e9, 2) == 5.14
    # what the cell's ``why`` quotes, in multiply-adds a token
    parts = opcount_ling3.layer_forward_flops_per_token(cfg, 8192)
    assert round(parts["kda_proj"] / 2e6, 1) == 26.3
    assert round(parts["routed"] / 2e6, 2) == 0.74
    assert round(parts["shared"] / 2e6, 1) == 5.9
    assert round(parts["router"] / 2e6, 1) == 1.3
    assert 2.0e9 < opcount_ling3.train_flops_per_token(cfg, 8192) < 2.2e9


def test_the_roofline_counts_at_a_toy_shape_by_hand():
    toy = {"hidden_size": 8, "num_attention_heads": 2, "head_dim": 4,
           "kv_lora_rank": 4, "qk_nope_head_dim": 4, "qk_rope_head_dim": 2,
           "v_head_dim": 4, "intermediate_size": 16,
           "moe_intermediate_size": 4,
           "moe_shared_expert_intermediate_size": 4, "vocab_size": 32,
           "num_hidden_layers": 2, "short_conv_kernel_size": 4,
           "num_experts": 2, "router_width": 8, "num_experts_per_tok": 2,
           "layer_group_size": 2, "first_k_dense_replace": 1,
           "deployment": {"kda_chunk": 4}}
    # the rule over 8 positions of 2 heads, chunks of 4, dk = dv = 4: a
    # position of a head 4 x (6 x 4 + 4 x 4) + 2 x 16 / 3 + 6 x 16 operations;
    # q, k, v, o 4 x 4 x 2 B, g 4 x 4 B, beta 4 B; 2 chunks x 2 heads of
    # states, 16 x 4 B, written and read
    rule = opcount_ling3.kda_rule(toy, 8)
    assert rule["flops"] == pytest.approx(8 * 2 * (160 + 32 / 3 + 96))
    assert rule["bytes"] == 8 * 2 * (32 + 16 + 4) + 2 * 2 * 2 * 64
    both = opcount_ling3.kda_rule(toy, 8, forwards=2, backwards=1)
    assert both["flops"] == pytest.approx(4 * rule["flops"])
    # latent attention: 36 causal pairs, 2 heads, keys 6 and values 4
    fwd = opcount_ling3.flash_forward(toy, 8)
    assert fwd["flops"] == 2 * 36 * 2 * (6 + 4)
    assert fwd["bytes"] == 8 * 2 * (2 * 6 + 2 * 4) * 2
    bwd = opcount_ling3.flash_backward(toy, 8)
    assert (bwd["flops"], bwd["bytes"]) == (2 * fwd["flops"],
                                            2 * fwd["bytes"])
    # three products an expert at width 4 over 10 pairs
    gp = opcount_ling3.grouped_products(toy, 10)
    assert gp["flops"] == 6 * 10 * 8 * 4
    assert gp["bytes"] == 2 * 3 * 8 * 4 * 2 + 10 * (3 * 8 + 4 * 4) * 2
    assert opcount_ling3.expected_pairs_per_token(toy) == 0.5
    assert opcount.roofline_seconds(rule, PEAK)["bound"] == "memory"


def test_the_readers_find_nothing_where_there_is_nothing(cfg):
    other = _json(B, "configs", "kanana2_30b_train_d5e16.json")
    for ctx in ({"cfg": other, "values": {"train_tok_s_chip": 1.0, "seq": 8,
                                          "moe_pairs_per_step": 8.0},
                 "peak": PEAK},
                {"cfg": cfg, "values": {}, "peak": None}):
        assert kda.train_mfu(ctx) is None
        assert kda.experts_roofline(ctx) is None
        assert kda.scan_roofline(ctx) is None
        assert kda.flash(ctx, "x") is None
    ctx = {"cfg": cfg, "peak": PEAK,
           "values": {"train_tok_s_chip": 30000.0, "seq": 8192}}
    flops = opcount_ling3.train_flops_per_token(cfg, 8192)
    assert kda.train_mfu(ctx) == pytest.approx(100 * flops * 30000 / 197e12)
    assert 0 < kda.train_mfu(ctx) < 100


def test_toy_widths_shrink_what_rehearsal_json_does_not_name(cfg):
    assert train_kda_moe.at_widths(cfg) is cfg
    toy = train_kda_moe.at_widths({**cfg, "hidden_size": 64})
    assert {k: toy[k] for k in train_kda_moe.TOY} == train_kda_moe.TOY
    rehearsal = _json(B, "rehearsal.json")["config"]
    assert not set(train_kda_moe.TOY) & set(rehearsal)
    assert set(train_kda_moe.FAULTS) == {"fp8", "unchanged", "decay_mean"}


def _recorded():
    return _json(B, "testdata", "ling3_first_step_readings.json")


@pytest.mark.parametrize("arm", ["program", "fp8", "decay_mean",
                                 "unchanged"])
def test_the_recorded_readings_meet_the_cells_judgement(cfg, arm):
    """What chip runs of the cell and of its ``--control`` arms read
    (``testdata/ling3_first_step_readings.json``: each run's largest
    difference by part) through the cell's own limits: every run of the
    program passes every limit, every run of a fault fails at least one."""
    check, runs = cfg["check"], _recorded()[arm]
    assert len(runs) >= (1 if arm == "unchanged" else 3)
    for run in runs:
        failed = []
        for name in check["compared"]:
            tol = check.get(f"{name}_rel_tol", check.get(f"{name}_abs_tol"))
            if not run[name] <= tol:
                failed.append(name)
        assert bool(failed) == (arm != "program"), (arm, run, failed)


def test_compare_reads_the_limits_the_file_states(cfg):
    want = {"loss": 10.0, "lb_loss": 6.0, "mix_out_ms": np.ones(7),
            "expert_pairs": np.full((6, 8), 1000.0), "grad_err": 0.0,
            "param_change_err": 0.0}
    problems, facts = compare(dict(want), want, cfg["check"])
    assert problems == [] and set(facts) == set(cfg["check"]["compared"])
    off = {**want, "mix_out_ms": np.ones(7) * (
        1 + 2 * cfg["check"]["mix_out_ms_rel_tol"])}
    assert compare(off, want, cfg["check"])[0]
