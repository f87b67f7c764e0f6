"""What this benchmark holds of Keye-VL-2.0-30B-A3B: the configuration keeps
what the catalog beside the ``model-configs`` guide has of the publisher's
``config.json`` and cuts depth, the experts held and the vocabulary alone;
the manifest takes the configuration, cell, traffic and metrics by files
alone; the parameter count is the program's (562,290,560 at the cut, 30.6 B
whole and 3.5 B active by the same formulas); the generator's positions
follow the rule the file states; the roofline counts at a toy shape by hand;
the readers return nothing where there is nothing to read and read a recorded
trace where there is; and each ``--control`` arm's recorded readings fail the
cell's judgement while the program's pass it."""

import gzip
import importlib
import json
import os
import shutil

import numpy as np
import pytest

from benchmarks import opcount, opcount_keye_vl2
from benchmarks.readers import dsa
from benchmarks.runners import train_dsa_moe
from benchmarks.runners.train_hybrid import compare

HERE = os.path.dirname(os.path.abspath(__file__))
B = os.path.join(HERE, "..")
ROOT = os.path.join(B, "..")
CELL = "keye_vl2_30b_train_1chip"
CONFIG = "keye_vl2_30b_train_d5e16v8"
REDUCED = {"num_hidden_layers": (48, 5), "num_experts": (128, 16),
           "vocab_size": (151936, 18992)}
#: published widths the file may never change
WIDTHS = {"hidden_size": 2048, "head_dim": 128, "intermediate_size": 6144,
          "moe_intermediate_size": 768, "num_attention_heads": 32,
          "num_key_value_heads": 4, "num_experts_per_tok": 8,
          "num_local_experts": 128, "rms_norm_eps": 1e-06,
          "rope_theta": 10000000, "max_position_embeddings": 262144}
NEW_METRICS = {
    "dsa_indexer_device_ms", "dsa_select_device_ms", "dsa_attend_device_ms",
    "dsa_loss_device_ms", "dsa_selected_share.train", "dsa_indexer_roofline",
    "dsa_attend_roofline", "moe_experts_roofline.dsa", "train_mfu.dsa"}
TAKEN = {
    "moe_router_device_ms", "moe_dispatch_device_ms", "moe_experts_device_ms",
    "moe_pairs_per_step.train", "moe_pairs_dropped.train",
    "moe_load_max_over_mean.train"}
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
    assert cfg["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert cfg["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert sorted(cfg["reduced"]) == sorted(REDUCED)
    for key, (published, here) in REDUCED.items():
        assert (cfg["reduced"][key]["published"], cfg["reduced"][key]["here"],
                cfg[key]) == (published, here, here), key
        assert cfg["reduced"][key]["why"]
    assert (cfg["router_width"], cfg["first_expert"]) == (128, 0)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Keye-VL-2.0-30B-A3B")
        assert cfg["source"] == row["source_url"]
        for key, val in row["config"].items():
            assert cfg[key] == (REDUCED[key][1] if key in REDUCED else val), \
                key
    for reading in ("qk_norm", "mrope_section", "indexer", "indexer_rope",
                    "indexer_precision", "selection", "indexer_loss",
                    "q_chunk_size, kv_chunk_size", "load_balance_term",
                    "vision_tower", "image_positions"):
        assert cfg["assumed"][reading], reading
    dep = cfg["deployment"]
    assert (dep["chips_sharing_a_layer"], dep["layers_a_stage"],
            dep["chips"]) == (8, 5, 1)
    assert "eight" in dep["stands_for"] and "5 layers" in dep["stands_for"]
    assert dep["ds_config"]["zero_optimization"]["stage"] == 0
    assert dep["indexer_loss_coef"] == 1.0
    check = cfg["check"]
    assert set(check["compared"]) == {
        "loss", "lb_loss", "indexer_loss", "mix_out_ms", "expert_pairs",
        "grad_err", "param_change_err", "set_differs_share"}
    assert check["tol_why"] and "TO_FILL" not in json.dumps(cfg)


def test_the_manifest_takes_the_cell_by_files_alone(cfg):
    m = _json(ROOT, "BENCHMARK.json")
    entry = next(w for w in m["workloads"] if w["name"] == CELL)
    f = _json(B, "workloads", f"{CELL}.json")
    assert (f["config"], f["traffic"], f["chips"], f["why"]) == \
        (entry["config"], entry["traffic"], entry["chips"], entry["why"])
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    conf = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert sorted(conf["reduced"]) == sorted(cfg["reduced"])
    assert cfg["source"] == conf["source"] and len(conf["why"]) <= 200
    traffic = _json(B, "traffic", f"{entry['traffic']}.json")
    assert (entry["traffic"], traffic["kind"], traffic["seq_len"],
            traffic["rows_per_chip"], traffic["image_spans"],
            traffic["image_grid"]) == ("long_doc_16k_1row", "train", 16384,
                                       1, 4, [32, 32])
    for mod in cfg["modules"].values():
        importlib.import_module(f"benchmarks.{mod}")
    assert f["runner"] == "train_dsa_moe" and callable(train_dsa_moe.run)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert CELL in e2e["train_tok_s_chip"]["workloads"]
    mine = {p["name"] for p in m["per_layer"] if CELL in p["workloads"]}
    everywhere = {p["name"] for p in m["per_layer"]
                  if "granite4_h_micro_train_1chip" in p["workloads"]
                  and "kanana2_30b_train_1chip" in p["workloads"]
                  and "mistral7b_train_1chip" in p["workloads"]}
    assert len(everywhere) == 29
    assert mine == NEW_METRICS | TAKEN | everywhere
    for p in m["per_layer"]:
        if p["name"] in NEW_METRICS:
            assert p["workloads"] == [CELL]
            assert p["moves"] == "train_tok_s_chip"
            spec = _json(B, "metrics", p["name"] + ".json")
            mod, fn = spec["reader"].split(":")
            assert callable(getattr(importlib.import_module(
                f"benchmarks.{mod}"), fn))
    # every share of a peak the cell reports carries mfu or roofline in its
    # name, the whole step's among them
    assert {n for n in mine if "mfu" in n} == {"train_mfu.dsa"}


def test_the_parameter_counts_are_the_issues(cfg):
    """562,290,560 at the cut; 30.6 B whole and 3.5 B active by the same
    formulas, which is how the shapes were read right ("30B-A3B")."""
    assert opcount_keye_vl2.attn_params(cfg) == 18_874_368 + 256
    assert opcount_keye_vl2.indexer_params(cfg) == 2_261_120
    assert opcount_keye_vl2.expert_params(cfg) == 4_718_592
    assert opcount_keye_vl2.layer_params(cfg) == 96_899_456
    assert opcount_keye_vl2.total_params(cfg) == 562_290_560
    assert round(opcount_keye_vl2.whole_model_params(cfg) / 1e9, 1) == 30.6
    assert round(opcount_keye_vl2.active_params_per_token(cfg) / 1e9, 1) \
        == 3.5
    # what the cell's ``why`` and the issue quote
    assert round(opcount_keye_vl2.selected_share(cfg, 16384), 3) == 0.234
    assert opcount_keye_vl2.expected_pairs_per_token(cfg) * 16384 / 16 \
        == 1024
    one = opcount_keye_vl2.attend(cfg, 16384)["flops"]
    assert round(one / 1e12, 2) == 0.52        # 0.55 at T x topk exactly
    assert round(opcount_keye_vl2.indexer(cfg, 16384, proj_forwards=0)[
        "flops"] / 1e12, 2) == 0.27
    assert 1.6e9 < opcount_keye_vl2.train_flops_per_token(cfg, 16384) < 1.9e9


def test_the_generator_follows_the_position_rule_it_states():
    traffic = _json(B, "traffic", "long_doc_16k_1row.json")
    rng = np.random.default_rng(3000000019)
    batch = train_dsa_moe.make_rows(rng, traffic, 18992, 1, 16384)
    ids, pos = batch["input_ids"], batch["position_ids"]
    assert ids.shape == (1, 16384) and pos.shape == (3, 1, 16384)
    assert ids.dtype == pos.dtype == np.int32
    assert 0 <= ids.min() and ids.max() < 18992
    t, h, w = pos[:, 0]
    image = (h != t) | (w != t)
    # four spans of 1,024 positions (the corner of a grid reads as text)
    starts = np.flatnonzero(np.diff(np.r_[0, (np.diff(t) == 0), 0]) == 1)
    assert len(starts) == 4 and image.sum() == 4 * (1024 - 1)
    for s in starts:
        assert (t[s:s + 1024] == t[s]).all()
        assert (h[s:s + 1024] == t[s] + np.repeat(np.arange(32), 32)).all()
        assert (w[s:s + 1024] == t[s] + np.tile(np.arange(32), 32)).all()
        if s + 1024 < 16384:       # the text resumes past the largest
            assert t[s + 1024] == h[s + 1024] == w[s + 1024] == t[s] + 32
    text = ~image
    assert (np.diff(t[text]) >= 0).all() and pos.max() < 16384
    assert t[0] == 0 or starts[0] == 0
    # the same seed gives the same batch, another seed another
    again = train_dsa_moe.make_rows(np.random.default_rng(3000000019),
                                    traffic, 18992, 1, 16384)
    assert (again["input_ids"] == ids).all() \
        and (again["position_ids"] == pos).all()
    other = train_dsa_moe.make_rows(np.random.default_rng(7), traffic, 18992,
                                    1, 16384)
    assert (other["position_ids"] != pos).any()
    # a row too short for the spans takes a small grid
    toy = train_dsa_moe.make_rows(rng, traffic, 256, 2, 128)
    assert toy["position_ids"].shape == (3, 2, 128)
    assert (toy["position_ids"][1] != toy["position_ids"][0]).sum() > 0


def test_the_roofline_counts_at_a_toy_shape_by_hand():
    toy = {"hidden_size": 8, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 4,
           "moe_intermediate_size": 4, "vocab_size": 32,
           "num_hidden_layers": 2, "num_experts": 2, "router_width": 8,
           "num_experts_per_tok": 2,
           "sa_config": {"indexer_num_heads": 2, "indexer_head_dim": 4,
                         "topk": 3}}
    # 8 positions keeping 3 keys: 1 + 2 + 3 pairs, then 5 x 3
    assert opcount_keye_vl2.selected_pairs(toy, 8) == 6 + 15
    assert opcount_keye_vl2.selected_share(toy, 8) == 21 / 36
    fwd = opcount_keye_vl2.attend(toy, 8)
    assert fwd["flops"] == 4 * 21 * 4 * 4
    assert fwd["bytes"] == 2 * 8 * (4 + 2) * 4 * 2
    both = opcount_keye_vl2.attend(toy, 8, forwards=2, backwards=1)
    assert both["flops"] == 4 * fwd["flops"]
    assert both["bytes"] == 4 * fwd["bytes"]
    # the indexer: 8 x (8 + 4 + 2) weights and 8 norm parameters; 36 causal
    # pairs of 2 heads of 4 channels
    assert opcount_keye_vl2.indexer_params(toy) == 8 * 14 + 8
    ix = opcount_keye_vl2.indexer(toy, 8)
    assert ix["flops"] == 2 * 8 * 120 + 2 * 36 * 2 * 4
    assert ix["bytes"] == 8 * (8 + 8 + 4) * 2 + 36 * 4
    all_ = opcount_keye_vl2.indexer(toy, 8, proj_backwards=1,
                                    score_backwards=1)
    assert all_["flops"] == 3 * ix["flops"]
    # three products an expert at width 4 over 10 pairs
    gp = opcount_keye_vl2.grouped_products(toy, 10)
    assert gp["flops"] == 6 * 10 * 8 * 4
    assert gp["bytes"] == 2 * 3 * 8 * 4 * 2 + 10 * (3 * 8 + 4 * 4) * 2
    assert opcount_keye_vl2.expected_pairs_per_token(toy) == 0.5
    # a token: 6 x (attention 8 x 16 x 2 + 8 x 8 x 2 + 8, router 64, half an
    # expert 48, head 256 / 2 layers) + 12 x 16 x 21 / 8 + the indexer thrice
    per_layer = (8 * 16 * 2 + 8 * 8 * 2 + 8) + 64 + 0.5 * 96
    assert opcount_keye_vl2.train_flops_per_token(toy, 8) == pytest.approx(
        6 * (2 * per_layer + 8 * 32) + 2 * (12 * 16 * 21 / 8
                                            + 3 * ix["flops"] / 8))
    assert opcount.roofline_seconds(fwd, PEAK)["bound"] == "memory"


def test_the_readers_find_nothing_where_there_is_nothing(cfg):
    other = _json(B, "configs", "mellum2_12b_train_d4e16.json")
    for ctx in ({"cfg": other, "values": {"train_tok_s_chip": 1.0, "seq": 8,
                                          "moe_pairs_per_step": 8.0},
                 "peak": PEAK},
                {"cfg": cfg, "values": {}, "peak": None}):
        assert dsa.train_mfu(ctx) is None
        assert dsa.experts_roofline(ctx) is None
        assert dsa.attend_roofline(ctx) is None
        assert dsa.indexer_roofline(ctx) is None
    # a program without the table's field (the parent's): nothing, no raise
    assert dsa.selected_share({"cfg": cfg, "values": {}}) is None
    ctx = {"cfg": cfg, "peak": PEAK, "cell": {"name": "no_such_cell"},
           "values": {"train_tok_s_chip": 20000.0, "seq": 16384, "rows": 1,
                      "chips": 1}}
    assert dsa.attend_roofline(ctx) is None and dsa.indexer_roofline(
        ctx) is None
    flops = opcount_keye_vl2.train_flops_per_token(cfg, 16384)
    assert dsa.train_mfu(ctx) == pytest.approx(100 * flops * 20000 / 197e12)
    assert 0 < dsa.train_mfu(ctx) < 100


def test_the_readers_read_a_recorded_trace(cfg, tmp_path, monkeypatch):
    """``testdata/dsa_tiny.*`` (``testdata/record_dsa_trace.py``, on a TPU
    v5e): a two-layer model of the cell's kind at its head sizes, two traced
    steps. Each of the four scopes has device time, forward and backward,
    the three shares lie between 0 and 100 %, and the attention's forward is
    found to run once under the file's policy."""
    from benchmarks import trace_reduce as tr
    from benchmarks.readers import looped, program

    data = os.path.join(B, "testdata")
    with gzip.open(os.path.join(data, "dsa_tiny.json.gz"), "rt") as f:
        facts = json.load(f)
    path = str(tmp_path / "dsa_tiny.xplane.pb")
    with gzip.open(os.path.join(data, "dsa_tiny.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    monkeypatch.setattr(program, "xplane_path", lambda name: path)
    trace = tr.load_xplane(path)
    toy = {**cfg, **facts["config"]}
    assert facts["facts"]["dsa_topk"] == 128 and facts["facts"][
        "mrope_axes"] == 3
    assert facts["facts"]["dsa_selected_share"] == pytest.approx(
        opcount_keye_vl2.selected_share(toy, facts["seq"]))
    assert facts["dsa_lowerings"] == {"jnp": 2}

    def ctx():
        return {"cfg": toy, "cell": {"name": "dsa_tiny"}, "peak": PEAK,
                "trace": trace, "program": {"hlo_text": facts["hlo_text"]},
                "reduced": {"window_ns": list(tr.window(trace))},
                "values": {"seq": facts["seq"], "rows": 1, "chips": 1,
                           "moe_pairs_per_step": facts["pairs_per_step"]}}

    ms = {s: looped.scope_device_ms(ctx(), s) for s in (
        "dsa_indexer", "dsa_select", "dsa_attend", "dsa_loss", "attn")}
    assert all(v and v > 0 for v in ms.values()), ms
    assert sum(ms[s] for s in ms if s != "attn") < ms["attn"]
    c = ctx()
    shares = {"attend": dsa.attend_roofline(c),
              "indexer": dsa.indexer_roofline(c),
              "experts": dsa.experts_roofline(c)}
    assert all(0 < v < 100 for v in shares.values()), shares
    notes = {n["what"]: n for n in c["roofline_notes"]}
    assert notes["dsa_attend a step"]["forwards"] == 1
    assert notes["dsa_indexer a step"]["forwards"] == (2, 1)
    # a program without the scopes (the parent's): nothing, and no raise
    other = {**ctx(), "program": {"hlo_text": facts["hlo_text"].replace(
        "dsa_", "xyz_")}}
    assert dsa.attend_roofline(other) is None
    assert dsa.indexer_roofline(other) is None


def test_toy_widths_shrink_what_rehearsal_json_does_not_name(cfg):
    assert train_dsa_moe.at_widths(cfg) is cfg
    toy = train_dsa_moe.at_widths({**cfg, "hidden_size": 64})
    assert {k: toy[k] for k in train_dsa_moe.TOY} == train_dsa_moe.TOY
    rehearsal = _json(B, "rehearsal.json")["config"]
    assert not set(train_dsa_moe.TOY) & set(rehearsal)
    assert set(train_dsa_moe.FAULTS) == {
        "fp8", "unchanged", "window", "rope_one_axis", "no_indexer_loss"}


def _recorded():
    return _json(B, "testdata", "keye_vl2_first_step_readings.json")


@pytest.mark.parametrize("arm", ["program", "fp8", "window", "rope_one_axis",
                                 "no_indexer_loss", "unchanged"])
def test_the_recorded_readings_meet_the_cells_judgement(cfg, arm):
    """What chip runs of the cell and of its ``--control`` arms read
    (``testdata/keye_vl2_first_step_readings.json``: each run's largest
    difference by part) through the cell's own limits: every run of the
    program passes every limit, every run of a fault fails at least one."""
    check, runs = cfg["check"], _recorded()[arm]
    assert len(runs) >= (10 if arm == "program" else 1)
    for run in runs:
        failed = []
        for name in check["compared"]:
            tol = check.get(f"{name}_rel_tol", check.get(f"{name}_abs_tol"))
            if not run[name] <= tol:
                failed.append(name)
        assert bool(failed) == (arm != "program"), (arm, run, failed)


def test_compare_reads_the_limits_the_file_states(cfg):
    want = {"loss": 10.8, "lb_loss": 5.1, "indexer_loss": 0.48,
            "mix_out_ms": np.ones(5), "expert_pairs": np.full((5, 16), 1000.0),
            "grad_err": 0.0, "param_change_err": 0.0,
            "set_differs_share": 0.0}
    problems, facts = compare(dict(want), want, cfg["check"])
    assert problems == [] and set(facts) == set(cfg["check"]["compared"])
    off = {**want, "set_differs_share": 2 * cfg["check"][
        "set_differs_share_abs_tol"]}
    assert compare(off, want, cfg["check"])[0]
