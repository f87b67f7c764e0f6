"""What this benchmark holds of Qwen3-Next-80B-A3B-Instruct: the configuration
keeps what the publisher's ``config.json`` says (as the catalog beside the
``model-configs`` guide has it) and cuts depth, the experts held and the
vocabulary alone; the manifest lists the cell, its metrics and their readers;
the operation and byte counts are the arithmetic ``PERF.md`` states and the
program's own; the readers on a trace recorded on the chip, and nothing where
there is nothing to read; a rehearsal of the cell on the CPU."""

import gzip
import importlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks import opcount_qwen3_next as oc
from benchmarks.readers import gdn, roofline
from benchmarks.runners import train_gdn_moe

HERE = os.path.dirname(os.path.abspath(__file__))
B = os.path.join(HERE, "..")
ROOT = os.path.join(B, "..")
CELL = "qwen3_next_80b_train_1chip"
CONFIG = "qwen3_next_80b_train_d4e32v8"
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
NEW_METRICS = {
    "train_mfu.gdn", "delta_scan_roofline.gdn", "flash_fwd_roofline.gdn",
    "flash_bwd_roofline.gdn", "moe_experts_roofline.gdn",
    "delta_qk_rows_per_step.train"}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _json(B, "configs", f"{CONFIG}.json")


def test_the_configuration_keeps_the_published_values(cfg):
    reduced = cfg["reduced"]
    assert sorted(reduced) == ["num_experts", "num_hidden_layers",
                               "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert reduced[key]["published"] == value
            assert reduced[key]["here"] == cfg[key] != value
            assert reduced[key]["why"]
        else:
            assert cfg[key] == value, key
    # no width among the cuts; the router keeps the published experts
    assert cfg["router_width"] == PUBLISHED["num_experts"]
    assert cfg["first_expert"] == 0
    assert cfg["source"].endswith(
        "Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json")
    for key in ("layer_types", "gate_a_channel", "zero_centred_norms",
                "load_balance_term", "multi_token_module", "delta_init"):
        assert key in cfg["assumed"], key
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] * cfg["num_experts"] \
        == PUBLISHED["num_experts"]
    assert dep["remat_policy"] in ("attn_saveable", "full")
    for name in cfg["check"]["compared"]:
        assert f"{name}_abs_tol" in cfg["check"] \
            or f"{name}_rel_tol" in cfg["check"], name


def test_the_manifest_lists_the_cell_and_its_files_exist(cfg):
    m = _json(ROOT, "BENCHMARK.json")
    entry = {w["name"]: w for w in m["workloads"]}[CELL]
    f = _json(B, "workloads", f"{CELL}.json")
    assert (f["config"], f["traffic"], f["chips"], f["why"]) == \
        (entry["config"], entry["traffic"], entry["chips"], entry["why"])
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert entry["traffic"] in ("packed_16k_1row", "packed_8k_1row")
    for said in ("320 pairs", "5,120", "3%"):
        assert said in entry["why"], said
    conf = {c["name"]: c for c in m["configs"]}[entry["config"]]
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert sorted(conf["reduced"]) == sorted(cfg["reduced"])
    assert cfg["source"] == conf["source"] and len(conf["why"]) <= 200
    traffic = _json(B, "traffic", "packed_16k_1row.json")
    assert {k: traffic[k] for k in traffic if k != "note"} == {
        "kind": "train", "seq_len": 16384, "rows_per_chip": 1}
    for mod in cfg["modules"].values():
        importlib.import_module(f"benchmarks.{mod}")
    assert f["runner"] == "train_gdn_moe" and callable(train_gdn_moe.run)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert e2e["train_tok_s_chip"]["workloads"][-1] == CELL
    mine = {p["name"] for p in m["per_layer"] if CELL in p["workloads"]}
    assert NEW_METRICS <= mine
    for name in ("train_step_ms", "train_step_device_ms", "train_host_ms",
                 "attn_device_ms", "mlp_device_ms", "head_loss_device_ms",
                 "optimizer_device_ms", "unscoped_device_ms",
                 "device_idle_share.train", "compiles_in_window.train",
                 "delta_proj_device_ms", "delta_conv_device_ms",
                 "delta_scan_device_ms", "delta_gate_device_ms",
                 "delta_chunks_per_step.train", "attn_gate_device_ms",
                 "attn_full_device_ms", "moe_router_device_ms",
                 "moe_dispatch_device_ms", "moe_experts_device_ms",
                 "moe_shared_device_ms", "moe_pairs_per_step.train",
                 "moe_pairs_dropped.train", "moe_load_max_over_mean.train",
                 "setup_import_s", "setup_step_first_call_s.train"):
        assert name in mine, name
    # their readers take another configuration's counts or kernel names
    assert not mine & {"delta_scan_roofline", "train_mfu.delta",
                       "flash_fwd_roofline.delta", "moe_experts_roofline",
                       "moe_experts_roofline.bd", "train_mfu.bd"}
    names = [p["name"] for p in m["per_layer"]]
    assert set(names[-len(NEW_METRICS):]) == NEW_METRICS   # added at the end
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


def test_the_counts_are_the_hand_sums_and_the_publishers(cfg):
    # the publisher's 80B-A3B, the table and the head among them
    assert oc.whole_model_params(cfg) == 79_674_391_296
    assert oc.active_params_per_token(cfg) == 3_874_929_408
    assert round(oc.whole_model_params(cfg) / 1e9, 2) == 79.67
    assert round(oc.active_params_per_token(cfg) / 1e9, 2) == 3.87
    # the cut, by hand (the issue's sums)
    delta = 2048 * (2 * 2048 + 2 * 4096 + 64) + 4096 * 2048 \
        + 4 * (2 * 2048 + 4096) + 64 + 128
    full = 2048 * 16 * 512 + 2 * 2048 * 512 + 4096 * 2048 + 512
    ffn = 2048 * 512 + 32 * 3 * 2048 * 512 + 3 * 2048 * 512 + 2048
    assert (delta, full, ffn) == (33_718_464, 27_263_488, 104_859_648)
    assert sum(oc.delta_params(cfg).values()) == delta
    assert sum(oc.attn_params(cfg).values()) == full
    assert oc.ffn_params(cfg) == ffn
    assert oc.layer_params(cfg, "linear_attention") == 138_582_208
    assert oc.layer_params(cfg, "full_attention") == 132_127_232
    assert oc.total_params(cfg) == 625_667_136 \
        == 3 * 138_582_208 + 132_127_232 + 77_791_232 + 2048
    assert oc.kinds(cfg) == ["linear_attention"] * 3 + ["full_attention"]
    assert oc.kinds(cfg, 48).count("full_attention") == 12


def test_one_layers_operations_are_the_hand_counts(cfg):
    T = 16384
    # a delta layer's rule, a position: a key head's K K^T and Q K^T at
    # chunk 64, a value head's inverse, its three products and the state's
    rule = oc.delta_rule(cfg, T)
    a_key_head = 4 * 64 * 128
    a_value_head = 64 * (2 * 128 + 4 * 128) + 2 * 64 * 64 / 3 + 6 * 128 * 128
    assert rule["flops"] == pytest.approx(
        T * (16 * a_key_head + 32 * a_value_head))
    # q and k once a key head, v and o a value head, g and beta, the states
    assert rule["bytes"] == T * (16 * 2 * 128 * 2 + 32 * (2 * 128 * 2 + 8)) \
        + 2 * 256 * 32 * 128 * 128 * 4
    both = oc.delta_rule(cfg, T, forwards=1, backwards=1)
    assert both["flops"] == 3 * rule["flops"]
    # repeated to the value heads the read of q and k would double
    repeated = {**cfg, "linear_num_key_heads": 32}
    assert oc.delta_rule(repeated, T)["bytes"] - rule["bytes"] \
        == T * 16 * 2 * 128 * 2
    # the full layer's attention at d 256 over the causal pairs
    pairs = T * (T + 1) // 2
    fwd, bwd = oc.flash(cfg, T), oc.flash(cfg, T, forwards=0, backwards=1)
    assert fwd["flops"] == 4 * pairs * 16 * 256
    assert bwd["flops"] == 8 * pairs * 16 * 256
    assert fwd["bytes"] == 2 * T * 18 * 256 * 2
    assert bwd["bytes"] == 2 * fwd["bytes"]
    # the thin grouped products: 320 pairs an expert, the weights' read
    # bounds them
    pairs_here = T * oc.expected_pairs_per_token(cfg)
    assert pairs_here == 10240 and pairs_here / 32 == 320
    g = oc.grouped_products(cfg, pairs_here, forwards=1)
    assert g["flops"] == 6 * 10240 * 2048 * 512
    assert g["bytes"] == 32 * 3 * 2048 * 512 * 2 \
        + 10240 * (3 * 2048 + 4 * 512) * 2
    assert g["bytes"] / PEAK["hbm_bytes_per_s"] \
        > g["flops"] / PEAK["bf16_flops_per_s"]
    # the whole step: 26 TFLOP, of which the routed products 3 %
    step = oc.train_flops_per_token(cfg, T) * T
    assert 26.0e12 < step < 26.6e12
    routed = 4 * 18 * 10240 * 2048 * 512
    assert 0.025 < routed / step < 0.035
    assert 0.24 < 12 * 16 * 256 * pairs / step < 0.26     # the full layer's


def test_toy_widths_shrink_what_rehearsal_json_does_not_name(cfg):
    toy = train_gdn_moe.at_widths({**cfg, "hidden_size": 64})
    assert toy["linear_num_key_heads"] * 2 == toy["linear_num_value_heads"]
    assert toy["num_experts"] < toy["router_width"]
    assert train_gdn_moe.at_widths(cfg) is cfg


def test_the_readers_return_nothing_where_there_is_nothing_to_read(cfg):
    empty = {"cell": {"name": CELL}, "cfg": cfg, "peak": None, "trace": None,
             "reduced": {}, "values": {}}
    assert gdn.scan_roofline(empty) is None
    assert gdn.flash_bwd(empty, "x") is None
    assert gdn.experts_roofline(empty) is None
    assert gdn.train_mfu(empty) is None
    assert gdn.qk_rows_per_step(empty) is None
    # another configuration's file, the parent's program (no such counter)
    other = {**empty, "cfg": {"hidden_size": 1}, "peak": PEAK,
             "values": {"train_tok_s_chip": 1.0, "seq": 8192, "rows": 1,
                        "moe_pairs_per_step": 5.0}}
    assert gdn.train_mfu(other) is None
    assert gdn.scan_roofline(other) is None
    assert gdn.experts_roofline(other) is None
    assert gdn.flash_bwd(other, "x") is None
    full = {**empty, "peak": PEAK,
            "values": {"train_tok_s_chip": 40000.0, "seq": 16384,
                       "delta_qk_rows_per_step": 1572864}}
    assert gdn.train_mfu(full) == pytest.approx(
        100 * 40000.0 * oc.train_flops_per_token(cfg, 16384) / 197e12)
    assert gdn.qk_rows_per_step(full) == 2 * 16384 * 16 * 3


def test_the_readers_read_a_recorded_trace(cfg, tmp_path, monkeypatch):
    """``testdata/gdn_tiny.*`` (``testdata/record_gdn_trace.py``, on a TPU
    v5e): the cell's one period at its head sizes, narrow and short, two
    traced steps. The rule's kernels ran under ``delta_scan`` with q and k
    read once a key head; the four shares lie between 0 and 100 %."""
    from benchmarks import trace_reduce as tr
    from benchmarks.readers import looped, program

    data = os.path.join(B, "testdata")
    with gzip.open(os.path.join(data, "gdn_tiny.json.gz"), "rt") as f:
        facts = json.load(f)
    path = str(tmp_path / "gdn_tiny.xplane.pb")
    with gzip.open(os.path.join(data, "gdn_tiny.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    monkeypatch.setattr(program, "xplane_path", lambda name: path)
    trace = tr.load_xplane(path)
    toy = {**cfg, **facts["config"],
           "deployment": {**cfg["deployment"],
                          "remat_policy": facts["remat_policy"]}}
    assert facts["device"] == "TPU v5 lite"
    assert facts["facts"]["delta_heads"] == [2, 4]
    assert facts["delta_rule_lowering"] == {"0": "pallas", "1": "pallas",
                                            "2": "pallas"}
    assert set(facts["counted"]["delta_scan"]) == {"pallas"}
    assert facts["qk_rows_per_step"] == 2 * facts["seq"] * 2 * 3
    assert set(facts["flash_bwd_arm"]) == {"256"}

    monkeypatch.setattr(gdn.bd, "_row",
                        lambda name: facts["counted"]["flash_bwd"])

    def ctx(**over):
        return {"cfg": toy, "cell": {"name": "gdn_tiny"}, "peak": PEAK,
                "trace": trace, "program": {"hlo_text": facts["hlo_text"]},
                "reduced": {"window_ns": list(tr.window(trace))},
                "values": {"seq": facts["seq"], "rows": 1, "chips": 1,
                           "moe_pairs_per_step": facts["pairs_per_step"]},
                **over}

    ms = {s: looped.scope_device_ms(ctx(), s) for s in (
        "delta_scan", "delta_conv", "delta_gate", "attn_gate", "attn_full",
        "moe_shared", "attn")}
    assert all(v and v > 0 for v in ms.values()), ms
    assert ms["delta_scan"] + ms["attn_full"] < ms["attn"]
    c = ctx()
    fwd = _json(B, "metrics", "flash_fwd_roofline.gdn.json")["args"]
    bwd = _json(B, "metrics", "flash_bwd_roofline.gdn.json")["args"]
    shares = {"scan": gdn.scan_roofline(c),
              "forward": roofline.flash_train(c, **fwd),
              "backward": gdn.flash_bwd(c, **bwd),
              "experts": gdn.experts_roofline(c)}
    assert all(0 < v < 100 for v in shares.values()), shares
    # the one full layer's forward once a step (the policy keeps what the
    # kernel named) and its backward: one call where fused, two where split
    k = tr.kernel_seconds(trace, tuple(tr.window(trace)), fwd["pattern"],
                          fwd["field"])
    again = 1 if facts["remat_policy"] == "attn_saveable" else 2
    assert k["calls"] == again * facts["traced_steps"]
    k = tr.kernel_seconds(trace, tuple(tr.window(trace)), bwd["pattern"],
                          bwd["field"])
    per = 2 if "split" in facts["counted"]["flash_bwd"] else 1
    assert k["calls"] == per * facts["traced_steps"]
    # a program without the scope or the kernels' names (the parent's, a
    # model of another kind): nothing, and no raise
    other = ctx(program={"hlo_text": facts["hlo_text"].replace(
        "delta_scan", "xyz_scan")})
    assert gdn.scan_roofline(other) is None
    assert gdn.flash_bwd(ctx(), "^%no_such_kernel", "label") is None


def test_the_cell_rehearses_on_the_cpu():
    """``run.py --rehearse``: the cell's control flow at toy widths, the
    program against the reference under the rehearsal's loose limits."""
    out = subprocess.run(
        [sys.executable, os.path.join(B, "run.py"), "--workload", CELL,
         "--seed", "5", "--seconds", "1", "--trace", "0", "--rehearse"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] and last["problems"] == []
    assert set(last["metric_names"]) >= {"setup_s", "train_tok_s_chip"}
