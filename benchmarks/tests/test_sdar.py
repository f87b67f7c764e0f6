"""What this benchmark holds of SDAR-30B-A3B-Chat: the configuration keeps
what the publisher's ``config.json`` says (as the catalog beside the
``model-configs`` guide has it) and cuts depth, the experts held and the
vocabulary alone; the manifest lists the cell, its metrics and their
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

from benchmarks import opcount, opcount_sdar as oc, reference_sdar
from benchmarks.readers import bd
from benchmarks.runners import train_bd_moe, train_hybrid

HERE = os.path.dirname(os.path.abspath(__file__))
B = os.path.join(HERE, "..")
ROOT = os.path.join(B, "..")
CELL = "sdar_30b_train_1chip"
CONFIG = "sdar_30b_a3b_train_d5e16v8"
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
NEW_METRICS = {
    "bd_cross_device_ms", "bd_own_device_ms", "bd_noise_host_ms",
    "bd_positions_per_step.train", "bd_masked_targets_per_step.train",
    "flash_fwd_roofline.bd", "flash_bwd_roofline.bd",
    "moe_experts_roofline.bd", "train_mfu.bd"}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _json(B, "configs", f"{CONFIG}.json")


def test_the_configuration_keeps_the_published_values(cfg):
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers",
                                      "vocab_size"]
    for key, val in PUBLISHED.items():
        if key in cfg["reduced"]:
            cut = cfg["reduced"][key]
            assert (cut["published"], cut["here"]) == (val, cfg[key]), key
        else:
            assert cfg[key] == val, key
    # the floors: four layers of the one kind, 8 experts a layer, an eighth
    # of the rows; every width as published
    assert cfg["num_hidden_layers"] >= 4 and cfg["num_experts"] >= 8
    assert cfg["router_width"] == 128 and cfg["first_expert"] == 0
    assert cfg["vocab_size"] * 8 >= 151936
    # what the published file does not give, each under assumed
    assert (cfg["block_length"], cfg["mask_token_id"]) == (4, 18991)
    assert cfg["mask_token_id"] == cfg["vocab_size"] - 1
    for name in ("qk_norm", "block_length", "noise_schedule", "t_draw",
                 "no_shift", "normaliser", "mask_token_id", "row", "rope",
                 "load_balance_term", "embedding_init"):
        assert len(cfg["assumed"][name]) > 40, name
    dep = cfg["deployment"]
    assert (dep["chips_sharing_a_layer"], dep["layers_a_stage"]) == (8, 5)
    assert (dep["load_balance_coef"], dep["local_pairs_factor"],
            dep["embed_init_std"]) == (0.001, 2.0, 1.0)
    assert dep["ds_config"]["optimizer"]["params"]["lr"] == 1e-6
    for part in ("source", "assumed", "deployment", "check", "modules"):
        assert cfg[part], part
    assert dep["expert_placement"] == "by_load"
    for text in (dep["remat_why"], dep["local_pairs_why"],
                 dep["expert_placement_why"], cfg["check"]["tol_why"]):
        assert len(text) > 100


def test_the_manifest_lists_the_cell_and_its_files_exist(cfg):
    m = _json(ROOT, "BENCHMARK.json")
    entry = {w["name"]: w for w in m["workloads"]}[CELL]
    f = _json(B, "workloads", f"{CELL}.json")
    assert (f["config"], f["traffic"], f["chips"], f["why"]) == \
        (entry["config"], entry["traffic"], entry["chips"], entry["why"])
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert entry["traffic"] == "block_diffusion_8k_1row"
    conf = {c["name"]: c for c in m["configs"]}[entry["config"]]
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert sorted(conf["reduced"]) == sorted(cfg["reduced"])
    assert cfg["source"] == conf["source"] and len(conf["why"]) <= 200
    traffic = _json(B, "traffic", f"{entry['traffic']}.json")
    assert {k: traffic[k] for k in traffic if k != "note"} == {
        "kind": "train", "seq_len": 8192, "rows_per_chip": 1,
        "block_length": 4, "t_min": 0.001, "t_draw": "block"}
    for mod in cfg["modules"].values():
        importlib.import_module(f"benchmarks.{mod}")
    assert f["runner"] == "train_bd_moe" and callable(train_bd_moe.run)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert e2e["train_tok_s_chip"]["workloads"][-1] == CELL
    mine = {p["name"] for p in m["per_layer"] if CELL in p["workloads"]}
    assert NEW_METRICS <= mine
    for name in ("train_step_ms", "train_step_device_ms", "train_host_ms",
                 "attn_device_ms", "mlp_device_ms", "head_loss_device_ms",
                 "optimizer_device_ms", "unscoped_device_ms",
                 "device_idle_share.train", "compiles_in_window.train",
                 "moe_router_device_ms", "moe_dispatch_device_ms",
                 "moe_experts_device_ms", "moe_pairs_per_step.train",
                 "moe_pairs_dropped.train", "moe_load_max_over_mean.train",
                 "layer_applications_per_step.train",
                 "setup_import_s", "setup_step_first_call_s.train"):
        assert name in mine, name
    # their readers take another configuration's counts or kernel names
    assert not mine & {"flash_fwd_roofline", "flash_bwd_roofline",
                       "flash_fwd_roofline.mixed", "moe_experts_roofline",
                       "moe_experts_roofline.dsa", "train_mfu",
                       "train_mfu.moe", "train_mfu.dsa"}
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


def test_the_counts_are_the_hand_sums_and_the_programs(cfg):
    D = 2048
    attn = 2 * D * 32 * 128 + 2 * D * 4 * 128 + 2 * 128
    assert oc.attn_params(cfg) == attn == 18_874_624
    assert oc.expert_params(cfg) == 3 * D * 768 == 4_718_592
    layer = attn + D * 128 + 16 * 4_718_592 + 2 * D
    assert oc.layer_params(cfg) == layer == 94_638_336
    assert oc.total_params(cfg) == 5 * layer + 2 * 18992 * D + D \
        == 550_984_960
    assert oc.total_params(cfg) * 18 / 1e9 == pytest.approx(9.92, abs=0.005)
    # the published model: 30.5 B stored, 3.3 B active a position ("30B-A3B")
    assert oc.whole_model_params(cfg) / 1e9 == pytest.approx(30.5, abs=0.1)
    assert oc.active_params_per_token(cfg) / 1e9 == pytest.approx(3.35,
                                                                  abs=0.1)
    from benchmarks import modelcfg_sdar

    tcfg = modelcfg_sdar.transformer_config(cfg, max_seq_len=8192,
                                            param_dtype="float32")
    assert tcfg.num_params_estimate() == 550_984_960
    assert tcfg.layer_kinds == ("full",) * 5
    assert (tcfg.diffusion_block, tcfg.mask_token_id) == (4, 18991)
    assert oc.expected_pairs_per_token(cfg) == 8 * 16 / 128


def test_the_rooflines_work_is_the_masks_pairs(cfg):
    L, nb = 8192, 2048
    clean = 16 * nb * (nb + 1) // 2
    cross = 16 * nb * (nb - 1) // 2
    assert clean + cross == oc.cross_pairs(cfg, L) == L * L
    assert oc.mask_pairs(cfg, L) == clean + cross + nb * 16 \
        == L * L + 4 * L == 67_141_632
    fwd = oc.attend(cfg, L, pairs=oc.cross_pairs(cfg, L))
    assert fwd["flops"] == 4.0 * L * L * 32 * 128 == pytest.approx(1.0995e12,
                                                                   rel=1e-4)
    assert fwd["bytes"] == 2 * 2 * L * (32 + 4) * 128 * 2
    bwd = oc.attend(cfg, L, forwards=0, backwards=1,
                    pairs=oc.cross_pairs(cfg, L))
    assert bwd["flops"] == 2 * fwd["flops"] and bwd["bytes"] == 2 * fwd["bytes"]
    assert opcount.roofline_seconds(fwd, PEAK)["bound"] == "compute"
    g = oc.grouped_products(cfg, 16384, forwards=2, backwards=1)
    assert g["flops"] == 24.0 * 16384 * 2048 * 768
    flops = oc.train_flops_per_token(cfg, L)
    # two positions a token through every layer, one at the head
    mat = 2 * 5 * (18_874_624 + 2048 * 128 + 4_718_592) + 2048 * 18992
    assert flops == 6.0 * mat + 5 * 12.0 * 32 * 128 * (L + 4)
    assert flops == pytest.approx(3.68e9, rel=5e-3)
    # the cell's why: the mask's pairs are over half of the step's
    # arithmetic, the held experts a twelfth
    assert 5 * 12.0 * 32 * 128 * (L + 4) / flops == pytest.approx(0.547,
                                                                  abs=0.01)
    assert 6.0 * 2 * 5 * 4_718_592 / flops == pytest.approx(0.077, abs=0.005)


def test_the_kernel_patterns_find_the_calls_by_their_results():
    fwd = _json(B, "metrics", "flash_fwd_roofline.bd.json")["args"]
    bwd = _json(B, "metrics", "flash_bwd_roofline.bd.json")["args"]
    tile = "{3,2,1,0:T(8,128)(2,1)}"
    forward = ("%bd_cross.62 = (bf16[1,32,8192,128]" + tile
               + ", f32[1,32,1,8192]{3,2,1,0:T(1,128)}) "
               "custom-call(%a, %b, %c), "
               "custom_call_target=\"tpu_custom_call\"")
    fused = ("%bd_cross.64 = (bf16[1,32,8,1024,128]"
             "{4,3,2,1,0:T(8,128)(2,1)}, bf16[2,1,32,8192,128]"
             "{4,3,2,1,0:T(8,128)(2,1)}) custom-call(%a), "
             "custom_call_target=\"tpu_custom_call\"")
    assert re.search(fwd["pattern"], forward)
    assert not re.search(fwd["pattern"], fused)
    assert re.search(bwd["pattern"], fused)
    assert not re.search(bwd["pattern"], forward)
    # another model's flash calls stand under another scope
    assert not re.search(fwd["pattern"], forward.replace("bd_cross",
                                                         "attn_full"))


RECORDED = {
    "loss": 10.4, "lb_loss": 5.1,
    "mix_out_ms": [0.21, 0.32, 0.41, 0.53, 0.6],
    "early_ms": [[0.4, 0.5]] * 5,
    "expert_pairs": [[1024.0] * 16] * 5,
    "masked_targets": 4100.0, "weight_sum": 8000.0,
    "grad_err": 0.0, "grad_err_all": 0.0, "param_change_err": 0.0}


def test_the_checks_rules_on_recorded_numbers(cfg):
    check = cfg["check"]
    assert check["compared"] == [
        "loss", "lb_loss", "mix_out_ms", "early_ms", "expert_pairs",
        "masked_targets", "weight_sum", "grad_err", "grad_err_all",
        "param_change_err"]
    want = {k: np.asarray(v, np.float64) for k, v in RECORDED.items()}
    ok = {**want, "loss": want["loss"] + 0.5 * check["loss_abs_tol"],
          "grad_err": 0.5 * check["grad_err_abs_tol"]}
    assert train_hybrid.compare(ok, want, check)[0] == []
    for name, off in (("loss", 2 * check["loss_abs_tol"]),
                      ("lb_loss", 2 * check["lb_loss_abs_tol"]),
                      ("expert_pairs", 2 * check["expert_pairs_abs_tol"]),
                      ("masked_targets", 1.0),
                      ("grad_err", 2 * check["grad_err_abs_tol"]),
                      ("grad_err_all", 2 * check["grad_err_all_abs_tol"]),
                      # a state left as it was reads 1
                      ("param_change_err", 1.0)):
        bad = {**want, name: want[name] + off}
        assert any(name in p for p in train_hybrid.compare(
            bad, want, check)[0]), name
    for name in ("mix_out_ms", "early_ms"):
        bad = {**want, name: want[name] * (1 + 2 * check[f"{name}_rel_tol"])}
        assert train_hybrid.compare(bad, want, check)[0]
    lo, hi = check["first_loss_range"]
    assert lo < np.log(18992) + 0.5 < hi
    # every limit lies between its two readings, with room on both sides
    # (the loss's excepted, where the control reads the harness's limit: the
    # file says so)
    assert set(check["readings"]) == set(check["compared"])
    for name, r in check["readings"].items():
        tol = check.get(f"{name}_abs_tol", check.get(f"{name}_rel_tol"))
        assert r["program_max"] < tol or tol == r["program_max"] == 0, name
        if name in check["decided_by"]:
            # between the two readings, with room on both sides
            assert 1.25 * r["program_max"] < tol < r["fp8_min"] / 1.25, name
    assert len(check["decided_by"]) == 6
    # the early positions: between the program's reading and the mask faults'
    early = check["readings"]["early_ms"]
    assert 3 * early["program_max"] < check["early_ms_rel_tol"] \
        < early["mask_fault_min"] / 1.5 < early["mask_fault_max"] / 10
    # the loss: the accepted cells' limit, three times of room over the
    # program's largest reading; fp8 does not always fail by it
    assert check["loss_abs_tol"] == 0.002 \
        >= 3 * check["readings"]["loss"]["program_max"]
    assert check["readings"]["loss"]["fp8_min"] < 0.002 \
        < check["readings"]["loss"]["fp8_max"]
    # the routed FFN's leaves are out of the worst leaf, by name
    assert train_bd_moe.ROUTED == ("router", "w_gate", "w_up", "w_down",
                                   "ln2")
    assert set(train_bd_moe.FAULTS) - {"fp8", "unchanged"} \
        == set(reference_sdar.FAULTS)
    assert set(check["controls"]) == set(train_bd_moe.FAULTS)


def test_toy_widths_shrink_what_rehearsal_json_does_not_name(cfg):
    assert train_bd_moe.at_widths(cfg) is cfg
    rehearsal = _json(B, "rehearsal.json")["config"]
    toy = train_bd_moe.at_widths({**cfg, **rehearsal})
    assert {k: toy[k] for k in train_bd_moe.TOY} == train_bd_moe.TOY
    assert not set(train_bd_moe.TOY) & set(rehearsal)
    assert toy["mask_token_id"] == rehearsal["vocab_size"] - 1


def test_the_batch_is_the_seeds_and_counts_tokens_not_positions(cfg):
    traffic = _json(B, "traffic", "block_diffusion_8k_1row.json")
    a = train_bd_moe.make_rows(np.random.default_rng(2 ** 31 + 5), traffic,
                               cfg, 1, 8192)
    b = train_bd_moe.make_rows(np.random.default_rng(2 ** 31 + 5), traffic,
                               cfg, 1, 8192)
    assert sorted(a) == ["input_ids", "loss_weights", "noised_ids"]
    for key in a:
        assert a[key].shape == (1, 8192)       # a batch stays L wide
        np.testing.assert_array_equal(a[key], b[key])
    assert a["input_ids"].max() < cfg["mask_token_id"]
    masked = a["noised_ids"] == cfg["mask_token_id"]
    # about half the positions are hidden (t is uniform over a block)
    assert 3500 < masked.sum() < 4700
    np.testing.assert_array_equal(masked, a["loss_weights"] > 0)


def test_the_readers_return_nothing_where_there_is_nothing_to_read(cfg):
    empty = {"cell": {"name": CELL}, "cfg": cfg, "peak": None, "trace": None,
             "reduced": {}, "values": {}}
    assert bd.flash_bd(empty, "x") is None
    assert bd.experts_roofline(empty) is None
    assert bd.train_mfu(empty) is None
    assert bd.host_span_ms(empty, "ds.data.block_noise") is None
    assert bd.positions_per_step(empty) is None
    # another configuration's file, the parent's program (no such fact)
    other = {**empty, "cfg": {"hidden_size": 1}, "peak": PEAK,
             "values": {"train_tok_s_chip": 1.0, "seq": 8192, "rows": 1}}
    assert bd.train_mfu(other) is None
    assert bd.flash_bd(other, "x") is None
    assert bd.experts_roofline(other) is None
    full = {**empty, "peak": PEAK,
            "values": {"train_tok_s_chip": 18000.0, "seq": 8192}}
    assert bd.train_mfu(full) == pytest.approx(
        100 * 18000.0 * oc.train_flops_per_token(cfg, 8192) / 197e12)


def test_the_readers_read_a_recorded_trace(cfg, tmp_path, monkeypatch):
    """``testdata/bd_tiny.*`` (``testdata/record_bd_trace.py``, on a TPU
    v5e): the cell's five layers at its head size and block length, narrow
    and short, two traced steps. Both scopes have device time inside
    ``attn``'s; the noising's host span is there; the three shares lie
    between 0 and 100 %, the kernels' work the mask's pairs over the clean
    keys; the row's facts are the toy's."""
    from benchmarks import trace_reduce as tr
    from benchmarks.readers import looped, program

    data = os.path.join(B, "testdata")
    with gzip.open(os.path.join(data, "bd_tiny.json.gz"), "rt") as f:
        facts = json.load(f)
    path = str(tmp_path / "bd_tiny.xplane.pb")
    with gzip.open(os.path.join(data, "bd_tiny.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    monkeypatch.setattr(program, "xplane_path", lambda name: path)
    trace = tr.load_xplane(path)
    toy = {**cfg, **facts["config"],
           "deployment": {**cfg["deployment"],
                          "remat_policy": facts["remat_policy"]}}
    said = facts["facts"]
    assert (said["diffusion_block"], said["positions_per_token"],
            said["head_rows"]) == (4, 2, facts["seq"])
    tiles = said["bd_mask_tiles"]
    assert tiles["pairs_kept"] == oc.mask_pairs(toy, facts["seq"])
    assert tiles["diag4"]["dead"] == tiles["diag4_strict"]["dead"] == 1
    assert facts["device"] == "TPU v5 lite"
    assert 800 < facts["masked_targets_per_step"] < 1250

    def ctx(**over):
        return {"cfg": toy, "cell": {"name": "bd_tiny"}, "peak": PEAK,
                "trace": trace, "program": {"hlo_text": facts["hlo_text"]},
                "reduced": {"window_ns": list(tr.window(trace))},
                "values": {"seq": facts["seq"], "rows": 1, "chips": 1,
                           "moe_pairs_per_step": facts["pairs_per_step"]},
                **over}

    ms = {s: looped.scope_device_ms(ctx(), s) for s in (
        "bd_cross", "bd_own", "attn")}
    assert all(v and v > 0 for v in ms.values()), ms
    assert ms["bd_cross"] + ms["bd_own"] < ms["attn"]
    c = ctx()
    fwd = _json(B, "metrics", "flash_fwd_roofline.bd.json")["args"]
    bwd = _json(B, "metrics", "flash_bwd_roofline.bd.json")["args"]
    shares = {"forward": bd.flash_bd(c, **fwd),
              "backward": bd.flash_bd(c, **bwd),
              "experts": bd.experts_roofline(c)}
    assert all(0 < v < 100 for v in shares.values()), shares
    # the forward's calls: two a layer (the clean half's, the noised half's
    # over the clean keys), once more each where the toy's policy keeps
    # nothing of them, in each traced step
    k = tr.kernel_seconds(trace, tuple(tr.window(trace)), fwd["pattern"],
                          fwd["field"])
    again = 1 if facts["remat_policy"] == "attn_saveable" else 2
    assert k["calls"] == 5 * 2 * again * facts["traced_steps"]
    k = tr.kernel_seconds(trace, tuple(tr.window(trace)), bwd["pattern"],
                          bwd["field"])
    assert k["calls"] == 5 * 2 * facts["traced_steps"]
    # the host span of the noising, through the program's own reading of
    # the trace's host events
    events = program.load_program_events(path)
    phases = program.host_phases(events["spans"], tuple(tr.window(trace)))
    assert 0 < phases["ds.data.block_noise"] < phases["ds.train.step"]
    with_host = ctx(program={"hlo_text": facts["hlo_text"],
                             "host_phases_ms": phases})
    assert bd.host_span_ms(with_host, "ds.data.block_noise") \
        == phases["ds.data.block_noise"]
    # a program without the scopes or the kernels' names (the parent's, a
    # model of another kind): nothing, and no raise
    other = ctx(program={"hlo_text": facts["hlo_text"].replace(
        "bd_own", "xyz_own")})
    assert looped.scope_device_ms(other, "bd_own") is None
    assert bd.flash_bd(ctx(), "^%no_such_kernel", "label") is None
    assert bd.host_span_ms(ctx(), "ds.data.block_noise") is None


def test_experts_are_dealt_by_load_one_hot_expert_to_a_share():
    """``deal``: a permutation, heaviest first in a snake, every share's
    load the mean's but for the tail. ``place_experts`` on a toy model whose
    masked positions all pick the same experts of a layer: placed, each of
    the shares holds its part of them, and the share held carries about the
    mean load where by index it may carry none or several."""
    counts = np.array([5, 900, 7, 3, 880, 2, 1, 9, 4, 6, 870, 8, 0, 860, 3, 2])
    src = reference_sdar.deal(counts, 4)
    assert sorted(src) == list(range(16))
    loads = counts[np.array(src)].reshape(4, 4).sum(axis=1)
    assert sorted(counts[np.array(src)].reshape(4, 4)[:, 0]) == [860, 870,
                                                                 880, 900]
    assert loads.max() - loads.min() <= 40      # of a mean of 890
    # a toy model: 2 layers, 16 experts of which 2 are held (8 shares), 2 a
    # position; half of the noised half is the mask token
    rng = np.random.default_rng(0)
    D, H, K, d, E, F, V, L = 32, 4, 2, 8, 16, 8, 64, 64
    cfg = {"num_hidden_layers": 2, "num_attention_heads": H,
           "num_key_value_heads": K, "head_dim": d, "rms_norm_eps": 1e-6,
           "rope_theta": 1e4, "num_experts": 2, "router_width": E,
           "first_expert": 0, "num_experts_per_tok": 2, "block_length": 4,
           "hidden_size": D}
    shapes = {"ln1": (D,), "ln2": (D,), "wq": (D, H * d), "wk": (D, K * d),
              "wv": (D, K * d), "wo": (H * d, D), "q_norm": (d,),
              "k_norm": (d,), "router": (D, E), "w_gate": (2, D, F),
              "w_up": (2, D, F), "w_down": (2, F, D)}
    w = {(n, i): (np.ones(s, np.float32) if "norm" in n or n.startswith("ln")
                  else rng.normal(0, s[-2] ** -0.5, s).astype(np.float32))
         for n, s in shapes.items() for i in range(2)}
    w[("embed", None)] = rng.normal(0, 1, (V, D)).astype(np.float32)
    get = lambda name, layer=None: w[(name, layer)]  # noqa: E731
    ids = rng.integers(0, V - 1, (1, L)).astype(np.int32)
    noised = ids.copy()
    noised[0, ::2] = V - 1
    batch = {"input_ids": ids, "noised_ids": noised,
             "loss_weights": (noised == V - 1).astype(np.float32)}
    placed = reference_sdar.place_experts(cfg, get, batch, 8)
    assert len(placed) == 2 and all(sorted(p) == list(range(E))
                                    for p in placed)
    # layer 0's routing does not depend on any placement: the masked
    # positions' two experts stand in two different shares afterwards
    import jax.numpy as jnp
    tokens = np.concatenate([noised[0], ids[0]])
    x = jnp.asarray(w[("embed", None)])[tokens]
    lw = {n: jnp.asarray(w[(n, 0)]) for n in shapes}
    a = x + reference_sdar.mixer(x, lw, cfg, L)
    _, top_e, _ = reference_sdar.route(
        reference_sdar.rms_norm(a, lw["ln2"], 1e-6), lw["router"], 2)
    hot = np.bincount(np.asarray(top_e)[tokens == V - 1].ravel(),
                      minlength=E)
    two = np.argsort(-hot)[:2]
    assert hot[two].sum() > 0.8 * hot.sum()         # they do all pick them
    where = [placed[0].index(int(e)) // 2 for e in two]
    assert where[0] != where[1]
