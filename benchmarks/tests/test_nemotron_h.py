"""What this benchmark holds of Nemotron-3-Super-120B-A12B: the configuration
keeps what the publisher's ``config.json`` says (as the catalog beside the
``model-configs`` guide has it) and cuts depth, the heads, groups and experts
held, the vocabulary and the multi-token module alone; the manifest takes the
configuration, traffic, cell and metrics by files alone; the operation, byte
and parameter counts are the arithmetic ``PERF.md`` states and the program's
own; the readers return nothing where there is nothing to read; a rehearsal
of the cell ends correct, and each fault put in the program's place
(``runners/train_latent_moe.py:control``) comes out of the same comparison
not correct."""

import importlib
import json
import os
import subprocess
import sys

import jax
import pytest

from benchmarks import modelcfg_nemotron_h, opcount, opcount_nemotron_h
from benchmarks.readers import latent_moe
from benchmarks.runners import train_latent_moe

HERE = os.path.dirname(os.path.abspath(__file__))
B = os.path.join(HERE, "..")
ROOT = os.path.join(B, "..")
CELL = "nemotron3_super_120b_train_1chip"
CONFIG = "nemotron3_super_120b_train_d11h16e8v8"
PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
           "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
PUBLISHED = {
    "model_type": "nemotron_h", "hidden_size": 4096, "num_hidden_layers": 88,
    "hybrid_override_pattern": PATTERN, "vocab_size": 131072,
    "num_attention_heads": 32, "num_key_value_heads": 2, "head_dim": 128,
    "mamba_num_heads": 128, "mamba_head_dim": 64, "ssm_state_size": 128,
    "n_groups": 8, "conv_kernel": 4, "chunk_size": 128, "expand": 2,
    "n_routed_experts": 512, "num_experts_per_tok": 22,
    "moe_latent_size": 1024, "moe_intermediate_size": 2688,
    "moe_shared_expert_intermediate_size": 5376, "n_shared_experts": 1,
    "routed_scaling_factor": 5, "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "mlp_hidden_act": "relu2",
    "layer_norm_epsilon": 1e-05, "intermediate_size": 2688,
    "num_nextn_predict_layers": 1, "mtp_hybrid_override_pattern": "*E",
    "tie_word_embeddings": False, "use_conv_bias": True, "rope_theta": 10000,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 0.0001}
REDUCED = {"num_hidden_layers": 11, "mamba_num_heads": 16, "n_groups": 1,
           "num_attention_heads": 4, "num_key_value_heads": 1,
           "n_routed_experts": 8, "vocab_size": 16384,
           "num_nextn_predict_layers": 0}
NEW_METRICS = {
    "moe_latent_device_ms", "train_mfu.latent_moe",
    "moe_experts_roofline.latent", "ssm_scan_roofline.latent",
    "flash_fwd_roofline.latent", "flash_bwd_roofline.latent"}
TAKEN = {
    "moe_router_device_ms", "moe_dispatch_device_ms", "moe_experts_device_ms",
    "moe_shared_device_ms", "moe_pairs_per_step.train",
    "moe_pairs_dropped.train", "moe_load_max_over_mean.train",
    "moe_bias_moved_per_step.train", "ssm_scan_device_ms",
    "ssm_proj_device_ms", "ssm_conv_device_ms", "ssm_gate_device_ms",
    "ssm_chunks_per_step.train", "layer_applications_per_step.train"}


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _json(B, "configs", f"{CONFIG}.json")


def test_the_configuration_keeps_the_published_values(cfg):
    assert sorted(cfg["reduced"]) == sorted(REDUCED)
    for key, val in PUBLISHED.items():
        if key in cfg["reduced"]:
            cut = cfg["reduced"][key]
            assert (cut["published"], cut["here"]) == (val, cfg[key]), key
            assert cut["here"] == REDUCED[key] and len(cut["why"]) > 40
        else:
            assert cfg[key] == val, key
    # the catalog's row, where the guide is at hand: every key it has
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == cfg["source"])
        for key, val in row["config"].items():
            assert cfg[key] == (REDUCED[key] if key in REDUCED else val), key
    # the floors: a whole period in the pattern's ratio, 8 experts, an eighth
    # of the rows; the router as wide as published
    kept = opcount_nemotron_h.kinds(cfg)
    assert kept == "MEMEMEM*EME" == PATTERN[:11]
    assert (cfg["router_width"], cfg["first_expert"]) == (512, 0)
    assert cfg["vocab_size"] * 8 >= 131072
    dep = cfg["deployment"]
    assert (dep["chips_sharing_a_mixer"], dep["chips_sharing_the_experts"],
            dep["chips"]) == (8, 64, 1)
    assert "512-chip" in dep["stands_for"]
    for part in ("source", "assumed", "deployment", "check", "modules"):
        assert cfg[part], part
    for text in (dep["remat_why"], dep["embed_init_why"],
                 dep["local_pairs_why"], cfg["check"]["tol_why"]):
        assert len(text) > 100
    for said in ("no_rope", "latent_maps", "router_and_shared_input",
                 "gated_norm", "expand", "initial_ranges", "float32_leaves"):
        assert said in cfg["assumed"]
    with pytest.raises(NotImplementedError, match="num_nextn_predict"):
        modelcfg_nemotron_h.transformer_config(
            {**cfg, "num_nextn_predict_layers": 1}, max_seq_len=128,
            param_dtype="float32")


def test_the_manifest_takes_the_cell_by_files_alone(cfg):
    m = _json(ROOT, "BENCHMARK.json")
    entry = {w["name"]: w for w in m["workloads"]}[CELL]
    f = _json(B, "workloads", f"{CELL}.json")
    assert (f["config"], f["traffic"], f["chips"], f["why"]) == \
        (entry["config"], entry["traffic"], entry["chips"], entry["why"])
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert len(m["workloads"]) == 7 and len(m["configs"]) == 7
    assert all(w["chips"] == 1 for w in m["workloads"])
    conf = {c["name"]: c for c in m["configs"]}[entry["config"]]
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert sorted(conf["reduced"]) == sorted(cfg["reduced"])
    assert cfg["source"] == conf["source"] and len(conf["why"]) <= 200
    traffic = _json(B, "traffic", f"{entry['traffic']}.json")
    assert (entry["traffic"], traffic["kind"], traffic["seq_len"],
            traffic["rows_per_chip"]) == ("packed_8k_1row", "train", 8192, 1)
    for mod in cfg["modules"].values():
        importlib.import_module(f"benchmarks.{mod}")
    assert f["runner"] == "train_latent_moe" and callable(train_latent_moe.run)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert e2e["train_tok_s_chip"]["workloads"][-1] == CELL
    mine = {p["name"] for p in m["per_layer"] if CELL in p["workloads"]}
    everywhere = {p["name"] for p in m["per_layer"]
                  if "granite4_h_micro_train_1chip" in p["workloads"]
                  and "kanana2_30b_train_1chip" in p["workloads"]
                  and "mistral7b_train_1chip" in p["workloads"]}
    assert mine == NEW_METRICS | TAKEN | everywhere
    for p in m["per_layer"]:
        if CELL in p["workloads"]:
            assert p["workloads"][-1] == CELL       # appended, nothing moved
        if p["name"] in NEW_METRICS:
            assert p["workloads"] == [CELL]
            assert p["moves"] == "train_tok_s_chip"
            spec = _json(B, "metrics", p["name"] + ".json")
            mod, fn = spec["reader"].split(":")
            assert callable(getattr(importlib.import_module(
                f"benchmarks.{mod}"), fn))


def test_the_counts_are_the_arithmetic_perf_md_states(cfg):
    oc = opcount_nemotron_h
    assert oc.mamba_layer_params(cfg) == 13_708_592
    assert oc.attention_layer_params(cfg) == 5_246_976
    assert oc.expert_layer_params(cfg) == 98_570_752
    assert oc.total_params(cfg) == 700_865_520 == \
        cfg["deployment"]["parameters"]
    assert cfg["deployment"]["state_bytes_at_16"] == 16 * 700_865_520
    # and the program's own leaves
    from deepspeed_tpu.models import TransformerLM

    model = TransformerLM(modelcfg_nemotron_h.transformer_config(
        cfg, max_seq_len=8192, param_dtype="float32"))
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) \
        == 700_865_520
    # the whole model by the same formulas: the published 120B-A12B
    whole = oc.published(cfg)
    assert (oc.mamba_layer_params(whole), oc.attention_layer_params(whole),
            oc._expert_layer(whole, 512)) \
        == (109_640_064, 35_655_680, 2_873_102_848)
    assert oc.whole_model_params(cfg) == pytest.approx(120.67e9, rel=1e-4)
    assert oc.active_params(cfg) == pytest.approx(12.77e9, rel=1e-4)
    assert (oc.kinds(whole).count("M"), oc.kinds(whole).count("*"),
            oc.kinds(whole).count("E")) == (40, 8, 40)
    # an expert is two products; the pairs are those really held
    assert oc.expert_params(cfg) == 2 * 1024 * 2688
    assert oc.expected_pairs_per_token(cfg) == 22 * 8 / 512
    g = oc.grouped_products(cfg, 2816, forwards=1, backwards=1)
    assert g["flops"] == 12.0 * 2816 * 1024 * 2688
    parts = oc.layer_forward_flops_per_token(cfg, 8192)
    assert parts["routed"] / sum(parts.values()) == pytest.approx(0.0335,
                                                                  abs=1e-3)
    at_model = {**whole, "n_routed_experts": 512}
    parts = oc.layer_forward_flops_per_token(at_model, 8192)
    assert parts["routed"] / sum(parts.values()) == pytest.approx(0.69,
                                                                  abs=0.01)
    # the scan: chunk 128, one group, 16 heads
    s = oc.ssd_scan(cfg, 8192)
    assert s["flops"] == 8192 * (2.0 * 128 * 128 + 2.0 * 128 * 64 * 16
                                 + 4.0 * 64 * 128 * 16)
    assert oc.ssd_scan(cfg, 8192, forwards=2, backwards=1)["bytes"] \
        == 4 * s["bytes"]
    # the attention layer by opcount.py's own formulas at the heads held
    f = opcount.flash_forward(cfg, 8192)
    assert f["flops"] == 4.0 * opcount.causal_pairs(8192, 8192) * 4 * 128
    assert 2.5e9 < oc.train_flops_per_token(cfg, 8192) < 2.7e9


def test_toy_widths_shrink_what_rehearsal_json_does_not_name(cfg):
    assert train_latent_moe.at_widths(cfg) is cfg
    toy = train_latent_moe.at_widths({**cfg, "hidden_size": 64})
    assert {k: toy[k] for k in train_latent_moe.TOY} == train_latent_moe.TOY
    assert toy["hybrid_override_pattern"] == cfg["hybrid_override_pattern"]
    rehearsal = _json(B, "rehearsal.json")["config"]
    assert not set(train_latent_moe.TOY) & set(rehearsal)


def test_the_readers_find_nothing_where_there_is_nothing(cfg):
    other = _json(B, "configs", "granite4_h_micro_train_d10v8.json")
    for ctx in ({"cfg": other, "values": {"train_tok_s_chip": 1.0, "seq": 8},
                 "peak": {"bf16_flops_per_s": 1.0}},
                {"cfg": cfg, "values": {}, "peak": None}):
        assert latent_moe.train_mfu(ctx) is None
        assert latent_moe.experts_roofline(ctx) is None
        assert latent_moe.scan_roofline(ctx) is None
    ctx = {"cfg": cfg, "peak": {"bf16_flops_per_s": 197e12},
           "values": {"train_tok_s_chip": 25000.0, "seq": 8192}}
    flops = opcount_nemotron_h.train_flops_per_token(cfg, 8192)
    assert latent_moe.train_mfu(ctx) == pytest.approx(
        100 * flops * 25000 / 197e12)
    assert 0 < latent_moe.train_mfu(ctx) < 100


def test_a_rehearsal_of_the_cell_ends_correct():
    out = subprocess.run(
        [sys.executable, os.path.join(B, "run.py"), "--workload", CELL,
         "--seed", "3000000019", "--seconds", "1", "--trace", "0",
         "--rehearse"], capture_output=True, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["problems"] == []
    check = next(json.loads(ln) for ln in out.stdout.splitlines()
                 if ln.startswith('{"check"'))
    program = check["step_program"]
    assert program["layer_pattern"] == [
        "ssm", "moe", "ssm", "moe", "ssm", "moe", "ssm", "full", "moe",
        "ssm", "moe"]
    assert program["layer_applications"] == 11
    assert program["experts_held"] == [0, 4, 32]
    assert program["ssm_chunks_per_step"] == 5 * 4     # 128 tokens, chunk 32
    assert set(check) >= {"loss", "lb_loss", "mix_out_ms", "expert_pairs",
                          "router_bias", "grad_err", "param_change_err"}
    assert len(check["mix_out_ms"]["system"]) == 11
    # the update given the step's own gradient is AdamW's arithmetic alone
    assert check["param_change_err_given_own_gradient"] < 0.01
    # (counted where traced: one expert layer's two products, the block of a
    # kind being traced once)
    assert program["moe_grouped_lowerings"] == {"xla": 2}
    window = next(json.loads(ln) for ln in out.stdout.splitlines()
                  if ln.startswith('{"window"'))["window"]
    assert window["pairs_dropped_in_window"] == 0


@pytest.mark.parametrize("fault", sorted(train_latent_moe.FAULTS))
def test_a_fault_in_the_programs_place_comes_out_not_correct(fault):
    """The runner's own comparison, at the rehearsal's widths (where its
    limits are the loosened ones): exit code 0 says the fault was seen."""
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.runners.train_latent_moe",
         "--control", fault, "--seed", "3000000019", "--rehearse"],
        capture_output=True, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["control"] == fault and last["correct"] is False
    assert last["problems"]
