"""What this benchmark holds of LFM2-24B-A2B: the configuration keeps what
the publisher's ``config.json`` says (as the catalog beside the
``model-configs`` guide has it) and cuts depth, the leading dense layers, the
experts held and the vocabulary alone; the manifest takes the configuration,
cell and metrics by files alone (the traffic file is one the benchmark had);
the operation, byte and parameter counts are the arithmetic ``PERF.md`` states
and the program's own; the readers return nothing where there is nothing to
read and the convolution's roofline reads what a recorded line read; a
rehearsal of the cell ends correct, and each fault put in the program's place
(``runners/train_conv_moe.py:control``) comes out of the same comparison not
correct."""

import importlib
import json
import os
import subprocess
import sys

import jax
import pytest

from benchmarks import modelcfg_lfm2, opcount, opcount_lfm2
from benchmarks.readers import moe_share, program, short_conv
from benchmarks.runners import train_conv_moe

HERE = os.path.dirname(os.path.abspath(__file__))
B = os.path.join(HERE, "..")
ROOT = os.path.join(B, "..")
CELL = "lfm2_24b_train_1chip"
CONFIG = "lfm2_24b_train_d5e8v8"
LAYER_TYPES = ["conv", "conv"] + ["full_attention", "conv", "conv",
                                  "conv"] * 9 + ["full_attention", "conv"]
PUBLISHED = {
    "model_type": "lfm2_moe", "hidden_size": 2048, "num_hidden_layers": 40,
    "layer_types": LAYER_TYPES, "vocab_size": 65536,
    "num_attention_heads": 32, "num_key_value_heads": 8,
    "intermediate_size": 11776, "moe_intermediate_size": 1536,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-05,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "use_expert_bias": True, "max_position_embeddings": 128000,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}
REDUCED = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8,
           "vocab_size": 8192}
NEW_METRICS = {
    "sconv_proj_device_ms", "sconv_conv_device_ms", "sconv_conv_roofline",
    "flash_fwd_roofline.sconv", "flash_bwd_roofline.sconv",
    "moe_experts_roofline.sconv", "train_mfu.sconv"}
TAKEN = {
    "moe_router_device_ms", "moe_dispatch_device_ms", "moe_experts_device_ms",
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
    # no width among what was cut
    assert not set(cfg["reduced"]) & {
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_experts_per_tok", "conv_L_cache", "num_attention_heads",
        "num_key_value_heads"}
    # the floors: the leading dense layers once and a whole period (four
    # layers after the dense one), 8 experts, an eighth of the rows; the
    # router as wide as published
    assert opcount_lfm2.kinds(cfg) == [
        ("conv", "dense"), ("full_attention", "moe"), ("conv", "moe"),
        ("conv", "moe"), ("conv", "moe")]
    assert (cfg["router_width"], cfg["first_expert"], cfg["first_layer"]) \
        == (64, 0, 1)
    assert cfg["vocab_size"] * 8 >= 65536 and cfg["tie_word_embeddings"]
    dep = cfg["deployment"]
    assert (dep["chips_sharing_a_layer"], dep["chips"]) == (8, 1)
    assert "eight-chip" in dep["stands_for"]
    for part in ("source", "assumed", "deployment", "check", "modules"):
        assert cfg[part], part
    for text in (dep["remat_why"], dep["embed_init_why"],
                 dep["local_pairs_why"], cfg["check"]["tol_why"]):
        assert len(text) > 100
    for said in ("tied_head", "training_recipe", "bias_update_rate",
                 "balance_coef", "bias_init", "conv_init", "sum_eps",
                 "embedding_init"):
        assert said in cfg["assumed"], said


def test_the_manifest_takes_the_cell_by_files_alone(cfg):
    m = _json(ROOT, "BENCHMARK.json")
    entry = {w["name"]: w for w in m["workloads"]}[CELL]
    f = _json(B, "workloads", f"{CELL}.json")
    assert (f["config"], f["traffic"], f["chips"], f["why"]) == \
        (entry["config"], entry["traffic"], entry["chips"], entry["why"])
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert m["workloads"][7]["name"] == CELL        # appended, nothing moved
    assert all(w["chips"] == 1 for w in m["workloads"])
    conf = {c["name"]: c for c in m["configs"]}[entry["config"]]
    assert m["configs"][7] is conf
    assert conf["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert sorted(conf["reduced"]) == sorted(cfg["reduced"])
    assert cfg["source"] == conf["source"] and len(conf["why"]) <= 200
    traffic = _json(B, "traffic", f"{entry['traffic']}.json")
    assert (entry["traffic"], traffic["kind"], traffic["seq_len"],
            traffic["rows_per_chip"]) == ("packed_8k", "train", 8192, 2)
    for mod in cfg["modules"].values():
        importlib.import_module(f"benchmarks.{mod}")
    assert f["runner"] == "train_conv_moe" and callable(train_conv_moe.run)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert e2e["train_tok_s_chip"]["workloads"][7] == CELL
    mine = {p["name"] for p in m["per_layer"] if CELL in p["workloads"]}
    everywhere = {p["name"] for p in m["per_layer"]
                  if "granite4_h_micro_train_1chip" in p["workloads"]
                  and "kanana2_30b_train_1chip" in p["workloads"]
                  and "mistral7b_train_1chip" in p["workloads"]}
    assert mine == NEW_METRICS | TAKEN | everywhere
    for p in m["per_layer"]:
        if p["name"] in NEW_METRICS:
            assert p["workloads"] == [CELL]
            assert p["moves"] == "train_tok_s_chip"
            spec = _json(B, "metrics", p["name"] + ".json")
            mod, fn = spec["reader"].split(":")
            assert callable(getattr(importlib.import_module(
                f"benchmarks.{mod}"), fn))
    # the flash pair: the Olmo-Hybrid cell's patterns and reader, by data
    for which in ("fwd", "bwd"):
        mine_, theirs = (_json(B, "metrics", f"flash_{which}_roofline.{x}.json")
                         for x in ("sconv", "delta"))
        assert (mine_["reader"], mine_["args"]) == (theirs["reader"],
                                                    theirs["args"])


def test_the_counts_are_the_arithmetic_perf_md_states(cfg):
    oc = opcount_lfm2
    assert oc.conv_mixer_params(cfg) == 16_783_360
    assert oc.attn_mixer_params(cfg) == 10_485_888
    assert oc.expert_params(cfg) == 9_437_184
    assert oc.layer_params(cfg, ("conv", "dense")) == 89_139_200
    assert oc.layer_params(cfg, ("full_attention", "moe")) == 86_118_592
    assert oc.layer_params(cfg, ("conv", "moe")) == 92_416_064
    assert oc.total_params(cfg) == 469_285_248 == \
        cfg["deployment"]["parameters"]
    assert cfg["deployment"]["state_bytes_at_16"] == 16 * 469_285_248
    # and the program's own leaves
    from deepspeed_tpu.models import TransformerLM

    model = TransformerLM(modelcfg_lfm2.transformer_config(
        cfg, max_seq_len=8192, param_dtype="float32"))
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) \
        == 469_285_248
    # the whole model by the same formulas: the published 24B-A2B
    whole = oc.published(cfg)
    assert oc.layer_params(whole, ("conv", "moe")) \
        == 16_783_360 + 4096 + 131_136 + 64 * 9_437_184
    assert oc.whole_model_params(cfg) == 23_843_661_440
    assert oc.active_params(cfg) == pytest.approx(2.327e9, rel=1e-3)
    kinds = oc.kinds(whole)
    assert len(kinds) == 40 and sum(k[0] == "conv" for k in kinds) == 30
    assert sum(k[1] == "dense" for k in kinds) == 2
    # the held experts' share: half a pair a token; 22 % of a routed conv
    # layer's matmul work here, 69 % in the model
    assert oc.expected_pairs_per_token(cfg) == 4 * 8 / 64
    parts = oc.layer_forward_flops_per_token(cfg, 8192)
    here = parts["routed"] / (parts["routed"] + parts["router"]
                              + parts["conv_proj"])
    assert here == pytest.approx(0.218, abs=2e-3)
    parts = oc.layer_forward_flops_per_token(whole, 8192)
    assert parts["routed"] / (parts["routed"] + parts["router"]
                              + parts["conv_proj"]) \
        == pytest.approx(0.69, abs=0.01)
    g = oc.grouped_products(cfg, 8192, forwards=1, backwards=1)
    assert g["flops"] == 18.0 * 8192 * 2048 * 1536
    # the gates and the convolution: four arrays a forward, seven a
    # backward, 0.33 and 0.57 ms a layer at 16,384 tokens on a v5e
    f = oc.short_conv(cfg, 8192, batch=2)
    b = oc.short_conv(cfg, 8192, batch=2, forwards=0, backwards=1)
    assert f["bytes"] == 4 * 16384 * 2048 * 2
    assert b["bytes"] == 7 * 16384 * 2048 * 2
    for ops, ms in ((f, 0.328), (b, 0.574)):
        roof = opcount.roofline_seconds(ops, PEAK)
        assert roof["bound"] == "memory"
        assert roof["seconds"] * 1e3 == pytest.approx(ms, abs=2e-3)
    # the attention layer by opcount.py's own formulas: 32 heads of 64 on 8
    fl = opcount.flash_forward(cfg, 8192)
    assert fl["flops"] == 4.0 * opcount.causal_pairs(8192, 8192) * 32 * 64
    assert oc.train_flops_per_token(cfg, 8192) == pytest.approx(1.2174e9,
                                                                rel=1e-4)


def test_toy_widths_shrink_what_rehearsal_json_does_not_name(cfg):
    assert train_conv_moe.at_widths(cfg) is cfg
    toy = train_conv_moe.at_widths({**cfg, "hidden_size": 64})
    assert {k: toy[k] for k in train_conv_moe.TOY} == train_conv_moe.TOY
    assert toy["layer_types"] == cfg["layer_types"]
    rehearsal = _json(B, "rehearsal.json")["config"]
    assert not set(train_conv_moe.TOY) & set(rehearsal)


def test_the_readers_find_nothing_where_there_is_nothing(cfg):
    other = _json(B, "configs", "granite4_h_micro_train_d10v8.json")
    for ctx in ({"cfg": other, "values": {"train_tok_s_chip": 1.0, "seq": 8},
                 "peak": {"bf16_flops_per_s": 1.0}},
                {"cfg": cfg, "values": {}, "peak": None}):
        assert short_conv.train_mfu(ctx) is None
        assert short_conv.experts_roofline(ctx) is None
        assert short_conv.conv_roofline(ctx) is None
    ctx = {"cfg": cfg, "peak": PEAK,
           "values": {"train_tok_s_chip": 60000.0, "seq": 8192}}
    flops = opcount_lfm2.train_flops_per_token(cfg, 8192)
    assert short_conv.train_mfu(ctx) == pytest.approx(
        100 * flops * 60000 / 197e12)
    assert 0 < short_conv.train_mfu(ctx) < 100


#: what a traced run of the cell read (its ``roofline`` earlier line)
RECORDED = {"sconv_conv_device_ms": 6.11341453846154, "forwards": 2,
            "sconv_conv_roofline": 80.4199}  # (my chip run, PR 49, seed 4100000093)


def test_the_convolutions_roofline_reads_what_a_recorded_line_read(
        cfg, monkeypatch):
    """The reader on what a traced chip run of the cell handed it (the
    scope's device time a step and the step program's text, here the one
    line that says the recomputed region holds the forward kernel): the
    share that run's line printed."""
    line = ('  %%sconv_conv.1 = bf16[8]{0} custom-call(%%p), metadata={'
            'op_name="jit(ds_train_step)/%s/attn/sconv_conv/'
            'jit(conv_fwd)/pallas_call"}')
    text = "\n".join([line % "jvp(layers)"] + [
        line % "transpose(jvp(layers))/checkpoint/rematted_computation"
    ] * (RECORDED["forwards"] - 1))
    monkeypatch.setattr(program, "analysis", lambda ctx: {"hlo_text": text})
    monkeypatch.setattr(moe_share, "scope_device_ms",
                        lambda ctx, scope: RECORDED["sconv_conv_device_ms"]
                        if scope == "sconv_conv" else None)
    ctx = {"cfg": cfg, "peak": PEAK, "cell": {"name": CELL},
           "values": {"seq": 8192, "rows": 2, "chips": 1}}
    share = short_conv.conv_roofline(ctx)
    assert share == pytest.approx(RECORDED["sconv_conv_roofline"], rel=1e-3)
    note = ctx["roofline_notes"][-1]
    assert note["bound"] == "memory" and note["forwards"] \
        == RECORDED["forwards"]
    assert note["roof_s"] == pytest.approx(4 * (
        RECORDED["forwards"] * 0.3278e-3 + 0.5736e-3), rel=1e-3)
    assert 0 < share < 100


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
    prog = check["step_program"]
    assert prog["layer_pattern"] == ["conv:dense", "full:moe", "conv:moe"]
    assert prog["layer_applications"] == 5
    assert prog["experts_held"] == [0, 8, 16]
    assert prog["moe_scoring"] == "sigmoid"
    assert prog["conv_lowerings"] == {"xla": 2}
    assert set(check) >= {"loss", "lb_loss", "mix_out_ms", "expert_pairs",
                          "router_bias", "grad_err", "param_change_err"}
    assert len(check["mix_out_ms"]["system"]) == 5
    assert len(check["expert_pairs"]["system"]) == 4
    # the update given the step's own gradient is AdamW's arithmetic alone
    assert check["param_change_err_given_own_gradient"] < 0.01
    window = next(json.loads(ln) for ln in out.stdout.splitlines()
                  if ln.startswith('{"window"'))["window"]
    assert window["pairs_dropped_in_window"] == 0


@pytest.mark.parametrize("fault", sorted(train_conv_moe.FAULTS))
def test_a_fault_in_the_programs_place_comes_out_not_correct(fault):
    """The runner's own comparison, at the rehearsal's widths (where its
    limits are the loosened ones): exit code 0 says the fault was seen."""
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.runners.train_conv_moe",
         "--control", fault, "--seed", "3000000019", "--rehearse"],
        capture_output=True, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["control"] == fault and last["correct"] is False
    assert last["problems"]
