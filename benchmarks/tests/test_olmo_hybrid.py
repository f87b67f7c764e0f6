"""What this benchmark holds of Olmo-Hybrid-7B: the configuration keeps what
the publisher's ``config.json`` says (as the catalog beside the
``model-configs`` guide has it) and cuts depth, the heads held and the
vocabulary alone; the manifest lists the cell and its files exist; the
reference's copy with the program's tests is the same file; the operation,
byte and parameter counts are the arithmetic ``PERF.md`` states and the
program's own; the readers return nothing where there is nothing to read, and
count the rule's forwards from the program's text; a rehearsal of the cell
ends correct, and each fault put in the program's place
(``runners/train_delta.py:control``) comes out of the same comparison not
correct."""

import filecmp
import importlib
import json
import os
import subprocess
import sys

import jax
import pytest

from benchmarks import modelcfg_olmo_hybrid, opcount, opcount_olmo_hybrid
from benchmarks.readers import delta

HERE = os.path.dirname(os.path.abspath(__file__))
B = os.path.join(HERE, "..")
ROOT = os.path.join(B, "..")
CELL = "olmo_hybrid_7b_train_1chip"
CONFIG = "olmo_hybrid_7b_train_d4h15v8"
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": PERIOD * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}
HEADS = ("linear_num_key_heads", "linear_num_value_heads",
         "num_attention_heads", "num_key_value_heads")
NEW_METRICS = {
    "delta_proj_device_ms", "delta_conv_device_ms", "delta_scan_device_ms",
    "delta_gate_device_ms", "delta_scan_roofline", "train_mfu.delta",
    "delta_chunks_per_step.train", "flash_fwd_roofline.delta",
    "flash_bwd_roofline.delta"}


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _json(B, "configs", f"{CONFIG}.json")


def test_the_configuration_keeps_the_published_values(cfg):
    assert sorted(cfg["reduced"]) == sorted(
        HEADS + ("num_hidden_layers", "vocab_size"))
    for key, val in PUBLISHED.items():
        if key in cfg["reduced"]:
            cut = cfg["reduced"][key]
            assert (cut["published"], cut["here"]) == (val, cfg[key]), key
            assert len(cut["why"]) > 40
        else:
            assert cfg[key] == val, key
    # no width among what was cut; the head's width is the published one
    assert cfg["head_dim"] == 3840 // 30
    # the floors: a whole period, an eighth of the rows; half the heads
    assert cfg["layer_types"][:cfg["num_hidden_layers"]] == PERIOD
    assert cfg["vocab_size"] * 8 >= 100352
    assert {cfg[k] for k in HEADS} == {15}
    assert modelcfg_olmo_hybrid.share(cfg) == 2
    dep = cfg["deployment"]
    assert (dep["pipeline_stages"], dep["chips_sharing_a_mixer"],
            dep["chips_sharing_the_table"]) == (8, 2, 8)
    for part in ("source", "assumed", "deployment", "check", "modules"):
        assert cfg[part], part
    for text in (dep["remat_why"], dep["embed_init_why"],
                 cfg["check"]["tol_why"]):
        assert len(text) > 100
    # the one place where the share changes arithmetic is named
    assert "q/k norm is over the width held" in \
        cfg["reduced"]["num_attention_heads"]["why"]


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
        == ("train", 4096, 1)
    for mod in cfg["modules"].values():
        importlib.import_module(f"benchmarks.{mod}")
    assert callable(importlib.import_module(
        f"benchmarks.runners.{f['runner']}").run)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert CELL in e2e["train_tok_s_chip"]["workloads"]
    mine = {p["name"] for p in m["per_layer"] if CELL in p["workloads"]}
    mine.remove("layer_applications_per_step.train")    # the looped cell's
    # with the nine of its own, what every training cell reports
    everywhere = {p["name"] for p in m["per_layer"]
                  if "granite4_h_micro_train_1chip" in p["workloads"]
                  and "kanana2_30b_train_1chip" in p["workloads"]
                  and "mistral7b_train_1chip" in p["workloads"]}
    assert mine == NEW_METRICS | everywhere and len(everywhere) == 23
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
        os.path.join(B, "reference_olmo_hybrid.py"),
        os.path.join(ROOT, "tests", "unit", "olmo_hybrid_reference.py"),
        shallow=False)


def test_the_counts_are_the_arithmetic_perf_md_states(cfg):
    oc = opcount_olmo_hybrid
    assert oc.layer_params(cfg, "linear_attention") == 171_195_102
    assert oc.layer_params(cfg, "full_attention") == 156_314_880
    assert oc.delta_params(cfg) == {
        "matrices": 3840 * (2 * 1440 + 2 * 2880 + 30) + 2880 * 3840,
        "other": 4 * 5760 + 30 + 192}
    assert oc.total_params(cfg) == 3 * 171_195_102 + 156_314_880 \
        + 2 * 12544 * 3840 + 3840 == 766_241_946
    assert 766_241_946 * 16 == pytest.approx(12.26e9, rel=1e-3)
    # whole heads: a layer of each kind, a period, and about 7B in all
    whole = {**cfg, **{k: 30 for k in HEADS}}
    assert oc.layer_params(whole, "linear_attention") == 215_570_172
    assert oc.layer_params(whole, "full_attention") == 185_809_920
    assert 3 * 215_570_172 + 185_809_920 == 832_520_436
    model = {**whole, "num_hidden_layers": 32, "vocab_size": 100352}
    assert oc.total_params(model) == pytest.approx(7.43e9, rel=2e-3)
    # the delta mixer's projections are 26 % of such a layer's matrix
    # parameters here, 41 % with every head
    for c, want in ((cfg, 0.259), (whole, 0.412)):
        mix = oc.delta_params(c)["matrices"]
        assert mix / (mix + oc.mlp_params(c)) == pytest.approx(want,
                                                               abs=1e-3)
    # and they are the program's own count
    from deepspeed_tpu.models import TransformerLM

    tcfg = modelcfg_olmo_hybrid.transformer_config(
        cfg, max_seq_len=4096, param_dtype="float32")
    assert (tcfg.num_heads, tcfg.heads_held, tcfg.head_dim,
            tcfg.delta_heads) == (30, 15, 128, 30)
    assert tcfg.num_params_estimate() == 766_241_946
    shapes = jax.eval_shape(TransformerLM(tcfg).init, jax.random.key(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) \
        == 766_241_946
    # a step: 6 x the matrices a token meets, the rules, the full layer
    mat = oc.matmul_params_per_token(cfg)
    assert mat == 3 * 44_352_000 + 29_491_200 + 4 * 126_812_160 \
        + 3840 * 12544
    per_tok = oc.train_flops_per_token(cfg, 4096)
    assert 6 * mat == pytest.approx(4.31e9, rel=1e-3)
    assert per_tok - 6 * mat == pytest.approx(0.075e9, rel=2e-2)


def test_the_rules_work_is_a_function_of_the_shapes_alone(cfg):
    oc = opcount_olmo_hybrid
    one = oc.delta_rule(cfg, 4096)
    # a position of a head: K K^T, Q K^T, the inverse, its two products, the
    # masked product, the state's two reads and its update
    per_pos = 64 * (6 * 96 + 4 * 192) + 2 * 64 * 64 / 3 + 6 * 96 * 192
    assert one["flops"] == pytest.approx(4096 * 15 * per_pos)
    assert per_pos == pytest.approx(199_338.7, rel=1e-6)
    # q, k, v, o bf16, g and beta float32; 64 chunk states written, read
    assert one["bytes"] == 4096 * 15 * (2 * 96 * 2 + 2 * 192 * 2 + 8) \
        + 2 * 64 * 15 * 96 * 192 * 4 == 212_828_160
    step = oc.delta_rule(cfg, 4096, forwards=2, backwards=1)
    assert step == {"flops": 4 * one["flops"], "bytes": 4 * one["bytes"]}
    assert oc.delta_rule(cfg, 4096, batch=2)["flops"] == 2 * one["flops"]
    # a ragged tail still holds a chunk state
    assert oc.delta_rule(cfg, 4097)["bytes"] - one["bytes"] \
        == 15 * (2 * 96 * 2 + 2 * 192 * 2 + 8) + 2 * 15 * 96 * 192 * 4
    # on a v5e the rule is bound by bytes: 0.26 ms a forward, 3.1 ms a step
    # of three layers under recomputation
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    roof = opcount.roofline_seconds(one, peak)
    assert roof["bound"] == "memory"
    assert roof["seconds"] == pytest.approx(0.2599e-3, rel=1e-3)
    assert 12 * roof["seconds"] == pytest.approx(3.12e-3, rel=1e-2)


def test_toy_widths_shrink_the_linear_keys_and_nothing_else(cfg):
    assert modelcfg_olmo_hybrid.at_widths(cfg) is cfg
    toy = {**cfg, "hidden_size": 64, "intermediate_size": 128,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 16, "vocab_size": 256}
    at = modelcfg_olmo_hybrid.at_widths(toy)
    assert (at["linear_num_key_heads"], at["linear_num_value_heads"],
            at["linear_key_head_dim"], at["linear_value_head_dim"]) \
        == (4, 4, 8, 16)
    tcfg = modelcfg_olmo_hybrid.transformer_config(
        toy, max_seq_len=32, param_dtype="float32")
    assert (tcfg.num_heads, tcfg.num_kv_heads, tcfg.heads_held,
            tcfg.kv_heads_here, tcfg.delta_heads) == (8, 4, 4, 2, 8)
    assert tcfg.attn_pattern == ("delta", "delta", "delta", "full")
    assert tcfg.remat_policy == cfg["deployment"]["remat_policy"]


def test_the_readers_find_nothing_where_there_is_nothing():
    ctx = {"values": {"train_tok_s_chip": 1.0, "seq": 4096, "rows": 1,
                      "chips": 1}, "cfg": {"deployment": {}}, "peak": None,
           "reduced": {}, "trace": None, "cell": {"name": CELL}}
    assert delta.scan_roofline(ctx) is None
    assert delta.train_mfu(ctx) is None
    # a program whose table has no such field (the parent's): nothing
    assert delta.chunks_per_step(ctx) is None or \
        delta.chunks_per_step(ctx) >= 0


def test_the_rules_forwards_are_counted_from_the_programs_text():
    """One where the recomputed region holds no product or kernel under the
    scope (``dots_saveable`` and the einsum form), two where it does."""
    line = ('  %%fusion.1 = f32[8]{0} fusion(%%p), kind=kOutput, metadata={'
            'op_name="jit(step)/%s/attn/delta_scan/%s"}')
    kept = "\n".join([
        line % ("jvp(layers)", "bnhid,bnhjd->bnhij/dot_general"),
        line % ("transpose(jvp(layers))/checkpoint", "dot_general"),
        line % ("transpose(jvp(layers))/checkpoint/rematted_computation",
                "exp")])
    assert delta.rule_forwards(kept, "delta_scan") == 1
    for again in ("bhid,bhde->bhie/dot_general", "jit(rule)/pallas_call"):
        rerun = kept + "\n" + line % (
            "transpose(jvp(layers))/checkpoint/rematted_computation", again)
        assert delta.rule_forwards(rerun, "delta_scan") == 2
        assert delta.rule_forwards(rerun, "delta_proj") == 1


def test_the_utilisation_counts_the_rule_in(cfg):
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"values": {"train_tok_s_chip": 18000.0, "seq": 4096, "rows": 1,
                      "chips": 1}, "cfg": cfg, "peak": peak}
    flops = opcount_olmo_hybrid.train_flops_per_token(cfg, 4096)
    assert delta.train_mfu(ctx) == pytest.approx(
        100 * flops * 18000 / 197e12)
    assert 0 < delta.train_mfu(ctx) < 100


def test_a_rehearsal_of_the_cell_ends_correct():
    out = subprocess.run(
        [sys.executable, os.path.join(B, "run.py"), "--workload", CELL,
         "--seed", "3000000019", "--seconds", "1", "--trace", "0",
         "--rehearse"], capture_output=True, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["problems"] == []
    check = next(json.loads(ln) for ln in out.stdout.splitlines()
                 if ln.startswith('{"check"'))
    assert check["step_program"]["layer_pattern"] == [
        "delta", "delta", "delta", "full"]
    assert check["step_program"]["heads_held"] == [4, 8]
    assert set(check) >= {"loss", "mix_out_ms", "grad_err",
                          "param_change_err", "sign_differs_share"}
    # the update given the step's own gradient is AdamW's arithmetic alone
    assert check["param_change_err_given_own_gradient"] < 0.01
    assert check["step_program"]["delta_scan_lowerings"] == {"xla": 3}


@pytest.mark.parametrize("fault", ["fp8", "inverse_bwd", "unchanged"])
def test_a_fault_in_the_programs_place_comes_out_not_correct(fault):
    """The runner's own comparison, at the rehearsal's widths (where its
    limits are the loosened ones): exit code 0 says the fault was seen."""
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.runners.train_delta", "--control",
         fault, "--seed", "3000000019", "--rehearse"], capture_output=True,
        text=True, cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["control"] == fault and last["correct"] is False
    seen = {p.split()[2] for p in last["problems"]}
    assert seen & {"grad_err", "param_change_err"}
