"""``configs/ouro2_6b_train_d6.json`` keeps what Ouro-2.6B's ``config.json``
publishes (as the catalog beside the ``model-configs`` guide has it), cuts the
depth alone, and names files that exist.

``test_manifest.py::test_configs_keep_the_published_widths`` holds every file
in ``configs/`` to Mistral's widths and fails on this one; scoping it is a
``benchmark`` issue's (PERF.md, open questions). This test holds the new file
in the same way."""

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
B = os.path.join(HERE, "..")
ROOT = os.path.join(B, "..")

PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def test_the_looped_configuration_keeps_the_published_values():
    cfg = _json(B, "configs", "ouro2_6b_train_d6.json")
    assert sorted(cfg["reduced"]) == ["num_hidden_layers"]
    for key, val in PUBLISHED.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == val, key
    cut = cfg["reduced"]["num_hidden_layers"]
    assert cut["published"] == PUBLISHED["num_hidden_layers"]
    assert cut["here"] == cfg["num_hidden_layers"]
    # the floors: a whole period (one layer) and at least four layers, every
    # pass, no width
    assert cfg["num_hidden_layers"] >= 4 and cfg["total_ut_steps"] == 4
    for part in ("source", "assumed", "deployment", "check", "modules"):
        assert cfg[part], part
    assert cfg["source"] == \
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"


def test_the_manifest_lists_the_looped_cell_and_its_files_exist():
    m = _json(ROOT, "BENCHMARK.json")
    cell = "ouro2_6b_train_1chip"
    entry = {w["name"]: w for w in m["workloads"]}[cell]
    f = _json(B, "workloads", f"{cell}.json")
    assert (f["config"], f["traffic"], f["chips"], f["why"]) == \
        (entry["config"], entry["traffic"], entry["chips"], entry["why"])
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    conf = {c["name"]: c for c in m["configs"]}[entry["config"]]
    assert conf["file"] == "benchmarks/configs/ouro2_6b_train_d6.json"
    assert conf["reduced"] == ["num_hidden_layers"]
    cfg = _json(ROOT, conf["file"])
    assert cfg["source"] == conf["source"]
    for mod in cfg["modules"].values():
        importlib.import_module(f"benchmarks.{mod}")
    assert callable(importlib.import_module(
        f"benchmarks.runners.{f['runner']}").run)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert cell in e2e["train_tok_s_chip"]["workloads"]
    mine = {p["name"] for p in m["per_layer"] if cell in p["workloads"]}
    assert {"train_mfu.looped", "exit_loss_device_ms",
            "layer_applications_per_step.train"} <= mine
    assert "train_mfu" not in mine       # its reader counts one pass
    # a looped metric is read in the looped cell alone
    for p in m["per_layer"]:
        if p["name"] in ("train_mfu.looped", "exit_loss_device_ms",
                         "layer_applications_per_step.train"):
            assert p["workloads"] == [cell]
            assert p["moves"] == "train_tok_s_chip"
