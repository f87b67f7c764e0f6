"""BENCHMARK.json and the files it names agree, letter for letter."""

import glob
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
B = os.path.join(HERE, "..")
ROOT = os.path.join(B, "..")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|"
                   r"_rank$|head_dim|experts_per_tok")


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def test_manifest_matches_the_files():
    m = _json(ROOT, "BENCHMARK.json")
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["benchmarks"] and 1 <= m["run_seconds"] <= 51
    configs = {c["name"]: c for c in m["configs"]}
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    cells = {w["name"]: w for w in m["workloads"]}
    assert sum(w["chips"] == 4 for w in cells.values()) <= 1
    for name, w in cells.items():
        f = _json(B, "workloads", f"{name}.json")
        assert (f["config"], f["traffic"], f["chips"], f["why"]) == \
            (w["config"], w["traffic"], w["chips"], w["why"])
        assert w["config"] in configs and len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(B, "traffic",
                                           f"{w['traffic']}.json"))
    for name, c in configs.items():
        f = _json(ROOT, c["file"])
        assert f["source"] == c["source"]
        assert sorted(f["reduced"]) == c["reduced"]
        assert not any(WIDTH.search(k) for k in c["reduced"])
    for p in m["per_layer"]:
        assert NAME.match(p["name"])
        # the file says how the metric is read; the cells, unit, layer and
        # what it moves stand in BENCHMARK.json alone
        assert set(_json(B, "metrics", f"{p['name']}.json")) \
            == {"name", "reader", "args"}
        assert p["moves"] in e2e
        moved = e2e[p["moves"]].get("workloads", list(cells))
        assert set(p["workloads"]) <= set(moved), p["name"]
    for name in cells:
        got_e2e = [e for e in e2e.values()
                   if name in e.get("workloads", [name])]
        assert len(got_e2e) >= 2
        assert any(name in p.get("workloads", [name])
                   for p in m["per_layer"])


def test_every_metric_file_names_a_reader_that_exists():
    import importlib

    for path in glob.glob(os.path.join(B, "metrics", "*.json")):
        spec = _json(path)
        assert os.path.basename(path) == spec["name"] + ".json"
        mod, fn = spec["reader"].split(":")
        assert callable(getattr(
            importlib.import_module(f"benchmarks.{mod}"), fn))


def test_a_cell_and_a_metric_are_added_by_files_alone(tmp_path, monkeypatch):
    """What a later PR does: new files and new manifest entries, no edit of
    a file that is there. The harness then finds the cell and reads the new
    metric for it, and only for it."""
    import shutil

    from benchmarks import harness

    for kind in ("workloads", "configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(B, kind), tmp_path / "b" / kind)
    m = _json(ROOT, "BENCHMARK.json")
    old = m["workloads"][0]["name"]
    cell = dict(_json(B, "workloads", f"{old}.json"), why="the same, again")
    (tmp_path / "b" / "workloads" / "new_cell.json").write_text(
        json.dumps(cell))
    (tmp_path / "b" / "metrics" / "steps.new.json").write_text(json.dumps(
        {"name": "steps.new", "reader": "readers.basic:value",
         "args": {"key": "steps"}}))
    m["workloads"].append({**m["workloads"][0], "name": "new_cell",
                           "traffic": "another"})
    m["end_to_end"].append({"name": "new_rate", "unit": "tokens/s",
                            "better": "higher", "bound": 0.01,
                            "source": "host_clock",
                            "workloads": ["new_cell"]})
    m["per_layer"].append({"name": "steps.new", "unit": "steps",
                           "better": "higher", "source": "program_counter",
                           "layer": "train engine", "moves": "new_rate",
                           "workloads": ["new_cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    monkeypatch.setattr(harness, "HERE", str(tmp_path / "b"))
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    assert harness.load_cell("new_cell")["config"]["hidden_size"] == 4096
    assert set(harness.end_to_end_for("new_cell")) == {"new_rate", "setup_s"}
    ctx = {"values": {"steps": 7}}
    assert harness.read_per_layer("new_cell", ctx) == {
        "steps.new": {"value": 7.0, "unit": "steps"}}
    assert "steps.new" not in {
        p["name"] for p in harness._listed(m["per_layer"], old)}


def test_configs_keep_the_published_widths():
    published = {"hidden_size": 4096, "intermediate_size": 14336,
                 "num_attention_heads": 32, "num_key_value_heads": 8,
                 "head_dim": 128, "vocab_size": 32000,
                 "rms_norm_eps": 1e-05, "tie_word_embeddings": False,
                 "max_position_embeddings": 32768}
    for path in glob.glob(os.path.join(B, "configs", "*.json")):
        cfg = _json(path)
        for key, val in published.items():
            assert cfg[key] == val, (path, key)
        if cfg["model"].startswith("Mixtral"):
            assert (cfg["num_local_experts"], cfg["num_experts_per_tok"],
                    cfg["rope_theta"], cfg["sliding_window"]) == \
                (8, 2, 1000000.0, None)
        else:
            assert (cfg["rope_theta"], cfg["sliding_window"]) == \
                (10000.0, 4096)
        assert cfg["reduced"]["num_hidden_layers"]["published"] == 32
        assert cfg["reduced"]["num_hidden_layers"]["here"] \
            == cfg["num_hidden_layers"]
