"""``readers/collectives.py``: the reductions on plain data (a synthetic
line, and ``testdata/zero3_4chip_planes.json.gz``: two runs of the ZeRO-3 step
on the four device planes of a v5e host, every ``XLA Ops`` event by name), and
the new cell's files and manifest entries."""

import gzip
import json
import os
import re

import pytest

from benchmarks.readers import collectives as C
from benchmarks.trace_reduce import Op

HERE = os.path.dirname(os.path.abspath(__file__))
B = os.path.join(HERE, "..")
CELL = "mistral7b_train_zero3_4chip"
CONFIG = "mistral7b_v03_train_zero3_d5"
NEW = {"collective_bytes_per_step.train": "collective_bytes_per_step",
       "collective_exposed_ms": "collective_exposed_ms",
       "device_step_skew_ms.train": "device_step_skew_ms"}


def _steplog():
    from deepspeed_tpu.observability import steplog
    return steplog


def _ms(x):
    return int(x * 1e6)


def _plane(shift=0):
    """One plane's op line: two runs of 10 ms; in each a ``while`` that holds
    a permute's halves around compute, and a blocking all-reduce after it."""
    ops, runs = [], []
    for k in range(2):
        t = _ms(20 * k) + shift
        runs.append(Op("jit_ds_train_step(1)", t, t + _ms(10)))
        ops += [Op("while.1", t, t + _ms(8)),
                Op("collective-permute-start.3", t + _ms(1), t + _ms(1.25)),
                Op("fusion.7", t + _ms(1.25), t + _ms(4)),
                Op("collective-permute-done.3", t + _ms(4), t + _ms(5)),
                Op("fusion.8", t + _ms(5), t + _ms(8)),
                Op("all-reduce.9", t + _ms(8), t + _ms(8.5)),
                Op("fusion.9", t + _ms(8.5), t + _ms(10))]
    # an exchange outside every run is not the step's
    ops.append(Op("all-reduce.9", _ms(15) + shift, _ms(16) + shift))
    return sorted(ops, key=lambda o: (o.start, -o.end)), runs


def test_exposed_time_is_the_self_time_of_the_exchanges_inside_the_runs():
    ops, runs = _plane()
    got = C.exposed_ms(ops, runs,
                       {"collective-permute-start.3": "attn",
                        "all-reduce.9": "loss"})
    assert got["exposed_ms"] == pytest.approx(0.25 + 1.0 + 0.5)
    assert got["by_kind"] == pytest.approx(
        {"collective-permute": 1.25, "all-reduce": 0.5})
    # a -done half is lent the scope of the -start that opened it
    assert got["by_scope"] == pytest.approx({"attn": 1.25, "loss": 0.5})
    assert 0 <= 100 * got["exposed_ms"] / 10.0 <= 100


def test_identical_planes_have_no_skew_and_a_late_one_has_its_lateness():
    _, runs = _plane()
    same = {f"/device:TPU:{i}": runs for i in range(4)}
    assert C.step_skew_ms(same) == 0.0
    late = dict(same)
    late["/device:TPU:3"] = _plane(shift=_ms(0.3))[1]
    assert C.step_skew_ms(late) == pytest.approx(0.3)
    assert C.step_skew_ms({"/device:TPU:0": runs}) is None
    cut = dict(same)
    cut["/device:TPU:2"] = runs[:1]
    assert C.step_skew_ms(cut) is None


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(B, "testdata", "zero3_4chip_planes.json.gz"),
                   "rt") as f:
        d = json.load(f)
    planes = {}
    for name, p in d["planes"].items():
        ops = sorted((Op(n, s, s + dur) for n, s, dur in p["ops"]),
                     key=lambda o: (o.start, -o.end))
        runs = [Op(n, s, s + dur) for n, s, dur in p["modules"]]
        planes[name] = (ops, runs)
    return d, planes


def test_the_recorded_four_planes_read_a_share_within_bounds(recorded):
    d, planes = recorded
    assert len(planes) == 4
    steplog = _steplog()
    scope_of = {r["name"]: r["scope"] for r in d["record"]}
    per_plane = {n: C.exposed_ms(ops, runs, scope_of)
                 for n, (ops, runs) in planes.items()}
    step_ms = [(r.end - r.start) / 1e6 for _, runs in planes.values()
               for r in runs]
    for got in per_plane.values():
        assert 0 < got["exposed_ms"] < min(step_ms)
        assert 0 < 100 * got["exposed_ms"] / min(step_ms) < 100
        # every exchange on the line is one the record lists: none unowned
        assert "(none)" not in got["by_scope"] or \
            got["by_scope"]["(none)"] < 0.05 * got["exposed_ms"]
        assert got["by_kind"]["collective-permute"] \
            == max(got["by_kind"].values())
    # the chips wait for one another inside the step, so they read alike
    xs = [g["exposed_ms"] for g in per_plane.values()]
    assert max(xs) - min(xs) < 0.25 * max(xs)
    runs = {n: r for n, (_, r) in planes.items()}
    skew = C.step_skew_ms(runs)
    assert skew is not None and 0 <= skew < 1.0
    # the record that went with the trace adds up
    sums = steplog.collective_sums(d["record"])
    assert sums["collective_calls_per_step"] == d["calls_per_step"]
    assert sums["collective_bytes_per_step"] == d["bytes_per_step"]


#: operations of a trace's op line that are compute, a copy or a container
NOT_EXCHANGES = ["fusion.848", "while.111", "copy-start.27", "copy-done.27",
                 "attn.3", "dynamic-slice_bitcast_fusion.13",
                 "convert_reduce_fusion", "all-gather_fusion_x",
                 "custom-call.31", "slice-start.2"]
#: and forms of an exchange's name that the recorded step does not hold
OTHER_EXCHANGES = ["all-gather-start.3", "all-gather-done.3",
                   "reduce-scatter.4", "%all-reduce.5",
                   "collective-permute-start"]


def test_the_list_of_exchanges_is_the_recorded_traces(recorded):
    """``is_collective`` against every instruction name of the recorded
    planes: what it names are the halves of the permutes, the blocking
    reductions and all-to-alls, and the gathers with their two wrapping
    fusions, and nothing else that the step ran. A change to the list
    that moves ``collective_exposed_ms`` fails here."""
    _, planes = recorded
    names = {op.name for ops, _ in planes.values() for op in ops}
    named = {n for n in names if C.is_collective(n)}
    kinds = {re.sub(r"(\.\d+)*$", "", n) for n in named}
    assert kinds == {"collective-permute-start", "collective-permute-done",
                     "all-reduce", "all-gather", "all-to-all",
                     "async-collective-start", "async-collective-done"}, kinds
    # every name that says it is an exchange is named, and none that is not
    says = {n for n in names if re.match(
        r"(all-|reduce-scatter|collective-|async-collective)", n)}
    assert named == says, sorted(says ^ named)
    assert not any(C.is_collective(n) for n in NOT_EXCHANGES)
    assert all(C.is_collective(n) for n in OTHER_EXCHANGES)


def test_a_program_without_the_record_reads_the_trace_alone(monkeypatch):
    """The parent of the PR that brought the record: no counter, no raise;
    and without a trace nothing at all."""
    monkeypatch.setattr(C, "_steplog", lambda: None)
    ctx = {"cell": {"name": CELL, "chips": 4}, "values": {}}
    for key in NEW.values():
        assert C.value(ctx, key) is None
    assert ctx["collectives"] == {}
    # a one-chip cell exchanges nothing and is asked nothing
    one = {"cell": {"name": "mistral7b_train_1chip", "chips": 1},
           "values": {}}
    assert C.analysis(one) == {}


def _by_name(entries, name):
    (found,) = [e for e in entries if e["name"] == name]
    return found


def test_the_manifest_lists_the_cell_and_its_files_exist():
    with open(os.path.join(B, "..", "BENCHMARK.json")) as f:
        m = json.load(f)
    cell = _by_name(m["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "packed_4k", 4)
    config = _by_name(m["configs"], CONFIG)
    assert config["reduced"] == ["num_hidden_layers"]
    assert sum(c["source"] == config["source"] for c in m["configs"]) == 1
    with open(os.path.join(B, "..", config["file"])) as f:
        file = json.load(f)
    published = {"hidden_size": 4096, "intermediate_size": 14336,
                 "num_attention_heads": 32, "num_key_value_heads": 8,
                 "vocab_size": 32768, "rope_theta": 1000000.0,
                 "sliding_window": None, "rms_norm_eps": 1e-05,
                 "hidden_act": "silu", "max_position_embeddings": 32768,
                 "tie_word_embeddings": False, "model_type": "mistral"}
    assert {k: file[k] for k in published} == published
    assert file["num_hidden_layers"] == 5
    assert file["reduced"]["num_hidden_layers"]["published"] == 32
    dep = file["deployment"]
    assert dep["chips"] == 4 and dep["ds_config"]["mesh"] == {"fsdp": 4}
    assert dep["ds_config"]["zero_optimization"]["stage"] == 3
    with open(os.path.join(B, "workloads", CELL + ".json")) as f:
        runner = json.load(f)["runner"]
    assert os.path.isfile(os.path.join(B, "runners", runner + ".py"))
    for name, key in NEW.items():
        p = _by_name(m["per_layer"], name)
        assert CELL in p["workloads"] and p["layer"] == "collectives"
        with open(os.path.join(B, "metrics", name + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"] == "readers.collectives:value"
        assert spec["args"] == {"key": key}
    names = {p["name"] for p in m["per_layer"]
             if CELL in p.get("workloads", [CELL])}
    assert {"train_mfu", "flash_fwd_roofline", "flash_bwd_roofline",
            "flash_bwd_fused_roofline", "unscoped_device_ms",
            "train_put_ms"} | set(NEW) <= names
