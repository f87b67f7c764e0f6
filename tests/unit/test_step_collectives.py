"""What a sharded step exchanges, read from its compiled text:
``steplog.collectives`` (a pure function of the text), the step-program row's
``collectives()`` and its sums as attributes on a toy ZeRO-3 step over four
of the eight virtual devices; and the four-chip cell's own runner at toy
widths: its rehearsal, and the faults its comparison has to refuse."""

import os
import subprocess
import sys

import numpy as np
import pytest

from deepspeed_tpu.observability import steplog

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LAYERS, HIDDEN, FFN, HEADS, KV, VOCAB, SEQ = 3, 64, 128, 4, 2, 256, 32
HEAD = HIDDEN // HEADS
#: parameters of one layer: q, k, v, o, gate, up, down, two norms
LAYER_PARAMS = (2 * HIDDEN * HIDDEN + 2 * HIDDEN * KV * HEAD
                + 3 * HIDDEN * FFN + 2 * HIDDEN)


def _texts():
    """The compiled text of a toy ZeRO-3 ``ds_train_step`` on ``{"fsdp": 4}``
    and of the same model's ZeRO-0 step on one device, as bytes, with what
    the rows themselves answered."""
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerConfig, TransformerLM
    from deepspeed_tpu.parallel import build_mesh

    cfg = TransformerConfig(
        vocab_size=VOCAB, hidden_size=HIDDEN, num_layers=LAYERS,
        num_heads=HEADS, num_kv_heads=KV, intermediate_size=FFN,
        max_seq_len=SEQ, arch="llama", dtype="bfloat16",
        param_dtype="float32", attention_impl="xla")

    def text(stage, topology, rows):
        conf = {"train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
                "bf16": {"enabled": True},
                # every leaf sharded, the norms' 64 floats too
                "zero_optimization": {"stage": stage,
                                      "param_persistence_threshold": 0},
                "steps_per_print": 10 ** 9}
        engine, *_ = ds.initialize(model=TransformerLM(cfg), config=conf,
                                   mesh=topology)
        loss = engine.fused_train_step(
            {"input_ids": np.zeros((rows, SEQ), np.int32)})
        assert np.isfinite(float(loss))
        row = [p for p in steplog.programs()
               if p.name == "ds_train_step"][-1]
        # the row's own method and the pure function agree, and the sums
        # are the row's attributes
        listed = row.collectives()
        assert listed == steplog.collectives(row.hlo_text())
        sums = steplog.collective_sums(listed)
        assert row.collective_bytes_per_step \
            == sums["collective_bytes_per_step"]
        assert row.collective_calls_by_kind \
            == sums["collective_calls_by_kind"]
        assert row.collective_nothing_of_the_kind is None
        facts = np.asarray([row.zero_stage, row.mesh_axes.get("fsdp", 0),
                            row.collective_calls_per_step,
                            row.collective_bytes_per_step])
        return np.frombuffer(row.hlo_text().encode(), np.uint8), facts

    sharded, facts = text(3, build_mesh(axis_sizes={"fsdp": 4},
                                        devices=jax.devices()[:4]), 4)
    one, facts_one = text(0, build_mesh(devices=jax.devices()[:1]), 1)
    return {"fsdp4": sharded, "one": one, "facts": facts,
            "facts_one": facts_one}


@pytest.fixture(scope="module")
def texts(run_memo):
    got = run_memo("step_collectives_texts_pr69", _texts)
    return {k: bytes(v).decode() if v.dtype == np.uint8 else v
            for k, v in got.items()}


def test_a_zero3_steps_record_names_gathers_and_a_reducing_kind(texts):
    listed = steplog.collectives(texts["fsdp4"])
    sums = steplog.collective_sums(listed)
    assert sums["collective_calls_by_kind"]["all-gather"] > 0
    assert any(sums["collective_calls_by_kind"].get(k, 0) > 0
               for k in ("all-reduce", "reduce-scatter"))
    assert set(sums["collective_calls_by_kind"]) \
        <= set(steplog.COLLECTIVE_KINDS)
    for what in ("calls", "bytes"):
        assert sums[f"collective_{what}_per_step"] \
            == sums[f"collective_{what}_in_layer_loop"] \
            + sums[f"collective_{what}_outside_layer_loop"] \
            == sum(sums[f"collective_{what}_by_kind"].values())
    assert sums["collective_unknown_trips"] == 0
    # the engine said how the state is partitioned, and the row answers
    # the sums
    stage, fsdp, calls, nbytes = texts["facts"]
    assert (stage, fsdp) == (3, 4)
    assert calls == sums["collective_calls_per_step"]
    assert nbytes == sums["collective_bytes_per_step"]


def test_the_records_bytes_are_the_shapes_arithmetic(texts):
    """XLA:CPU gathers a layer's float32 leaves whole, once for the forward
    and once more in the backward's loop, and reduces the layer's float32
    gradients once, all inside the layer loop. A device receives three
    quarters of a gather's result, and of an all-reduce's twice."""
    listed = steplog.collectives(texts["fsdp4"])
    inside = [r for r in listed if r["in_layer_loop"]]
    gathers = [r for r in inside if r["kind"] == "all-gather"]
    for backward in (False, True):
        assert sum(r["bytes"] for r in gathers
                   if r["backward"] == backward) == 3 * LAYER_PARAMS
    reduced = [r for r in inside if r["kind"] in ("all-reduce",
                                                  "reduce-scatter")]
    assert sum(r["bytes"] for r in reduced) in (
        6 * LAYER_PARAMS,       # all-reduced: 2 x 3/4 of 4 B a parameter
        3 * LAYER_PARAMS)       # reduce-scattered: 3/4
    sums = steplog.collective_sums(listed)
    assert sums["collective_bytes_in_layer_loop"] == LAYERS * (
        6 * LAYER_PARAMS + sum(r["bytes"] for r in reduced))
    # the head's gather stands outside the loop
    assert sums["collective_bytes_outside_layer_loop"] \
        >= 3 * HIDDEN * VOCAB


def test_a_gather_in_the_layer_scan_counts_once_a_trip(texts):
    listed = steplog.collectives(texts["fsdp4"])
    assert {(r["loops"], r["trips"]) for r in listed
            if r["in_layer_loop"]} == {(1, LAYERS)}
    assert {(r["loops"], r["trips"]) for r in listed
            if not r["in_layer_loop"]} == {(0, 1)}
    for r in listed:
        assert r["group"] == 4 and r["unknown_trips"] == 0
        assert not r["async"]           # one blocking op each on the CPU


def test_every_collective_of_the_sharded_step_has_an_owner(texts):
    """No exchange of the ZeRO-3 step is unscoped: a weight's gather carries
    the scope of the layer that uses it, a gradient's reduction the
    backward's, the loss's and the norm's scalars theirs."""
    from deepspeed_tpu.models.transformer import STEP_SCOPES

    listed = steplog.collectives(texts["fsdp4"])
    assert all(r["scope"] in STEP_SCOPES for r in listed), \
        [r["name"] for r in listed if r["scope"] not in STEP_SCOPES]
    in_loop = {r["scope"] for r in listed if r["in_layer_loop"]}
    assert in_loop <= {"attn", "mlp", "layers"} and "attn" in in_loop
    outside = {r["scope"] for r in listed if not r["in_layer_loop"]}
    assert {"lm_head", "loss"} <= outside


def test_the_zero0_program_of_the_same_model_exchanges_nothing(texts):
    """0, not None: a ZeRO-3 step that lost its sharding would read 0 and
    not "absent"."""
    assert steplog.collectives(texts["one"]) == []
    stage, fsdp, calls, nbytes = texts["facts_one"]
    assert (stage, fsdp, calls, nbytes) == (0, 0, 0, 0)
    sums = steplog.collective_sums([])
    assert sums["collective_bytes_per_step"] == 0
    assert sums["collective_calls_by_kind"] == {}


def test_no_step_path_asks_for_the_record():
    """``collectives()`` compiles again: it is for a reader after the
    window, and nothing the engine runs on a step may call it."""
    with open(os.path.join(ROOT, "deepspeed_tpu", "runtime",
                           "engine.py")) as f:
        engine = f.read()
    assert "collectives(" not in engine.replace(
        "StepProgram.collectives)", "")
    for attribute in steplog.collective_sums([]):
        assert attribute not in engine


# ---- kept snippets ----------------------------------------------------------

_SUM = """
%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.0 = f32[] add(%a, %b)
}
"""


def _module(entry: str, *computations: str) -> str:
    return ("HloModule jit_f, is_scheduled=true\n" + _SUM
            + "\n".join(computations)
            + "\nENTRY %main (p: f32[8,16]) -> f32[8,16] {\n"
            "  %p = f32[8,16]{1,0} parameter(0)\n" + entry + "}\n")


ASYNC_PAIR = _module("""
  %all-gather-start.3 = (f32[8,16]{1,0}, f32[32,16]{1,0}) all-gather-start(%p), channel_id=1, replica_groups=[1,4]<=[4], dimensions={0}, metadata={op_name="jit(f)/jvp(lm_head)/dot_general"}
  %all-gather-done.3 = f32[32,16]{1,0} all-gather-done(%all-gather-start.3)
  %collective-permute-start.1 = (bf16[8,16]{1,0}, bf16[8,16]{1,0}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%p), channel_id=2, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
  %collective-permute-done.1 = bf16[8,16]{1,0} collective-permute-done(%collective-permute-start.1)
  %mul.1 = bf16[8,16]{1,0} multiply(%collective-permute-done.1, %collective-permute-done.1), metadata={op_name="jit(f)/transpose(jvp(layers))/while/body/mlp/mul"}
  ROOT %all-reduce-start.2 = f32[8,16]{1,0} all-reduce-start(%p), channel_id=3, replica_groups={{0,1},{2,3}}, to_apply=%sum, metadata={op_name="jit(f)/optimizer/reduce_sum"}
""")

IN_A_FUSION = _module("""
  %async-collective-start = (f32[8,16]{1,0}, f32[32,16]{1,0}, s32[2]{0}) fusion(%p), kind=kCustom, calls=%fused_computation.1
  %fusion.7 = (f32[8,16]{1,0}, f32[32,16]{1,0}) fusion(%async-collective-start), kind=kOutput, calls=%async_collective_fusion.7, metadata={op_name="jit(f)/jvp(layers)/while/body/mlp/dot_general"}
  ROOT %async-collective-done = f32[32,16]{1,0} fusion(%fusion.7), kind=kCustom, calls=%fused_computation.2
""", """
%fused_computation.1 (param_0: f32[8,16]) -> (f32[8,16], f32[32,16], s32[2]) {
  %param_0 = f32[8,16]{1,0} parameter(0)
  %all-gather.40 = f32[32,16]{1,0} all-gather(%param_0), channel_id=135, replica_groups=[1,4]<=[4], dimensions={0}, metadata={op_name="jit(f)/jvp(lm_head)/dot_general"}, backend_config={"async_collective_fusion_config":{"flag_start":"-1"}}
  ROOT %custom-call.1 = (f32[8,16]{1,0}, f32[32,16]{1,0}, s32[2]{0}) custom-call(%all-gather.40), custom_call_target="x"
}

%async_collective_fusion.7 (param_0.1: f32[8,16]) -> (f32[8,16], f32[32,16]) {
  %param_0.1 = f32[8,16]{1,0} parameter(0)
  %all-gather.42 = f32[32,16]{1,0} all-gather(%param_0.1), channel_id=135, replica_groups=[1,4]<=[4], dimensions={0}, backend_config={"async_collective_fusion_config":{"flag_start":"2"}}
  ROOT %tuple.1 = (f32[8,16]{1,0}, f32[32,16]{1,0}) tuple(%param_0.1, %all-gather.42)
}

%fused_computation.2 (param_0.2: f32[8,16]) -> f32[32,16] {
  %param_0.2 = f32[8,16]{1,0} parameter(0)
  ROOT %all-gather.44 = f32[32,16]{1,0} all-gather(%param_0.2), channel_id=135, replica_groups=[1,4]<=[4], dimensions={0}, backend_config={"async_collective_fusion_config":{"flag_start":"2"}}
}
""")


def _loop(name, trips_text, body_lines, cond_bound=None,
          op_name="jit(f)/jvp(layers)/while"):
    """A ``while`` over ``(counter, f32[8,16])`` and its computations;
    ``trips_text`` goes into the loop's ``backend_config``."""
    cond = "" if cond_bound is None else f"""
%cond_{name} (c: (s32[], f32[8,16])) -> pred[] {{
  %c = (s32[], f32[8,16]{{1,0}}) parameter(0)
  %bound_{name} = s32[] constant({cond_bound})
  %i_{name} = s32[] get-tuple-element(%c), index=0
  ROOT %lt_{name} = pred[] compare(%i_{name}, %bound_{name}), direction=LT
}}
"""
    body = f"""
%body_{name} (b: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {{
  %b_{name} = (s32[], f32[8,16]{{1,0}}) parameter(0)
  %n_{name} = s32[] get-tuple-element(%b_{name}), index=0
  %x_{name} = f32[8,16]{{1,0}} get-tuple-element(%b_{name}), index=1
  %one_{name} = s32[] constant(1)
  %next_{name} = s32[] add(%n_{name}, %one_{name})
{body_lines}
  ROOT %out_{name} = (s32[], f32[8,16]{{1,0}}) tuple(%next_{name}, %y_{name})
}}
"""
    call = (f"  %zero_{name} = s32[] constant(0)\n"
            f"  %init_{name} = (s32[], f32[8,16]{{1,0}}) tuple(%zero_{name}, %p)\n"
            f"  %while_{name} = (s32[], f32[8,16]{{1,0}}) while(%init_{name}), "
            f"condition=%cond_{name}, body=%body_{name}, "
            f"metadata={{op_name=\"{op_name}\"}}, "
            f"backend_config={{{trips_text}}}\n")
    return cond, body, call


def _reduce_in(name, operand):
    return (f"  %y_{name} = f32[8,16]{{1,0}} all-reduce(%{operand}), "
            f"channel_id=9, replica_groups=[1,4]<=[4], to_apply=%sum, "
            f"metadata={{op_name=\"jit(f)/transpose(jvp(layers))/while/body/"
            f"checkpoint/rematted_computation/attn/reduce_sum\"}}")


def _one_loop(trips_text, cond_bound):
    cond, body, call = _loop("a", trips_text, _reduce_in("a", "x_a"),
                             cond_bound)
    if cond_bound is None:      # a condition that is no counted loop's
        cond = """
%cond_a (c: (s32[], f32[8,16])) -> pred[] {
  %c = (s32[], f32[8,16]{1,0}) parameter(0)
  ROOT %go = pred[] custom-call(%c), custom_call_target="until"
}
"""
    return _module(call + "  ROOT %r = f32[8,16]{1,0} get-tuple-element("
                   "%while_a), index=1\n", cond, body)


def _nested():
    """An all-reduce in an inner loop of 5 inside an outer loop of 3 (the
    layer loop), and a reduce-scatter beside the inner loop."""
    cond_i, body_i, call_i = _loop(
        "i", '"known_trip_count":{"n":"5"}', _reduce_in("i", "x_i"), 5,
        op_name="jit(f)/jvp(layers)/while/body/attn/while")
    call_i = call_i.replace("%p)", "%x_o)")
    outer_lines = (call_i + "  %z_o = f32[8,16]{1,0} get-tuple-element("
                   "%while_i), index=1\n"
                   "  %y_o = f32[2,16]{1,0} reduce-scatter(%z_o), "
                   "channel_id=11, replica_groups={{0,1,2,3}}, "
                   "dimensions={0}, to_apply=%sum")
    cond_o, body_o, call_o = _loop("o", '"known_trip_count":{"n":"3"}',
                                   outer_lines, 3)
    body_o = body_o.replace("(s32[], f32[8,16]{1,0}) tuple(%next_o, %y_o)",
                            "(s32[], f32[8,16]{1,0}) tuple(%next_o, %z_o)")
    return _module(call_o + "  ROOT %r = f32[8,16]{1,0} get-tuple-element("
                   "%while_o), index=1\n", cond_i, body_i, cond_o, body_o)


def _sums(text):
    s = steplog.collective_sums(steplog.collectives(text))
    return {"calls": s["collective_calls_by_kind"],
            "bytes": s["collective_bytes_by_kind"],
            "in": s["collective_bytes_in_layer_loop"],
            "unknown": s["collective_unknown_trips"]}


CASES = {
    # an asynchronous pair counts once, at its -start: three quarters of the
    # gather's result (the tuple's second part), the permute's operand
    # whole, a reduction's own result twice over half the group
    "async_pair": (ASYNC_PAIR, {
        "calls": {"all-gather": 1, "collective-permute": 1, "all-reduce": 1},
        "bytes": {"all-gather": 32 * 16 * 4 * 3 // 4,
                  "collective-permute": 8 * 16 * 2,
                  "all-reduce": 8 * 16 * 4 * 2 // 2},
        "in": 0, "unknown": 0}),
    # a gather the v5e compiler spreads over a chain of fusions: found
    # inside them, and one exchange (one channel), not three
    "inside_fusions": (IN_A_FUSION, {
        "calls": {"all-gather": 1},
        "bytes": {"all-gather": 32 * 16 * 4 * 3 // 4},
        "in": 0, "unknown": 0}),
    # the compiler wrote the trip count
    "known_trips": (_one_loop('"known_trip_count":{"n":"7"}', 7), {
        "calls": {"all-reduce": 7}, "bytes": {"all-reduce": 7 * 768},
        "in": 7 * 768, "unknown": 0}),
    # it did not (the v5e compiler never does): a counted loop's own text
    "trips_from_the_condition": (_one_loop('"flag_configs":[]', 6), {
        "calls": {"all-reduce": 6}, "bytes": {"all-reduce": 6 * 768},
        "in": 6 * 768, "unknown": 0}),
    # no trip count anywhere: once, and said so
    "unknown_trips": (_one_loop('"flag_configs":[]', None), {
        "calls": {"all-reduce": 1}, "bytes": {"all-reduce": 768},
        "in": 768, "unknown": 1}),
    # nested loops multiply; a reduce-scatter receives three results
    "nested_loops": (_nested(), {
        "calls": {"all-reduce": 15, "reduce-scatter": 3},
        "bytes": {"all-reduce": 15 * 768, "reduce-scatter": 3 * 384},
        "in": 15 * 768 + 3 * 384, "unknown": 0}),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_record_of_a_kept_text(name):
    text, want = CASES[name]
    assert _sums(text) == want


def test_a_row_says_where_each_exchange_stands():
    pair = {r["name"]: r for r in steplog.collectives(ASYNC_PAIR)}
    gather = pair["all-gather-start.3"]
    assert (gather["async"], gather["scope"], gather["group"],
            gather["backward"]) == (True, "lm_head", 4, False)
    assert pair["all-reduce-start.2"]["group"] == 2
    assert pair["all-reduce-start.2"]["scope"] == "optimizer"
    # no op_name of its own: the nearest user's scope
    assert pair["collective-permute-start.1"]["group"] == 4
    assert pair["collective-permute-start.1"]["scope"] == "mlp"
    (chain,) = steplog.collectives(IN_A_FUSION)
    assert (chain["name"], chain["async"], chain["scope"]) \
        == ("all-gather.40", True, "lm_head")
    (looped,) = steplog.collectives(_one_loop("", 6))
    assert looped["in_layer_loop"] and looped["trips"] == 6
    assert (looped["scope"], looped["backward"], looped["async"]) \
        == ("attn", True, False)
    inner = [r for r in steplog.collectives(_nested())
             if r["kind"] == "all-reduce"]
    assert (inner[0]["loops"], inner[0]["trips"]) == (2, 15)
    # nothing names a scope: None, and a reader counts it unscoped
    bare = _module("  ROOT %all-to-all = f32[8,16]{1,0} all-to-all(%p), "
                   "channel_id=4, replica_groups={{0,1,2,3}}, "
                   "dimensions={0}\n")
    (alone,) = steplog.collectives(bare)
    assert alone["scope"] is None and alone["bytes"] == 8 * 16 * 4 * 3 // 4


def _lines(argv):
    import json

    out = subprocess.run(
        [sys.executable] + argv, capture_output=True, text=True, cwd=ROOT,
        timeout=600,
        env={k: v for k, v in os.environ.items() if k != "XLA_FLAGS"})
    return out, [json.loads(x) for x in out.stdout.splitlines()
                 if x.startswith("{")]


def test_the_four_chip_cell_rehearses_on_four_cpu_devices():
    """``run.py --rehearse`` of ``mistral7b_train_zero3_4chip``: its runner
    drives ZeRO-3 over ``fsdp=4`` at toy widths to "done"; the first step's
    loss, gradient and update are compared with the plain reference over
    all four rows after the window; and the traced rehearsal's
    ``collectives`` line holds the record the counters read."""
    out, lines = _lines(
        ["benchmarks/run.py", "--workload", "mistral7b_train_zero3_4chip",
         "--seed", "3000000019", "--seconds", "1", "--trace", "1",
         "--rehearse"])
    assert out.returncode == 0, out.stderr[-2000:]
    last = lines[-1]
    assert last["rehearsal"].startswith("done") and last["correct"]
    assert "collective_bytes_per_step.train" in last["metric_names"]
    (said,) = [x["collectives"] for x in lines if "collectives" in x]
    assert said["zero_stage"] == 3 and said["mesh_axes"] == {"fsdp": 4}
    assert said["collective_calls_per_step"] > 0
    assert said["collective_bytes_per_step"] == sum(
        said["collective_bytes_by_kind"].values())
    (check,) = [x for x in lines if "check" in x]
    assert set(check) >= {"loss", "grad_err", "param_change_err"}
    # the sound step: every leaf's gradient near the reference's, and the
    # optimizer's arithmetic on the step's own gradient exact
    assert 0 < check["grad_err"]["system"] < 0.1
    assert check["param_change_err_given_own_gradient"] < 1e-3
    assert len(check["by_leaf_grad_err_change_err_sign_share"]) == 3 + 9 * 5
    # the check stands after the window: set-up holds none of it
    order = [next(iter(x)) for x in lines]
    assert order.index("window") < order.index("check")


FAULTS = ("fp8", "no_reduce", "half_batch", "unchanged")


@pytest.fixture(scope="module")
def controls():
    out, lines = _lines(
        ["-m", "benchmarks.runners.train_sharded", "--control",
         ",".join(FAULTS), "--seed", "3000000019", "--rehearse"])
    return out, {x["control"]: x for x in lines if "control" in x}


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_in_the_sharded_steps_place_comes_out_not_correct(
        controls, fault):
    """Through the cell's own comparison at toy widths: the reference in a
    lower precision, a shard's gradient never reduced over the chips, half
    the batch, a state left as it was. A gradient that was not reduced reads
    well over 1, a state left unchanged exactly 1."""
    out, said = controls
    assert out.returncode == 0, out.stderr[-2000:]
    line = said[fault]
    assert not line["correct"] and line["problems"]
    got = {k: v["max_abs_diff"] for k, v in line["readings"].items()}
    if fault == "unchanged":
        assert got["grad_err"] == got["param_change_err"] == 1.0
    if fault == "no_reduce":
        assert got["grad_err"] > 1.2 and got["param_change_err"] > 1.0
    if fault == "half_batch":
        assert got["grad_err"] > 0.8
