"""Training cells of a model whose window and full attention layers differ in
their query heads (a stack of leaves a kind, two group sizes over the same
key-value heads), each kind under a rope of its own width and every head
under a gate, over a leading dense layer and a held share of sigmoid-routed
experts beside a shared one (Laguna-S-2.1, a chip's share of heads, experts
and vocabulary): ``deepspeed_tpu.initialize`` -> ``engine.fused_train_step``.

Set-up, window and what ``correct`` compares are
``runners/train_mla_moe.py``'s, which this runner calls: the first step's
loss, its balance term, each layer's mixer-output mean square and the (token,
expert) pairs each held expert of each routed layer received, as the timed
step program itself returned them, and the selection biases the step left,
against the reference on the same bf16-rounded weights and the same batch;
and that no pair was left out of the buffer of held pairs in any step of the
window. What this runner adds: it refuses a program that cannot hold query
heads by kind, and it holds the step-program row to the configuration's
heads (each kind's ``(held, all)`` and their sum over the layers), so that a
program that ran every layer at one head count is not correct.

``python3 -m benchmarks.runners.train_heads_moe --control fp8 --seed n`` puts
the reference with fp8-rounded weights (e4m3, the nearest precision below the
bf16 stated) in the program's place and prints what the same comparison says
of it (:func:`control`): the limits' second readings come from there.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

from benchmarks import harness
from benchmarks.runners import train_mla_moe
from benchmarks.runners.train_hybrid import compare
from benchmarks.runners.train_looped import _modules

#: what the program's TransformerConfig has to know for this runner's cells
NEEDS = ("heads_by_kind", "heads_held", "mla_head_gate", "first_k_dense",
         "moe_scoring", "moe_bias_rate", "moe_experts_held")
#: toy sizes for a rehearsal, for the keys ``rehearsal.json`` does not name
#: (it substitutes a hidden size of 64, 4 heads of 16 over 2 key-value
#: heads, a dense FFN of 128 and 128 positions): experts wider than the
#: hidden state are a toy's
TOY = {"heads": 8, "kv_heads": 4, "moe_intermediate_size": 48,
       "shared_expert_intermediate_size": 48, "router_width": 32,
       "num_experts": 4, "num_experts_per_tok": 4}
KINDS = {"sliding_attention": "window", "full_attention": "full"}


def at_widths(cfg: Dict) -> Dict:
    """``cfg`` as it is run: at the published widths as it is; under
    ``rehearsal.json``'s toy hidden size with :data:`TOY` and the toy's four
    heads on a full layer, six on a sliding one."""
    if int(cfg["hidden_size"]) >= int(cfg["moe_intermediate_size"]):
        return cfg
    full = int(cfg["num_attention_heads"])
    return {**cfg, **TOY, "num_attention_heads_per_layer": [
        full if kind == "full_attention" else full * 3 // 2
        for kind in cfg["layer_types"]]}


def heads_said(cfg: Dict) -> Dict:
    """What the step-program row has to say of the configuration ``cfg``'s
    heads: ``heads_held`` (each kind's held and published count) and
    ``attn_heads_per_step`` (the held heads summed over the kept layers)."""
    L = int(cfg["num_hidden_layers"])
    held = [int(n) for n in cfg["num_attention_heads_per_layer"][:L]]
    share = int(cfg["heads"]) // int(cfg["num_attention_heads"])
    by_kind = {KINDS[k]: (n, n * share)
               for k, n in zip(cfg["layer_types"][:L], held)}
    return {"heads_held": by_kind if share > 1 else None,
            "attn_heads_per_step": sum(held)}


def run(cell: Dict, args) -> Dict:
    from deepspeed_tpu.models import TransformerConfig

    missing = [f for f in NEEDS if f not in
               {x.name for x in dataclasses.fields(TransformerConfig)}]
    if missing:
        raise SystemExit(
            f"benchmarks: cell {cell['name']} needs a program whose "
            f"TransformerConfig has {', '.join(missing)} (window and full "
            f"attention layers with query heads, a rope width and a stack of "
            f"leaves by kind, every head under a gate); this checkout's has "
            f"not")
    cfg = cell["config"] = at_widths(cell["config"])
    result = train_mla_moe.run(cell, args)
    from deepspeed_tpu.observability import steplog

    row = [p for p in steplog.programs()
           if p.name.startswith("ds_train_step")][-1]
    want = heads_said(cfg)
    said = {name: getattr(row, name, None) for name in want}
    harness.say(step_program_heads=said)
    for name, value in want.items():
        if said[name] != value:
            result["problems"].append(
                f"the step program's {name} is {said[name]}, the "
                f"configuration says {value}")
    if result["problems"]:
        result["correct"], result["failed"] = False, result["attempted"]
    return result


def control(cell: Dict, args) -> Dict:
    """The reference on fp8-rounded weights in the program's place, through
    the cell's own comparison (no step is run: the biases are the rule's on
    either side's counts): the line says what the comparison made of it."""
    jax, devices, _ = harness.setup_jax(cell["chips"], args.rehearse)
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.parallel import build_mesh

    cfg = cell["config"] = at_widths(cell["config"])
    mods = _modules(cfg)
    seq = int(cell["traffic"]["seq_len"])
    rows = int(cell["traffic"]["rows_per_chip"]) * cell["chips"]
    tcfg = mods["modelcfg"].transformer_config(cfg, max_seq_len=seq,
                                               param_dtype="float32")
    engine, *_ = ds.initialize(
        model=TransformerLM(tcfg), mesh=build_mesh(devices=devices),
        config=dict(cfg["deployment"]["ds_config"],
                    seed=int(args.seed) % (2 ** 31),
                    train_micro_batch_size_per_gpu=rows // cell["chips"]))
    ids = np.random.default_rng(int(args.seed)).integers(
        0, tcfg.vocab_size, (rows, seq), dtype=np.int32)
    want = train_mla_moe._reference(jax, engine, cfg, ids, mods)
    got = train_mla_moe._reference(jax, engine, cfg, ids, mods,
                                   rounded="float8_e4m3fn")
    problems, facts = compare(got, want, dict(cfg["check"]))
    gamma = float(cfg["deployment"]["bias_update_rate"])
    before = np.array(mods["modelcfg"].biases(engine.params))
    after = mods["reference"].bias_after(before, got["router_counts"], gamma)
    bias_problems, facts["router_bias"] = train_mla_moe.compare_biases(
        before, np.asarray(after), want, dict(cfg["check"]), gamma, mods)
    line = {"control": "fp8", "seed": int(args.seed),
            "correct": not (problems + bias_problems),
            "problems": problems + bias_problems,
            "readings": {k: {x: f[x] for x in f if x.startswith("max_")
                             or x == "tol"} for k, f in facts.items()}}
    harness.say(**line)
    return line


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=control.__doc__)
    ap.add_argument("--workload", default="laguna_s21_train_1chip")
    ap.add_argument("--control", required=True, choices=("fp8",))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if args.rehearse:
        cell = harness.apply_rehearsal(cell)
    # a fault that comes out correct is the failure here
    return 1 if control(cell, args)["correct"] else 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
