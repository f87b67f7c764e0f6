"""``readers/looped.py`` on plain data: which instructions of a compiled
text lie under a scope, the utilisation's arithmetic, the step-program
table's field; and that each returns None where there is nothing to read."""

import json
import os

from benchmarks import opcount_ouro
from benchmarks.readers import looped

HERE = os.path.dirname(os.path.abspath(__file__))

HLO = '''
%fused_gate (p0: f32[4096]) -> f32[4096] {
  %p0 = f32[4096]{0} parameter(0)
  %log.1 = f32[4096]{0} log(%p0), metadata={op_name="jit(ds_train_step)/jvp(loss)/exit_gate/log"}
  ROOT %neg.1 = f32[4096]{0} negate(%log.1), metadata={op_name="jit(ds_train_step)/jvp(loss)/exit_gate/neg"}
}

%fused_ce (p0: f32[4096]) -> f32[4096] {
  %p0.1 = f32[4096]{0} parameter(0)
  ROOT %exp.1 = f32[4096]{0} exponential(%p0.1), metadata={op_name="jit(ds_train_step)/jvp(loss)/exp"}
}

ENTRY %main (a: f32[4096]) -> f32[4096] {
  %a = f32[4096]{0} parameter(0)
  %fusion.1 = f32[4096]{0} fusion(%a), kind=kLoop, calls=%fused_gate
  %fusion.2 = f32[4096]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_ce, metadata={op_name="jit(ds_train_step)/jvp(loss)/exp"}
  ROOT %add.3 = f32[4096]{0} add(%fusion.2, %a), metadata={op_name="jit(ds_train_step)/transpose(jvp(loss))/exit_gate/add"}
}
'''


def test_instructions_under_a_scope():
    under = looped.instructions_under(HLO, "exit_gate")
    assert under["log.1"] and under["neg.1"] and under["add.3"]
    assert under["fusion.1"]            # no path of its own: its members vote
    assert not under["fusion.2"] and not under["exp.1"] and not under["a"]
    assert not any(looped.instructions_under(HLO, "optimizer").values())


def test_readers_return_none_where_there_is_nothing_to_read():
    ctx = {"values": {}, "peak": None, "cfg": {}, "cell": {"name": "none"},
           "trace": None, "reduced": {}}
    assert looped.train_mfu(ctx) is None
    assert looped.scope_device_ms(ctx, scope="exit_gate") is None
    from deepspeed_tpu.observability import steplog
    if not any(p.name.startswith("ds_train_step")
               for p in steplog.programs()):
        assert looped.layer_applications(ctx) is None


def test_utilisation_and_table_field():
    with open(os.path.join(HERE, "..", "configs",
                           "ouro2_6b_train_d6.json")) as f:
        cfg = json.load(f)
    ctx = {"values": {"train_tok_s_chip": 9500.0, "seq": 4096},
           "peak": {"bf16_flops_per_s": 197e12}, "cfg": cfg}
    want = 100 * opcount_ouro.train_flops_per_token(cfg, 4096) * 9500 / 197e12
    assert looped.train_mfu(ctx) == want and 50 < want < 56
    from deepspeed_tpu.observability import steplog
    steplog.record_program("ds_train_step", 1, lambda: None, None,
                           layer_applications=24)
    assert looped.layer_applications({}) == 24.0
