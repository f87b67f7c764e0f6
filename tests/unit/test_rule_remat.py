"""What a recomputation policy keeps of the delta and KDA rules' kernels.
Their forward rules name the rule's output and the chunks' incoming states
(``ops/delta_rule.py:_rule_pallas_fwd``, ``ops/kda_rule.py:_rule_pallas_fwd``:
``RULE_CHECKPOINT_NAMES``), the two values the backward kernel reads of the
forward, and ``attn_saveable`` keeps those names as ``dots_saveable`` does
(``runtime/activation_checkpointing.py:resolve_policy``): the differentiated
program of a checkpointed mixer block then holds one forward kernel call,
where ``full`` holds two. Interpreted here, as ``test_flash_remat.py``'s flash
cases are; the count is the program's, whatever runs it."""

import re
from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

from deepspeed_tpu.ops import delta_rule as dr, kda_rule as kr
from deepspeed_tpu.runtime import activation_checkpointing as ac

B, T, H, WIDTH = 1, 128, 2, 64
CHUNKS = T // dr.CHUNK


class Rule(NamedTuple):
    op: Callable
    kernels: Tuple[str, str]    # the jitted forward's and backward's names
    dk: int
    dv: int
    channel: bool               # a decay a key channel (else one a head)
    out: Tuple[int, ...]        # the shape the forward rule names ``o`` in


# the delta rule names its output as it hands it back, the KDA rule with the
# heads side by side in lanes, as its kernel wrote it
RULES = {
    "delta": Rule(dr.chunked_delta_rule, ("rule_fwd", "rule_bwd"), 32, 64,
                  False, (B, T, H, 64)),
    "kda": Rule(kr.chunked_kda_rule, ("kda_fwd", "kda_bwd"), 128, 128, True,
                (B, T, H * 128)),
}
POLICIES = ("full", "attn_saveable", "dots_saveable")


def _operands(rule):
    r = RULES[rule]
    widths = {"q": H * r.dk, "k": H * r.dk, "v": H * r.dv, "b": H,
              "g": H * r.dk if r.channel else H}
    keys = jax.random.split(jax.random.key(0), len(widths) + 2)
    ws = {n: 0.1 * jax.random.normal(k, (WIDTH, w), jnp.float32)
          for k, (n, w) in zip(keys, sorted(widths.items()))}
    ws["o"] = 0.1 * jax.random.normal(keys[-2], (widths["v"], WIDTH),
                                      jnp.float32)
    return ws, jax.random.normal(keys[-1], (B, T, WIDTH), jnp.float32)


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _mixer(rule, ws, x):
    """A mixer as the models write it: projections, unit keys, a decay's
    logarithm below 0 and a step in (0, 1), the rule's kernels, ``wo``."""
    r = RULES[rule]
    heads = lambda n, d: (x @ ws[n]).reshape(B, T, H, d)          # noqa: E731
    g = -jax.nn.softplus(x @ ws["g"])
    o = r.op(_unit(heads("q", r.dk)) * r.dk ** -0.5, _unit(heads("k", r.dk)),
             heads("v", r.dv), g.reshape(B, T, H, r.dk) if r.channel else g,
             jax.nn.sigmoid(x @ ws["b"]), interpret=True)
    return o.reshape(B, T, H * r.dv) @ ws["o"]


def _loss(rule, policy):
    """The sum of squares of a mixer block (the mixer and the residual add)
    under ``policy``; None: no ``jax.checkpoint`` at all."""
    def block(ws, x):
        return x + _mixer(rule, ws, x)

    if policy is not None:
        block = ac.checkpoint_wrapper(block, policy=policy)
    return lambda ws, x: jnp.sum(block(ws, x) ** 2)


def _kernel_calls(rule, jaxpr):
    """(forward, backward) calls of the rule's jitted kernels in a jaxpr."""
    fwd, bwd = RULES[rule].kernels
    names = re.findall(r"\bname=(\w+)", str(jaxpr))
    return names.count(fwd), names.count(bwd)


_UNWRAPPED = {}


def _unwrapped_grads(rule):
    if rule not in _UNWRAPPED:
        _UNWRAPPED[rule] = jax.grad(_loss(rule, None))(*_operands(rule))
    return _UNWRAPPED[rule]


@pytest.mark.parametrize("rule", sorted(RULES))
@pytest.mark.parametrize("policy", POLICIES)
def test_a_keeping_policy_runs_the_rules_forward_kernel_once(policy, rule):
    """``full`` runs the rule's forward kernel again in the backward's
    recomputed region; ``attn_saveable`` and ``dots_saveable`` do not: they
    keep the output and the chunks' states once each, as the forward rule
    named them, and the gradients are the unwrapped block's to the bit, the
    backward kernel reading the arrays a second call would have made
    again."""
    ws, x = _operands(rule)
    loss = _loss(rule, policy)
    calls = _kernel_calls(rule, jax.make_jaxpr(jax.grad(loss))(ws, x))
    assert calls == (2 if policy == "full" else 1, 1)

    r = RULES[rule]
    states = (B, H, CHUNKS, r.dk, r.dv)
    kept = [(tuple(aval.shape), why) for aval, why in
            saved_residuals(loss, ws, x) if "argument" not in why]
    # what is kept of the rule's own lines: the named output (a name reads
    # "reduce_precision" once the region is cut) and the named states
    module = r.op.__module__.rsplit(".", 1)[-1]
    of_rule = [shape for shape, why in kept if f"/{module}.py:" in why]
    if policy == "full":
        assert of_rule == []
    else:
        assert sorted(of_rule) == sorted([r.out, states])
        assert sum(f"'{ac.RULE_CHECKPOINT_NAMES[1]}'" in why
                   for _, why in kept) == 1
    # the names alone: no product of the mixer beside them
    assert any("(_mixer" in why for _, why in kept) == (
        policy == "dots_saveable")

    grads = jax.grad(loss)(ws, x)
    for name, g in _unwrapped_grads(rule).items():
        np.testing.assert_array_equal(np.asarray(grads[name]), np.asarray(g),
                                      err_msg=name)


@pytest.mark.parametrize("rule", sorted(RULES))
def test_outside_a_checkpoint_the_rules_names_change_nothing(rule):
    """No ``jax.checkpoint``: value-and-grad holds one forward and one
    backward kernel call and the two names stand in the program once each."""
    jaxpr = jax.make_jaxpr(jax.value_and_grad(_loss(rule, None)))(
        *_operands(rule))
    assert _kernel_calls(rule, jaxpr) == (1, 1)
    assert sorted(re.findall(r"name\[name=(\w+)\]", str(jaxpr))) == sorted(
        ac.RULE_CHECKPOINT_NAMES)
