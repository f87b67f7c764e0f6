"""What a recomputation policy keeps of the flash kernels. Their forward rules
name the kernel's output and its log-sum-exp in the kernel's own layout
(``ops/flash_attention.py:_named_fwd``), and every policy that keeps anything
keeps those names (``runtime/activation_checkpointing.py:resolve_policy``):
the differentiated program of a checkpointed attention block then holds one
forward kernel call, where ``full`` holds two. Interpreted here; the count is
the program's, whatever runs it."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

from deepspeed_tpu.ops import flash_attention as fa
from deepspeed_tpu.runtime import activation_checkpointing as ac

B, T, H, K, D_HEAD, D_ROPE = 1, 256, 2, 1, 64, 32
WIDTH = H * D_HEAD
KW = dict(causal=True, interpret=True, block_q=128, block_k=128)
KEEPING = ("attn_saveable", "dots_saveable", "dots_and_attn_saveable",
           "offload_attn")
OUT, MODEL_LAYOUT, LSE = (B, H, T, D_HEAD), (B, T, H, D_HEAD), (B, H, 1, T)


def _heads(x, w, n):
    return (x @ w).reshape(B, T, n, -1)


def _plain(ws, x, **kw):
    return fa.flash_attention(_heads(x, ws["q"], H), _heads(x, ws["k"], K),
                              _heads(x, ws["v"], K), **KW, **kw)


def _parts(ws, x):
    """The Kanana-2 form: q beside its rope columns, a head's keys and values
    as one array (``v=None``), one rope key a position."""
    return fa.flash_attention(
        _heads(x, ws["q"], H), _heads(x, ws["kv"], K), None,
        q_rope=_heads(x, ws["q_rope"], H), k_rope=_heads(x, ws["k_rope"], 1),
        **KW)


def _with_lse(ws, x):
    """The FPDT path's entry, with a cotangent on the log-sum-exp."""
    out, lse = fa.flash_attention_lse(
        _heads(x, ws["q"], H), _heads(x, ws["k"], K), _heads(x, ws["v"], K),
        **KW)
    return out * jnp.tanh(lse).transpose(0, 2, 1, 3)


FORMS = {
    "plain": _plain,
    "window": lambda ws, x: _plain(ws, x, window=96),
    "parts": _parts,
    "lse": _with_lse,
}


def _operands():
    widths = {"q": WIDTH, "k": K * D_HEAD, "v": K * D_HEAD,
              "kv": K * 2 * D_HEAD, "q_rope": H * D_ROPE, "k_rope": D_ROPE,
              "o": WIDTH}
    keys = jax.random.split(jax.random.key(0), len(widths) + 1)
    ws = {n: 0.1 * jax.random.normal(k, (WIDTH, w), jnp.float32)
          for k, (n, w) in zip(keys, sorted(widths.items()))}
    return ws, jax.random.normal(keys[-1], (B, T, WIDTH), jnp.float32)


def _loss(form, policy):
    """The sum of squares of an attention block (the projections, the kernel,
    the output's product, the residual add) under ``policy``, a name of the
    table's or a ``jax.checkpoint_policies`` callable; None: no
    ``jax.checkpoint`` at all."""
    def block(ws, x):
        return x + FORMS[form](ws, x).reshape(B, T, WIDTH) @ ws["o"]

    if callable(policy):
        block = jax.checkpoint(block, policy=policy)
    elif policy is not None:
        block = ac.checkpoint_wrapper(block, policy=policy)
    return lambda ws, x: jnp.sum(block(ws, x) ** 2)


def _kernel_calls(jaxpr):
    """(forward, backward) ``pallas_call``s in a jaxpr's text: a forward's
    results are the output and the float32 log-sum-exp rows."""
    calls = re.findall(r"\n\s*([^\n=]*?) = pallas_call\[", str(jaxpr))
    fwd = [c for c in calls if re.fullmatch(
        rf"\w+:f32\[{B},{H},{T},{D_HEAD}\] \w+:f32\[{B},{H},1,{T}\]",
        c.strip())]
    return len(fwd), len(calls) - len(fwd)


_UNWRAPPED = {}


def _unwrapped_grads(form):
    if form not in _UNWRAPPED:
        _UNWRAPPED[form] = jax.grad(_loss(form, None))(*_operands())
    return _UNWRAPPED[form]


def test_the_kernels_names_are_the_policy_tables():
    """The forward rules' literals (``ops/flash_attention.py`` imports
    nothing of ``runtime``) and the table's constants are the same names."""
    assert fa.RESIDUAL_NAMES == (ac.ATTN_CHECKPOINT_NAME,
                                 ac.ATTN_LSE_CHECKPOINT_NAME)


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("policy", ("full",) + KEEPING)
def test_a_keeping_policy_runs_the_forward_kernel_once(policy, form):
    """``full`` runs the forward kernel again in the backward's recomputed
    region; a policy that keeps the kernel's names does not: it keeps the
    output once (in the kernel's layout, none in the model's) and the
    log-sum-exp, and the gradients are the unwrapped block's to the bit, the
    backward reading the values a second call would have made again."""
    ws, x = _operands()
    loss = _loss(form, policy)
    fwd, bwd = _kernel_calls(jax.make_jaxpr(jax.grad(loss))(ws, x))
    assert (fwd, bwd) == (2 if policy == "full" else 1, 1)

    kept = [tuple(aval.shape) for aval, why in saved_residuals(loss, ws, x)
            if "argument" not in why]
    if policy == "full":
        assert OUT not in kept and LSE not in kept
    else:
        assert kept.count(OUT) == 1 and kept.count(LSE) == 1
    assert MODEL_LAYOUT not in kept

    grads = jax.grad(loss)(ws, x)
    for name, g in _unwrapped_grads(form).items():
        np.testing.assert_array_equal(np.asarray(grads[name]), np.asarray(g),
                                      err_msg=name)


@pytest.mark.parametrize("policy", ("none", "full", "nothing_saveable"))
def test_a_policy_that_keeps_nothing_traces_the_same_program(policy):
    """Under ``none``, ``full`` and ``nothing_saveable`` a name is inert:
    the differentiated program is, primitive for primitive, the program of
    a block whose kernel results carry no name."""
    ws, x = _operands()

    def primitives():
        text = str(jax.make_jaxpr(jax.grad(_loss("plain", policy)))(ws, x))
        return _kernel_calls(text), re.findall(r" = (\w+)", text)

    calls, named = primitives()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fa, "checkpoint_name", lambda value, name: value)
        bare_calls, bare = primitives()
    assert calls == bare_calls == (1 if policy == "none" else 2, 1)
    assert "name" in named and "name" not in bare
    assert [p for p in named if p != "name"] == bare


def test_names_that_nothing_carries_keep_nothing_more():
    """``attn_saveable`` also keeps the delta and KDA rules' names and the
    selected-key op's: a block whose one kernel is the flash kernel carries
    none of them, and its differentiated program is, to the letter, the
    program under a policy of the flash kernel's two names alone."""
    ws, x = _operands()

    def text(policy):
        jaxpr = jax.make_jaxpr(jax.grad(_loss("plain", policy)))(ws, x)
        return re.sub(r"0x[0-9a-f]+", "", str(jaxpr))

    assert set(ac.RULE_CHECKPOINT_NAMES + ac.DSA_CHECKPOINT_NAMES).isdisjoint(
        fa.RESIDUAL_NAMES)
    assert text("attn_saveable") == text(
        jax.checkpoint_policies.save_only_these_names(*fa.RESIDUAL_NAMES))


@pytest.mark.parametrize("form", sorted(FORMS))
def test_outside_a_checkpoint_the_names_change_nothing(form):
    """No ``jax.checkpoint``: value-and-grad holds one forward and one
    backward kernel call, the two names stand in the program once each, and
    the value is ``full``'s."""
    ws, x = _operands()
    loss = _loss(form, None)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(ws, x)
    assert _kernel_calls(jaxpr) == (1, 1)
    assert sorted(re.findall(r"name\[name=(\w+)\]", str(jaxpr))) == sorted(
        fa.RESIDUAL_NAMES)
    np.testing.assert_array_equal(
        np.asarray(loss(ws, x)), np.asarray(_loss(form, "full")(ws, x)))
