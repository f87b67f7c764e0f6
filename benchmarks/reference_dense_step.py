"""The dense decoder's first training step in plain float32: the loss of a
micro-batch, its gradient by every stored tensor, and the change AdamW's
first step makes of it. ``reference.py`` (unchanged) is the model: its
``attention_half``, ``swiglu``, ``rms_norm`` and ``next_token_loss`` are
called here as they stand, under ``default_matmul_precision("highest")``.

No kernels, no sharding, no batching: one sequence at a time on one device,
the rows' terms added in float32. The derivative is ``jax.vjp``'s of those
functions, half a layer at a time so that the whole fits beside a sharded
program's state: the forward keeps each half-layer's input, the head gives
the cotangent of the last, and each half in turn, last first, its weights'
gradients and its input's cotangent. A layer's gradient is summed over the
rows before the next layer is begun, and handed on as soon as it is whole.
"""

from __future__ import annotations

from typing import Callable, Dict, Sequence

import jax
import jax.numpy as jnp

from benchmarks import reference
from benchmarks.reference import F32

ATTN = ("ln1", "wq", "wk", "wv", "wo")
MLP = ("ln2", "w_gate", "w_up", "w_down")


def mlp_half(x, w: Dict, cfg: Dict):
    """x + swiglu(norm(x)) for one layer, x [T, D] float32."""
    h = reference.rms_norm(x, w["ln2"], float(cfg["rms_norm_eps"]))
    return x + reference.swiglu(h, w["w_gate"], w["w_up"], w["w_down"])


def head_nll_sum(x, norm, head, tokens, eps: float):
    """The summed next-token cross-entropy of one sequence's last stream."""
    logits = reference.rms_norm(x, norm, eps) @ head
    return reference.next_token_loss(logits, tokens) * (tokens.shape[0] - 1)


def batch_loss_and_grads(cfg: Dict, get: Callable, rows: Sequence,
                         sink: Callable) -> Dict:
    """The mean next-token loss of ``rows`` (token ids, one sequence each)
    over all their targets, and its gradient by every tensor
    ``get(name, layer)`` returns (``reference.forward``'s names), float32,
    taken at the tensor upcast to float32. Each gradient goes to
    ``sink(name, layer, grad)`` as soon as it is whole, so that the caller
    may move it off the device. Returns ``{"loss": ...}``."""
    if int(cfg.get("num_local_experts", 1) or 1) > 1:
        raise ValueError("the dense decoder only")
    eps = float(cfg["rms_norm_eps"])
    n_layers = int(cfg["num_hidden_layers"])
    rows = [jnp.asarray(r, jnp.int32) for r in rows]
    targets = sum(int(r.shape[0]) - 1 for r in rows)

    def up(w):
        return {n: t.astype(F32) for n, t in w.items()}

    halves = {"attn": (ATTN, lambda x, w, pos: reference.attention_half(
                  x, w, cfg, pos)),
              "mlp": (MLP, lambda x, w, pos: mlp_half(x, w, cfg))}
    fwd = {k: jax.jit(lambda x, w, pos, f=f: f(x, up(w), pos))
           for k, (_, f) in halves.items()}
    bwd = {k: jax.jit(lambda x, w, pos, dy, f=f: jax.vjp(
               lambda x, w: f(x, w, pos), x, up(w))[1](dy))
           for k, (_, f) in halves.items()}
    head = jax.jit(jax.value_and_grad(
        lambda x, norm, head, tokens: head_nll_sum(
            x, norm.astype(F32), head.astype(F32), tokens, eps) / targets,
        argnums=(0, 1, 2)))

    def weights(names, layer=None):
        return {n: jnp.asarray(get(n, layer)) for n in names}

    def add(total, part):
        return part if total is None else jax.tree_util.tree_map(
            jnp.add, total, part)

    with jax.default_matmul_precision("highest"):
        pos = [jnp.arange(r.shape[0]) for r in rows]
        table = weights(("embed",))["embed"]
        # forward: kept[r] holds the input of every half-layer of row r
        kept = [[table[r].astype(F32)] for r in rows]
        for i in range(n_layers):
            for k, (names, _) in halves.items():
                w = weights(names, i)
                for r, xs in enumerate(kept):
                    xs.append(fwd[k](xs[-1], w, pos[r]))
        w = weights(("final_norm", "head"))
        loss, dxs, d_w = 0.0, [], None
        for r, xs in enumerate(kept):
            part, (dx, d_norm, d_head) = head(
                xs.pop(), w["final_norm"], w["head"], rows[r])
            loss, d_w = loss + part, add(d_w, (d_norm, d_head))
            dxs.append(dx)
        sink("final_norm", None, d_w[0])
        sink("head", None, d_w[1])
        del w, d_w
        for i in reversed(range(n_layers)):
            for k in ("mlp", "attn"):
                w, d_w = weights(halves[k][0], i), None
                for r, xs in enumerate(kept):
                    dxs[r], part = bwd[k](xs.pop(), w, pos[r], dxs[r])
                    d_w = add(d_w, part)
                for n, g in d_w.items():
                    sink(n, i, g)
                del w, d_w
        d_table = jnp.zeros(table.shape, F32)
        for r, dx in zip(rows, dxs):
            d_table = d_table.at[r].add(dx)
        sink("embed", None, d_table)
    return {"loss": loss}


def adamw_first_step(g, w, lr: float, b1: float = 0.9, b2: float = 0.999,
                     eps: float = 1e-8, weight_decay: float = 0.0):
    """The change AdamW's first step makes to ``w`` given the gradient ``g``
    (Loshchilov & Hutter; moments from zero, both bias corrections, the
    decay decoupled)::

        m = (1 - b1) g,  v = (1 - b2) g^2
        -lr ((m / (1 - b1)) / (sqrt(v / (1 - b2)) + eps) + weight_decay w)

    which is ``-lr g / (|g| + eps)`` without decay: each element's sign,
    where it is not within ``eps`` of zero."""
    m, v = (1.0 - b1) * g, (1.0 - b2) * g * g
    return -lr * ((m / (1.0 - b1)) / (jnp.sqrt(v / (1.0 - b2)) + eps)
                  + weight_decay * w)
