"""Top-k gating + capacity-based expert dispatch (GShard algebra).

Parity target: ``deepspeed/moe/sharded_moe.py`` — ``top1gating`` :184, ``top2gating``
:291, ``topkgating`` :375, ``TopKGate`` :452, ``MOELayer`` :536. The torch version
builds dispatch/combine masks then calls ``_AllToAll`` over the EP process group; here
the masks feed einsums and the ``[E, C, D]`` dispatched tensor is sharding-constrained
to the ``ep`` axis — the all-to-all is XLA's, riding ICI.

Static-shape discipline: capacity ``C`` is computed from *static* sequence length and
capacity factor, so the whole layer jits with fixed shapes (no ragged dispatch in the
hot path; dropped tokens pass through the residual, exactly like the reference with
``drop_tokens=True``).

The dropless twin (``moe_dispatch: grouped``) sorts the (token, expert) pairs by expert
and runs the experts' FFN as grouped products over the sorted rows (``moe_kernel:
ragged``). ``ragged`` names the algebra, not a lowering: each product is the Pallas
kernel of ``ops/grouped_matmul.py`` (forward and both transposes) where its rule finds
the measured case — a TPU, dense bf16 stacks, whole-lane widths, a tile of rows an
expert — and ``jax.lax.ragged_dot`` everywhere else (int8 serving stacks, decode steps,
other backends), exactly as before. Dispatch and combine, the moves between token order
and that sorted order, take the same answer: the row kernels of ``ops/moe_rows.py``
(each row that carries a pair copied once, its weight and its sum done in the same pass)
where the products take the Pallas kernels, ``jnp.take`` everywhere else.
"""

from __future__ import annotations

import functools
import logging
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops import lowerings
from deepspeed_tpu.ops.topk_select import topk_select
from deepspeed_tpu.parallel.sharding import constrain
from deepspeed_tpu.utils.logging import log_dist

#: placement-table leaves (moe/balancer.py) that ride the expert weight
#: dict replicated — everything else under the dict is an expert stack
PLACEMENT_LEAVES = ("place_dest", "place_slot", "place_nrep")


def _capacity(num_tokens: int, num_experts: int, capacity_factor: float,
              min_capacity: int) -> int:
    cap = int(math.ceil(num_tokens / num_experts * capacity_factor))
    return max(cap, min_capacity)


# ---------------------------------------------------------------------------
# grouped-GEMM kernel selection (the PR 18 decode_kernel pattern)
# ---------------------------------------------------------------------------

MOE_KERNELS = ("ragged", "padded")
_SUPPORT_MEMO: Optional[Tuple[Optional[str], str]] = None
_FALLBACK_WARNED = False


def moe_kernel_support() -> Tuple[Optional[str], str]:
    """How the dropless grouped expert GEMM can run on this backend:
    ``("native", why)`` when ``jax.lax.ragged_dot`` lowers here, ``(None,
    why)`` otherwise — callers log ``why`` once and fall back to
    ``moe.kernel: padded`` (the capacity-einsum reference). ``ragged_dot``
    is the lowering every backend and shape can take; where the Pallas
    kernels run instead is ``ops/grouped_matmul.py:grouped_lowering``'s to
    say, call by call."""
    global _SUPPORT_MEMO
    if _SUPPORT_MEMO is not None:
        return _SUPPORT_MEMO
    if not hasattr(jax.lax, "ragged_dot"):
        _SUPPORT_MEMO = (None, "this jax has no lax.ragged_dot")
        return _SUPPORT_MEMO
    try:
        jax.jit(jax.lax.ragged_dot).lower(
            jax.ShapeDtypeStruct((4, 2), jnp.float32),
            jax.ShapeDtypeStruct((2, 2, 3), jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.int32)).compile()
    except Exception as e:                     # no backend lowering
        _SUPPORT_MEMO = (None, f"ragged_dot probe failed: {e!r}")
        return _SUPPORT_MEMO
    _SUPPORT_MEMO = ("native", "lax.ragged_dot grouped GEMM compiles here")
    return _SUPPORT_MEMO


def resolve_moe_kernel(kernel: str) -> Tuple[str, str]:
    """Resolve a configured ``moe.kernel`` against backend support:
    ``ragged`` degrades to ``padded`` with ONE logged warning when the
    grouped GEMM cannot lower (never silently — the reason is returned
    for the engine to surface). Returns ``(kernel, fallback_reason)``."""
    global _FALLBACK_WARNED
    if kernel not in MOE_KERNELS:
        raise ValueError(f"moe kernel must be one of {MOE_KERNELS}, "
                         f"got {kernel!r}")
    if kernel == "padded":
        return "padded", ""
    mode, reason = moe_kernel_support()
    if mode is None:
        if not _FALLBACK_WARNED:
            log_dist(f"moe.kernel: ragged grouped GEMM unavailable "
                     f"({reason}); falling back to the padded capacity "
                     f"einsum", level=logging.WARNING)
            _FALLBACK_WARNED = True
        return "padded", reason
    return "ragged", ""


# ---------------------------------------------------------------------------
# expert-load observation (AutoEP input — moe/balancer.py)
# ---------------------------------------------------------------------------

_TRACKER = None


def set_expert_tracker(tracker) -> None:
    """Install (or clear, with ``None``) the process-wide expert-load
    tracker. Checked at TRACE time: install it before the first jitted
    dispatch or the counts callback is not baked into the program.
    ``None`` (the default) costs nothing in the hot path."""
    global _TRACKER
    _TRACKER = tracker


def _emit_expert_counts(counts) -> None:
    """``jax.debug.callback`` body: forward one dispatch's per-expert
    routed-token counts (a partial sum under ep — shards' contributions
    add up to the global count) to the installed tracker."""
    t = _TRACKER
    if t is not None:
        t.observe(counts)


def _hot(idx: jax.Array, n: int) -> jax.Array:
    """``idx`` [...] against ``arange(n)``: bool [..., n], true where the
    index names the column (an index outside ``0 .. n - 1`` names none)."""
    return idx[..., None] == jnp.arange(n, dtype=idx.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _pick(s: jax.Array, idx: jax.Array, E: int) -> jax.Array:
    """``jnp.take_along_axis(s, idx, axis=-1)`` over ``s`` [..., E] and ``idx``
    [..., k], to the bit, without a gather: a compare of ``idx`` against the
    experts, a select and a sum along them, in which one term is ``s``'s and
    the others are zeros (a v5e takes 9 ns a gathered scalar: 1.8 ms for
    8192 tokens' 22 picks, where this is one pass over ``s``). The
    derivative is the same compare summed along ``k`` (each expert gets the
    cotangent of the one pick that named it, in place of a scatter-add), and
    what is kept for it is ``idx``, never the one-hot [..., k, E]."""
    return jnp.where(_hot(idx, E), s[..., None, :], 0).sum(axis=-1)


def _pick_fwd(s, idx, E):
    return _pick(s, idx, E), idx


def _pick_bwd(E, idx, g):
    return jnp.where(_hot(idx, E), g[..., None], 0).sum(axis=-2), None


_pick.defvjp(_pick_fwd, _pick_bwd)


def _route(logits: jax.Array, k: int, rng: Optional[jax.Array] = None,
           noise_std: float = 0.0, valid: Optional[jax.Array] = None,
           psum_axis: Optional[str] = None):
    """Shared router prefix for ALL dispatch algebras: fp32 gates, GShard
    top-1 aux loss (sharded_moe.py:184 l_aux), renormalized top-k weights.

    ``valid`` [S] masks padding/idle rows (decode-batch no-op lanes): they are
    excluded from the aux stats and their combine weights are zeroed, so they
    can neither shift the load-balancing loss nor occupy expert capacity.
    ``psum_axis`` makes the aux stats global across a manual mesh axis (the
    ep shard_map region) — psum-of-sums equals the single-shard means.
    """
    E = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    if noise_std > 0.0 and rng is not None:  # noisy_gate_policy='RSample' parity
        logits = logits + noise_std * jax.random.normal(rng, logits.shape)
    gates = jax.nn.softmax(logits, axis=-1)  # [S, E]
    top1 = jnp.argmax(gates, axis=-1)
    mask1 = jax.nn.one_hot(top1, E, dtype=jnp.float32)
    vf = None if valid is None else valid.astype(jnp.float32)
    g_sum = gates.sum(0) if vf is None else (gates * vf[:, None]).sum(0)
    m_sum = mask1.sum(0) if vf is None else (mask1 * vf[:, None]).sum(0)
    cnt = jnp.float32(logits.shape[0]) if vf is None else vf.sum()
    if psum_axis is not None:
        g_sum = jax.lax.psum(g_sum, psum_axis)
        m_sum = jax.lax.psum(m_sum, psum_axis)
        cnt = jax.lax.psum(cnt, psum_axis)
    denom = jnp.maximum(cnt, 1.0)
    aux_loss = jnp.sum(g_sum * m_sum) / (denom * denom) * E
    # the chosen gates by :func:`_pick`, the values ``top_k`` gives to the
    # bit: the transpose of ``top_k``'s own is a scatter-add of S k scalars
    topk_idx, _ = topk_select(gates, k)  # [S, k]
    topk_vals = _pick(gates, topk_idx, E)
    # renormalize the kept gate mass (reference normalizes combine weights)
    topk_vals = topk_vals / jnp.maximum(topk_vals.sum(-1, keepdims=True), 1e-9)
    if valid is not None:
        topk_vals = topk_vals * valid[:, None].astype(topk_vals.dtype)
    return gates, aux_loss, topk_vals, topk_idx


def _route_sigmoid(logits: jax.Array, bias: jax.Array, k: int, scale: float,
                   groups: Tuple[int, int] = (1, 1)):
    """The DeepSeek-V3 family's router (``moe_scoring="sigmoid"``) over
    ``logits`` [B, T, E] in float32: scores ``s = sigmoid(logits)``, the
    ``k`` experts with the largest ``s + bias`` (the selection bias picks
    and gets no gradient; with ``groups`` (n_group, topk_group) above (1, 1)
    among the experts of the groups the limit keeps:
    ``ops/topk_select.py``, which picks without a sort where it can), weights
    ``scale x s_i / sum_{chosen} s_j`` (the bias is not in them). Returns
    ``(balance term, weights [B T, k], experts [B T, k], counts [E], groups
    kept [n_group] or None)``: the sequence-wise
    balance term ``sum_i f_i
    P_i``, ``f_i = E / (k T) x`` the sequence's pairs of expert i (the chosen
    pairs, bias included: a constant), ``P_i`` the sequence's mean of ``s_i /
    sum_j s_j``, averaged over the sequences; the pairs each expert
    received from all of them; and the tokens that kept each group. The
    chosen scores (:func:`_pick`) and the
    counts both come from compares of the chosen experts against
    ``arange(E)``: no gather of a scalar a pair, no scatter-add behind it."""
    B, T, E = logits.shape
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    idx, keep = topk_select(s + bias.astype(jnp.float32), k, groups)
    kept = None if keep is None else keep.sum(axis=(0, 1), dtype=jnp.int32)
    picked = _pick(s, idx, E)
    weights = scale * picked / picked.sum(-1, keepdims=True)
    by_seq = _hot(idx, E).sum(axis=(1, 2), dtype=jnp.int32)   # [B, E]
    f = by_seq.astype(jnp.float32) * (E / (k * T))
    p = (s / s.sum(-1, keepdims=True)).mean(axis=1)           # [B, E]
    aux_loss = (f * p).sum(-1).mean()
    return (aux_loss, weights.reshape(B * T, k), idx.reshape(B * T, k),
            by_seq.sum(axis=0), kept)


# the running count's chunk of rows
_RUN = 128
# (held experts + 1) x pairs up to which an expert and a pair are one int32 key
_MOST_KEYS = 2 ** 31 - 1


def _count_before(hot: jax.Array) -> jax.Array:
    """The exclusive running sum down the rows of ``hot`` [S, G] (int, 0 or
    1): ``out[t, g]`` counts the ones of column ``g`` in rows before ``t``.
    Two products with a triangle of ones, within chunks of ``_RUN`` rows and
    over the chunks' totals, because XLA's ``cumsum`` is a ``reduce-window``
    on a TPU (PERF.md section 6, PR 38). Float32 at the highest precision:
    the operands are 0, 1 and a chunk's total, whole numbers under 2**8,
    which every piece the MXU splits a float32 into holds exactly, and the
    sums are float32, exact under 2**24 rows."""
    S, G = hot.shape
    n = -(-S // _RUN)
    x = jnp.pad(hot, ((0, n * _RUN - S), (0, 0))).reshape(n, _RUN, G) \
        .astype(jnp.float32)

    def earlier(m):                       # [from, to]: 1 where from < to
        i = jnp.arange(m, dtype=jnp.int32)
        return (i[:, None] < i[None, :]).astype(jnp.float32)

    exact = jax.lax.Precision.HIGHEST
    within = jnp.einsum("ncg,cd->ndg", x, earlier(_RUN), precision=exact)
    chunks = jnp.einsum("ng,nm->mg", x.sum(axis=1), earlier(n),
                        precision=exact)
    return (within + chunks[:, None, :]).astype(jnp.int32) \
        .reshape(n * _RUN, G)[:S]


def _placement(topk_idx: jax.Array, weights: jax.Array, first: int,
               n_held: int, bound: int):
    """Where the (token, expert) pairs of ``topk_idx`` [S, k] go in the
    buffer of ``bound`` rows sorted by expert, for the ``n_held`` experts from
    ``first`` on. Returns ``(order [S k], row_weight [bound], slot [S, k],
    group_sizes [held], n_here, counts [held])``: the pairs ``t * k + j`` in
    sorted order, of which the first ``bound`` are the pair in each row
    (``rows``); the weight ``weights[t, j]`` of each row's pair (a constant:
    no gradient goes through it); each pair's row (``bound``, a row that
    reads as zeros, for a pair whose expert is absent or that the buffer had
    no room for), the rows each held expert has, their sum, and the pairs
    each held expert received (``counts - group_sizes`` did not fit).

    Everything but the sort comes from one compare of the pairs' experts
    against ``arange(n_held)``, [S, k, held]: summed along ``k`` it says which
    tokens chose which held expert (a token picks an expert at most once),
    summed down the tokens the counts, and its running count down the tokens
    (:func:`_count_before`) a pair's place in its expert's group, since the
    sort is stable and a group's pairs lie in token order: ``slot = start[e]
    + (pairs of e from earlier tokens)``, read back at the pair by the same
    compare. That is the inverse permutation the ``argsort`` would need a
    scatter of S k elements for, and the counts a ``bincount`` (a
    scatter-add) gave: a v5e runs either at 9 ns an element. The sort
    itself is kept (``jnp.argsort``'s order: by expert, stable), and the
    pairs' weights ride it, so that a row's weight is there without the
    gather ``weights.reshape(-1)[rows]``; ``order`` whole is the key that
    brings a scalar a row back to pair order (:func:`_pairs_of_rows`)."""
    S, k = topk_idx.shape
    local = topk_idx - first
    hot = _hot(local, n_held)                                  # [S, k, held]
    chose = hot.sum(axis=1, dtype=jnp.int32)                   # [S, held]
    counts = chose.sum(axis=0, dtype=jnp.int32)
    total = jnp.cumsum(counts)
    ends = jnp.minimum(total, bound)
    group_sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
    n_here = ends[-1]                             # rows that carry a pair
    place = (total - counts) + _count_before(chose)            # [S, held]
    rank = jnp.where(hot, place[:, None, :], 0).sum(axis=-1)   # [S, k]
    here = hot.any(axis=-1)
    slot = jnp.where(here & (rank < n_here), rank, bound)
    key = jnp.where(here, local, n_held).reshape(-1)           # absent: last
    n = S * k
    pair = jnp.arange(n, dtype=jnp.int32)
    by_pair = jax.lax.stop_gradient(weights).reshape(-1)
    if (n_held + 1) * n <= _MOST_KEYS:
        # expert and pair as one key, all different: the stable sort's order
        # from a sort of two operands that need not be stable (a third less
        # time on a v5e than key, pair and weight sorted stably)
        both, by_row = jax.lax.sort((key * n + pair, by_pair), num_keys=1,
                                    is_stable=False)
        order = jax.lax.rem(both, jnp.int32(n))
    else:
        _, order, by_row = jax.lax.sort((key, pair, by_pair), num_keys=1,
                                        is_stable=True)
    return order, by_row[:bound], slot, group_sizes, n_here, counts


def _pairs_of_rows(by_row: jax.Array, order: jax.Array,
                   slot: jax.Array) -> jax.Array:
    """``jnp.take(by_row, slot, mode="fill", fill_value=0)``, a float32 a
    buffer row brought to pair order [S, k], to the bit and without a gather
    of S k scalars (1.0-1.3 ms a layer on a v5e, where this is 0.1-0.25):
    ``order`` [S k] is the pair each sorted place holds (the buffer's rows
    are its first ``bound``), so a sort of the rows' values, padded to S k,
    keyed on it (the keys are all different) puts each at its pair. A pair
    without a row (``slot == bound``) gets its zero by a select, never by a
    product: the rows past the last live tile are never written and may hold
    NaN."""
    n = order.shape[0]
    _, by_pair = jax.lax.sort(
        (order, jnp.pad(by_row, (0, n - by_row.shape[0]))), num_keys=1,
        is_stable=False)
    return jnp.where(slot < by_row.shape[0], by_pair.reshape(slot.shape), 0)


def _relu2(v: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(v))


_gelu_tanh = functools.partial(jax.nn.gelu, approximate=True)


def _ungated_act(cfg: Any):
    """What an expert without a gate product applies between its two
    products: ``relu(.)^2`` where the config says ``activation="relu2"``
    (Nemotron-H), the tanh gelu otherwise, as before."""
    return _relu2 if getattr(cfg, "activation", None) == "relu2" \
        else _gelu_tanh


def _shared_ffn(h: jax.Array, w: Dict[str, jax.Array], act) -> jax.Array:
    """The shared experts, every token's: one SwiGLU, or without a gate
    product two products round ``act``; with ``w["w_sg"]`` [D, 1] times
    ``sigmoid(h w_sg)``."""
    if "w_gate" in w:
        out = (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
    else:
        out = act(h @ w["w_up"]) @ w["w_down"]
    if "w_sg" in w:
        # one scalar a token on the shared experts' output (``cfg.
        # moe_shared_gate``): the sigmoid in float32, rounded once
        out = (out.astype(jnp.float32) * jax.nn.sigmoid(
            (h @ w["w_sg"]).astype(jnp.float32))).astype(out.dtype)
    return out


def topk_gating(logits: jax.Array, k: int = 2, capacity_factor: float = 1.25,
                min_capacity: int = 4, rng: Optional[jax.Array] = None,
                noise_std: float = 0.0, valid: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array, jax.Array, Dict[str, jax.Array]]:
    """GShard top-k gating with per-expert capacity.

    Args:
        logits: [S, E] raw router outputs (fp32 recommended).
        valid: [S] bool — False rows (decode-batch padding/idle lanes) do not
            compete for expert capacity and carry zero combine weight.
    Returns:
        (dispatch [S, E, C] float, combine [S, E, C] float, aux_loss scalar, stats)
    """
    S, E = logits.shape
    C = _capacity(S, E, capacity_factor, min_capacity)
    _gates, aux_loss, topk_vals, topk_idx = _route(logits, k, rng, noise_std,
                                                   valid=valid)

    dispatch = jnp.zeros((S, E, C), jnp.float32)
    combine = jnp.zeros((S, E, C), jnp.float32)
    counts = jnp.zeros((E,), jnp.int32)  # tokens already assigned per expert
    for j in range(k):
        idx_j = topk_idx[:, j]                       # [S]
        mask_j = jax.nn.one_hot(idx_j, E, dtype=jnp.int32)   # [S, E]
        if valid is not None:
            mask_j = mask_j * valid[:, None].astype(jnp.int32)
        pos_in_expert = jnp.cumsum(mask_j, axis=0) - mask_j  # position among j-th picks
        loc = jnp.sum(pos_in_expert * mask_j, axis=1) + counts[idx_j]  # [S]
        keep = loc < C
        counts = counts + jnp.sum(mask_j * keep[:, None].astype(jnp.int32), axis=0)
        onehot_loc = jax.nn.one_hot(loc, C, dtype=jnp.float32) * keep[:, None]
        sel = mask_j.astype(jnp.float32)[:, :, None] * onehot_loc[:, None, :]  # [S,E,C]
        dispatch = dispatch + sel
        combine = combine + sel * topk_vals[:, j][:, None, None]

    stats = {"capacity": jnp.asarray(C), "tokens_per_expert": counts,
             "drop_fraction": 1.0 - dispatch.sum() / (S * k)}
    return dispatch, combine, aux_loss, stats


def top1_gating(logits: jax.Array, **kw):
    """``top1gating`` parity (switch-transformer routing)."""
    return topk_gating(logits, k=1, **kw)


def _expert_weight(w: Dict[str, jax.Array], name: str, dt) -> jax.Array:
    """Expert stack [E, D, F] in the compute dtype. Serving engines may
    replace the dense stack with int8 leaves (``name+'_q'`` packed values +
    ``name+'_s'`` per-group scales, see ``inference/quant.py``) — the
    dequant here is elementwise, so XLA folds it into the grouped GEMM's
    operand read and expert weights stream from HBM at 1 byte/element
    (reference ``inference/v2/kernels/cutlass_ops/moe_gemm`` W8A16 parity:
    expert stacks are exactly where serving HBM pressure concentrates)."""
    if name in w:
        return w[name].astype(dt)
    q, s = w[name + "_q"], w[name + "_s"]
    E, D, F = q.shape
    G = s.shape[1]
    return (q.astype(dt).reshape(E, G, D // G, F)
            * s.astype(dt).reshape(E, G, 1, F)).reshape(E, D, F)


def _has_gate(w: Dict[str, jax.Array]) -> bool:
    return "w_gate" in w or "w_gate_q" in w


def moe_mlp_block(h: jax.Array, w: Dict[str, jax.Array], cfg: Any,
                  valid: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Drop-in MoE MLP for ``TransformerLM`` (the ``moe_fn`` hook in
    ``models/transformer.py`` ``transformer_block``).

    h: [B, T, D]; w: router [D, E], w_gate/w_up [E, D, F], w_down [E, F, D];
    valid: optional [B, T] bool — padding/idle decode lanes that must not
    consume expert capacity or shift the aux stats.
    """
    B, T, D = h.shape
    E = w["router"].shape[-1]
    x = h.reshape(B * T, D)
    logits = x.astype(jnp.float32) @ w["router"].astype(jnp.float32)
    dispatch, combine, aux, stats = topk_gating(
        logits, k=cfg.top_k, capacity_factor=cfg.capacity_factor,
        min_capacity=getattr(cfg, "min_capacity", 4),
        valid=None if valid is None else valid.reshape(-1))
    if _TRACKER is not None:
        jax.debug.callback(_emit_expert_counts,
                           stats["tokens_per_expert"].astype(jnp.int32))

    dt = h.dtype
    xe = jnp.einsum("sec,sd->ecd", dispatch.astype(dt), x)       # [E, C, D]
    xe = constrain(xe, P("ep", None, None))
    if _has_gate(w):
        act = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe,
                                     _expert_weight(w, "w_gate", dt)))
        act = act * jnp.einsum("ecd,edf->ecf", xe,
                               _expert_weight(w, "w_up", dt))
    else:
        act = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe,
                                     _expert_weight(w, "w_up", dt)),
                          approximate=True)
    act = constrain(act, P("ep", None, "tp"))
    ye = jnp.einsum("ecf,efd->ecd", act,
                    _expert_weight(w, "w_down", dt))             # [E, C, D]
    ye = constrain(ye, P("ep", None, None))
    y = jnp.einsum("sec,ecd->sd", combine.astype(dt), ye)
    return y.reshape(B, T, D), aux


def _padded_ffn(xs: jax.Array, group_sizes: jax.Array,
                w: Dict[str, jax.Array], dt, act=_gelu_tanh) -> jax.Array:
    """The pad-to-capacity einsum reference at ``capacity_factor=∞``:
    every expert padded to the FULL token count and computed with the
    same einsum chain as the capacity path. O(N·E) flops and an
    ``[E, N, D]`` intermediate vs the grouped path's O(N) — this is the
    baseline the ragged kernel is measured against (``moe.kernel:
    padded``, and the automatic fallback when ``ragged_dot`` cannot
    lower). Still dropless: padding rows carry zero and drop nothing."""
    N = xs.shape[0]
    E = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    seg = jnp.sum(jnp.arange(N)[:, None] >= ends[None, :], axis=-1)
    oh = jax.nn.one_hot(seg, E, dtype=dt)                 # [N, E]
    xe = jnp.einsum("ne,nd->end", oh, xs)                 # [E, N, D]
    if _has_gate(w):
        act = jax.nn.silu(jnp.einsum("end,edf->enf", xe,
                                     _expert_weight(w, "w_gate", dt)))
        act = act * jnp.einsum("end,edf->enf", xe,
                               _expert_weight(w, "w_up", dt))
    else:
        act = act(jnp.einsum("end,edf->enf", xe,
                             _expert_weight(w, "w_up", dt)))
    ye = jnp.einsum("enf,efd->end", act,
                    _expert_weight(w, "w_down", dt))      # [E, N, D]
    return jnp.einsum("ne,end->nd", oh, ye)


def _stack_names(w) -> Tuple[str, ...]:
    return ("w_gate", "w_up", "w_down") if _has_gate(w) else ("w_up", "w_down")


def _ffn_lowering(rows: int, dtype, w: Dict[str, jax.Array], dt,
                  interpret: Optional[bool]) -> str:
    """``grouped_lowering``'s answer for the FFN over ``rows`` buffer rows:
    one for its products, and for the moves into and out of the buffer."""
    from deepspeed_tpu.ops.grouped_matmul import grouped_lowering

    names = _stack_names(w)
    up = w["w_up"] if "w_up" in w else w["w_up_q"]
    experts, width, inner = up.shape
    took, _ = grouped_lowering(
        rows, width, inner, experts, jnp.result_type(dtype, dt),
        dense=all(name in w for name in names),
        tpu=None if interpret is None else True)
    return took


def _grouped_ffn(xs: jax.Array, group_sizes: jax.Array, w: Dict[str, jax.Array],
                 dt, kernel: str = "ragged", interpret: Optional[bool] = None,
                 rows_past_groups: bool = False, fetch=None,
                 buffer_rows: Optional[int] = None,
                 act=_gelu_tanh) -> jax.Array:
    """Expert-grouped FFN over tokens sorted by expert. ``kernel="ragged"``
    names the algebra: every row times its own expert's weights, no padding
    to a capacity. Its products take the lowering
    ``ops/grouped_matmul.py:grouped_lowering`` picks from the call's own
    facts, one answer for the FFN: the Pallas kernels (forward and both
    transposes) on a TPU for dense bf16 stacks of whole-lane widths with a
    tile of rows a group (training, prefill); ``lax.ragged_dot`` for
    everything else (int8 serving stacks, which dequantise inside its
    operand read, see :func:`_expert_weight`; a decode step's few rows;
    other backends). ``"padded"`` is the capacity-einsum reference twin
    (:func:`_padded_ffn`) the engines fall back to when ragged_dot has no
    backend lowering. ``interpret`` is the kernels' test handle (None: ask
    the backend). Stacks without ``w_gate`` are experts of two products
    round ``act`` (default the tanh gelu; :func:`_ungated_act`).

    ``rows_past_groups``: the groups may end before the rows do. No consumer
    reads such a row of the result (a pair's ``slot`` never names one), but
    its cotangent arrives as if it carried a pair. The kernels work only the
    rows a group holds, forward and backward, whatever the others contain,
    so nothing is masked there; ``ragged_dot`` leaves those rows
    uninitialised and its transposes are given the cotangent as it comes,
    so its result is masked (and with it the cotangent), as before.

    ``fetch``: ``xs`` is then the tokens, and ``fetch(xs)`` the
    ``buffer_rows`` sorted rows (the row kernels' dispatch). The first
    products are taken from it under a ``jax.checkpoint`` that keeps their
    results and the packed tokens but not the rows: the backward fetches
    them again for the weights' gradients (0.65 ms a layer at the Mellum2
    cell, against 302 MB a layer kept from the forward to the backward,
    which XLA, left to itself, rematerialises for a ``jnp.take`` and cannot
    for a kernel)."""
    with jax.named_scope("moe_experts"):
        if kernel == "padded":
            return _padded_ffn(xs, group_sizes, w, dt, act)
    # imported here: a model without experts never loads the kernels
    from deepspeed_tpu.ops.grouped_matmul import grouped_matmul

    names = _stack_names(w)
    stacks = {name: _expert_weight(w, name, dt) for name in names}
    n_rows = xs.shape[0] if fetch is None else buffer_rows
    took = _ffn_lowering(n_rows, xs.dtype, w, dt, interpret)

    def product(rows, stack):
        return grouped_matmul(rows, stack, group_sizes, lowering=took,
                              interpret=bool(interpret))

    def first(src, *first_stacks):
        rows = src if fetch is None else fetch(src)
        with jax.named_scope("moe_experts"):
            return product(rows, first_stacks)

    if fetch is not None:
        first = jax.checkpoint(
            first, policy=jax.checkpoint_policies.save_only_these_names(
                PACKED_TOKENS))
    # (outside the scope: the fetch inside ``first`` is the dispatch's)
    # one call for the two stacks that read xs: its backward sums their two
    # cotangents of xs inside one kernel
    firsts = first(xs, *(stacks[name] for name in names[:-1]))
    with jax.named_scope("moe_experts"):
        if _has_gate(w):
            gate, up = firsts
            act = jax.nn.silu(gate) * up
        else:
            act = act(firsts[0])
        ys = product(act, stacks["w_down"])
        if rows_past_groups and took == "xla":
            held = jnp.arange(n_rows) < group_sizes.sum()
            ys = jnp.where(held[:, None], ys, 0)
    return ys


def grouped_moe_mlp_block(h: jax.Array, w: Dict[str, jax.Array], cfg: Any,
                          valid: Optional[jax.Array] = None, *,
                          kernel: Optional[str] = None,
                          a2a_bits: Optional[int] = None,
                          a2a_slice: Optional[int] = None,
                          interpret: Optional[bool] = None
                          ) -> Tuple[jax.Array, jax.Array]:
    """Dropless sort-based dispatch over grouped GEMMs — the
    ``inference/v2/kernels/cutlass_ops/moe_gemm`` (MegaBlocks-style) analog:
    ``kernel="ragged"`` is every pair computed over the rows sorted by
    expert, by the Pallas grouped matmul or ``jax.lax.ragged_dot`` as
    :func:`_grouped_ffn` says (``kernel="padded"`` swaps in the
    capacity-einsum reference twin; the default resolves ``cfg.moe_kernel``
    with automatic fallback).

    Unlike the capacity path, every (token, expert) pair is computed — no
    ``capacity_factor`` padding waste and no dropped tokens — at the price of
    data-dependent group sizes (static TOTAL shape ``S*k``, so it still jits).
    Which row of the buffer a pair takes, the rows each expert has and the
    pairs it received are the router's index work (:func:`_placement`, under
    the scope ``moe_router``): compares of the chosen experts against the
    held ones, summed and counted down the tokens; only the pair of each row
    (``rows``) is a sort, which carries the pairs' weights with it, and
    nothing there gathers or scatters a scalar a pair.
    Dispatch (tokens into the buffer of sorted pairs) and combine (each
    token's weighted sum of its pairs' rows) are one-to-one moves of whole
    rows, gathers both ways; they take the lowering the FFN's products take
    (:func:`_moves_lowering`): the row kernels of ``ops/moe_rows.py``, which
    copy only the rows that carry a pair, each once, and weight or sum them
    in the same pass, or ``jnp.take``. ``interpret`` is the kernels' test
    handle (None: ask the backend). The two scalars a row of the combine's
    backward move by sorts in either lowering, so nothing there gathers or
    scatters a scalar a pair or a row either: a row's weight is the one the
    router's sort carried, and the rows' dots, the weights' gradient, come
    back to pair order keyed on that sort's order (:func:`_pairs_of_rows`).
    With a held share of the experts (``cfg.moe_experts_held``) the router,
    the top k and their renormalised weights are the whole model's, only the
    pairs whose expert is here are computed, what the absent experts would
    add is left out, and the second value returned is a dict: the
    load-balance term under ``lb`` and the router's counts.
    ``cfg.moe_scoring="sigmoid"`` scores with :func:`_route_sigmoid` (the
    selection bias ``w["router_bias"]``, ``cfg.moe_routed_scale``, the groups
    of ``cfg.moe_n_group`` / ``cfg.moe_topk_group``) and always
    returns the dict, with ``router_counts`` [E] beside the held experts'
    ``expert_pairs`` (and ``groups_kept`` [n_group] under group-limited
    selection); ``w["shared"]`` (the shared experts' SwiGLU) is added
    for every token under the scope ``moe_shared``, whole on every share:
    summed over shares it counts once. Experts without ``w_gate`` are two
    products round :func:`_ungated_act`'s activation, routed and shared.
    With ``w["latent_down"]`` / ``w["latent_up"]`` (LatentMoE) the tokens are
    projected into the latent before the dispatch and the combined rows back
    after it, under the scope ``moe_latent``: rows move and the routed
    experts work at the latent's width, the router and the shared experts
    read ``h``; with a held share the partial sum is projected up.
    Under ``ep > 1`` dispatch routes through ``_grouped_moe_ep`` — an explicit
    padded all-to-all over the ``ep`` axis feeding per-shard grouped GEMMs (the
    ``_AllToAll`` of reference ``moe/sharded_moe.py:97``, made dropless) —
    with ``a2a_bits``/``a2a_slice`` selecting the quantized / two-hop wire
    format (``comm/quantized.py``). ``valid`` [B, T] masks padding/idle decode
    lanes out of the aux stats and combine weights.
    """
    if kernel is None:
        kernel, _ = resolve_moe_kernel(getattr(cfg, "moe_kernel", "ragged"))
    mesh = jax.sharding.get_abstract_mesh()
    if (mesh is not None and not mesh.empty and "ep" in mesh.axis_names
            and mesh.shape["ep"] > 1
            and "ep" not in set(getattr(mesh, "manual_axes", ()) or ())):
        if "latent_down" in w:
            raise NotImplementedError(
                "a latent round the routed experts (moe_latent_size) under "
                "an ep axis: the exchange is written for full-width rows")
        return _grouped_moe_ep(h, w, cfg, mesh, valid, kernel=kernel,
                               a2a_bits=a2a_bits, a2a_slice=a2a_slice)
    B, T, D = h.shape
    E = w["router"].shape[-1]
    k = cfg.top_k
    x = h.reshape(B * T, D)
    S = x.shape[0]
    n = S * k
    # the experts held here: all of them, or the share the config names
    # (what one chip of an expert-parallel host holds of the layer)
    held = getattr(cfg, "moe_experts_held", None)
    first = int(getattr(cfg, "moe_first_expert", 0) or 0) if held else 0
    n_held = int(held or E)
    # rows of the buffer of local pairs: every pair (dropless whatever the
    # router does), or moe_ep_capacity_factor times the balanced load of the
    # held experts; pairs past it are counted (``pairs_dropped``)
    factor = float(getattr(cfg, "moe_ep_capacity_factor", 0.0) or 0.0)
    bound = n if not held or factor <= 0.0 else min(
        n, int(math.ceil(n * n_held / E * factor)))
    sigmoid = getattr(cfg, "moe_scoring", "softmax") == "sigmoid"
    if sigmoid and valid is not None:
        raise NotImplementedError(
            "moe_scoring='sigmoid' routes whole training batches: no "
            "`valid` mask (the serving paths refuse the model)")
    with jax.named_scope("moe_router"):
        logits = x.astype(jnp.float32) @ w["router"].astype(jnp.float32)
        # the top k and their weights over ALL experts, as the whole model
        if sigmoid:
            (aux_loss, topk_vals, topk_idx, router_counts,
             groups_kept) = _route_sigmoid(
                logits.reshape(B, T, E), w["router_bias"], k,
                float(getattr(cfg, "moe_routed_scale", 1.0)),
                (int(getattr(cfg, "moe_n_group", 1)),
                 int(getattr(cfg, "moe_topk_group", 1))))
        else:
            _gates, aux_loss, topk_vals, topk_idx = _route(
                logits, k,
                valid=None if valid is None else valid.reshape(-1))
        order, row_weight, slot, group_sizes, n_here, counts = _placement(
            topk_idx, topk_vals, first, n_held, bound)
        rows = order[:bound]                      # the pair in each row
    if _TRACKER is not None:
        real = (jnp.ones((S,), bool) if valid is None
                else valid.reshape(-1))
        cnt = jnp.sum(jax.nn.one_hot(topk_idx.reshape(-1), E, dtype=jnp.int32)
                      * jnp.repeat(real, k)[:, None].astype(jnp.int32),
                      axis=0)
        jax.debug.callback(_emit_expert_counts, cnt)

    dt = h.dtype
    act = _ungated_act(cfg)
    xz = x.astype(dt)
    if "latent_down" in w:
        # LatentMoE: dispatch, the routed experts and combine run in the
        # latent; the router above and the shared experts below read ``h``
        with jax.named_scope("moe_latent"):
            xz = xz @ w["latent_down"].astype(dt)
    how = _moves_lowering(S, bound, w, dt, kernel, interpret)
    with jax.named_scope("moe_dispatch"):
        moves = _row_moves(rows, group_sizes, n_here, S, k, how)
    # a dispatch and a combine by the lowering they took: counted where the
    # layer is traced, not in the move (the dispatch is traced again for the
    # backward's fetch), and once more each when its backward is traced
    lowerings.count("moe_dispatch", how[0], 2)

    def fetch(x):           # [bound, D]
        with jax.named_scope("moe_dispatch"):
            return _rows_of_tokens(x, rows // k, slot, moves, how)

    # [bound, D]; rows past n_here carry no pair and are never read
    if how[0] == "pallas":
        ys = _grouped_ffn(xz, group_sizes, w, dt, kernel,
                          interpret=interpret, rows_past_groups=True,
                          fetch=fetch, buffer_rows=bound, act=act)
    else:
        ys = _grouped_ffn(fetch(xz), group_sizes, w, dt, kernel,
                          rows_past_groups=True, act=act)
    with jax.named_scope("moe_dispatch"):
        out = _weighted_sum_of_rows(ys, topk_vals, row_weight, order, slot,
                                    moves, how)
    if "latent_up" in w:
        with jax.named_scope("moe_latent"):
            out = out @ w["latent_up"].astype(dt)
    out = out.reshape(B, T, D)
    if "shared" in w:
        with jax.named_scope("moe_shared"):
            out = out + _shared_ffn(h, w["shared"], act)
    if not held and not sigmoid:
        return out, aux_loss
    # what the step record carries of a share (models/transformer.py:
    # _share_parts); the partial sum goes on to the next layer as it is
    aux = {"lb": aux_loss, "expert_pairs": counts,
           "pairs_dropped": counts.sum() - n_here}
    if sigmoid:
        aux["router_counts"] = router_counts
        if groups_kept is not None:
            aux["groups_kept"] = groups_kept
    return out, aux


# Dispatch and combine as gathers both ways. A pair's row in the buffer is a
# one-to-one map (``rows``: row -> pair, ``slot``: pair -> row), so the
# transpose of either gather is the other one, not a scatter-add (which a
# v5e runs about ten times slower than the gather over the same rows).
# One algorithm, two lowerings, by ``how = (lowering, interpret)``, the
# lowering the FFN's products took (:func:`_ffn_lowering`): ``"pallas"`` is
# the row kernels of ``ops/moe_rows.py``, which copy only the rows that carry
# a pair, each once out of a packed copy of the operand, and weight or sum
# them in the same pass (f32, j ascending, one rounding: the takes' results
# to the bit); ``"xla"`` is ``jnp.take``, which fetches every row it is
# given (the CPU, float32, int8 stacks, a decode step's few rows).

# the ``checkpoint_name`` of the dispatch's packed tokens
PACKED_TOKENS = "moe_packed_tokens"
# the kernels keep a word a buffer row in scalar memory (512 KiB here)
_MOST_ROWS = 131072


def _moves_lowering(S: int, bound: int, w: Dict[str, jax.Array], dt,
                    kernel: str, interpret: Optional[bool]
                    ) -> Tuple[str, bool]:
    """``(lowering, interpret)`` of a layer's dispatch and combine: the
    lowering its FFN's products take over the same ``bound`` rows (a TPU,
    dense bf16 stacks, whole-lane widths, a tile of rows a group), given
    whole tiles of tokens and indices that fit the kernels' scalar memory."""
    if (kernel == "ragged" and S % 8 == 0 and bound <= _MOST_ROWS
            and _ffn_lowering(bound, dt, w, dt, interpret) == "pallas"):
        return "pallas", bool(interpret)
    return "xla", False


def _row_moves(rows: jax.Array, group_sizes: jax.Array, n_here: jax.Array,
               S: int, k: int, how: Tuple[str, bool]):
    """What the kernels walk beside ``rows`` and ``slot``: the rows that
    carry a pair, and each token tile's runs of them; None for the takes."""
    if how[0] != "pallas":
        return None
    from deepspeed_tpu.ops.moe_rows import token_tile_runs

    line, runs = token_tile_runs(rows, group_sizes, S=S, k=k)
    return {"n_here": n_here, "line": line, "runs": runs}


def _fetch_rows(x: jax.Array, tok: jax.Array, slot: jax.Array, moves,
                how: Tuple[str, bool]):
    """``x[tok]``: the token each buffer row reads, [bound, D] (the kernels
    leave the tiles of rows past the last pair unwritten: no consumer reads
    them)."""
    if how[0] == "xla":
        return x[tok]
    from deepspeed_tpu.ops import moe_rows

    packed = jax.ad_checkpoint.checkpoint_name(
        moe_rows.pack_rows(x, interpret=how[1]), PACKED_TOKENS)
    return moe_rows.rows_of_tokens(packed, tok, moves["n_here"], D=x.shape[1],
                                   dtype=x.dtype, interpret=how[1])


_rows_of_tokens = jax.custom_vjp(_fetch_rows, nondiff_argnums=(4,))


def _rows_fwd(x, tok, slot, moves, how):
    return _fetch_rows(x, tok, slot, moves, how), (slot, moves)


def _rows_bwd(how, res, g):
    slot, moves = res
    lowerings.count("moe_dispatch", how[0])
    if how[0] == "xla":
        return _sum_of_rows(g, slot, None).astype(g.dtype), None, None, None
    from deepspeed_tpu.ops import moe_rows

    packed = moe_rows.pack_rows(g, moves["n_here"], interpret=how[1])
    return moe_rows.sum_of_rows(
        packed, slot, moves["line"], moves["runs"], None, D=g.shape[1],
        dtype=g.dtype, interpret=how[1]), None, None, None


_rows_of_tokens.defvjp(_rows_fwd, _rows_bwd)


def _sum_of_rows(ys, slot, weights):
    """``sum_j weights[:, j] * ys[slot[:, j]]`` in f32, [S, D]; a slot past
    the last row reads zeros."""
    acc = None
    for j in range(slot.shape[1]):
        term = jnp.take(ys, slot[:, j], axis=0, mode="fill",
                        fill_value=0).astype(jnp.float32)
        if weights is not None:
            term = term * weights[:, j, None]
        acc = term if acc is None else acc + term
    return acc


def _sum_pairs(ys: jax.Array, weights: jax.Array, row_weight: jax.Array,
               order: jax.Array, slot: jax.Array, moves,
               how: Tuple[str, bool]):
    """Each token's ``sum_j weights[t, j] * ys[slot[t, j]]``, summed in f32:
    ys [bound, D], weights [S, k] f32, ``slot`` [S, k] the row of each pair;
    ``row_weight`` [bound] (each row's pair's weight) and ``order`` [S k]
    (the pairs sorted by expert: the pair of each row first), as
    :func:`_placement` gives them, are the backward's."""
    if how[0] == "xla":
        return _sum_of_rows(ys, slot, weights).astype(ys.dtype)
    from deepspeed_tpu.ops import moe_rows

    packed = moe_rows.pack_rows(ys, moves["n_here"], interpret=how[1])
    return moe_rows.sum_of_rows(
        packed, slot, moves["line"], moves["runs"], weights, D=ys.shape[1],
        dtype=ys.dtype, interpret=how[1])


_weighted_sum_of_rows = jax.custom_vjp(_sum_pairs, nondiff_argnums=(6,))


def _wsum_fwd(ys, weights, row_weight, order, slot, moves, how):
    return _sum_pairs(ys, weights, row_weight, order, slot, moves, how), \
        (ys, row_weight, order, slot, moves)


def _wsum_bwd(how, res, g):
    ys, row_weight, order, slot, moves = res
    k = slot.shape[1]
    tok = order[:ys.shape[0]] // k                # the token of each row
    lowerings.count("moe_dispatch", how[0])
    # a row's cotangent: its token's, times its pair's weight (a row that
    # carries no pair gets one all the same: ``_grouped_ffn`` cuts it off, by
    # its mask or by kernels that work only the rows a group holds); a
    # weight's gradient: the dot ``<g[token], ys[row]>`` of its pair's row,
    # brought from row order to pair order by a sort (:func:`_pairs_of_rows`)
    if how[0] == "xla":
        gf = g[tok].astype(jnp.float32)
        dys = (gf * row_weight[:, None]).astype(ys.dtype)
        dot = (gf * ys.astype(jnp.float32)).sum(axis=-1)
    else:
        from deepspeed_tpu.ops import moe_rows

        # the same pass gives each row's dot
        dys, dot = moe_rows.rows_of_tokens(
            moe_rows.pack_rows(g, interpret=how[1]), tok, moves["n_here"],
            D=g.shape[1], dtype=ys.dtype, weight=row_weight, ys=ys,
            interpret=how[1])
    dw = _pairs_of_rows(dot, order, slot).astype(row_weight.dtype)
    return dys, dw, None, None, None, None


_weighted_sum_of_rows.defvjp(_wsum_fwd, _wsum_bwd)


def _grouped_moe_ep(h: jax.Array, w: Dict[str, jax.Array], cfg: Any,
                    mesh, valid: Optional[jax.Array] = None,
                    kernel: str = "ragged", a2a_bits: Optional[int] = None,
                    a2a_slice: Optional[int] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel dropless dispatch: tokens resharded over ``ep``, routed
    through a capacity-padded ``all_to_all`` to the shard owning each expert,
    run through the local grouped GEMM, and returned by the mirror a2a.

    This is the explicit-collective form of reference
    ``moe/sharded_moe.py:97`` (``_AllToAll`` over the EP process group) with
    MegaBlocks-style grouped compute instead of the capacity einsum — every
    routed (token, expert) pair is computed exactly, so an imported Mixtral
    keeps its released routing function under ``ep > 1``.

    Shapes are static: the a2a moves ``[ep, cap, D]`` activations plus an
    ``[ep, cap]`` int32 slot-id exchange per shard (ids ride their own
    dense a2a so wire quantization can never corrupt routing), with ``cap
    = S_local * top_k`` by default (worst-case dropless — total payload
    equals the single-shard dispatch size). ``cfg.moe_ep_capacity_factor
    > 0`` shrinks ``cap`` toward the balanced-load size ``S_local*k/ep``
    at the cost of dropping overflow pairs under extreme imbalance
    (documented trade, like the reference's ``capacity_factor``). Token
    count is padded up to a multiple of ``ep`` (pad rows route with zero
    combine weight and are masked out of the aux stats), so B=1
    single-request decode works on any ep mesh.

    The wire format follows ``comm/quantized.py``: ``a2a_bits`` (default
    ``cfg.moe_a2a_bits``, 0 = dense bf16) quantizes the activation
    payload blockwise; ``a2a_slice`` (default ``cfg.moe_a2a_slice``)
    selects the hierarchical two-hop a2a — int8 across DCN, bf16 inside
    a slice — and everything flows through the comm byte accounting
    (``comm_drill --scenario moe-a2a`` asserts the analytic payload).

    Placement tables (``moe/balancer.py`` AutoEP): when ``w`` carries
    ``place_dest``/``place_slot``/``place_nrep`` leaves, the expert
    stacks are in PHYSICAL slot order (hot experts replicated, cold ones
    re-placed) and each routed pair picks a replica deterministically —
    outputs are bit-identical to the natural layout because replicas are
    exact weight copies and no pair is ever dropped by placement.
    Without tables the natural layout applies (expert ``e`` lives on
    shard ``e // e_local``), which requires ``E % ep == 0``.
    """
    from deepspeed_tpu.comm import quantized as cq

    B, T, D = h.shape
    E = w["router"].shape[-1]
    ep = mesh.shape["ep"]
    k = cfg.top_k
    has_place = all(n in w for n in PLACEMENT_LEAVES)
    if not has_place and E % ep:
        raise ValueError(f"num_experts ({E}) must divide by ep ({ep}) "
                         "without placement tables")
    e_local = E // ep if not has_place else 0
    bits = int(a2a_bits if a2a_bits is not None
               else getattr(cfg, "moe_a2a_bits", 0) or 0)
    hop = int(a2a_slice if a2a_slice is not None
              else getattr(cfg, "moe_a2a_slice", 0) or 0)
    block = int(getattr(cfg, "moe_a2a_block", 512) or 512)
    S = B * T
    s_local = -(-S // ep)          # ceil: pad rows are masked below
    s_pad = s_local * ep
    factor = float(getattr(cfg, "moe_ep_capacity_factor", 0.0) or 0.0)
    if factor > 0.0:
        cap = min(s_local * k, int(math.ceil(s_local * k / ep * factor)))
    else:
        cap = s_local * k
    dt = h.dtype

    def shard(x, vrow, router, wl):
        my = jax.lax.axis_index("ep")
        # row mask: caller's valid lanes minus the up-to-ep padding rows
        real = ((my * s_local + jnp.arange(s_local)) < S) & vrow  # [S_l]
        logits = x.astype(jnp.float32) @ router
        _gates, aux, topk_vals, topk_idx = _route(logits, k, valid=real,
                                                  psum_axis="ep")

        n = s_local * k
        flat_e = topk_idx.reshape(-1)                          # [n]
        real_pairs = jnp.repeat(real, k)                       # [n]
        if has_place:
            # replica choice spreads a hot expert's pairs round-robin over
            # its copies; dest/slot come from the balancer's tables
            rep = ((my * n + jnp.arange(n))
                   % wl["place_nrep"][flat_e])
            dest = wl["place_dest"][flat_e, rep]               # owning shard
            lslot = wl["place_slot"][flat_e, rep]              # its local slot
        else:
            dest = flat_e // e_local
            lslot = flat_e % e_local
        # this shard's routed-token counts leave the region as an output:
        # jax 0.9's debug-callback lowering fails inside a region that
        # leaves more than one mesh axis auto, so the callback sits outside
        cnt = jnp.sum(jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
                      * real_pairs[:, None].astype(jnp.int32), axis=0)
        oh = jax.nn.one_hot(dest, ep, dtype=jnp.int32) \
            * real_pairs[:, None].astype(jnp.int32)
        slot = jnp.sum((jnp.cumsum(oh, axis=0) - oh) * oh, axis=1)  # per-dest pos
        # invalid rows never occupy an a2a slot (they would otherwise evict
        # real pairs under a finite moe_ep_capacity_factor)
        slot = jnp.where(real_pairs, slot, cap)
        tok = jnp.arange(n) // k
        send_x = jnp.zeros((ep, cap, D), dt).at[dest, slot].set(
            x[tok].astype(dt), mode="drop")
        # slot id + 1 (0 = empty a2a slot) rides its own exact int32 a2a
        send_id = jnp.zeros((ep, cap), jnp.int32).at[dest, slot].set(
            lslot.astype(jnp.int32) + 1, mode="drop")
        recv_x = cq.moe_all_to_all(send_x, "ep", bits=bits,
                                   block_size=block, slice_size=hop)
        recv_id = cq.moe_all_to_all(send_id, "ep", bits=0, slice_size=hop)

        stack = next(v for name, v in wl.items()
                     if name not in PLACEMENT_LEAVES)
        slots = stack.shape[0]                                 # local experts
        re = recv_id.reshape(ep * cap) - 1
        ok = re >= 0
        local_e = jnp.where(ok, re, 0)
        rx = jnp.where(ok[:, None], recv_x.reshape(ep * cap, D), 0)
        order = jnp.argsort(local_e)
        xs = rx[order]
        group_sizes = jnp.bincount(local_e, length=slots).astype(jnp.int32)
        ys = _grouped_ffn(xs, group_sizes, wl, dt, kernel)     # [ep*cap, D]
        y_back = cq.moe_all_to_all(
            jnp.zeros_like(ys).at[order].set(ys).reshape(ep, cap, D),
            "ep", bits=bits, block_size=block, slice_size=hop)

        keep = (slot < cap).astype(dt)                         # 1 unless factor drops
        wgt = topk_vals.reshape(-1).astype(dt) * keep          # invalid rows: 0
        y_pair = y_back[dest, jnp.minimum(slot, cap - 1)]      # [n, D]
        out = jnp.zeros((s_local, D), dt).at[tok].add(y_pair * wgt[:, None])
        return out, aux, cnt

    ew = P("ep", None, None)
    experts = {n: v for n, v in w.items() if n != "router"}
    # placement tables enter replicated — every shard routes with the same
    # global view; only the expert stacks are ep-sharded
    especs = {n: (P(*([None] * v.ndim)) if n in PLACEMENT_LEAVES else ew)
              for n, v in experts.items()}
    x2 = h.reshape(S, D)
    v2 = (jnp.ones((S,), bool) if valid is None else valid.reshape(S))
    if s_pad != S:
        # jnp.pad lowers to a collective-free layout into the ep region
        x2 = jnp.pad(x2, ((0, s_pad - S), (0, 0)))
        v2 = jnp.pad(v2, (0, s_pad - S))
    # router enters replicated-over-ep in fp32: its cotangent is a psum over
    # ep, and a *bf16* replicated-in grad trips an XLA:CPU check failure in
    # AllReducePromotion (all-reduce with copy reduction); fp32 sidesteps it
    # and is what _route computes in anyway.
    out2, aux, cnt = jax.shard_map(
        shard, mesh=mesh,
        in_specs=(P("ep", None), P("ep"), P(None, None), especs),
        out_specs=(P("ep", None), P(), P("ep")), axis_names={"ep"},
        check_vma=False)(x2, v2, w["router"].astype(jnp.float32), experts)
    if _TRACKER is not None:
        jax.debug.callback(_emit_expert_counts,
                           cnt.reshape(ep, E).sum(axis=0))
    if s_pad != S:
        # the sliced-off-pad result has no expressible ep sharding — pin it
        # replicated (pad only occurs at decode-sized S, where this is cheap)
        out2 = constrain(out2[:S], P(None, None))
    else:
        out2 = out2[:S]
    return out2.reshape(B, T, D), aux


def moe_block_for(cfg: Any):
    """Select the dispatch algebra from ``cfg.moe_dispatch``."""
    dispatch = getattr(cfg, "moe_dispatch", "capacity")
    if dispatch == "grouped":
        return grouped_moe_mlp_block
    if dispatch != "capacity":
        raise ValueError(f"unknown moe_dispatch '{dispatch}' "
                         "(have: capacity, grouped)")
    return moe_mlp_block


class MoE:
    """Layer-shaped parity wrapper (``deepspeed.moe.layer.MoE`` layer.py:17)."""

    def __init__(self, hidden_size: int, num_experts: int = 1, k: int = 2,
                 capacity_factor: float = 1.25, eval_capacity_factor: float = 2.0,
                 min_capacity: int = 4, drop_tokens: bool = True,
                 noisy_gate_policy: Optional[str] = None, **_):
        self.hidden_size = hidden_size
        self.num_experts = num_experts
        self.k = k
        self.capacity_factor = capacity_factor
        self.min_capacity = min_capacity

    def __call__(self, h: jax.Array, w: Dict[str, jax.Array]) -> Tuple[jax.Array, jax.Array]:
        class _Cfg:
            top_k = self.k
            capacity_factor = self.capacity_factor
            min_capacity = self.min_capacity

        return moe_mlp_block(h, w, _Cfg())
