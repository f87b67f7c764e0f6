"""Time the expert layer's row kernels alone on the chip, at the Mellum2 cell's
shape (S 16,384 tokens, k 8 of 64 experts, 16 held, a buffer of 65,536 rows of
which about 32,768 carry a pair, D 2304), beside the ``jnp.take`` lowering of
the same moves (PERF.md section 6, PR 36).

    chiprun -- python tools/moe_rows_bench.py [--calls 20] [--seed 0]

Prints one JSON line per (move, implementation): milliseconds a call over
``--calls`` calls dispatched back to back, nanoseconds a live row, and whether
the kernel's result equals the takes' to the bit on the rows that carry a pair.
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.moe import sharded_moe as sm
from deepspeed_tpu.ops import moe_rows as mr

S, K, E, HELD, D = 16384, 8, 64, 16, 2304
BOUND = S * K * HELD // E * 2


def expert_of(seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.argsort(rng.random((S, E)), axis=1)[:, :K].reshape(-1)


def routing(seed: int):
    """``order`` (its first ``BOUND`` are ``rows``), ``slot``, ``n_here`` as
    ``grouped_moe_mlp_block`` makes them, from uniform random top-k
    choices."""
    expert = expert_of(seed)
    key = np.where(expert < HELD, expert, HELD)
    order = np.argsort(key, kind="stable")
    n_here = min(int((key < HELD).sum()), BOUND)
    rank = np.empty(S * K, np.int64)
    rank[order] = np.arange(S * K)
    slot = np.where(rank < n_here, rank, BOUND).reshape(S, K)
    return (jnp.asarray(order, jnp.int32), jnp.asarray(slot, jnp.int32),
            n_here)


def timed(fn, args, calls):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="256 tokens, the kernels interpreted: the CPU")
    ap.add_argument("--skip", nargs="*", default=[], choices=["take"],
                    help="leave out the takes")
    a = ap.parse_args()
    if a.rehearse:
        import functools
        global S, BOUND
        S, BOUND = 256, 256 * K * HELD // E * 2
        for name in ("pack_rows", "rows_of_tokens", "sum_of_rows"):
            setattr(mr, name, functools.partial(getattr(mr, name),
                                                interpret=True))
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform}))
    order, slot, n_here = routing(a.seed)
    rows = order[:BOUND]
    tok = rows // K
    n = jnp.int32(n_here)
    live = (jnp.arange(BOUND) < n_here)[:, None]
    ks = jax.random.split(jax.random.PRNGKey(a.seed), 4)
    x = jax.random.normal(ks[0], (S, D), jnp.bfloat16)
    ys = jnp.where(live, jax.random.normal(ks[1], (BOUND, D), jnp.bfloat16),
                   jnp.nan)
    weights = jax.random.uniform(ks[2], (S, K), jnp.float32)
    wrow = weights.reshape(-1)[rows]

    def say(move, impl, fn, args, ref=None, rows_only=False,
            fetched=n_here):
        """Time ``fn``; with ``ref`` say whether the result equals it (with
        ``rows_only``, on the buffer rows that carry a pair)."""
        if impl == "take" and "take" in a.skip:
            return None
        ms, out = timed(jax.jit(fn), args, a.calls)
        same = None
        if ref is not None:
            keep = live if rows_only else True
            same = bool(jnp.array_equal(jnp.where(keep, out, 0),
                                        jnp.where(keep, ref, 0)))
        print(json.dumps({"move": move, "impl": impl, "ms": round(ms, 4),
                          "ns_a_live_row": round(ms * 1e6 / fetched, 1),
                          "equal": same}), flush=True)
        return out

    say("pack x [16384]", "pallas", mr.pack_rows, (x,), fetched=S)
    say("pack ys [65536], live tiles", "pallas", mr.pack_rows, (ys, n))
    xp, ysp = mr.pack_rows(x), mr.pack_rows(ys, n)
    sizes = jnp.asarray(np.bincount(
        expert_of(a.seed)[np.asarray(rows[:n_here])], minlength=HELD),
        jnp.int32)
    line, runs = say("runs of a token tile", "xla", lambda r, g:
                     mr.token_tile_runs(r, g, S=S, k=K), (rows, sizes),
                     fetched=BOUND)

    ref = say("dispatch", "take", lambda x, t: x[t], (x, tok))
    say("dispatch", "pallas", lambda xp, t, n: mr.rows_of_tokens(
        xp, t, n, D=D), (xp, tok, n), ref, True)
    say("dispatch", "pallas+pack", lambda x, t, n: mr.rows_of_tokens(
        mr.pack_rows(x), t, n, D=D), (x, tok, n), ref, True)

    for name, wts in (("combine", weights), ("dispatch bwd", None)):
        ref = say(name, "take", lambda y, s, w: sm._sum_of_rows(
            y, s, w).astype(jnp.bfloat16), (ys, slot, wts))
        say(name, "pallas", lambda yp, s, w: mr.sum_of_rows(
            yp, s, line, runs, w, D=D), (ysp, slot, wts), ref)
        say(name, "pallas+pack", lambda y, s, w, n: mr.sum_of_rows(
            mr.pack_rows(y, n), s, line, runs, w, D=D), (ys, slot, wts, n),
            ref)

    def bwd_take(g, ys, weights, rows, slot):
        dys = (g[rows // K].astype(jnp.float32)
               * weights.reshape(-1)[rows][:, None]).astype(ys.dtype)
        gf = g.astype(jnp.float32)
        dw = jnp.stack([
            (gf * jnp.take(ys, slot[:, j], axis=0, mode="fill", fill_value=0)
             .astype(jnp.float32)).sum(axis=-1) for j in range(K)], axis=1)
        return dys, dw

    def bwd_rows(g, ys, wrow, order, slot, n):
        dys, dot = mr.rows_of_tokens(
            mr.pack_rows(g), order[:BOUND] // K, n, D=D, weight=wrow, ys=ys)
        return dys, sm._pairs_of_rows(dot, order, slot)

    ref = say("combine bwd", "take", bwd_take, (x, ys, weights, rows, slot))
    got = say("combine bwd", "pallas+pack+sort", bwd_rows,
              (x, ys, wrow, order, slot, n))
    if ref is not None:
        same = bool(jnp.array_equal(jnp.where(live, got[0], 0),
                                    jnp.where(live, ref[0], 0)))
        err = float(jnp.abs(got[1] - ref[1]).max() / jnp.abs(ref[1]).max())
        print(json.dumps({"move": "combine bwd", "dys_equal": same,
                          "dw_rel_err": err}))
    say("combine bwd, rows only", "pallas", lambda gp, t, n, w, y:
        mr.rows_of_tokens(gp, t, n, D=D, weight=w, ys=y),
        (xp, tok, n, wrow, ys))
    # the two scalars a row: the gathers the program made until PR 48, and
    # the sort that brings the dots to pair order now (a row's weight rides
    # the router's sort: ``sharded_moe._placement``)
    dot = jnp.where(live[:, 0], jax.random.normal(ks[3], (BOUND,)), jnp.nan)
    ref = say("combine bwd, dw = dot[slot]", "take", lambda d, s: jnp.take(
        d, s, mode="fill", fill_value=0), (dot, slot), fetched=S * K)
    say("combine bwd, dw = dot[slot]", "sort", sm._pairs_of_rows,
        (dot, order, slot), ref, fetched=S * K)
    say("combine bwd, w[rows]", "take", lambda w, r: w.reshape(-1)[r],
        (weights, rows), fetched=BOUND)


if __name__ == "__main__":
    main()
