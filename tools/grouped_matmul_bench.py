"""Time the grouped product's three kernels alone on the chip, at the Mellum2
cell's shapes, beside ``lax.ragged_dot`` and a dense einsum over equal groups
(the yardsticks of PERF.md section 6, PR 34; jax's own megablox ``gmm`` raises
an AttributeError from ``custom_api_util`` under jax 0.9.0 and is left out).

    chiprun -- python tools/grouped_matmul_bench.py [--tm 256 512] [--calls 20]

Prints one JSON line per (rows given, layout, product, implementation):
milliseconds a call over ``--calls`` calls dispatched back to back, and the
largest difference from ``ragged_dot`` on the rows the groups hold.
"""

import argparse
import functools
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops import grouped_matmul as gm

E, HELD = 16, 32768


def sizes(layout: str, seed: int) -> np.ndarray:
    if layout == "equal":
        return np.full((E,), HELD // E, np.int32)
    rng = np.random.default_rng(seed)
    got = rng.multinomial(HELD, rng.dirichlet(np.full((E,), 40.0)))
    return got.astype(np.int32)


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
    ap.add_argument("--tm", type=int, nargs="*", default=[0])
    ap.add_argument("--rows", type=int, nargs="*", default=[65536, 32768])
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform}))
    key = jax.random.PRNGKey(a.seed)
    for rows in a.rows:
        for K, N in ((2304, 896), (896, 2304)):
            k1, k2, k3 = jax.random.split(jax.random.fold_in(key, K), 3)
            xs = jax.random.normal(k1, (rows, K), jnp.bfloat16)
            dys = jax.random.normal(k2, (rows, N), jnp.bfloat16)
            w = (jax.random.normal(k3, (E, K, N), jnp.float32)
                 * 0.02).astype(jnp.bfloat16)
            for layout in ("uneven", "equal"):
                g = jnp.asarray(sizes(layout, a.seed))
                live = (jnp.arange(rows) < HELD)[:, None]

                def say(product, impl, fn, args, ref=None, rows_only=True):
                    try:
                        ms, out = timed(jax.jit(fn), args, a.calls)
                    except Exception as e:          # a yardstick may not run
                        print(json.dumps({"product": product, "impl": impl,
                                          "error": repr(e)[:300],
                                          "at": traceback.format_exc()[-200:]}))
                        return None
                    err = None
                    if ref is not None:
                        d = jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))
                        err = float(jnp.max(jnp.where(live, d, 0))
                                    if rows_only else jnp.max(d))
                    print(json.dumps({
                        "rows": rows, "K": K, "N": N, "layout": layout,
                        "product": product, "impl": impl,
                        "ms": round(ms, 4), "max_err": err}), flush=True)
                    return out

                ref_f = say("fwd", "ragged_dot", jax.lax.ragged_dot,
                            (xs, w, g))
                ref_x = say("dxs", "ragged_dot", lambda d, w, g: jax.vjp(
                    lambda x: jax.lax.ragged_dot(x, w, g), xs)[1](d)[0],
                    (dys, w, g))
                ref_w = say("dw", "ragged_dot", lambda x, d, g: jax.vjp(
                    lambda w_: jax.lax.ragged_dot(x, w_, g), w)[1](d)[0],
                    (xs, dys, g))
                for tm in a.tm:
                    kw = {"tm": tm or None}
                    tag = f"pallas_tm{tm or 'auto'}"
                    say("fwd", tag, functools.partial(gm.gmm, **kw),
                        (xs, w, g), ref_f)
                    say("dxs", tag, functools.partial(
                        gm.gmm, transpose_w=True, **kw), (dys, w, g), ref_x)
                    say("dw", tag, functools.partial(gm.tgmm, **kw),
                        (xs, dys, g), ref_w, rows_only=False)
                # the rows' cotangent over two stacks (gate and up): one call
                # that sums in the kernel, against two calls and an add
                w2, dys2 = w[::-1], dys[::-1]
                say("dxs-of-two", "pallas_one_call", lambda d, d2, w, w2, g:
                    gm.gmm((d, d2), (w, w2), g, transpose_w=True),
                    (dys, dys2, w, w2, g))
                say("dxs-of-two", "pallas_two_and_add", lambda d, d2, w, w2, g:
                    gm.gmm(d, w, g, transpose_w=True)
                    + gm.gmm(d2, w2, g, transpose_w=True),
                    (dys, dys2, w, w2, g))
                if layout == "equal" and rows == HELD:
                    say("fwd", "dense_einsum", lambda x, w: jnp.einsum(
                        "gmk,gkn->gmn", x.reshape(E, -1, K), w
                    ).reshape(rows, N), (xs, w), ref_f)


if __name__ == "__main__":
    main()
