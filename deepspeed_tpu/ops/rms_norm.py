"""Fused RMS norm Pallas kernel.

Parity target: ``csrc/transformer/inference/csrc/rms_norm.cu`` (fused RMS/pre-RMS) and
``normalize_kernels.cu``. One VMEM pass per row block; fp32 statistics; custom VJP with
the closed-form backward (XLA fuses the backward fine — the kernel matters on the
forward inference path).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.accelerator.real_accelerator import on_tpu


def _rms_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    o_ref[:] = (x * inv * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _rms_pallas(x2d: jax.Array, w: jax.Array, eps: float, block_rows: int,
                interpret: bool) -> jax.Array:
    n, d = x2d.shape
    grid = (n // block_rows,)
    return pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x2d.dtype),
        interpret=interpret,
    )(x2d, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms(x2d, w, eps):
    interpret = not on_tpu()
    block = 256
    n = x2d.shape[0]
    while n % block != 0:
        block //= 2
    return _rms_pallas(x2d, w, eps, max(block, 1), interpret)


def _rms_fwd(x2d, w, eps):
    out = _rms(x2d, w, eps)
    return out, (x2d, w)


def _rms_bwd(eps, res, g):
    x, w = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True) + eps
    inv = jax.lax.rsqrt(ms)
    xhat = xf * inv
    dxhat = gf * wf
    # d/dx of x * rsqrt(mean(x^2)+eps)
    dx = inv * (dxhat - xhat * jnp.mean(dxhat * xhat, axis=-1, keepdims=True))
    dw = jnp.sum(gf * xhat, axis=0)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_rms.defvjp(_rms_fwd, _rms_bwd)


def fused_rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMS-normalize the last dim of ``x`` (any leading shape) scaled by ``weight``."""
    shape = x.shape
    out = _rms(x.reshape(-1, shape[-1]), weight, eps)
    return out.reshape(shape)
