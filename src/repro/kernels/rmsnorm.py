"""Fused RMSNorm as a Pallas TPU kernel.

The dry-run traffic analysis (EXPERIMENTS.md §Roofline) shows f32
normalization chains crossing fusion boundaries are a top HBM-traffic
contributor; fusing square/mean/rsqrt/scale into one VMEM pass removes
them.  Rows are blocked (rows x d) with d fully VMEM-resident; backward is
composed in jnp from the saved inverse-rms (cheap relative to matmuls).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import fit_block

DEFAULT_BLOCK_ROWS = 256


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    o_ref[...] = (x * inv * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_fwd_pallas(x2d: jax.Array, w: jax.Array, *, eps: float,
                       block_rows: int, interpret: bool) -> jax.Array:
    n, d = x2d.shape
    block_rows = min(block_rows, n)
    assert n % block_rows == 0, (n, block_rows)
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x2d.dtype),
        name="rmsnorm",
        interpret=interpret,
    )(x2d, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def rmsnorm(x, w, eps=1e-5, block_rows=DEFAULT_BLOCK_ROWS, interpret=False):
    """x: (..., d); w: (d,)."""
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    out = rmsnorm_fwd_pallas(x2d, w, eps=eps, block_rows=fit_block(block_rows, x2d.shape[0], 8),
                             interpret=interpret)
    return out.reshape(shape)


def _fwd(x, w, eps, block_rows, interpret):
    return rmsnorm(x, w, eps, block_rows, interpret), (x, w)


def _bwd(eps, block_rows, interpret, res, g):
    x, w = res
    x32 = x.astype(jnp.float32).reshape(-1, x.shape[-1])
    g32 = g.astype(jnp.float32).reshape(-1, x.shape[-1])
    w32 = w.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = x32 * inv
    gw = g32 * w32
    d = x.shape[-1]
    dx = inv * (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    dw = jnp.sum(g32 * xhat, axis=0)
    return dx.reshape(x.shape).astype(x.dtype), dw.astype(w.dtype)


rmsnorm.defvjp(_fwd, _bwd)
