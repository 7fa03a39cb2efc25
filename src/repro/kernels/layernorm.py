"""Fused LayerNorm as a Pallas TPU kernel (mirrors ``rmsnorm.py``).

gpt-paper and seamless configs use ``norm="layernorm"``; before this kernel
they warn-fell-back to the jnp path under ``kernels=True``.  Same structure
as the rmsnorm kernel: rows blocked (rows x d) with d fully VMEM-resident,
mean/var/rsqrt/scale/shift fused into one pass; backward composed in jnp
from the saved (x, w) — cheap relative to matmuls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import fit_block

DEFAULT_BLOCK_ROWS = 256


def _layernorm_kernel(x_ref, w_ref, b_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    out = (x - mean) * inv * w_ref[...].astype(jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    o_ref[...] = out.astype(o_ref.dtype)


def layernorm_fwd_pallas(x2d: jax.Array, w: jax.Array, b: jax.Array, *,
                         eps: float, block_rows: int,
                         interpret: bool) -> jax.Array:
    n, d = x2d.shape
    block_rows = min(block_rows, n)
    assert n % block_rows == 0, (n, block_rows)
    return pl.pallas_call(
        functools.partial(_layernorm_kernel, eps=eps),
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x2d.dtype),
        name="layernorm",
        interpret=interpret,
    )(x2d, w, b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def layernorm(x, w, b, eps=1e-5, block_rows=DEFAULT_BLOCK_ROWS,
              interpret=False):
    """x: (..., d); w/b: (d,)."""
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    out = layernorm_fwd_pallas(x2d, w, b, eps=eps,
                               block_rows=fit_block(block_rows, x2d.shape[0], 8),
                               interpret=interpret)
    return out.reshape(shape)


def _fwd(x, w, b, eps, block_rows, interpret):
    return layernorm(x, w, b, eps, block_rows, interpret), (x, w, b)


def _bwd(eps, block_rows, interpret, res, g):
    x, w, b = res
    d = x.shape[-1]
    x32 = x.astype(jnp.float32).reshape(-1, d)
    g32 = g.astype(jnp.float32).reshape(-1, d)
    w32 = w.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = (x32 - mean) * inv
    gw = g32 * w32
    dx = inv * (gw - jnp.mean(gw, axis=-1, keepdims=True)
                - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    dw = jnp.sum(g32 * xhat, axis=0)
    db = jnp.sum(g32, axis=0)
    return (dx.reshape(x.shape).astype(x.dtype), dw.astype(w.dtype),
            db.astype(b.dtype))


layernorm.defvjp(_fwd, _bwd)
