"""Fused GELU MLP input half as a Pallas TPU kernel: gelu(x @ w1) in one
VMEM-resident pass (mirrors ``swiglu.py`` minus the gate branch).

gpt-paper and seamless use ``act="gelu"``; before this kernel their MLPs
warn-fell-back to the jnp path under ``kernels=True``.  Uses the tanh
approximation (matching ``jax.nn.gelu(approximate=True)``, the reference
path in ``models/layers.py``).  Differentiable via ``custom_vjp``: the
forward saves only (x, w1); the backward recomputes the matmul in fp32 —
the pre-activation is never a residual.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import fit_block

DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_F = 512

_SQRT_2_OVER_PI = 0.7978845608028654
_GELU_C = 0.044715


def _gelu_f32(a):
    u = _SQRT_2_OVER_PI * (a + _GELU_C * a * a * a)
    return 0.5 * a * (1.0 + jnp.tanh(u))


def _gelu_mlp_kernel(x_ref, w1_ref, o_ref):
    # storage-dtype operands, fp32 accumulation (see swiglu.py)
    a = jax.lax.dot_general(x_ref[...], w1_ref[...], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[...] = _gelu_f32(a).astype(o_ref.dtype)


def gelu_mlp_fwd_pallas(x2d: jax.Array, w1: jax.Array, *,
                        block_n: int, block_f: int,
                        interpret: bool) -> jax.Array:
    N, d = x2d.shape
    F = w1.shape[1]
    bn, bf = fit_block(block_n, N, 8), fit_block(block_f, F, 128)
    return pl.pallas_call(
        _gelu_mlp_kernel,
        grid=(N // bn, F // bf),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bf), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn, bf), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N, F), x2d.dtype),
        name="gelu_mlp_in",
        interpret=interpret,
    )(x2d, w1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _gelu_mlp(x2d, w1, block_n, block_f, interpret):
    return gelu_mlp_fwd_pallas(x2d, w1, block_n=block_n, block_f=block_f,
                               interpret=interpret)


def _gelu_mlp_fwd(x2d, w1, block_n, block_f, interpret):
    return _gelu_mlp(x2d, w1, block_n, block_f, interpret), (x2d, w1)


def _gelu_mlp_bwd(block_n, block_f, interpret, res, g):
    x, w1 = res
    x32 = x.astype(jnp.float32)
    w1_32 = w1.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    a = x32 @ w1_32
    u = _SQRT_2_OVER_PI * (a + _GELU_C * a * a * a)
    t = jnp.tanh(u)
    # d gelu(a)/da = 0.5 (1 + t) + 0.5 a (1 - t^2) * du/da
    du = _SQRT_2_OVER_PI * (1.0 + 3.0 * _GELU_C * a * a)
    da = g32 * (0.5 * (1.0 + t) + 0.5 * a * (1.0 - t * t) * du)
    dx = da @ w1_32.T
    dw1 = x32.T @ da
    return dx.astype(x.dtype), dw1.astype(w1.dtype)


_gelu_mlp.defvjp(_gelu_mlp_fwd, _gelu_mlp_bwd)


def gelu_mlp_in(x2d: jax.Array, w1: jax.Array, *,
                block_n: int = DEFAULT_BLOCK_N,
                block_f: int = DEFAULT_BLOCK_F,
                interpret: bool = False) -> jax.Array:
    """x2d: (N, d); w1: (d, F) -> gelu(x2d @ w1) (N, F).  Differentiable."""
    return _gelu_mlp(x2d, w1, block_n, block_f, interpret)

