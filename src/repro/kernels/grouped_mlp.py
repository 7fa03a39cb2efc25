"""Fused grouped expert MLP as a Pallas kernel: each block of an expert's
slots runs w1/[w3]/w2 over the expert-major (E, N, d) layout (N = groups *
capacity slots), streaming d_ff blocks of the weights past an fp32 VMEM
accumulator so the hidden activation never reaches HBM, with the slot validity
mask applied in-kernel — padded capacity slots contribute exactly zero to
the output and to every weight gradient, matching the reference semantics
where they cost no FLOPs.

Two activation flavours cover the MoE model zoo: ``swiglu``
(silu(x@w1) * (x@w3), llama4-maverick) and ``gelu`` (arctic-style
gelu(x@w1), tanh approximation).  Differentiable via ``custom_vjp``: the
forward saves only (x, weights, mask) and the backward recomputes the
gate matmuls in fp32 — same residual discipline as ``kernels/swiglu.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import fit_block

DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_F = 256
VMEM_LIMIT = 64 * 1024 * 1024


def _accumulate(h, w2_ref, m, o_ref, acc_ref):
    """acc += h @ w2 over the d_ff blocks; the last block writes the masked
    output.  The activation is elementwise in d_ff, so splitting d_ff into
    blocks and summing their down-projections is exact."""
    f = pl.program_id(2)

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        h.astype(w2_ref.dtype), w2_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(f == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] * m).astype(o_ref.dtype)


def _up(x, w_ref):
    return jax.lax.dot_general(x, w_ref[0], (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _swiglu_kernel(x_ref, w1_ref, w3_ref, w2_ref, m_ref, o_ref, acc_ref):
    m = m_ref[0]                                     # (bn, 1)
    x = x_ref[0] * m.astype(x_ref.dtype)
    a = _up(x, w1_ref)
    h = a * jax.nn.sigmoid(a) * _up(x, w3_ref)
    _accumulate(h, w2_ref, m, o_ref, acc_ref)


def _gelu_kernel(x_ref, w1_ref, w2_ref, m_ref, o_ref, acc_ref):
    m = m_ref[0]
    x = x_ref[0] * m.astype(x_ref.dtype)
    h = jax.nn.gelu(_up(x, w1_ref), approximate=True)
    _accumulate(h, w2_ref, m, o_ref, acc_ref)


def _fwd_pallas(x, w1, w3, w2, mask, *, block_n: int, interpret: bool):
    # grid (E, N blocks, d_ff blocks), d_ff minor-most: the (bn, d) fp32
    # accumulator stays in VMEM while (d, bf)/(bf, d) weight blocks stream —
    # whole (d, F) expert weights would overrun VMEM at arctic widths
    E, N, d = x.shape
    F = w1.shape[-1]
    bn = fit_block(block_n, N, 8)
    bf = fit_block(DEFAULT_BLOCK_F, F, 128)
    xm_spec = [pl.BlockSpec((1, bn, d), lambda e, i, f: (e, i, 0))]
    w_in = pl.BlockSpec((1, d, bf), lambda e, i, f: (e, 0, f))
    w_out = pl.BlockSpec((1, bf, d), lambda e, i, f: (e, f, 0))
    # the slot mask rides as an (E, N, 1) column: an (E, N) operand would
    # need a (1, bn) block, which breaks the (8, 128) tiling
    m_spec = pl.BlockSpec((1, bn, 1), lambda e, i, f: (e, i, 0))
    mask3 = mask.astype(jnp.float32)[..., None]
    if w3 is not None:
        kernel, in_specs, args = (_swiglu_kernel,
                                  xm_spec + [w_in, w_in, w_out, m_spec],
                                  (x, w1, w3, w2, mask3))
    else:
        kernel, in_specs, args = (_gelu_kernel,
                                  xm_spec + [w_in, w_out, m_spec],
                                  (x, w1, w2, mask3))
    return pl.pallas_call(
        kernel,
        grid=(E, N // bn, F // bf),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bn, d), lambda e, i, f: (e, i, 0)),
        out_shape=jax.ShapeDtypeStruct((E, N, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bn, d), jnp.float32)],
        # at d_model ~7k the double-buffered x/weight/output blocks plus the
        # accumulator need ~40 MiB: above the 16 MiB default scoped limit,
        # well inside a v5e core's 128 MiB of VMEM
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        name="grouped_mlp",
        interpret=interpret,
    )(*args)


def _act_and_grads(x32, w1_32, w3_32, act: str):
    """fp32 recompute of the hidden activation h and its vjp pieces."""
    a = jnp.einsum("end,edf->enf", x32, w1_32)
    if act == "swiglu":
        b = jnp.einsum("end,edf->enf", x32, w3_32)
        sig = jax.nn.sigmoid(a)
        h = a * sig * b

        def bwd(dh):
            da = dh * b * (sig * (1.0 + a * (1.0 - sig)))
            db = dh * a * sig
            return da, db
        return h, bwd
    h = jax.nn.gelu(a, approximate=True)
    _, vjp = jax.vjp(lambda t: jax.nn.gelu(t, approximate=True), a)

    def bwd(dh):
        return vjp(dh)[0], None
    return h, bwd


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _grouped(x, w1, w3, w2, mask, act, block_n, interpret):
    if act == "swiglu":
        return _fwd_pallas(x, w1, w3, w2, mask, block_n=block_n,
                           interpret=interpret)
    return _fwd_pallas(x, w1, None, w2, mask, block_n=block_n,
                       interpret=interpret)


def _grouped_fwd(x, w1, w3, w2, mask, act, block_n, interpret):
    return (_grouped(x, w1, w3, w2, mask, act, block_n, interpret),
            (x, w1, w3, w2, mask))


def _grouped_bwd(act, block_n, interpret, res, g):
    x, w1, w3, w2, mask = res
    m32 = mask.astype(jnp.float32)[..., None]
    x32 = x.astype(jnp.float32) * m32
    w1_32 = w1.astype(jnp.float32)
    w3_32 = None if w3 is None else w3.astype(jnp.float32)
    w2_32 = w2.astype(jnp.float32)
    g32 = g.astype(jnp.float32) * m32
    h, act_bwd = _act_and_grads(x32, w1_32, w3_32, act)
    dh = jnp.einsum("end,efd->enf", g32, w2_32)
    dw2 = jnp.einsum("enf,end->efd", h, g32)
    da, db = act_bwd(dh)
    dx = jnp.einsum("enf,edf->end", da, w1_32)
    dw1 = jnp.einsum("end,enf->edf", x32, da)
    if act == "swiglu":
        dx = dx + jnp.einsum("enf,edf->end", db, w3_32)
        dw3 = jnp.einsum("end,enf->edf", x32, db)
    else:
        dw3 = None
    dx = dx * m32  # masked slots: zero output and zero input-gradient
    return (dx.astype(x.dtype), dw1.astype(w1.dtype),
            None if w3 is None else dw3.astype(w3.dtype),
            dw2.astype(w2.dtype), jnp.zeros_like(mask))


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_mlp(x: jax.Array, w1: jax.Array, w3: jax.Array | None,
                w2: jax.Array, mask: jax.Array, *, act: str = "swiglu",
                block_n: int = DEFAULT_BLOCK_N,
                interpret: bool = False) -> jax.Array:
    """x: (E, N, d); w1/w3: (E, d, F); w2: (E, F, d); mask: (E, N) in
    {0, 1} -> (E, N, d).  Differentiable; ``mask`` gets a zero cotangent."""
    if act == "swiglu":
        if w3 is None:
            raise ValueError("act='swiglu' needs w3")
    elif act != "gelu":
        raise ValueError(f"unsupported grouped-MLP act {act!r}")
    return _grouped(x, w1, w3, w2, mask, act, block_n, interpret)
