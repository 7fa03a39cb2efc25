"""Mamba-2 chunked SSD scan + fused single-token decode as Pallas kernels.

Train/prefill kernel: grid (B, H, nc) with the chunk index minor-most, so
the length-nc recurrence over chunks runs sequentially per (batch, head)
while the (P, N) state lives in VMEM scratch.  The kernel reads head-major
(B, H, T, P) copies of its inputs, whose (chunk, P) blocks Mosaic can tile,
and takes the in-chunk cumsum of the decay as a lower-triangular matmul
(Mosaic has no cumsum).  The intra-chunk work is
two MXU matmuls (the (Q x Q) masked decay attention and its (Q x P) apply)
plus the (P x N) state outer product, exactly the chunk structure of the
jnp reference ``models/ssm.py:_ssd_chunked`` / ``kernels/ref.py:
ssd_scan_ref`` (fp32 accumulation, zero initial state).

Differentiable via ``custom_vjp`` in the grouped-MLP idiom: the forward
saves only the inputs and the backward recomputes the chunked scan in fp32
through ``jax.vjp`` over the reference — numerically the grads of the same
chunk algebra, and memory-equivalent to the reference's per-chunk remat.

Decode kernel: the state update + read-out of ``models/ssm.py:
mamba_decode`` fused into one pass over the (B, H, P, N) state, on per-head
(P, N) tiles; the conv window and softplus(dt) gate ahead of it are small
elementwise work left to XLA.  It follows the jnp decode algebra, so
interpret mode agrees with the reference to fp32 rounding; no vjp
(serving only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, la_ref, y_ref, st_ref, s_ref,
                 *, nc: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    xc = x_ref[0, 0].astype(jnp.float32)                  # (Q, P)
    dtc = dt_ref[0, 0].astype(jnp.float32)                # (Q, 1)
    Bc = b_ref[0].astype(jnp.float32)                     # (Q, N)
    Cc = c_ref[0].astype(jnp.float32)                     # (Q, N)
    logA = la_ref[0]                                      # (1, 1), fp32

    Q = xc.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = row >= col
    la = dtc * logA                                       # (Q, 1)
    # inclusive cumsum as a lower-triangular matmul (Mosaic has no cumsum)
    cum = jax.lax.dot_general(causal.astype(jnp.float32), la,
                              (((1,), (0,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)  # (Q, 1)
    total = cum[Q - 1:, :]                                # (1, 1)

    # intra-chunk: W[i, j] = (C_i . B_j) exp(cum_i - cum_j) dt_j  (j <= i);
    # the j-indexed rows come from transposing a broadcast column
    Gsc = jax.lax.dot_general(Cc, Bc, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (Q, Q)
    gap = cum - jnp.broadcast_to(cum, (Q, Q)).T           # cum_i - cum_j
    L = jnp.exp(jnp.where(causal, gap, -jnp.inf))
    W = Gsc * L * jnp.broadcast_to(dtc, (Q, Q)).T
    y = jax.lax.dot_general(W, xc, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)    # (Q, P)

    # inter-chunk: contribution of the carried state
    S = s_ref[...]                                        # (P, N)
    y = y + jax.lax.dot_general(Cc, S, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
        * jnp.exp(cum)

    # state update: S' = exp(total) S + sum_j dt_j exp(total - cum_j) x_j B_j
    xw = xc * (dtc * jnp.exp(total - cum))                # (Q, P)
    # (1, 1) -> (P, N) in two steps with the exp between them (so they do
    # not fold into one): Mosaic broadcasts lanes or sublanes, not both
    decay = jnp.exp(jnp.broadcast_to(total, (S.shape[0], 1)))
    S_new = decay * S + jax.lax.dot_general(
        xw, Bc, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # (P, N)
    s_ref[...] = S_new
    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _finish():
        st_ref[0, 0] = S_new


def _fwd_pallas(x, dt, Bm, Cm, A_log, *, chunk: int, interpret: bool):
    # the kernel runs head-major: a (chunk, P) block of a (B, H, T, P)
    # array is tile-legal, a (chunk, 1, P) block of (B, T, H, P) is not
    B, T, H, P = x.shape
    N = Bm.shape[-1]
    nc = T // chunk
    logA = -jnp.exp(A_log.astype(jnp.float32)).reshape(H, 1, 1)
    y, state = pl.pallas_call(
        functools.partial(_scan_kernel, nc=nc),
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, h, c: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[
            # VMEM recurrent state carried across the nc chunk loop
            pltpu.VMEM((P, N), jnp.float32),
        ],
        name="ssd_scan",
        interpret=interpret,
    )(x.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1)[..., None], Bm, Cm, logA)
    return y.transpose(0, 2, 1, 3), state


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd(x, dt, Bm, Cm, A_log, chunk, interpret):
    return _fwd_pallas(x, dt, Bm, Cm, A_log, chunk=chunk, interpret=interpret)


def _ssd_fwd(x, dt, Bm, Cm, A_log, chunk, interpret):
    return _ssd(x, dt, Bm, Cm, A_log, chunk, interpret), (x, dt, Bm, Cm, A_log)


def _ssd_bwd(chunk, interpret, res, g):
    x, dt, Bm, Cm, A_log = res
    _, vjp = jax.vjp(
        lambda *a: ref.ssd_scan_ref(*a, chunk=chunk), x, dt, Bm, Cm, A_log)
    return vjp(g)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x: jax.Array, dt: jax.Array, Bm: jax.Array, Cm: jax.Array,
             A_log: jax.Array, *, chunk: int,
             interpret: bool = False):
    """x: (B, T, H, P); dt: (B, T, H); Bm/Cm: (B, T, N); A_log: (H,).
    Returns (y (B, T, H, P) in x.dtype, final state (B, H, P, N) fp32).
    Differentiable (backward recomputes via ``ref.ssd_scan_ref``)."""
    assert x.shape[1] % chunk == 0, (x.shape, chunk)
    return _ssd(x, dt, Bm, Cm, A_log, chunk, interpret)


# ---------------------------------------------------------------------------
# Fused single-token decode
# ---------------------------------------------------------------------------

def _decode_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, s_ref, y_ref,
                   so_ref):
    # per-head (P, N) state tiles: x arrives as (H, P, 1) columns, the
    # per-head gates as (H, 1, 1) and B/C as (1, 1, N) rows, so every
    # product is a plain lane or sublane broadcast
    x = x_ref[0]                                          # (H, P, 1)
    state = a_ref[0] * s_ref[0] + (dt_ref[0] * b_ref[0]) * x   # (H, P, N)
    y_ref[0] = jnp.sum(c_ref[0] * state, axis=-1, keepdims=True) \
        + d_ref[...] * x
    so_ref[0] = state


def mamba_decode_step(window, conv_w, conv_b, dt_raw, dt_bias, A_log, D,
                      state, *, n_heads: int, head_dim: int,
                      interpret: bool = False):
    """Fused mamba decode: conv window -> silu -> softplus(dt) gate -> state
    update -> read-out.  The conv and gates are small (B, ch) / (B, H)
    elementwise work left to XLA; the kernel fuses the (B, H, P, N) state
    update with its read-out, one pass over the state per step.

    window: (B, K, ch); conv_w: (K, ch); conv_b: (ch,); dt_raw: (B, H);
    dt_bias/A_log/D: (H,); state: (B, H, P, N) fp32.
    Returns (y (B, H, P) fp32, new state (B, H, P, N) fp32)."""
    B = window.shape[0]
    H, P = n_heads, head_dim
    N = state.shape[-1]
    di = H * P
    conv_out = jax.nn.silu(jnp.einsum("bkc,kc->bc", window, conv_w) + conv_b)
    xin, Bm, Cm = jnp.split(conv_out, [di, di + N], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + dt_bias.astype(jnp.float32))   # (B, H)
    a = jnp.exp(dt * -jnp.exp(A_log.astype(jnp.float32)))
    gate = lambda g: g.reshape(B, H, 1, 1)  # noqa: E731
    row = lambda m: m.astype(jnp.float32).reshape(B, 1, 1, N)  # noqa: E731
    st = pl.BlockSpec((1, H, P, N), lambda b: (b, 0, 0, 0))
    g_spec = pl.BlockSpec((1, H, 1, 1), lambda b: (b, 0, 0, 0))
    r_spec = pl.BlockSpec((1, 1, 1, N), lambda b: (b, 0, 0, 0))
    x_spec = pl.BlockSpec((1, H, P, 1), lambda b: (b, 0, 0, 0))
    y, new_state = pl.pallas_call(
        _decode_kernel,
        grid=(B,),
        in_specs=[x_spec, g_spec, g_spec, r_spec, r_spec,
                  pl.BlockSpec((H, 1, 1), lambda b: (0, 0, 0)), st],
        out_specs=[x_spec, st],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, P, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        name="ssd_decode",
        interpret=interpret,
    )(xin.astype(jnp.float32).reshape(B, H, P, 1), gate(dt), gate(a),
      row(Bm), row(Cm), D.astype(jnp.float32).reshape(H, 1, 1), state)
    return y[..., 0], new_state
