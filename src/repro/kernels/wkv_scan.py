"""RWKV-6 chunked wkv recurrence + fused single-token decode as Pallas kernels.

Train/prefill kernel: grid (B, H, nc) with the chunk index minor-most, so
the inter-chunk carry runs sequentially per (batch, head) while the
(K, V) state lives in VMEM scratch.  It reads head-major (B, H, T, *)
copies of its inputs, whose (chunk, K) blocks Mosaic can tile.  Per chunk:
log-space per-channel decays (the cumsums of log w, taken by XLA ahead of
the kernel since Mosaic has no cumsum), the strictly-causal intra-chunk
score matrix, built one column at a time
with the decay gap applied inside the exponent (masked to -inf, so
exp() never sees future-position deltas), the bonus (current-token)
``u`` term, and the MXU matmuls against the carried state — the same
chunk algebra as ``models/rwkv.py:_wkv_chunked`` / ``kernels/ref.py:
wkv_scan_ref``.

Differentiable via ``custom_vjp`` in the grouped-MLP idiom: forward saves
only the inputs; backward recomputes through ``jax.vjp`` over the fp32
reference — memory-equivalent to the reference's per-chunk remat.

Decode kernel: the O(1) time-mix core step (``models/rwkv.py:
_time_mix_core``) fused into one launch — rank-1 state update ``w*S + k
v^T`` plus the bonus read-out, on per-head (K, V) tiles.  Follows the jnp
decode algebra, so interpret mode agrees with the reference to fp32
rounding; no vjp.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref


def _scan_kernel(r_ref, k_ref, v_ref, cum_ref, cp_ref, u_ref, s0_ref, y_ref,
                 st_ref, s_ref, *, nc: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0]

    rc = r_ref[0, 0]                                      # (Q, K) fp32
    kc = k_ref[0, 0]
    vc = v_ref[0, 0]                                      # (Q, V)
    cum = cum_ref[0, 0]                                   # (Q, K), < 0
    cum_prev = cp_ref[0, 0]                               # cum_{t-1}
    uc = u_ref[0]                                         # (1, K)

    Q, K = rc.shape
    V = vc.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    S = s_ref[...]                                        # (K, V)

    # inter-chunk: y_t += (r_t * exp(cum_{t-1})) @ S
    rd = rc * jnp.exp(cum_prev)
    y = jax.lax.dot_general(rd, S, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, V)

    # intra-chunk: score_{t,i} = sum_k r_tk k_ik exp(cum_{t-1,k} - cum_{i,k})
    # for i < t, built one column i at a time from 2-D (Q, K) tiles (the
    # (t, i, K) gap tensor needs 3-D relayouts Mosaic does not do); the
    # decay gap stays inside the exponent, masked before exp()
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (Q, K), 0)
    score = jnp.zeros((Q, Q), jnp.float32)
    for i in range(Q):
        gap = jnp.where(t_idx > i, cum_prev - cum[i:i + 1, :], -jnp.inf)
        s_i = jnp.sum(rc * kc[i:i + 1, :] * jnp.exp(gap), axis=1,
                      keepdims=True)                      # (Q, 1)
        score = score + jnp.where(col == i, s_i, 0.0)
    y = y + jax.lax.dot_general(score, vc, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    # bonus (current token) term
    y = y + jnp.sum(rc * (uc * kc), axis=-1, keepdims=True) * vc

    # state update: S' = diag(exp(total)) S + sum_i exp(total - cum_i) k_i v_i;
    # the per-row decay column comes from transposing a broadcast row
    total = cum[Q - 1:, :]                                # (1, K)
    kw = kc * jnp.exp(total - cum)                        # (Q, K)
    decay = jnp.broadcast_to(jnp.exp(total), (V, K)).T    # (K, V)
    S_new = decay * S + jax.lax.dot_general(
        kw, vc, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # (K, V)
    s_ref[...] = S_new
    y_ref[0, 0] = y

    @pl.when(ic == nc - 1)
    def _finish():
        st_ref[0, 0] = S_new


def _chunk_cumsums(w, chunk: int):
    """Inclusive and exclusive per-chunk cumsums of log(w), (B, T, H, K).
    XLA computes them ahead of the kernel (Mosaic has no cumsum), in the
    same order as the reference's ``jnp.cumsum``."""
    B, T, H, K = w.shape
    lw = jnp.log(w).reshape(B, T // chunk, chunk, H, K)
    cum = jnp.cumsum(lw, axis=2)
    cum_prev = jnp.concatenate(
        [jnp.zeros_like(cum[:, :, :1]), cum[:, :, :-1]], axis=2)
    return cum.reshape(B, T, H, K), cum_prev.reshape(B, T, H, K)


def _fwd_pallas(r, k, v, w, u, state, *, chunk: int, interpret: bool):
    # head-major inside the kernel (see ssd_scan.py): (chunk, K) blocks of
    # (B, H, T, K) arrays are tile-legal
    B, T, H, K = r.shape
    V = v.shape[-1]
    nc = T // chunk
    cum, cum_prev = _chunk_cumsums(w, chunk)
    seq_spec = lambda D: pl.BlockSpec((1, 1, chunk, D),  # noqa: E731
                                      lambda b, h, c: (b, h, c, 0))
    hm = lambda a: a.transpose(0, 2, 1, 3)  # noqa: E731
    y, st = pl.pallas_call(
        functools.partial(_scan_kernel, nc=nc),
        grid=(B, H, nc),
        in_specs=[
            seq_spec(K), seq_spec(K), seq_spec(V), seq_spec(K), seq_spec(K),
            pl.BlockSpec((1, 1, K), lambda b, h, c: (h, 0, 0)),
            pl.BlockSpec((1, 1, K, V), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            seq_spec(V),
            pl.BlockSpec((1, 1, K, V), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, V), jnp.float32),
            jax.ShapeDtypeStruct((B, H, K, V), jnp.float32),
        ],
        scratch_shapes=[
            # VMEM recurrent state carried across the nc chunk loop
            pltpu.VMEM((K, V), jnp.float32),
        ],
        name="wkv_scan",
        interpret=interpret,
    )(hm(r), hm(k), hm(v), hm(cum), hm(cum_prev), u.reshape(H, 1, K), state)
    return hm(y), st


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _wkv(r, k, v, w, u, state, chunk, interpret):
    return _fwd_pallas(r, k, v, w, u, state, chunk=chunk, interpret=interpret)


def _wkv_fwd(r, k, v, w, u, state, chunk, interpret):
    return (_wkv(r, k, v, w, u, state, chunk, interpret),
            (r, k, v, w, u, state))


def _wkv_bwd(chunk, interpret, res, g):
    r, k, v, w, u, state = res
    _, vjp = jax.vjp(
        lambda *a: ref.wkv_scan_ref(*a, chunk=chunk), r, k, v, w, u, state)
    return vjp(g)


_wkv.defvjp(_wkv_fwd, _wkv_bwd)


def wkv_scan(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
             u: jax.Array, state: jax.Array, *, chunk: int,
             interpret: bool = False):
    """r/k/w: (B, T, H, K) fp32; v: (B, T, H, V) fp32; u: (H, K);
    state: (B, H, K, V) fp32.  Returns (y (B, T, H, V) fp32, final state).
    Differentiable (backward recomputes via ``ref.wkv_scan_ref``)."""
    assert r.shape[1] % chunk == 0, (r.shape, chunk)
    return _wkv(r, k, v, w, u, state, chunk, interpret)


# ---------------------------------------------------------------------------
# Fused single-token decode
# ---------------------------------------------------------------------------

def _decode_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s_ref, y_ref, so_ref):
    # per-head (K, V) tiles: r/k/w/u arrive as (H, K, 1) columns and v as
    # (H, 1, V) rows, so every product is a plain lane or sublane broadcast
    # (Mosaic cannot lower the batched dot an einsum would emit)
    r = r_ref[0]                                          # (H, K, 1)
    k = k_ref[0]
    v = v_ref[0]                                          # (H, 1, V)
    w = w_ref[0]
    u = u_ref[...]                                        # (H, K, 1)
    state = s_ref[0]                                      # (H, K, V)
    kv = k * v                                            # (H, K, V)
    y_ref[0] = jnp.sum(r * (state + u * kv), axis=1, keepdims=True)
    so_ref[0] = w * state + kv


def wkv_decode_step(r, k, v, w, u, state, interpret: bool = False):
    """Fused rwkv time-mix core step (one token).

    r/k/w: (B, H, K) fp32; v: (B, H, V) fp32; u: (H, K) fp32;
    state: (B, H, K, V) fp32.  Returns (out (B, H, V), new state)."""
    B, H, K = r.shape
    V = v.shape[-1]
    col = pl.BlockSpec((1, H, K, 1), lambda b: (b, 0, 0, 0))
    st = pl.BlockSpec((1, H, K, V), lambda b: (b, 0, 0, 0))
    out, new_state = pl.pallas_call(
        _decode_kernel,
        grid=(B,),
        in_specs=[
            col, col,
            pl.BlockSpec((1, H, 1, V), lambda b: (b, 0, 0, 0)),
            col,
            pl.BlockSpec((H, K, 1), lambda b: (0, 0, 0)),
            st,
        ],
        out_specs=[pl.BlockSpec((1, H, 1, V), lambda b: (b, 0, 0, 0)), st],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, 1, V), jnp.float32),
            jax.ShapeDtypeStruct((B, H, K, V), jnp.float32),
        ],
        name="wkv_decode",
        interpret=interpret,
    )(r[..., None], k[..., None], v[:, :, None, :], w[..., None],
      u[..., None], state)
    return out[:, :, 0, :], new_state
