"""FlashAttention-2 as a Pallas TPU kernel (fwd + bwd via custom_vjp).

The paper reports ~30% throughput from FlashAttention-2 (ported to MI250X
via Composable Kernel).  The TPU adaptation re-thinks the GPU algorithm for
the memory hierarchy here: instead of warp-level softmax reductions in
shared memory, blocks of Q stay resident in VMEM while K/V blocks stream
HBM->VMEM; the MXU handles the (bq x hd) @ (hd x bk) and (bq x bk) @
(bk x hd) matmuls, so block shapes are multiples of the 128-lane MXU tile.

Layout: (B, H, S, hd).  Grid = (B, H, nq, nk) — nk is the minor-most grid
dim, so on TPU the K-loop for one Q block runs sequentially and the online
softmax state (m, l, acc) lives in VMEM scratch across those steps.

Causal + sliding-window masking is applied in-kernel; fully-masked K blocks
are skipped with ``pl.when`` (no MXU work issued).  Logit softcapping
(gemma-style ``tanh(s/c)*c``, applied after scaling and before masking) is
native: the backward kernels recompute ``t = tanh(s/c)`` from Q/K and fold
the ``1 - t^2`` Jacobian into ``ds``, so softcap models no longer fall back
to the jnp path.

GQA is native: K/V carry their ``Hkv`` heads unreplicated and the BlockSpec
index maps route query head ``h`` to KV head ``h // G`` — no ``jnp.repeat``
materializing G copies of the KV tensors (fwd, residuals, and dq all stream
the shared blocks).  dK/dV accumulate over the group inside the kernel by
folding the G query heads into the minor-most grid dims, so the gradients
also come out at ``Hkv`` heads.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *,
                scale: float, causal: bool, window: int | None,
                softcap: float | None,
                block_q: int, block_k: int, nk: int, q_offset: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_offset
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    # block-level relevance (skip fully-masked K blocks)
    q_last = (iq + 1) * block_q - 1 + q_offset
    q_first = iq * block_q + q_offset
    k_first = ik * block_k
    k_last = (ik + 1) * block_k - 1
    relevant = jnp.bool_(True)
    if causal:
        relevant = jnp.logical_and(relevant, k_first <= q_last)
    if window is not None:
        relevant = jnp.logical_and(relevant, q_first - k_last < window)

    @pl.when(relevant)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)           # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)           # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)           # (bk, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        mask = None
        if causal:
            mask = k_pos <= q_pos
        if window is not None:
            wmask = q_pos - k_pos < window
            mask = wmask if mask is None else jnp.logical_and(mask, wmask)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                           # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(safe_l)


def _group_size(q, k) -> int:
    Hq, Hkv = q.shape[1], k.shape[1]
    assert Hq % Hkv == 0, (Hq, Hkv)
    return Hq // Hkv


def flash_attention_fwd(q, k, v, *, causal, window, q_offset,
                        block_q, block_k, interpret, softcap=None):
    B, H, Sq, hd = q.shape
    Skv = k.shape[2]
    g = _group_size(q, k)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, block_q, Skv, block_k)
    nq, nk = Sq // block_q, Skv // block_k
    scale = 1.0 / np.sqrt(hd)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap,
        block_q=block_q, block_k=block_k, nk=nk, q_offset=q_offset)

    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, i, j: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, i, j: (b, h // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            # VMEM online-softmax state carried across the nk loop
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        name="flash_fwd",
        interpret=interpret,
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, scale, causal, window, softcap, block_q,
                   block_k, nk, q_offset):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_offset
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    q_last = (iq + 1) * block_q - 1 + q_offset
    q_first = iq * block_q + q_offset
    relevant = jnp.bool_(True)
    if causal:
        relevant = jnp.logical_and(relevant, ik * block_k <= q_last)
    if window is not None:
        relevant = jnp.logical_and(relevant, q_first - ((ik + 1) * block_k - 1) < window)

    @pl.when(relevant)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        tcap = None
        if softcap is not None:
            tcap = jnp.tanh(s / softcap)
            s = tcap * softcap
        mask = None
        if causal:
            mask = k_pos <= q_pos
        if window is not None:
            w = q_pos - k_pos < window
            mask = w if mask is None else jnp.logical_and(mask, w)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        if tcap is not None:
            ds = ds * (1.0 - tcap * tcap)   # d tanh(s/c)*c / ds
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale, causal, window, softcap, block_q, block_k, nq, ng,
                    q_offset):
    # grid (B, Hkv, nk, G, nq): the G query heads sharing this KV head are the
    # second-minor grid dim, so dk/dv accumulate over the whole group in VMEM
    # scratch and the gradients come out unreplicated at Hkv heads.
    ik = pl.program_id(2)
    ig = pl.program_id(3)
    iq = pl.program_id(4)

    @pl.when((iq == 0) & (ig == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_offset
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    q_last = (iq + 1) * block_q - 1 + q_offset
    q_first = iq * block_q + q_offset
    relevant = jnp.bool_(True)
    if causal:
        relevant = jnp.logical_and(relevant, ik * block_k <= q_last)
    if window is not None:
        relevant = jnp.logical_and(relevant, q_first - ((ik + 1) * block_k - 1) < window)

    @pl.when(relevant)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        tcap = None
        if softcap is not None:
            tcap = jnp.tanh(s / softcap)
            s = tcap * softcap
        mask = None
        if causal:
            mask = k_pos <= q_pos
        if window is not None:
            w = q_pos - k_pos < window
            mask = w if mask is None else jnp.logical_and(mask, w)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)                              # (bq, bk)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        if tcap is not None:
            ds = ds * (1.0 - tcap * tcap)   # d tanh(s/c)*c / ds
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when((iq == nq - 1) & (ig == ng - 1))
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, out, lse, do, *, causal, window, q_offset,
                        block_q, block_k, interpret, softcap=None):
    B, H, Sq, hd = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    g = _group_size(q, k)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    nq, nk = Sq // block_q, Skv // block_k
    scale = 1.0 / np.sqrt(hd)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
                    keepdims=True)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          window=window, softcap=softcap, block_q=block_q,
                          block_k=block_k, nk=nk, q_offset=q_offset),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, i, j: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, i, j: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        name="flash_bwd_dq",
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # grid (B, Hkv, nk, G, nq): query head = kvh * G + ig for the q-side
    # operands; dk/dv blocks are revisited only across the two minor-most
    # dims, so the VMEM accumulators carry the whole group reduction
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          window=window, softcap=softcap, block_q=block_q,
                          block_k=block_k, nq=nq, ng=g, q_offset=q_offset),
        grid=(B, Hkv, nk, g, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, j, gg, i: (b, h * g + gg, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, j, gg, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, j, gg, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, j, gg, i: (b, h * g + gg, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, j, gg, i: (b, h * g + gg, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, j, gg, i: (b, h * g + gg, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, j, gg, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, j, gg, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, hd), jnp.float32),
            pltpu.VMEM((block_k, hd), jnp.float32),
        ],
        name="flash_bwd_dkv",
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention(q, k, v, causal=True, window=None, q_offset=0,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=False, softcap=None):
    """q: (B, Hq, Sq, hd); k/v: (B, Hkv, Skv, hd) with Hq % Hkv == 0 — GQA
    KV heads stay unreplicated (shared blocks via the grid index maps).
    ``softcap`` applies gemma-style logit capping ``tanh(s/c)*c`` in-kernel
    (trailing arg so existing positional call sites stay valid)."""
    out, _ = flash_attention_fwd(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset, block_q=block_q,
                                 block_k=block_k, interpret=interpret,
                                 softcap=softcap)
    return out


def _fa_fwd(q, k, v, causal, window, q_offset, block_q, block_k, interpret,
            softcap):
    out, lse = flash_attention_fwd(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset, block_q=block_q,
                                   block_k=block_k, interpret=interpret,
                                   softcap=softcap)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, window, q_offset, block_q, block_k, interpret, softcap,
            res, do):
    q, k, v, out, lse = res
    dq, dk, dv = flash_attention_bwd(
        q, k, v, out, lse, do, causal=causal, window=window,
        q_offset=q_offset, block_q=block_q, block_k=block_k,
        interpret=interpret, softcap=softcap)
    return dq, dk, dv


flash_attention.defvjp(_fa_fwd, _fa_bwd)
