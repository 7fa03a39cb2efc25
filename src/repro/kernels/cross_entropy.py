"""Blocked cross-entropy as a Pallas TPU kernel (logits never hit HBM).

The §Perf attribution showed the unembedding/CE path dominating wide-vocab
models: the (tokens, vocab) logits tensor is pure intermediate state.  This
kernel streams W column-blocks through VMEM, maintaining a running
(max, sumexp, label-logit) triple per token row — an online-logsumexp, the
CE analogue of flash attention's online softmax.

Grid = (n_token_blocks, n_vocab_blocks); the vocab loop is minor-most so the
running stats live in VMEM scratch.  Returns (lse, label_logit) per token;
loss = lse - label_logit.  :func:`cross_entropy_tokens` wraps the kernel in a
``custom_vjp`` whose backward recomputes logits in token chunks from the
saved lse (p = exp(logits - lse)), so neither direction ever materializes the
full (N, V) tensor — this is what puts the kernel on the training path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.tiling import fit_block
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_V = 512   # (d, block_v) weight blocks fit scoped VMEM at d ~ 2k-4k
NEG_INF = -1e30


def _ce_kernel(h_ref, w_ref, y_ref, lse_ref, ylogit_ref,
               m_ref, s_ref, yl_ref, *, block_v: int, nv: int, valid_vocab: int):
    # per-row stats are (bn, 1) columns: a 1-D (bn,) block breaks Mosaic's
    # (8, 128) tiling unless bn is a multiple of 128
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        s_ref[...] = jnp.zeros_like(s_ref)
        yl_ref[...] = jnp.full_like(yl_ref, NEG_INF)

    # storage-dtype operands, fp32 accumulation (see swiglu.py)
    logits = jax.lax.dot_general(
        h_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # mask padded vocab columns
    col = iv * block_v + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(col < valid_vocab, logits, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    s_ref[...] = s_ref[...] * corr + jnp.sum(jnp.exp(logits - m_new), axis=1,
                                             keepdims=True)
    m_ref[...] = m_new

    # this block's label logits: an iota-compare select (Mosaic has no
    # in-kernel gather)
    y = y_ref[...]                                 # (bn, 1)
    in_block = (y >= iv * block_v) & (y < (iv + 1) * block_v)
    picked = jnp.sum(jnp.where(col == y, logits, 0.0), axis=1, keepdims=True)
    yl_ref[...] = jnp.where(in_block, picked, yl_ref[...])

    @pl.when(iv == nv - 1)
    def _finish():
        lse_ref[...] = m_ref[...] + jnp.log(s_ref[...])
        ylogit_ref[...] = yl_ref[...]


def ce_logsumexp_pallas(h: jax.Array, w: jax.Array, labels: jax.Array, *,
                        valid_vocab: int | None = None,
                        block_n: int = DEFAULT_BLOCK_N,
                        block_v: int = DEFAULT_BLOCK_V,
                        interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """h: (N, d); w: (d, V); labels: (N,) -> (lse (N,), label_logit (N,))."""
    N, d = h.shape
    V = w.shape[1]
    valid_vocab = valid_vocab or V
    rows_block = fit_block(block_n, N, 8)
    if rows_block > block_n:
        # no 8-aligned divisor (e.g. B * (T - 1) shifted-label rows): pad
        # to whole blocks rather than make one VMEM-sized block of all N
        rows_block = max(8, block_n - block_n % 8)
        pad = -N % rows_block
        h = jnp.pad(h, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad))
    block_n, rows = rows_block, h.shape[0]
    block_v = fit_block(block_v, V, 128)
    nn, nv = rows // block_n, V // block_v
    col_spec = pl.BlockSpec((block_n, 1), lambda i, j: (i, 0))
    lse, ylogit = pl.pallas_call(
        functools.partial(_ce_kernel, block_v=block_v, nv=nv,
                          valid_vocab=valid_vocab),
        grid=(nn, nv),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, block_v), lambda i, j: (0, j)),
            col_spec,
        ],
        out_specs=[col_spec, col_spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
        ],
        name="cross_entropy",
        interpret=interpret,
    )(h, w, labels.reshape(rows, 1))
    return lse[:N, 0], ylogit[:N, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def cross_entropy_tokens(h, w, labels, valid_vocab=None, interpret=False):
    """Per-token CE losses (N,) fp32; differentiable w.r.t. h and w.

    Per-token (instead of mean) so callers can apply loss masks and their
    own normalization outside the kernel."""
    lse, ylogit = ce_logsumexp_pallas(h, w, labels, valid_vocab=valid_vocab,
                                      interpret=interpret)
    return lse - ylogit


def _ce_tokens_fwd(h, w, labels, valid_vocab, interpret):
    lse, ylogit = ce_logsumexp_pallas(h, w, labels, valid_vocab=valid_vocab,
                                      interpret=interpret)
    return lse - ylogit, (h, w, labels, lse)


def _ce_tokens_bwd(valid_vocab, interpret, res, g):
    h, w, labels, lse = res
    N, d = h.shape
    V = w.shape[1]
    vv = valid_vocab or V
    w32 = w.astype(jnp.float32)
    chunk = fit_block(DEFAULT_BLOCK_N, N)
    nc = N // chunk

    def body(dw, xs):
        hb, yb, lseb, gb = xs
        logits = hb.astype(jnp.float32) @ w32                  # (chunk, V)
        if vv < V:
            logits = jnp.where(jnp.arange(V)[None, :] >= vv, NEG_INF, logits)
        p = jnp.exp(logits - lseb[:, None])                    # softmax via saved lse
        dlogits = (p - jax.nn.one_hot(yb, V, dtype=jnp.float32)) * gb[:, None]
        dh = dlogits @ w32.T
        return dw + hb.astype(jnp.float32).T @ dlogits, dh

    xs = (h.reshape(nc, chunk, d), labels.reshape(nc, chunk),
          lse.reshape(nc, chunk), g.reshape(nc, chunk).astype(jnp.float32))
    dw, dhs = jax.lax.scan(body, jnp.zeros((d, V), jnp.float32), xs)
    return (dhs.reshape(N, d).astype(h.dtype), dw.astype(w.dtype),
            np.zeros(labels.shape, jax.dtypes.float0))


cross_entropy_tokens.defvjp(_ce_tokens_fwd, _ce_tokens_bwd)


def cross_entropy(h, w, labels, valid_vocab=None, interpret=False):
    """Mean CE loss over tokens; logits stay in VMEM.  Differentiable."""
    return jnp.mean(cross_entropy_tokens(h, w, labels, valid_vocab, interpret))
