"""jit'd public wrappers around the Pallas kernels.

On CPU (this container) the kernels execute in interpret mode — the kernel
body runs in Python for correctness validation against ``ref.py``; on TPU
they lower via Mosaic.  Layout conversion and block fitting happen here; GQA
is native to the flash kernel (KV heads stay unreplicated — the kernel's
grid index maps share each KV block across its G query heads, instead of the
old ``jnp.repeat`` that materialized G full copies of K/V).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa
from repro.kernels import rmsnorm as rn
from repro.kernels.tiling import fit_block


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def flash_attention(
    q: jax.Array,   # (B, Sq, Hq, hd) — model layout
    k: jax.Array,   # (B, Skv, Hkv, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    sliding_window: int | None = None,
    softcap: float | None = None,
    q_offset: int = 0,
    block_q: int = fa.DEFAULT_BLOCK_Q,
    block_k: int = fa.DEFAULT_BLOCK_K,
    interpret: bool | None = None,
) -> jax.Array:
    """FlashAttention over the model's (B, S, H, hd) layout; ``softcap``
    applies the gemma-style logit cap in-kernel."""
    if interpret is None:
        interpret = _on_cpu()
    B, Sq, Hq, hd = q.shape
    _, Skv, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    bq = fit_block(block_q, Sq, 8)
    bk = fit_block(block_k, Skv, 8)
    out = fa.flash_attention(qt, kt, vt, causal, sliding_window, q_offset,
                             bq, bk, interpret, softcap)
    return out.transpose(0, 2, 1, 3)


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5,
            interpret: bool | None = None) -> jax.Array:
    if interpret is None:
        interpret = _on_cpu()
    return rn.rmsnorm(x, w, eps, rn.DEFAULT_BLOCK_ROWS, interpret)


def layernorm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float = 1e-5,
              interpret: bool | None = None) -> jax.Array:
    from repro.kernels import layernorm as ln
    if interpret is None:
        interpret = _on_cpu()
    return ln.layernorm(x, w, b, eps, ln.DEFAULT_BLOCK_ROWS, interpret)


def cross_entropy(h: jax.Array, w: jax.Array, labels: jax.Array,
                  valid_vocab: int | None = None,
                  interpret: bool | None = None) -> jax.Array:
    """Blocked CE: the (N, V) logits tensor never reaches HBM."""
    from repro.kernels import cross_entropy as ce
    if interpret is None:
        interpret = _on_cpu()
    return ce.cross_entropy(h, w, labels, valid_vocab=valid_vocab,
                            interpret=interpret)


def cross_entropy_tokens(h: jax.Array, w: jax.Array, labels: jax.Array,
                         valid_vocab: int | None = None,
                         interpret: bool | None = None) -> jax.Array:
    """Per-token CE losses (N,) fp32 — the train-path entry point (callers
    apply their own loss mask / normalization).  Differentiable."""
    from repro.kernels import cross_entropy as ce
    if interpret is None:
        interpret = _on_cpu()
    return ce.cross_entropy_tokens(h, w, labels, valid_vocab, interpret)


def swiglu(x: jax.Array, w1: jax.Array, w3: jax.Array,
           interpret: bool | None = None) -> jax.Array:
    """Fused silu(x@w1) * (x@w3); x: (..., d)."""
    from repro.kernels import swiglu as sg
    if interpret is None:
        interpret = _on_cpu()
    shape = x.shape
    out = sg.swiglu(x.reshape(-1, shape[-1]), w1, w3, interpret=interpret)
    return out.reshape(*shape[:-1], w1.shape[1])


def gelu_mlp_in(x: jax.Array, w1: jax.Array,
                interpret: bool | None = None) -> jax.Array:
    """Fused gelu(x@w1) (tanh approximation); x: (..., d)."""
    from repro.kernels import gelu_mlp as gm
    if interpret is None:
        interpret = _on_cpu()
    shape = x.shape
    out = gm.gelu_mlp_in(x.reshape(-1, shape[-1]), w1, interpret=interpret)
    return out.reshape(*shape[:-1], w1.shape[1])


def ssd_scan(x: jax.Array, dt: jax.Array, Bm: jax.Array, Cm: jax.Array,
             A_log: jax.Array, *, chunk: int,
             interpret: bool | None = None):
    """Fused mamba2 chunked SSD scan: x (B, T, H, P), dt (B, T, H),
    Bm/Cm (B, T, N), A_log (H,) -> (y (B, T, H, P), state (B, H, P, N)
    fp32).  ``chunk`` must divide T (pick via ``tiling.pick_chunk``);
    differentiable."""
    from repro.kernels import ssd_scan as ssd
    if interpret is None:
        interpret = _on_cpu()
    return ssd.ssd_scan(x, dt, Bm, Cm, A_log, chunk=chunk,
                        interpret=interpret)


def wkv_scan(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
             u: jax.Array, state: jax.Array, *, chunk: int,
             interpret: bool | None = None):
    """Fused rwkv chunked wkv scan: r/k/w (B, T, H, K), v (B, T, H, V),
    u (H, K), state (B, H, K, V) -> (y (B, T, H, V) fp32, final state).
    All operands are computed in fp32 (matching the reference recurrence);
    ``chunk`` must divide T; differentiable."""
    from repro.kernels import wkv_scan as wkv
    if interpret is None:
        interpret = _on_cpu()
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    return wkv.wkv_scan(f32(r), f32(k), f32(v), f32(w), f32(u), f32(state),
                        chunk=chunk, interpret=interpret)


def mamba_decode_step(window: jax.Array, conv_w: jax.Array, conv_b: jax.Array,
                      dt_raw: jax.Array, dt_bias: jax.Array, A_log: jax.Array,
                      D: jax.Array, state: jax.Array, *, n_heads: int,
                      head_dim: int, interpret: bool | None = None):
    """Fused single-token mamba decode chain (conv window -> gate -> state
    update -> read-out): window (B, K, ch), state (B, H, P, N) fp32 ->
    (y (B, H, P) fp32, new state).  Serving path only (no vjp)."""
    from repro.kernels import ssd_scan as ssd
    if interpret is None:
        interpret = _on_cpu()
    return ssd.mamba_decode_step(window, conv_w, conv_b, dt_raw, dt_bias,
                                 A_log, D, state, n_heads=n_heads,
                                 head_dim=head_dim, interpret=interpret)


def wkv_decode_step(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
                    u: jax.Array, state: jax.Array,
                    interpret: bool | None = None):
    """Fused single-token rwkv time-mix core step: r/k/w (B, H, K) fp32,
    v (B, H, V) fp32, u (H, K), state (B, H, K, V) fp32 ->
    (out (B, H, V) fp32, new state).  Serving path only (no vjp)."""
    from repro.kernels import wkv_scan as wkv
    if interpret is None:
        interpret = _on_cpu()
    return wkv.wkv_decode_step(r, k, v, w, u, state, interpret=interpret)


def grouped_mlp(x: jax.Array, w1: jax.Array, w3: jax.Array | None,
                w2: jax.Array, mask: jax.Array, act: str = "swiglu",
                interpret: bool | None = None) -> jax.Array:
    """Fused grouped expert MLP over the expert-major slot layout:
    x (E, N, d), w1/w3 (E, d, F), w2 (E, F, d), mask (E, N) -> (E, N, d).
    Masked (padded-capacity) slots produce zero output and zero weight
    gradients.  ``act`` in {"swiglu", "gelu"}; differentiable."""
    from repro.kernels import grouped_mlp as gm
    if interpret is None:
        interpret = _on_cpu()
    return gm.grouped_mlp(x, w1, w3, w2, mask, act=act, interpret=interpret)


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    block_table: jax.Array, lengths: jax.Array, *,
                    softcap: float | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """Paged decode attention over the pool's live blocks: q (B, Hq, hd),
    k_pool/v_pool (n_blocks, bs, Hkv, hd), block_table (B, max_blocks),
    lengths (B,) (``pos + 1``; 0 for an inactive slot, which yields zeros)
    -> (B, Hq, hd).  Serving path only (no vjp)."""
    from repro.kernels import paged_attention as pa
    if interpret is None:
        interpret = _on_cpu()
    return pa.paged_attention(q, k_pool, v_pool, block_table, lengths,
                              softcap=softcap, interpret=interpret)
