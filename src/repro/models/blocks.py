"""Transformer block builders: attention blocks (self / cross, GQA, SWA,
qk-norm) and dense MLP blocks, as (spec, apply) pairs over explicit pytrees.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.core.compute import ComputePolicy, resolve as resolve_policy
from repro.models import layers
from repro.models.common import ModelConfig, Spec


def norm_spec(d: int, kind: str, axis: str = "embed") -> dict:
    spec = {"scale": Spec((d,), (axis,), init="ones")}
    if kind == "layernorm":
        spec["bias"] = Spec((d,), (axis,), init="zeros")
    return spec


def attn_specs(cfg: ModelConfig, *, cross: bool = False) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    spec = {
        "ln": norm_spec(d, cfg.norm),
        "wq": Spec((d, hq * hd), ("embed", "heads")),
        "wk": Spec((d, hkv * hd), ("embed", "kv_heads")),
        "wv": Spec((d, hkv * hd), ("embed", "kv_heads")),
        "wo": Spec((hq * hd, d), ("heads", "embed")),
    }
    if cfg.qk_norm and not cross:
        spec["q_norm"] = Spec((hd,), ("head_dim",), init="ones")
        spec["k_norm"] = Spec((hd,), ("head_dim",), init="ones")
    return spec


def _project_qkv(params: dict, xq: jax.Array, xkv: jax.Array, cfg: ModelConfig):
    B, Sq, _ = xq.shape
    Skv = xkv.shape[1]
    hd = cfg.resolved_head_dim
    q = (xq @ params["wq"]).reshape(B, Sq, cfg.n_heads, hd)
    k = (xkv @ params["wk"]).reshape(B, Skv, cfg.n_kv_heads, hd)
    v = (xkv @ params["wv"]).reshape(B, Skv, cfg.n_kv_heads, hd)
    if "q_norm" in params:
        q = layers.rms_norm(q, params["q_norm"], cfg.rms_eps)
        k = layers.rms_norm(k, params["k_norm"], cfg.rms_eps)
    return q, k, v


def self_attn_block(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array | None = None,
    causal: bool = True,
    q_chunk: int = 1024,
    return_kv: bool = False,
    policy: ComputePolicy | None = None,
):
    """Full-sequence (train / prefill) self attention with residual.

    With ``return_kv=True`` also returns the (possibly RoPE'd) K and V,
    which prefill places into the decode cache.  ``policy.kernels`` routes
    the norm through the fused rmsnorm kernel and attention through the
    Pallas flash kernel (logit softcap is applied in-kernel)."""
    pol = resolve_policy(policy)
    h = layers.apply_norm(x, params["ln"], cfg.norm, cfg.rms_eps,
                          use_kernel=pol.kernels)
    q, k, v = _project_qkv(params, h, h, cfg)
    if cfg.pos == "rope":
        pos = positions if positions is not None else jnp.arange(x.shape[1])
        q = layers.apply_rope(q, pos, cfg.rope_theta)
        k = layers.apply_rope(k, pos, cfg.rope_theta)
    out = layers.attention(
        q, k, v,
        causal=causal,
        sliding_window=cfg.sliding_window if causal else None,
        softcap=cfg.attn_logit_softcap,
        q_chunk=q_chunk,
        use_flash=cfg.use_flash,
        policy=pol,
    )
    B, S = x.shape[:2]
    out = out.reshape(B, S, -1) @ params["wo"]
    if return_kv:
        return x + out, k, v
    return x + out


def self_attn_decode(
    params: dict,
    x: jax.Array,              # (B, 1, d)
    cache: dict,               # {"k": (B, C, Hkv, hd), "v": ...} — C may be a ring
    pos: jax.Array,            # scalar or (B,) int32 — absolute write position(s)
    cfg: ModelConfig,
) -> tuple[jax.Array, dict]:
    """One-token cached attention.  ``pos`` is a scalar when the whole batch
    decodes in lockstep (the seed-era path) or a (B,) vector when every slot
    sits at its own position (continuous batching)."""
    h = layers.apply_norm(x, params["ln"], cfg.norm, cfg.rms_eps)
    q, k, v = _project_qkv(params, h, h, cfg)
    batched = pos.ndim == 1
    if cfg.pos == "rope":
        p = pos[:, None] if batched else pos[None]
        q = layers.apply_rope(q, p, cfg.rope_theta)
        k = layers.apply_rope(k, p, cfg.rope_theta)
    clen = cache["k"].shape[1]
    slot = jnp.mod(pos, clen)
    quant = "k_scale" in cache
    if quant:
        kq, ks = layers.kv_quantize(k)
        vq, vs = layers.kv_quantize(v)
        ck, cv = layers.cache_update(cache["k"], cache["v"], kq, vq, slot)
        if batched:
            b = jnp.arange(x.shape[0])
            cks = cache["k_scale"].at[b, slot].set(ks[:, 0])
            cvs = cache["v_scale"].at[b, slot].set(vs[:, 0])
        else:
            idx3 = (0, slot.astype(jnp.int32), 0)
            cks = jax.lax.dynamic_update_slice(cache["k_scale"], ks, idx3)
            cvs = jax.lax.dynamic_update_slice(cache["v_scale"], vs, idx3)
        k_att = layers.kv_dequantize(ck, cks, q.dtype)
        v_att = layers.kv_dequantize(cv, cvs, q.dtype)
        new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
    else:
        ck, cv = layers.cache_update(cache["k"], cache["v"], k, v, slot)
        k_att, v_att = ck.astype(q.dtype), cv.astype(q.dtype)
        new_cache = {"k": ck, "v": cv}
    # absolute position held by each ring slot (negative = not yet written);
    # for a full-length cache this reduces to arange masked beyond `pos`.
    slots = jnp.arange(clen)
    if batched:
        kv_positions = pos[:, None] - jnp.mod(pos[:, None] - slots[None, :], clen)
    else:
        kv_positions = pos - jnp.mod(pos - slots, clen)
    out = layers.attention(
        q, k_att, v_att,
        causal=True,
        q_offset=pos,
        sliding_window=cfg.sliding_window,
        softcap=cfg.attn_logit_softcap,
        kv_positions=kv_positions,
    )
    B = x.shape[0]
    out = out.reshape(B, 1, -1) @ params["wo"]
    return x + out, new_cache


def paged_kernel_route(*, quant: bool, unsharded: bool) -> bool:
    """Whether paged decode attention takes the Pallas kernel
    (``kernels/paged_attention.py``): on a TPU, over an unquantized pool,
    in a decode built without a mesh.  Elsewhere it gathers the block
    table: on the CPU the kernel would run interpreted on every tick, an
    int8 pool is dequantized by the gather, and GSPMD does not partition a
    Mosaic call."""
    return unsharded and not quant and jax.default_backend() == "tpu"


def paged_attn_decode(
    params: dict,
    x: jax.Array,              # (B, 1, d)
    cache: dict,               # pooled: {"k": (n_blocks, bs, Hkv, hd), "v": ...}
    block_table: jax.Array,    # (B, max_blocks) int32 — logical -> physical
    pos: jax.Array,            # (B,) int32 — absolute write position per slot
    cfg: ModelConfig,
    active: jax.Array | None = None,   # (B,) — inactive slots write block 0
    unsharded: bool = False,
) -> tuple[jax.Array, dict]:
    """One-token attention over a paged KV pool (vLLM-style block tables).

    The pool is shared across decode slots: slot ``b`` owns the physical
    blocks ``block_table[b, :n_alloc_b]``; logical block ``j`` holds
    positions ``[j*bs, (j+1)*bs)``.  Rows past a slot's allocation may point
    anywhere (conventionally block 0, the reserved garbage block) — their
    logical positions exceed ``pos`` so the causal mask hides them.  The new
    token's KV is scattered into the pool *before* attention, so position
    ``pos`` itself is attended; inactive slots are redirected to block 0 so
    a retired slot can never corrupt blocks reallocated to a new request.

    Attention then takes one of two paths, by :func:`paged_kernel_route`.
    The Pallas kernel reads only each active slot's first ``pos + 1``
    positions (an inactive slot reads nothing and yields zeros).  The gather
    reads the whole block table of every slot and masks by position.
    ``unsharded`` is static: ``serve_loop.build_decode_step`` sets it when
    it builds the decode without a mesh, the one case the kernel serves.
    """
    if cfg.sliding_window is not None:
        raise ValueError("paged KV pool serves full-attention caches; "
                         "SWA rings are fixed-size (whole-slot swap)")
    h = layers.apply_norm(x, params["ln"], cfg.norm, cfg.rms_eps)
    q, k, v = _project_qkv(params, h, h, cfg)
    if cfg.pos == "rope":
        q = layers.apply_rope(q, pos[:, None], cfg.rope_theta)
        k = layers.apply_rope(k, pos[:, None], cfg.rope_theta)
    B = x.shape[0]
    bs = cache["k"].shape[1]
    b = jnp.arange(B)
    phys = block_table[b, pos // bs]
    if active is not None:
        phys = jnp.where(active, phys, 0)
    off = jnp.mod(pos, bs)
    quant = "k_scale" in cache
    if quant:
        kq, ks = layers.kv_quantize(k)
        vq, vs = layers.kv_quantize(v)
        ck = cache["k"].at[phys, off].set(kq[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[phys, off].set(vq[:, 0].astype(cache["v"].dtype))
        cks = cache["k_scale"].at[phys, off].set(ks[:, 0])
        cvs = cache["v_scale"].at[phys, off].set(vs[:, 0])
        new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
    else:
        ck = cache["k"].at[phys, off].set(k[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[phys, off].set(v[:, 0].astype(cache["v"].dtype))
        new_cache = {"k": ck, "v": cv}
    if paged_kernel_route(quant=quant, unsharded=unsharded):
        from repro.kernels import ops as kernel_ops
        lengths = pos + 1 if active is None else jnp.where(active, pos + 1, 0)
        out = kernel_ops.paged_attention(
            q[:, 0], ck, cv, block_table, lengths,
            softcap=cfg.attn_logit_softcap)
    else:
        if quant:
            gk = layers.kv_dequantize(ck[block_table], cks[block_table],
                                      q.dtype)
            gv = layers.kv_dequantize(cv[block_table], cvs[block_table],
                                      q.dtype)
        else:
            gk = ck[block_table].astype(q.dtype)
            gv = cv[block_table].astype(q.dtype)
        # gathered view: (B, max_blocks, bs, ...) -> (B, max_blocks*bs, ...);
        # entry j*bs+o sits at logical position j*bs+o by construction
        skv = block_table.shape[1] * bs
        gk = gk.reshape(B, skv, *gk.shape[3:])
        gv = gv.reshape(B, skv, *gv.shape[3:])
        out = layers.attention(
            q, gk, gv,
            causal=True,
            q_offset=pos,
            softcap=cfg.attn_logit_softcap,
            kv_positions=jnp.arange(skv),
        )
    out = out.reshape(B, 1, -1) @ params["wo"]
    return x + out, new_cache


def cross_attn_block(
    params: dict,
    x: jax.Array,
    memory: jax.Array,         # encoder output (B, T, d)
    cfg: ModelConfig,
    policy: ComputePolicy | None = None,
) -> jax.Array:
    pol = resolve_policy(policy)
    h = layers.apply_norm(x, params["ln"], cfg.norm, cfg.rms_eps,
                          use_kernel=pol.kernels)
    q, k, v = _project_qkv(params, h, memory, cfg)
    out = layers.attention(q, k, v, causal=False, use_flash=cfg.use_flash,
                           policy=pol)
    B, S = x.shape[:2]
    out = out.reshape(B, S, -1) @ params["wo"]
    return x + out


def mlp_specs(cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    spec = {
        "ln": norm_spec(d, cfg.norm),
        "w1": Spec((d, ff), ("embed", "mlp")),
        "w2": Spec((ff, d), ("mlp", "embed")),
    }
    if cfg.act == "swiglu":
        spec["w3"] = Spec((d, ff), ("embed", "mlp"))
    return spec


def mlp_block(params: dict, x: jax.Array, cfg: ModelConfig,
              policy: ComputePolicy | None = None) -> jax.Array:
    pol = resolve_policy(policy)
    h = layers.apply_norm(x, params["ln"], cfg.norm, cfg.rms_eps,
                          use_kernel=pol.kernels)
    return x + layers.mlp(h, params, cfg.act, use_kernel=pol.kernels)


def segment_body(cfg: ModelConfig, policy: ComputePolicy | None,
                 q_chunk: int, *, causal: bool = True, cross: bool = False):
    """StageProgram scan body over one stacked transformer block.

    Covers the dense/vlm stack, the encoder stack (``causal=False``), the
    hybrid family's shared attention+MLP block, and — with ``cross=True`` —
    the encdec decoder block, whose cross-attention memory arrives via the
    ``carry["memory"]`` channel (it rides the pipeline with the
    activations; see ``core/stage_program.py``).
    """
    def body(lp: dict, x: jax.Array, carry: dict):
        x = self_attn_block(lp["attn"], x, cfg, causal=causal,
                            q_chunk=q_chunk, policy=policy)
        if cross:
            x = cross_attn_block(lp["cross"], x, carry["memory"], cfg,
                                 policy=policy)
        x = mlp_block(lp["mlp"], x, cfg, policy=policy)
        return x, carry
    return body
