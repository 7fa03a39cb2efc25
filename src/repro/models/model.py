"""Top-level Model: composes blocks per architecture family and exposes

  * ``param_specs()``    — declarative tree (shapes/axes/init) — no allocation
  * ``init(key)``        — real parameters
  * ``loss(params, batch)``      — training objective (chunked CE + MoE aux)
  * ``prefill(params, batch)``   — full-sequence forward that builds a cache
  * ``decode_step(params, cache, batch)`` — one-token serving step
  * ``cache_specs(batch)``       — declarative cache tree for the dry-run

Families: dense | moe | hybrid (zamba2) | rwkv | encdec (seamless) | vlm.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import stage_program as sp
from repro.core.compute import ComputePolicy, resolve as resolve_policy
from repro.core.stage_program import unknown_family
from repro.models import blocks, layers, moe, rwkv, ssm
from repro.models.common import (
    ModelConfig, Spec, axes_tree, init_params, is_spec, param_count,
    shape_dtype_tree, spec_tree_map,
)

MOE_AUX_COEF = 0.01


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def grad_cast(x, dtype):
    """Identity forward; casts the cotangent to ``dtype`` on the way back.

    Without this, the f32 loss cotangent infects the entire backward layer
    scan (f32 x bf16 promotes to f32): every saved-residual
    dynamic-update-slice becomes a whole-stack f32<->bf16 convert round-trip
    in the lowered HLO (measured: 11.2 TB/device of the qwen3-32b train_4k
    traffic; see EXPERIMENTS.md §Perf).  fp32 gradient *accumulation* across
    microbatches is unaffected (it happens outside the model)."""
    return x


def _grad_cast_fwd(x, dtype):
    return x, None


def _grad_cast_bwd(dtype, _, g):
    return (g.astype(dtype),)


grad_cast.defvjp(_grad_cast_fwd, _grad_cast_bwd)


def stack_specs(tree: Any, n: int) -> Any:
    return spec_tree_map(
        lambda s: dataclasses.replace(s, shape=(n,) + s.shape, axes=("layers",) + s.axes),
        tree,
    )


def _layer_specs(cfg: ModelConfig) -> dict:
    fam = cfg.family
    if fam in ("dense", "vlm"):
        return {"attn": blocks.attn_specs(cfg), "mlp": blocks.mlp_specs(cfg)}
    if fam == "moe":
        unit = {"attn": blocks.attn_specs(cfg), "moe": moe.moe_specs(cfg)}
        if cfg.moe_every > 1:
            dense = {"attn": blocks.attn_specs(cfg),
                     "mlp": blocks.mlp_specs(cfg, cfg.dense_d_ff or cfg.d_ff)}
            unit["dense"] = stack_specs(dense, cfg.moe_every - 1)
        return unit
    if fam == "hybrid":
        return ssm.mamba_specs(cfg)
    if fam == "rwkv":
        return rwkv.rwkv_specs(cfg)
    if fam == "encdec":
        return {
            "attn": blocks.attn_specs(cfg),
            "cross": blocks.attn_specs(cfg, cross=True),
            "mlp": blocks.mlp_specs(cfg),
        }
    unknown_family(cfg)


def _n_super(cfg: ModelConfig) -> int:
    per = cfg.hybrid_attn_every or cfg.n_layers
    assert cfg.n_layers % per == 0, (cfg.n_layers, per)
    return cfg.n_layers // per


def _n_stack(cfg: ModelConfig) -> int:
    """Number of stacked scan units ("layers" leading dim)."""
    if cfg.family == "moe" and cfg.moe_every > 1:
        assert cfg.n_layers % cfg.moe_every == 0, (cfg.n_layers, cfg.moe_every)
        return cfg.n_layers // cfg.moe_every
    return cfg.n_layers


class Model:
    def __init__(self, cfg: ModelConfig, compute_dtype: Any = jnp.bfloat16,
                 q_chunk: int = 1024,
                 compute: ComputePolicy | None = None,
                 comm: Any = None, ep: Any = None):
        self.cfg = cfg
        self.compute_dtype = compute_dtype
        self.q_chunk = q_chunk
        # compute-path policy (remat mode + fused-kernel routing); None keeps
        # the seed behaviour: full remat on every stack, jnp compute path
        self.compute = resolve_policy(compute)
        # communication-path hook (runtime/qcollect.py:LayerComm): when the
        # plan overlaps weight gathers with compute, run_program consumes it
        # for per-chunk gathers of the layer stack; None = plain scans
        self.comm = comm
        # expert-parallel dispatch context (models/moe.py:ExpertDispatch):
        # built by the executor for plans with ep > 1 — the MoE blocks wrap
        # their expert compute in its all-to-all sharding constraints.
        # None (serving paths, ep == 1) = replicated/data-axis experts
        self.ep = ep

    # ------------------------------------------------------------------
    # Specs / init
    # ------------------------------------------------------------------
    def param_specs(self) -> dict:
        cfg = self.cfg
        d, V = cfg.d_model, cfg.padded_vocab
        specs: dict[str, Any] = {
            "embed": Spec((V, d), ("vocab", "embed"), scale=0.02),
            "final_norm": blocks.norm_spec(d, cfg.norm),
            "layers": stack_specs(_layer_specs(cfg), _n_stack(cfg)),
        }
        if not cfg.tie_embeddings:
            specs["lm_head"] = Spec((d, V), ("embed", "vocab"), scale=0.02)
        if cfg.family == "hybrid":
            specs["shared"] = {
                "attn": blocks.attn_specs(cfg),
                "mlp": blocks.mlp_specs(cfg),
            }
        if cfg.family == "encdec":
            enc_layer = {"attn": blocks.attn_specs(cfg), "mlp": blocks.mlp_specs(cfg)}
            specs["encoder"] = {
                "in_proj": Spec((cfg.frontend_dim, d), (None, "embed")),
                "layers": stack_specs(enc_layer, cfg.enc_layers),
                "final_norm": blocks.norm_spec(d, cfg.norm),
            }
        if cfg.family == "vlm":
            specs["proj"] = Spec((cfg.frontend_dim, d), (None, "embed"))
        return specs

    def param_axes(self) -> Any:
        return axes_tree(self.param_specs())

    def param_shapes(self, dtype: Any = jnp.float32) -> Any:
        return shape_dtype_tree(self.param_specs(), dtype)

    def init(self, key: jax.Array, dtype: Any = jnp.float32) -> Any:
        return init_params(self.param_specs(), key, dtype)

    def n_params(self) -> int:
        return param_count(self.param_specs())

    # ------------------------------------------------------------------
    # Embedding / unembedding
    # ------------------------------------------------------------------
    def _embed(self, params: dict, batch: dict) -> jax.Array:
        cfg = self.cfg
        tok = jnp.take(params["embed"], batch["tokens"], axis=0)
        tok = tok.astype(self.compute_dtype)
        if cfg.family == "vlm":
            patches = (batch["patches"].astype(self.compute_dtype)
                       @ params["proj"].astype(self.compute_dtype))
            tok = jnp.concatenate([patches, tok], axis=1)
        return tok

    def _unembed_matrix(self, params: dict) -> jax.Array:
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["lm_head"]

    # ------------------------------------------------------------------
    # StageProgram lowering: the family-agnostic layer-stack IR
    # ------------------------------------------------------------------
    def stage_program(self, params: dict,
                      multi_segment: bool = False) -> sp.StageProgram:
        """Lower this family's layer stack into the StageProgram IR
        (``core/stage_program.py``): a tagged segment sequence plus the
        carry contract, consumed by both the non-pipelined executor and
        the pp>1 pipeline.  ``params`` is the *storage-dtype* tree — the
        executor casts slices to compute dtype inside each scan body so
        the scan transpose accumulates per-microbatch gradients in fp32.

        ``multi_segment=True`` (hybrid only) lowers the alternating zamba2
        pattern into an explicit two-segment-kind sequence
        ``[mamba_i, shared] * n_super`` instead of one fused "super"
        segment: each mamba segment carries ``origin``/``origin_index``
        provenance into the grouped stack so ``split_stages``'s grouped
        path rebuilds per-stage params as a pure reshape+slice (no
        re-stacking), and the weight-tied shared block becomes a
        ``tied=True`` segment closed over by every stage.
        """
        cfg = self.cfg
        pol = self.compute
        fam = cfg.family
        cast = lambda t: _cast_floating(t, self.compute_dtype)  # noqa: E731
        layer_params = params["layers"]
        aux = (sp.CarrySpec("aux", sp.ACCUM),)
        if fam in ("dense", "vlm"):
            segments = (sp.Segment(
                "block", layer_params, _n_stack(cfg),
                blocks.segment_body(cfg, pol, self.q_chunk)),)
            carries = aux
        elif fam == "moe":
            segments = (sp.Segment(
                "moe_unit", layer_params, _n_stack(cfg),
                moe.segment_body(cfg, pol, self.q_chunk, ep=self.ep)),)
            carries = aux + (sp.CarrySpec("moe_drop", sp.ACCUM),)
        elif fam == "rwkv":
            segments = (sp.Segment(
                "rwkv", layer_params, cfg.n_layers,
                rwkv.segment_body(cfg, pol)),)
            carries = aux
        elif fam == "hybrid":
            # zamba2's alternating pattern, flattened into a tagged unit
            # sequence: each "super" unit scans its per-unit mamba
            # sub-stack then applies the weight-tied shared attn+mlp block
            # (closed over, not stacked — see ssm.hybrid_segment_body).
            # The (n_super, per, ...) grouping is a pure reshape of the
            # layer stack, so the pipelined stage split stays a local
            # reshape of the pipe-sharded leading dim.
            n_super = _n_super(cfg)
            per = cfg.n_layers // n_super
            grouped = jax.tree.map(
                lambda a: a.reshape(n_super, per, *a.shape[1:]), layer_params)
            if multi_segment:
                # explicit [mamba_i, shared] * n_super sequence (see
                # docstring); the dim-1 lead on the shared params is a pure
                # reshape so the tied segment scans like any other
                shared_stacked = jax.tree.map(lambda a: a[None],
                                              params["shared"])
                mamba_body = ssm.segment_body(cfg, pol)
                shared_body = blocks.segment_body(cfg, pol, self.q_chunk)
                seg_list = []
                for i in range(n_super):
                    seg_list.append(sp.Segment(
                        "mamba", jax.tree.map(lambda a, _i=i: a[_i], grouped),
                        per, mamba_body, origin=grouped, origin_index=i))
                    seg_list.append(sp.Segment(
                        "shared", shared_stacked, 1, shared_body, tied=True))
                segments = tuple(seg_list)
            else:
                segments = (sp.Segment(
                    "super", grouped, n_super,
                    ssm.hybrid_segment_body(cfg, pol, self.q_chunk,
                                            params["shared"], cast)),)
            carries = aux
        elif fam == "encdec":
            segments = (sp.Segment(
                "decoder", layer_params, cfg.n_layers,
                blocks.segment_body(cfg, pol, self.q_chunk, cross=True)),)
            carries = aux + (sp.CarrySpec("memory", sp.INPUT),)
        else:
            unknown_family(cfg)
        return sp.StageProgram(segments, carries, cast=cast)

    def encoder_program(self, params: dict) -> sp.StageProgram:
        """The encdec encoder stack as its own carry-less StageProgram —
        the first half of the two-program composition whose output becomes
        the decoder program's ``memory`` carry."""
        cfg = self.cfg
        return sp.StageProgram(
            (sp.Segment("encoder", params["encoder"]["layers"],
                        cfg.enc_layers,
                        blocks.segment_body(cfg, self.compute, self.q_chunk,
                                            causal=False)),),
            carry_spec=(),
            cast=lambda t: _cast_floating(t, self.compute_dtype))

    def encode(self, params: dict, frames: jax.Array) -> jax.Array:
        """Audio/encoder stack: frame embeddings (B, T, fd) -> memory (B, T, d)."""
        cfg = self.cfg
        pol = self.compute
        enc = params["encoder"]
        x = frames.astype(self.compute_dtype) @ enc["in_proj"].astype(self.compute_dtype)
        prog = self.encoder_program(params)
        x, _ = sp.run_program(prog, x, {}, policy=pol)
        return layers.apply_norm(x, enc["final_norm"], cfg.norm, cfg.rms_eps,
                                 use_kernel=pol.kernels)

    # ------------------------------------------------------------------
    # Forward / loss
    # ------------------------------------------------------------------
    def hidden_states(self, params: dict, batch: dict
                      ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Returns (final-normed hidden states, moe aux loss, moe drop)."""
        cfg = self.cfg
        cparams = _cast_floating(params, self.compute_dtype,
                                 skip=("state",))  # weights in compute dtype
        x = self._embed(cparams, batch)
        inputs = {}
        if cfg.family == "encdec":
            inputs["memory"] = self.encode(params, batch["frames"])
        prog = self.stage_program(params)
        x, carry = sp.run_program(prog, x, prog.init_carry(inputs),
                                  policy=self.compute, comm=self.comm)
        x = layers.apply_norm(x, cparams["final_norm"], cfg.norm, cfg.rms_eps,
                              use_kernel=self.compute.kernels)
        return (x, carry.get("aux", jnp.float32(0.0)),
                carry.get("moe_drop", jnp.float32(0.0)))

    def logits(self, params: dict, batch: dict) -> jax.Array:
        h, _, _ = self.hidden_states(params, batch)
        W = self._unembed_matrix(params).astype(self.compute_dtype)
        return (h @ W).astype(jnp.float32)[..., :self.cfg.vocab_size]

    def _loss_from_hidden(self, params: dict, h: jax.Array, batch: dict,
                          aux: jax.Array,
                          drop: jax.Array | float = 0.0) -> tuple[jax.Array, dict]:
        """Shared LM-loss tail: final-normed hidden states -> (loss, metrics)."""
        cfg = self.cfg
        # keep the backward signal through the stack in compute dtype
        h = grad_cast(h, self.compute_dtype)
        if cfg.family == "vlm":
            h = h[:, cfg.num_patches:, :]
        labels = batch["tokens"][:, 1:]
        h = h[:, :-1, :]
        mask = batch.get("loss_mask")
        mask = jnp.ones_like(labels, jnp.float32) if mask is None else mask[:, 1:]
        W = self._unembed_matrix(params).astype(self.compute_dtype)
        ce = _chunked_cross_entropy(h, W, labels, mask,
                                    valid_vocab=self.cfg.vocab_size,
                                    policy=self.compute)
        total = ce + MOE_AUX_COEF * aux / max(cfg.n_layers, 1)
        # per-block mean of the accumulated measured drop fraction
        n_moe = _n_stack(cfg) if cfg.family == "moe" else 1
        return total, {"ce": ce, "moe_aux": aux,
                       "moe_drop": jnp.float32(drop) / n_moe}

    def loss(self, params: dict, batch: dict) -> tuple[jax.Array, dict]:
        h, aux, drop = self.hidden_states(params, batch)
        return self._loss_from_hidden(params, h, batch, aux, drop)

    def loss_pipelined(self, params: dict, batch: dict, *, mesh: Any,
                       pp: int, n_micro: int, virtual_stages: int = 1,
                       pipe_axis: str = "pipe", data_axis: str = "data",
                       multi_segment: bool = False) -> tuple[jax.Array, dict]:
        """Same objective as :meth:`loss`, with the layer stack run as a
        ``pp``-stage (``virtual_stages``-interleaved when > 1) pipeline —
        for *every* model family, via the StageProgram IR.

        The batch is split into ``n_micro`` microbatches that flow through
        :func:`repro.core.pipeline.pipeline_spmd`; the program's carries
        (MoE aux accumulator, encdec cross-attention memory) ride the same
        collective-permute channel as the activations.  Embed / final norm
        / CE head (and the encdec encoder, the first program of the
        two-program composition) run on every pipe rank — they stay
        TP/DP-sharded by GSPMD exactly as in the non-pipelined path.
        Mathematically this matches the pp==1 path at the same ``n_micro``
        (per-microbatch MoE routing and aux means included) — the pipeline
        is pure scheduling, and the in-body param cast keeps the
        cross-microbatch gradient accumulation of the pipeline scan's
        transpose in fp32 (see ``core/stage_program.py``).
        """
        from repro.core import pipeline as pipe

        cfg = self.cfg
        cparams = _cast_floating(params, self.compute_dtype)
        x = self._embed(cparams, batch)
        B = x.shape[0]
        if B % n_micro != 0:
            raise ValueError(f"batch {B} not divisible by n_micro={n_micro}")

        pol = self.compute
        prog = self.stage_program(params, multi_segment=multi_segment)
        stage_params, stage_fn = sp.split_stages(
            prog, pp * virtual_stages, policy=pol)

        inputs = {}
        if cfg.family == "encdec":
            inputs["memory"] = self.encode(params, batch["frames"])

        mbs = B // n_micro

        def split(a):
            return a.reshape(n_micro, mbs, *a.shape[1:])

        payload = {"x": split(x)}
        for cs in prog.carry_spec:
            payload[cs.name] = (jnp.zeros((n_micro,), jnp.float32)
                                if cs.kind == sp.ACCUM
                                else split(inputs[cs.name]))

        pipelined = pipe.pipeline_spmd(
            stage_fn, mesh, n_stages=pp, v=virtual_stages,
            pipe_axis=pipe_axis, data_axis=data_axis)
        out = pipelined(stage_params, payload)
        h = out["x"].reshape(B, *x.shape[1:])
        # per-microbatch aux/drop means match the pp==1 gas scan's average
        aux = (jnp.mean(out["aux"]) if "aux" in out else jnp.float32(0.0))
        drop = (jnp.mean(out["moe_drop"]) if "moe_drop" in out
                else jnp.float32(0.0))
        h = layers.apply_norm(h, cparams["final_norm"], cfg.norm, cfg.rms_eps,
                              use_kernel=pol.kernels)
        return self._loss_from_hidden(params, h, batch, aux, drop)

    # ------------------------------------------------------------------
    # Caches
    # ------------------------------------------------------------------
    def _attn_cache_len(self, cache_len: int) -> int:
        if self.cfg.sliding_window is not None:
            return min(cache_len, self.cfg.sliding_window)
        return cache_len

    def paged_cache_specs(self, n_slots: int, n_blocks: int,
                          block_size: int) -> dict:
        """Declarative cache tree for the paged/block KV pool (serving):
        every KV leaf of :meth:`cache_specs` becomes a pool of ``n_blocks``
        physical blocks of ``block_size`` positions, shared across decode
        slots via a block table (see ``blocks.paged_attn_decode`` and
        ``runtime/serve_engine.py``); ``pos`` becomes a per-slot vector.

        Only full-attention KV families page — fixed-size caches (SWA
        rings, SSD/wkv state) swap whole slots instead."""
        if not self.paged_cacheable:
            raise ValueError(
                f"{self.cfg.family} (sliding_window="
                f"{self.cfg.sliding_window}) has a fixed-size cache; paged "
                "pools serve full-attention KV families only")
        specs = self.cache_specs(1, block_size)

        def repage(s: Spec) -> Spec:
            # kv leaves are (layers..., 1, block_size, ...): swap the unit
            # batch dim for the physical-block dim
            i = s.axes.index("cache_batch")
            assert s.shape[i] == 1, s
            return dataclasses.replace(
                s, shape=s.shape[:i] + (n_blocks,) + s.shape[i + 1:],
                axes=s.axes[:i] + ("cache_blocks",) + s.axes[i + 1:])

        return {
            "pos": Spec((n_slots,), ("cache_batch",), init="zeros",
                        dtype=jnp.int32),
            "layers": spec_tree_map(repage, specs["layers"]),
        }

    @property
    def paged_cacheable(self) -> bool:
        """True when this family's decode cache is a growing full-attention
        KV (pageable); False for fixed-size caches (ring KV, SSD/wkv
        state, hybrid) that the serve engine slot-swaps instead."""
        return (self.cfg.family in ("dense", "vlm", "moe", "encdec")
                and self.cfg.sliding_window is None)

    def cache_specs(self, batch: int, cache_len: int) -> dict:
        cfg = self.cfg
        hd = cfg.resolved_head_dim
        clen = self._attn_cache_len(cache_len)

        def kv():
            dt = jnp.int8 if cfg.kv_quant else self.compute_dtype
            spec = {
                "k": Spec((batch, clen, cfg.n_kv_heads, hd),
                          ("cache_batch", "cache_seq", "cache_heads", "head_dim"),
                          init="zeros", dtype=dt),
                "v": Spec((batch, clen, cfg.n_kv_heads, hd),
                          ("cache_batch", "cache_seq", "cache_heads", "head_dim"),
                          init="zeros", dtype=dt),
            }
            if cfg.kv_quant:
                spec["k_scale"] = Spec((batch, clen, cfg.n_kv_heads),
                                       ("cache_batch", "cache_seq", "cache_heads"),
                                       init="zeros", dtype=jnp.float32)
                spec["v_scale"] = Spec((batch, clen, cfg.n_kv_heads),
                                       ("cache_batch", "cache_seq", "cache_heads"),
                                       init="zeros", dtype=jnp.float32)
            return spec
        specs: dict[str, Any] = {"pos": Spec((), (), init="zeros", dtype=jnp.int32)}
        if cfg.family == "moe" and cfg.moe_every > 1:
            unit = {"moe_kv": kv(), "dense": stack_specs(kv(), cfg.moe_every - 1)}
            specs["layers"] = stack_specs(unit, _n_stack(cfg))
        elif cfg.family in ("dense", "vlm", "moe", "encdec"):
            specs["layers"] = stack_specs(kv(), cfg.n_layers)
        elif cfg.family == "rwkv":
            specs["layers"] = stack_specs(
                rwkv.rwkv_cache_specs(cfg, batch, self.compute_dtype), cfg.n_layers)
        elif cfg.family == "hybrid":
            specs["layers"] = stack_specs(
                ssm.mamba_cache_specs(cfg, batch, self.compute_dtype), cfg.n_layers)
            specs["shared"] = stack_specs(kv(), _n_super(cfg))
        else:
            unknown_family(cfg)
        return specs

    def init_cache(self, batch: int, cache_len: int) -> dict:
        return init_params(self.cache_specs(batch, cache_len), jax.random.PRNGKey(0))

    # ------------------------------------------------------------------
    # Prefill: full-sequence forward that fills the cache
    # ------------------------------------------------------------------
    def prefill(self, params: dict, batch: dict, cache_len: int,
                lens: jax.Array | None = None) -> tuple[jax.Array, dict]:
        """Returns (last-token logits (B, V), cache at pos=S).

        ``lens`` (B,) int32 — per-request true token counts for
        right-padded prompts (length-bucketed serving prefill): logits are
        taken at each request's last *real* token, KV/ring placement uses
        the true length (pad positions never enter the cache — the causal
        mask already keeps them out of every real token's attention), and
        ``cache["pos"]`` becomes the per-slot position vector.  Recurrent
        state (rwkv/hybrid SSD) summarizes the whole padded sequence, so
        those families must be prefilled at exact length (``lens == S``) —
        the serve engine does."""
        cfg = self.cfg
        pol = self.compute
        cparams = _cast_floating(params, self.compute_dtype)
        x = self._embed(cparams, batch)
        B, S = x.shape[:2]
        clen = self._attn_cache_len(cache_len)
        patch_off = cfg.num_patches if cfg.family == "vlm" else 0
        if lens is None:
            total = None
            cache: dict[str, Any] = {"pos": jnp.int32(S)}
        else:
            total = lens.astype(jnp.int32) + patch_off   # positions written
            cache = {"pos": total}

        if cfg.family == "moe" and cfg.moe_every > 1:
            def body(carry, lp):
                x, aux = carry

                def dense_body(c, dlp):
                    c, k, v = blocks.self_attn_block(
                        dlp["attn"], c, cfg, causal=True,
                        q_chunk=self.q_chunk, return_kv=True, policy=pol)
                    c = blocks.mlp_block(dlp["mlp"], c, cfg, policy=pol)
                    return c, _kv_into_cache(k, v, clen, cfg.kv_quant, lens=total)

                x, dense_kvs = jax.lax.scan(dense_body, x, lp["dense"])
                x, k, v = blocks.self_attn_block(lp["attn"], x, cfg, causal=True,
                                                 q_chunk=self.q_chunk,
                                                 return_kv=True, policy=pol)
                x, a, _ = moe.moe_block(lp["moe"], x, cfg, policy=pol)
                return (x, aux + a), {"moe_kv": _kv_into_cache(k, v, clen, cfg.kv_quant, lens=total),
                                      "dense": dense_kvs}

            (x, _), kvs = jax.lax.scan(pol.checkpoint(body), (x, jnp.float32(0.0)),
                                       cparams["layers"])
            cache["layers"] = kvs
        elif cfg.family in ("dense", "vlm", "moe"):
            def body(carry, lp):
                x, aux = carry
                x, k, v = blocks.self_attn_block(lp["attn"], x, cfg, causal=True,
                                                 q_chunk=self.q_chunk,
                                                 return_kv=True, policy=pol)
                if cfg.family == "moe":
                    x, a, _ = moe.moe_block(lp["moe"], x, cfg, policy=pol)
                    aux = aux + a
                else:
                    x = blocks.mlp_block(lp["mlp"], x, cfg, policy=pol)
                return (x, aux), _kv_into_cache(k, v, clen, cfg.kv_quant, lens=total)

            (x, _), kvs = jax.lax.scan(pol.checkpoint(body), (x, jnp.float32(0.0)),
                                       cparams["layers"])
            cache["layers"] = kvs
        elif cfg.family == "encdec":
            memory = self.encode(cparams, batch["frames"])

            def body(carry, lp):
                x, _ = carry
                x, k, v = blocks.self_attn_block(lp["attn"], x, cfg, causal=True,
                                                 q_chunk=self.q_chunk,
                                                 return_kv=True, policy=pol)
                x = blocks.cross_attn_block(lp["cross"], x, memory, cfg,
                                            policy=pol)
                x = blocks.mlp_block(lp["mlp"], x, cfg, policy=pol)
                return (x, jnp.float32(0.0)), _kv_into_cache(k, v, clen, cfg.kv_quant, lens=total)

            (x, _), kvs = jax.lax.scan(pol.checkpoint(body), (x, jnp.float32(0.0)),
                                       cparams["layers"])
            cache["layers"] = kvs
        elif cfg.family == "rwkv":
            def body(x, lp):
                x, c = rwkv.rwkv_prefill(lp, x, cfg, policy=pol)
                return x, c
            x, cs = jax.lax.scan(pol.checkpoint(body), x, cparams["layers"])
            cache["layers"] = cs
        elif cfg.family == "hybrid":
            n_super = _n_super(cfg)
            per = cfg.n_layers // n_super
            grouped = jax.tree.map(
                lambda a: a.reshape(n_super, per, *a.shape[1:]), cparams["layers"])
            shared = cparams["shared"]

            def super_body(x, lp_group):
                def inner(x2, lp):
                    return ssm.mamba_prefill(lp, x2, cfg, policy=pol)
                x, mcs = jax.lax.scan(inner, x, lp_group)
                x, k, v = blocks.self_attn_block(shared["attn"], x, cfg, causal=True,
                                                 q_chunk=self.q_chunk,
                                                 return_kv=True, policy=pol)
                x = blocks.mlp_block(shared["mlp"], x, cfg, policy=pol)
                return x, (mcs, _kv_into_cache(k, v, clen, cfg.kv_quant, lens=total))

            x, (mcs, kvs) = jax.lax.scan(pol.checkpoint(super_body), x, grouped)
            cache["layers"] = jax.tree.map(
                lambda a: a.reshape(cfg.n_layers, *a.shape[2:]), mcs)
            cache["shared"] = kvs
        else:
            unknown_family(cfg)

        x = layers.apply_norm(x, cparams["final_norm"], cfg.norm, cfg.rms_eps)
        W = self._unembed_matrix(cparams)
        last = x[:, -1, :] if total is None else x[jnp.arange(B), total - 1]
        logits = (last @ W).astype(jnp.float32)[..., :cfg.vocab_size]
        return logits, cache

    # ------------------------------------------------------------------
    # Decode
    # ------------------------------------------------------------------
    def decode_step(self, params: dict, cache: dict, batch: dict, *,
                    unsharded: bool = False) -> tuple[jax.Array, dict]:
        """One serving step: batch = {"token": (B, 1)} (+ "memory" for encdec).

        Serving-engine extensions (all optional, absent = training-era
        semantics): ``cache["pos"]`` may be a per-slot (B,) vector;
        ``batch["active"]`` (B,) bool freezes finished/idle slots (their
        cache state and pos don't advance); ``batch["block_table"]``
        (B, max_blocks) int32 switches full-attention KV families to the
        paged pool layout (``paged_cache_specs``), where inactive slots'
        writes are redirected to the reserved garbage block 0.
        ``unsharded`` (static) says the step is built without a mesh, which
        lets the paged pool take the Pallas kernel
        (``blocks.paged_attn_decode``).

        Returns (logits (B, V), updated cache)."""
        cfg = self.cfg
        cparams = _cast_floating(params, self.compute_dtype)
        pos = cache["pos"]
        active = batch.get("active")
        bt = batch.get("block_table")
        x = jnp.take(cparams["embed"], batch["token"], axis=0)
        pos_t = pos  # vlm positions already include the patch offset

        step = jnp.int32(1) if active is None else active.astype(pos.dtype)
        new_cache: dict[str, Any] = {"pos": pos + step}

        def attn(ap, c, kvc):
            if bt is not None:
                return blocks.paged_attn_decode(ap, c, kvc, bt, pos_t, cfg,
                                                active=active,
                                                unsharded=unsharded)
            return blocks.self_attn_decode(ap, c, kvc, pos_t, cfg)

        if cfg.family == "moe" and cfg.moe_every > 1:
            def body(x, xs):
                lp, cl = xs

                def dense_body(c, ys):
                    dlp, dcl = ys
                    c, nkv = attn(dlp["attn"], c, dcl)
                    return blocks.mlp_block(dlp["mlp"], c, cfg), nkv

                x, ndense = jax.lax.scan(dense_body, x, (lp["dense"], cl["dense"]))
                x, nkv = attn(lp["attn"], x, cl["moe_kv"])
                x, _, _ = moe.moe_block(lp["moe"], x, cfg)
                return x, {"moe_kv": nkv, "dense": ndense}
            x, ncs = jax.lax.scan(body, x, (cparams["layers"], cache["layers"]))
            new_cache["layers"] = ncs
        elif cfg.family in ("dense", "vlm", "moe"):
            def body(x, xs):
                lp, cl = xs
                x, nc = attn(lp["attn"], x, cl)
                if cfg.family == "moe":
                    x, _, _ = moe.moe_block(lp["moe"], x, cfg)
                else:
                    x = blocks.mlp_block(lp["mlp"], x, cfg)
                return x, nc
            x, ncs = jax.lax.scan(body, x, (cparams["layers"], cache["layers"]))
            new_cache["layers"] = ncs
        elif cfg.family == "encdec":
            memory = batch["memory"].astype(self.compute_dtype)

            def body(x, xs):
                lp, cl = xs
                x, nc = attn(lp["attn"], x, cl)
                x = blocks.cross_attn_block(lp["cross"], x, memory, cfg)
                x = blocks.mlp_block(lp["mlp"], x, cfg)
                return x, nc
            x, ncs = jax.lax.scan(body, x, (cparams["layers"], cache["layers"]))
            new_cache["layers"] = ncs
        elif cfg.family == "rwkv":
            def body(x, xs):
                lp, cl = xs
                return rwkv.rwkv_decode(lp, x, cl, cfg, policy=self.compute)
            x, ncs = jax.lax.scan(body, x, (cparams["layers"], cache["layers"]))
            new_cache["layers"] = ncs
        elif cfg.family == "hybrid":
            n_super = _n_super(cfg)
            per = cfg.n_layers // n_super
            grouped = jax.tree.map(
                lambda a: a.reshape(n_super, per, *a.shape[1:]), cparams["layers"])
            gcache = jax.tree.map(
                lambda a: a.reshape(n_super, per, *a.shape[1:]), cache["layers"])
            shared = cparams["shared"]

            def super_body(x, xs):
                lp_group, mc_group, skv = xs

                def inner(x2, ys):
                    lp, mc = ys
                    return ssm.mamba_decode(lp, x2, mc, cfg, policy=self.compute)
                x, nmc = jax.lax.scan(inner, x, (lp_group, mc_group))
                x, nkv = blocks.self_attn_decode(shared["attn"], x, skv, pos_t, cfg)
                x = blocks.mlp_block(shared["mlp"], x, cfg)
                return x, (nmc, nkv)

            x, (nmc, nkv) = jax.lax.scan(super_body, x,
                                         (grouped, gcache, cache["shared"]))
            new_cache["layers"] = jax.tree.map(
                lambda a: a.reshape(cfg.n_layers, *a.shape[2:]), nmc)
            new_cache["shared"] = nkv
        else:
            unknown_family(cfg)

        if active is not None and bt is None:
            # slot-swap mode: a frozen slot's ring/state must not drift
            # between its finish and the next admission into that slot
            new_cache["layers"] = _freeze_inactive(
                new_cache["layers"], cache["layers"], active)
            if "shared" in new_cache:
                new_cache["shared"] = _freeze_inactive(
                    new_cache["shared"], cache["shared"], active)

        x = layers.apply_norm(x, cparams["final_norm"], cfg.norm, cfg.rms_eps)
        W = self._unembed_matrix(cparams)
        logits = (x[:, 0, :] @ W).astype(jnp.float32)[..., :cfg.vocab_size]
        return logits, new_cache


def _freeze_inactive(new: Any, old: Any, active: jax.Array) -> Any:
    """Keep the old cache state for inactive decode slots.  Stacked cache
    leaves carry batch on axis 1 (axis 0 is the layer stack)."""
    def leaf(n, o):
        keep = active.reshape((1, active.shape[0]) + (1,) * (n.ndim - 2))
        return jnp.where(keep, n, o)
    return jax.tree.map(leaf, new, old)


def _ring_place(x: jax.Array, clen: int,
                lens: jax.Array | None = None) -> jax.Array:
    """Place full-sequence entries (B, S, ...) into a length-``clen`` ring,
    slot(t) = t % clen (matches decode-time writes).

    With per-request ``lens`` (right-padded prompts), the last real token of
    request b sits at t = lens[b]-1; slot s then holds timeline position
    t(s) = (lens-1) - ((lens-1-s) mod clen), dropped when t < 0 (slot not
    yet reached).  This reduces to slot(t) = t % clen when lens == S, and to
    plain copy+zero-tail when clen >= S — one formula for both the full
    cache and the SWA ring."""
    B, S = x.shape[:2]
    if lens is None:
        if S == clen:
            return x
        if S < clen:
            pad = [(0, 0), (0, clen - S)] + [(0, 0)] * (x.ndim - 2)
            return jnp.pad(x, pad)
        slots = np.arange(S - clen, S) % clen
        out = jnp.zeros((B, clen, *x.shape[2:]), x.dtype)
        return out.at[:, slots].set(x[:, S - clen:])
    last = lens.astype(jnp.int32)[:, None] - 1          # (B, 1)
    slots = jnp.arange(clen)[None, :]                   # (1, clen)
    t = last - jnp.mod(last - slots, clen)              # (B, clen)
    valid = t >= 0
    idx = jnp.clip(t, 0, S - 1).reshape(B, clen, *([1] * (x.ndim - 2)))
    gathered = jnp.take_along_axis(x, idx, axis=1)
    keep = valid.reshape(B, clen, *([1] * (x.ndim - 2)))
    return jnp.where(keep, gathered, jnp.zeros((), x.dtype))


def _kv_into_cache(k: jax.Array, v: jax.Array, clen: int, quant: bool = False,
                   lens: jax.Array | None = None):
    if quant:
        kq, ks = layers.kv_quantize(k)
        vq, vs = layers.kv_quantize(v)
        return {"k": _ring_place(kq, clen, lens), "v": _ring_place(vq, clen, lens),
                "k_scale": _ring_place(ks, clen, lens),
                "v_scale": _ring_place(vs, clen, lens)}
    return {"k": _ring_place(k, clen, lens), "v": _ring_place(v, clen, lens)}


def _cast_floating(tree: Any, dtype: Any, skip: tuple = ()) -> Any:
    return jax.tree.map(
        lambda x: x.astype(dtype)
        if (hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)) else x,
        tree,
    )


def _chunked_cross_entropy(h: jax.Array, W: jax.Array, labels: jax.Array,
                           mask: jax.Array, target_chunk: int = 8192,
                           valid_vocab: int | None = None,
                           policy: ComputePolicy | None = None) -> jax.Array:
    """CE over (B, S, d) hidden vs (d, V) unembedding, chunked over tokens so
    the full (N, V) logits tensor is never materialized (vocab up to 256k).

    ``policy.kernels`` routes through the fused Pallas online-logsumexp
    kernel (per-token losses; the mask/normalization stay outside).  The
    chunk body stays under full ``jax.checkpoint`` regardless of
    ``policy.remat``: saving the per-chunk logits as residuals would
    materialize exactly the (N, V) tensor this formulation exists to avoid —
    the remat knob governs the layer stacks, not this loss tail.
    """
    pol = resolve_policy(policy)
    B, S, d = h.shape
    N = B * S
    hf = h.reshape(N, d)
    yf = labels.reshape(N)
    mf = mask.reshape(N)
    if pol.kernels:
        from repro.kernels import ops as kernel_ops
        losses = kernel_ops.cross_entropy_tokens(hf, W, yf, valid_vocab)
        return jnp.sum(losses * mf) / jnp.maximum(jnp.sum(mf), 1.0)
    chunk = N
    for c in (target_chunk, 4096, 2048, 1024, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1):
        if c <= N and N % c == 0:
            chunk = c
            break
    n_chunks = N // chunk

    Vp = W.shape[-1]
    pad_mask = (jnp.arange(Vp) >= valid_vocab) if (valid_vocab is not None
                                                   and valid_vocab < Vp) else None

    @jax.checkpoint
    def body(carry, xs):
        loss_sum, count = carry
        hc, yc, mc = xs
        logits = (hc @ W).astype(jnp.float32)
        if pad_mask is not None:
            logits = jnp.where(pad_mask[None, :], -1e30, logits)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
        loss_sum = loss_sum + jnp.sum((logz - ll) * mc)
        count = count + jnp.sum(mc)
        return (loss_sum, count), None

    xs = (hf.reshape(n_chunks, chunk, d), yf.reshape(n_chunks, chunk),
          mf.reshape(n_chunks, chunk))
    (loss_sum, count), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)), xs)
    return loss_sum / jnp.maximum(count, 1.0)


@functools.lru_cache(maxsize=64)
def _cached_model(cfg: ModelConfig, dtype_name: str, q_chunk: int,
                  compute: ComputePolicy | None) -> Model:
    return Model(cfg, jnp.dtype(dtype_name), q_chunk, compute)


def build_model(cfg: ModelConfig, compute_dtype: Any = jnp.bfloat16,
                q_chunk: int = 1024,
                compute: ComputePolicy | None = None) -> Model:
    return _cached_model(cfg, jnp.dtype(compute_dtype).name, q_chunk, compute)
