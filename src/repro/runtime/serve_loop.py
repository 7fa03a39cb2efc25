"""Serving: prefill + batched single-token decode with sharded caches."""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import sharding as shd
from repro.models.common import ModelConfig, axes_tree, shape_dtype_tree
from repro.models.model import Model
from repro.runtime.train_loop import TrainPlan, replicated


def decode_batch_specs(cfg: ModelConfig, batch: int, *, engine: bool = False,
                       max_blocks: int | None = None) -> tuple[dict, dict]:
    """Decode-tick batch shapes + logical axes.  ``engine=True`` adds the
    serve-engine inputs: the per-slot active mask and — when ``max_blocks``
    is given (paged families) — the block table."""
    specs = {"token": jax.ShapeDtypeStruct((batch, 1), jnp.int32)}
    axes = {"token": ("batch", None)}
    if engine:
        specs["active"] = jax.ShapeDtypeStruct((batch,), jnp.bool_)
        axes["active"] = ("batch",)
        if max_blocks is not None:
            specs["block_table"] = jax.ShapeDtypeStruct(
                (batch, max_blocks), jnp.int32)
            axes["block_table"] = ("batch", None)
    if cfg.family == "encdec":
        specs["memory"] = jax.ShapeDtypeStruct(
            (batch, cfg.enc_seq_len, cfg.d_model), jnp.float32)
        axes["memory"] = ("batch", None, "act_heads")
    return specs, axes


def cache_sds_and_shardings(model: Model, batch: int, cache_len: int,
                            mesh: Mesh, plan: TrainPlan,
                            cache_specs: dict | None = None):
    """``cache_specs`` overrides the default per-slot tree — the serve
    engine passes ``model.paged_cache_specs(...)`` here so the decode jit
    shards the shared block pool instead of a per-request cache."""
    cspecs = cache_specs if cache_specs is not None \
        else model.cache_specs(batch, cache_len)
    sds = shape_dtype_tree(cspecs)
    axes = axes_tree(cspecs)
    shardings = shd.tree_shardings(sds, axes, mesh, plan.sharding_rules())
    return sds, shardings


def build_decode_step(model: Model, mesh: Mesh | None = None,
                      plan: TrainPlan | None = None,
                      batch: int | None = None, cache_len: int | None = None,
                      cache_specs: dict | None = None,
                      batch_specs: tuple[dict, dict] | None = None):
    """jit decode step; with a mesh, attaches explicit shardings + cache
    donation.  ``cache_specs`` / ``batch_specs`` override the default
    per-slot cache tree and tick-batch shapes (serve-engine pool/batch).
    Without a mesh the step is built ``unsharded``, so a paged pool may
    take the Pallas decode kernel."""
    def decode_step(params, cache, batch_in):
        return model.decode_step(params, cache, batch_in,
                                 unsharded=mesh is None)

    if mesh is None:
        return jax.jit(decode_step, donate_argnums=(1,))

    assert plan is not None and batch is not None
    assert cache_len is not None or cache_specs is not None
    rules = plan.sharding_rules()
    pshapes = model.param_shapes()
    psh = shd.tree_shardings(pshapes, model.param_axes(), mesh, rules)
    _, csh = cache_sds_and_shardings(model, batch, cache_len, mesh, plan,
                                     cache_specs=cache_specs)
    bspecs, baxes = (batch_specs if batch_specs is not None
                     else decode_batch_specs(model.cfg, batch))
    bsh = shd.tree_shardings(bspecs, baxes, mesh, rules)
    logits_sh = shd.sharding_for((batch, model.cfg.vocab_size),
                                 ("batch", "vocab"), mesh, rules)
    return jax.jit(
        decode_step,
        in_shardings=(psh, csh, bsh),
        out_shardings=(logits_sh, csh),
        donate_argnums=(1,),
    )


def build_prefill(model: Model, cache_len: int, *, with_lens: bool = False):
    """jit prefill at a fixed cache length.  ``with_lens=True`` exposes the
    per-request true-length argument (length-bucketed serving prefill)."""
    if with_lens:
        def prefill_lens(params, batch_in, lens):
            return model.prefill(params, batch_in, cache_len, lens=lens)
        return jax.jit(prefill_lens)

    def prefill(params, batch_in):
        return model.prefill(params, batch_in, cache_len)
    return jax.jit(prefill)


def greedy_generate(model: Model, params: Any, prompt: jax.Array,
                    n_steps: int, cache_len: int,
                    extras: dict | None = None) -> jax.Array:
    """Simple greedy loop used by examples/tests (CPU scale) — the
    temperature-0 reference the serve engine must token-match.  Decode runs
    through :func:`build_decode_step` so every tick donates the cache
    in place instead of copying it.  ``extras`` carries the non-token
    prefill inputs (``frames`` for encdec, ``patches`` for vlm)."""
    pb: dict[str, Any] = {"tokens": prompt}
    if extras:
        pb.update(extras)
    logits, cache = build_prefill(model, cache_len)(params, pb)
    tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    decode = build_decode_step(model)
    db_extra: dict[str, Any] = {}
    if model.cfg.family == "encdec":
        db_extra["memory"] = model.encode(params, extras["frames"])
    outs = [tok]
    for _ in range(n_steps - 1):
        logits, cache = decode(params, cache, {"token": tok, **db_extra})
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        outs.append(tok)
    return jnp.concatenate(outs, axis=1)
