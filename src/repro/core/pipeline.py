"""Pipeline parallelism: circular microbatch pipeline over a "pipe" mesh axis.

The paper's second parallel dimension (§II.C): the model's layers are split
into p stages, each stage pinned to one device group; microbatches flow
through the ring via ``lax.ppermute``.  JAX-native equivalent of
GPipe/PipeDream scheduling:

  * forward: stage s processes microbatch j at tick t = j + s,
  * total ticks T = m + p - 1, so the idle (bubble) fraction per device is
    (p-1)/(m+p-1) ~= (p-1)/m — exactly the paper's bubble formula,
  * backward runs through ``jax.grad`` of the whole pipelined computation
    (an all-forward-then-all-backward GPipe schedule; 1F1B's memory benefit
    is modeled analytically in ``core/bubble.py`` — DESIGN.md §2).

``stage_fn(stage_params, x) -> x`` is applied once per device per tick;
stage parameters live sharded over the pipe axis (leading ``stage`` dim).

Two implementations coexist:

  * :func:`pipeline_apply` / :func:`pipeline_apply_interleaved` — explicit
    ``shard_map`` ring with manual ``ppermute``; requires every mesh axis to
    be manual, so it only composes with TP/DP via hand-written collectives.
    Kept for the pipe-only analysis meshes, tests, and examples.
  * :func:`pipeline_spmd` — the unified 3D executor's path: ``vmap`` over
    the stage dim plus ``jnp.roll`` shifts under plain GSPMD.  XLA lowers
    the roll of a pipe-sharded dim to the same collective-permute as the
    manual ring, while the "data"/"model" axes stay auto-sharded — this is
    what lets one ``jit_train_step`` express any (dp, tp, pp) plan.  It
    moves arbitrary *payload pytrees* (activations + the StageProgram
    carries: MoE aux accumulators, encdec cross-attention memory) and, for
    ``virtual_stages > 1``, realizes Megatron's interleaved-1F1B
    round-robin stage assignment whose bubble shrinks with v
    (:func:`spmd_idle_fraction` vs ``core/bubble.py``).

Stage functions for any model family come from
``repro.core.stage_program.split_stages`` (the family-agnostic IR);
:func:`layer_stage_fn` adapts a bare ``layer_fn`` through the same IR for
the manual-ring/analysis paths.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    mesh: Mesh,
    *,
    pipe_axis: str = "pipe",
) -> Callable[[Any, jax.Array], jax.Array]:
    """Returns pipelined(stacked_stage_params, microbatches).

    ``stacked_stage_params``: pytree, leading dim = n_stages (= pipe axis
    size), sharded over ``pipe_axis``.
    ``microbatches``: (m, mbs, ...) — replicated over the pipe axis.
    Returns (m, mbs, ...) outputs after all stages (replicated).
    """
    p = mesh.shape[pipe_axis]
    perm = [(i, (i + 1) % p) for i in range(p)]

    def inner(params_local, micro):
        params_local = jax.tree.map(lambda a: a[0], params_local)
        idx = jax.lax.axis_index(pipe_axis)
        is_first = idx == 0
        is_last = idx == p - 1
        m = micro.shape[0]
        T = m + p - 1
        zero = jnp.zeros_like(micro[0])

        def tick(recv, t):
            mb = jnp.clip(t, 0, m - 1)
            x0 = jax.lax.dynamic_index_in_dim(micro, mb, 0, keepdims=False)
            inp = jnp.where(is_first, x0, recv)
            out = stage_fn(params_local, inp)
            nxt = jax.lax.ppermute(out, pipe_axis, perm)
            return nxt, out

        _, ys = jax.lax.scan(tick, zero, jnp.arange(T))
        outs = jax.lax.dynamic_slice_in_dim(ys, p - 1, m, axis=0)
        outs = jnp.where(is_last, outs, 0)
        return jax.lax.psum(outs, pipe_axis)

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(pipe_axis), P()),
        out_specs=P(),
        check_vma=False,
    )


def pipeline_apply_interleaved(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    mesh: Mesh,
    *,
    v: int,
    pipe_axis: str = "pipe",
) -> Callable[[Any, jax.Array], jax.Array]:
    """Interleaved virtual stages: device d hosts logical stages
    {d, d+p, ..., d+(v-1)p}; activations loop the ring v times.

    Microbatches are injected in waves of (at most) p, each wave taking
    v*p + w - 1 ticks — the circular analogue of Megatron's interleaved
    1F1B whose bubble is (p-1)/(v*m + p - 1) (see core/bubble.py; matches
    the measured tick counts in tests/test_pipeline_interleaved.py).

    ``stacked_stage_params``: leading dims (v*p, layers_per_stage, ...); the
    v*p logical stages are distributed so slot k of device d is logical
    stage k*p + d.
    """
    p = mesh.shape[pipe_axis]
    perm = [(i, (i + 1) % p) for i in range(p)]

    def inner(params_local, micro):
        # params_local: (v, layers_per_stage, ...) — this device's slots
        params_local = jax.tree.map(lambda a: a[0], params_local)
        idx = jax.lax.axis_index(pipe_axis)
        is_first = idx == 0
        is_last = idx == p - 1
        m = micro.shape[0]
        waves = -(-m // p)
        zero = jnp.zeros_like(micro[0])
        S = v * p

        def run_wave(w_start, w_size_ticks):
            def tick(recv, t):
                # device d serves the item at logical stage s = t - d (ring),
                # using local slot s // p
                s = t - idx
                slot = jnp.clip(jnp.floor_divide(s, p), 0, v - 1)
                mb = jnp.clip(w_start + t, w_start, m - 1)
                x0 = jax.lax.dynamic_index_in_dim(micro, mb, 0, keepdims=False)
                inp = jnp.where((slot == 0) & is_first & (t < p), x0, recv)
                lp = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, slot, 0, keepdims=False),
                    params_local)
                out = stage_fn(lp, inp)
                nxt = jax.lax.ppermute(out, pipe_axis, perm)
                return nxt, out

            T = S + p - 1
            _, ys = jax.lax.scan(tick, zero, jnp.arange(T))
            outs = jax.lax.dynamic_slice_in_dim(ys, S - 1, p, axis=0)
            outs = jnp.where(is_last, outs, 0)
            return jax.lax.psum(outs.astype(jnp.float32), pipe_axis).astype(outs.dtype)

        wave_outs = []
        for w in range(waves):
            w_size = min(p, m - w * p)
            wave_outs.append(run_wave(w * p, w_size)[:w_size])
        return jnp.concatenate(wave_outs, axis=0)

    def reshape_params(stacked, micro):
        # (v*p, lps, ...) -> per-device (v, lps, ...): slot k = stage k*p + d
        def re(a):
            vp = a.shape[0]
            assert vp == v * p, (vp, v, p)
            return a.reshape(v, p, *a.shape[1:]).swapaxes(0, 1)
        return jax.tree.map(re, stacked)

    smapped = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(pipe_axis), P()),
        out_specs=P(),
        check_vma=False,
    )

    def apply(stacked_stage_params, micro):
        return smapped(reshape_params(stacked_stage_params, micro), micro)

    return apply


def _waves(p: int, m: int) -> list[tuple[int, int]]:
    """Interleaved schedule: microbatches enter in waves of at most ``p``."""
    return [(s, min(p, m - s)) for s in range(0, m, p)]


def spmd_schedule(p: int, m: int, v: int = 1) -> tuple[int, int, int]:
    """The realized tick schedule of :func:`pipeline_spmd`:
    ``(total_ticks, stage_applications_per_tick_per_ring, useful_applications)``.

    These are the very numbers that size the implementation's scans (the
    v==1 path runs one ``m + S - 1``-tick scan applying all ``p*v`` logical
    stages per tick; the v>1 interleaved path runs ``ceil(m/p)`` waves of
    ``S + p - 1`` ticks applying one logical stage per rank per tick), so
    the idle fraction derived from them is the *measured* bubble of the
    executor, not a re-derivation of the analytic model.
    """
    S = v * p
    if v == 1:
        return m + S - 1, p * v, m * S
    ticks = sum(S + p - 1 for _ in _waves(p, m))
    return ticks, p, m * S


def spmd_idle_fraction(p: int, m: int, v: int = 1) -> float:
    """Measured idle fraction of the GSPMD pipeline's schedule; compare to
    ``core.bubble.bubble_fraction`` (exactly equal for v==1/GPipe, and for
    the interleaved path on a single full wave, ``m == p`` — with multiple
    waves each drains fully before the next injects, so the realized value
    is the per-wave bubble, not the analytic ``(p-1)/(v*m+p-1)``)."""
    if p <= 1:
        return 0.0
    ticks, per_tick, useful = spmd_schedule(p, m, v)
    return 1.0 - useful / (ticks * per_tick)


def pipeline_spmd(
    stage_fn: Callable[[Any, Any], Any],
    mesh: Mesh,
    *,
    n_stages: int,
    v: int = 1,
    pipe_axis: str = "pipe",
    data_axis: str = "data",
) -> Callable[[Any, Any], Any]:
    """GSPMD circular pipeline — composes with auto TP/DP axes.

    Returns ``pipelined(stacked_stage_params, payload)`` where

      * ``stacked_stage_params``: pytree with leading dim ``v * n_stages``
        in logical-stage order (produced by
        ``core.stage_program.split_stages`` for any model family),
      * ``payload``: the pytree that flows through the ring — a bare
        ``(m, mbs, ...)`` microbatch array, or a dict
        ``{"x": activations, **carries}`` whose extra leaves (MoE aux
        accumulators, encdec cross-attention memory) ride the same
        collective-permute channel as the activations,

    and the result has the same structure after all ``v * n_stages``
    logical stages.  ``stage_fn(stage_params_slice, payload_slice)``
    applies one logical stage.

    ``v == 1`` (and the contiguous stage assignment it implies — logical
    stage ``s`` on pipe-rank ``s``): a ``(p, 1, mbs, ...)``-per-leaf
    in-flight buffer, one tick per microbatch-advance; total ticks
    ``m + S - 1`` give the GPipe bubble ``(S-1)/(m+S-1)``
    (``core/bubble.py``).

    ``v > 1`` — **interleaved-1F1B virtual staging** (Megatron §2.2): the
    ``S = v*p`` logical stages are assigned *round-robin*, rank ``d``
    hosting stages ``{d, d+p, ..., d+(v-1)p}``, and activations loop the
    ring ``v`` times.  Microbatches enter in waves of at most ``p``; each
    wave drains in ``S + p - 1`` ticks of *one* stage-application per rank
    (each application is a 1/v-depth stage chunk), so the realized bubble
    is ``(p-1)/(v*m + p - 1)`` for ``m = p`` per wave — *shrinking* with
    ``v`` exactly as ``core/bubble.py``'s interleaved model, instead of the
    contiguous assignment's ``(S-1)/(m+S-1)`` that grows with ``S``.  The
    tradeoffs are Megatron's: v× more, 1/v-sized cross-stage transfers per
    microbatch, and the round-robin assignment means the pipe-sharded layer
    stack is regathered once per step (GSPMD inserts the reshard) instead
    of the contiguous split's free local reshape.

    No manual collectives in either mode: the advance is a ``jnp.roll``
    over the pipe-sharded buffer dim (lowered by XLA to a
    collective-permute) and the "data"/"model" mesh axes remain auto, so
    TP-sharded stage params and DP-sharded microbatches work unchanged
    inside ``stage_fn``.
    """
    p = n_stages
    S = v * p

    def _keep(tree, lead: int):
        """Per-leaf sharding constraint: pipe on dim 0 of every payload
        leaf — what makes XLA lower the ring advance to a
        collective-permute.  The microbatch dim is left to propagation:
        pinning it to the data axis here miscompiles the hybrid (mamba)
        stage bodies on the XLA CPU partitioner (wrong numerics, not an
        error — same compiler family as the shard_map gotchas in
        .claude/skills/verify), and GSPMD recovers the DP sharding from
        the batch inputs anyway."""
        if pipe_axis not in mesh.shape or mesh.shape[pipe_axis] <= 1:
            return tree

        def one(x):
            parts = [pipe_axis] + [None] * min(lead - 1, x.ndim - 1)
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(*parts)))
        return jax.tree.map(one, tree)

    def _index(tree, i):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            tree)

    def pipelined_contiguous(stacked_stage_params, micro):
        m = jax.tree.leaves(micro)[0].shape[0]
        stages = jax.tree.map(
            lambda a: a.reshape(p, v, *a.shape[1:]), stacked_stage_params)

        buf = _keep(jax.tree.map(
            lambda a: jnp.zeros((p, v) + a.shape[1:], a.dtype), micro), 2)

        def tick(buf, t):
            mb = jnp.clip(t, 0, m - 1)
            x0 = _index(micro, mb)
            buf = jax.tree.map(
                lambda b, x: b.at[0, 0].set(x.astype(b.dtype)), buf, x0)
            out = jax.vmap(jax.vmap(stage_fn))(stages, _keep(buf, 2))
            out = _keep(out, 2)
            y = jax.tree.map(lambda o: o[-1, -1], out)
            # advance every in-flight microbatch one logical stage
            # (s = d*v + slot): slots shift locally within each pipe rank;
            # the slot=0 column is fed by the previous rank's last slot —
            # the only cross-pipe transfer, one collective-permute per tick
            nxt = jax.tree.map(lambda o: jnp.roll(o, 1, axis=1), out)
            nxt = jax.tree.map(
                lambda n, o: n.at[:, 0].set(jnp.roll(o[:, -1], 1, axis=0)),
                nxt, out)
            return _keep(nxt, 2), y

        _, ys = jax.lax.scan(tick, buf, jnp.arange(m + S - 1))
        return jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, S - 1, m, axis=0), ys)

    def pipelined_interleaved(stacked_stage_params, micro):
        m = jax.tree.leaves(micro)[0].shape[0]
        # round-robin assignment: [d, k] = logical stage k*p + d
        stages = jax.tree.map(
            lambda a: a.reshape(v, p, *a.shape[1:]).swapaxes(0, 1),
            stacked_stage_params)
        d_idx = jnp.arange(p)

        def run_wave(w_start: int, w: int):
            buf = _keep(jax.tree.map(
                lambda a: jnp.zeros((p,) + a.shape[1:], a.dtype), micro), 1)

            def tick(buf, t):
                # rank d serves the microbatch at logical stage s = t - j
                # (j its injection tick); its local slot is s // p
                slot = jnp.clip((t - d_idx) // p, 0, v - 1)
                mb = jnp.clip(w_start + t, w_start, m - 1)
                x0 = _index(micro, mb)
                inject = t < w  # rank 0 is at slot 0 while t < w <= p
                buf = jax.tree.map(
                    lambda b, x: b.at[0].set(
                        jnp.where(inject, x.astype(b.dtype), b[0])), buf, x0)
                lp = jax.tree.map(lambda a: a[d_idx, slot], stages)
                out = jax.vmap(stage_fn)(lp, _keep(buf, 1))
                out = _keep(out, 1)
                y = jax.tree.map(lambda o: o[-1], out)
                nxt = jax.tree.map(lambda o: jnp.roll(o, 1, axis=0), out)
                return _keep(nxt, 1), y

            _, ys = jax.lax.scan(tick, buf, jnp.arange(S + p - 1))
            return jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, S - 1, w, axis=0),
                ys)

        outs = [run_wave(w_start, w) for w_start, w in _waves(p, m)]
        if len(outs) == 1:
            return outs[0]
        return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *outs)

    return pipelined_contiguous if v == 1 else pipelined_interleaved


def stack_stages(stacked_layers: Any, n_stages: int) -> Any:
    """(L, ...) layer-stacked params -> (n_stages, L/p, ...) — the
    single-segment case of ``core.stage_program.split_stages``."""
    def reshape(a):
        L = a.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return a.reshape(n_stages, L // n_stages, *a.shape[1:])
    return jax.tree.map(reshape, stacked_layers)


def layer_stage_fn(layer_fn: Callable[[Any, jax.Array], jax.Array],
                   remat: bool = False, *, policy: Any = None):
    """stage_fn that scans ``layer_fn`` over the stage's layer slice, by
    wrapping it as a one-segment carry-less StageProgram and running the
    IR executor.

    ``policy`` (a :class:`repro.core.compute.ComputePolicy`) drives the
    per-layer rematerialization — the same selectable activation-checkpoint
    policy as every StageProgram segment.  The legacy ``remat=True`` flag
    maps to the "full" policy; ``remat=False`` (no wrapping) to "none".
    """
    from repro.core import stage_program as sp
    from repro.core.compute import ComputePolicy

    if policy is None:
        policy = ComputePolicy("full" if remat else "none")

    def body(lp, x, carry):
        return layer_fn(lp, x), carry

    def stage(stage_params, x):
        n = jax.tree.leaves(stage_params)[0].shape[0]
        prog = sp.StageProgram(
            (sp.Segment("layers", stage_params, n, body),), carry_spec=())
        y, _ = sp.run_program(prog, x, {}, policy=policy)
        return y
    return stage


def pipeline_loss_fn(
    layer_fn: Callable[[Any, jax.Array], jax.Array],
    embed_fn: Callable[[Any, jax.Array], jax.Array],
    head_fn: Callable[[Any, jax.Array, jax.Array], jax.Array],
    mesh: Mesh,
    *,
    n_stages: int,
    n_micro: int,
    pipe_axis: str = "pipe",
):
    """End-to-end pipelined LM loss:

      loss(params, batch) where params = {"embed_side": ..., "layers": (L,...)}
      batch = {"tokens": (B, S)}; B is split into ``n_micro`` microbatches.
    """
    pipelined = pipeline_apply(layer_stage_fn(layer_fn), mesh, pipe_axis=pipe_axis)

    def loss(params, batch):
        tokens = batch["tokens"]
        B = tokens.shape[0]
        assert B % n_micro == 0, (B, n_micro)
        mbs = B // n_micro
        x = embed_fn(params, tokens)                      # (B, S, d)
        micro = x.reshape(n_micro, mbs, *x.shape[1:])
        stages = stack_stages(params["layers"], n_stages)
        y = pipelined(stages, micro)                      # (m, mbs, S, d)
        y = y.reshape(B, *x.shape[1:])
        return head_fn(params, y, tokens)

    return loss
