"""Mesh construction for the production target and CPU experiments.

TPU v5e target: one pod = a 16x16 chip grid (256 chips); multi-pod = 2 pods
(512 chips) with a slower "pod" axis (DCN-class links).  The paper's rule —
TP inside the fast interconnect, DP (or PP) across the slow one — maps to
TP on "model" (intra-pod ICI) and DP/PP on "data"/"pod".

Axis conventions (the unified 3D executor, see runtime/train_loop.py):

  * ``"pipe"``  — pipeline stages (slowest links; point-to-point ppermute)
  * ``"data"``  — data parallel + ZeRO-1 optimizer-state sharding
  * ``"model"`` — Megatron tensor parallel (fastest links)

``make_production_mesh`` is a *function* so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first jax use).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # every mesh in this repo is Auto-typed: GSPMD propagates shardings
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh_2d(data: int, model: int):
    """Arbitrary (data, model) mesh — used by tests/benchmarks on CPU."""
    return _mesh((data, model), ("data", "model"))


def make_mesh_3d(pipe: int, data: int, model: int):
    """The unified executor's 3D mesh: ("pipe", "data", "model").

    Axis order is slowest-to-fastest interconnect: PP's point-to-point
    transfers tolerate the slow links, DP/ZeRO-1 collectives the middle,
    Megatron TP all-reduces need the fastest.
    """
    return _mesh((pipe, data, model), ("pipe", "data", "model"))


def make_mesh_4d(node: int, pipe: int, data: int, model: int):
    """The hierarchical executor's 4D mesh: ("node", "pipe", "data", "model").

    Node-major device order: with the "node" axis first, each "data" group
    spans *adjacent* device ids (one node's fast intra-node links) and each
    "node" group spans strided ids (the slow inter-node fabric).  ZeRO specs
    that carry both axes (see core/commplan.py) then lower to two-phase
    intra-node-then-inter-node collectives.
    """
    return _mesh((node, pipe, data, model), ("node", "pipe", "data", "model"))


def make_mesh_4d_ep(pipe: int, data: int, expert: int, model: int):
    """Expert-parallel 4D mesh: ("pipe", "data", "expert", "model").

    "expert" sits between "data" and "model": TP all-reduces keep the
    fastest innermost links, the MoE token all-to-all the next tier, and
    DP/PP the slower ones.  The token-group dim is sharded over the
    *composite* ("data", "expert") batch axes (see
    ParallelPlan.sharding_rules), so an ep plan sees the same per-device
    token count as the flat dp·ep plan and matches its trajectory exactly.
    """
    return _mesh((pipe, data, expert, model),
                 ("pipe", "data", "expert", "model"))


def make_mesh_5d(node: int, pipe: int, data: int, expert: int, model: int):
    """Hierarchical + expert-parallel mesh:
    ("node", "pipe", "data", "expert", "model") — node-major like
    make_mesh_4d, with the expert axis inserted per make_mesh_4d_ep.
    """
    return _mesh((node, pipe, data, expert, model),
                 ("node", "pipe", "data", "expert", "model"))


def make_pipeline_mesh(pipe: int, data: int = 1):
    """Mesh for pipeline-parallel experiments: stages on the "pipe" axis."""
    return _mesh((pipe, data), ("pipe", "data"))


def single_device_mesh():
    return _mesh((1, 1), ("data", "model"))


def validate_plan_shape(pipe: int, data: int, model: int,
                        n_devices: int | None = None,
                        node: int = 1, ep: int = 1) -> None:
    """Raise a clear error when (node, pp, dp, ep, tp) cannot tile the
    devices."""
    for name, v in (("pp", pipe), ("dp", data), ("tp", model),
                    ("node", node), ("ep", ep)):
        if v < 1:
            raise ValueError(f"--{name} must be >= 1, got {v}")
    n = jax.device_count() if n_devices is None else n_devices
    want = node * pipe * data * ep * model
    plan_txt = f"pp={pipe} x dp={data} x tp={model}"
    if ep > 1:
        plan_txt = f"pp={pipe} x dp={data} x ep={ep} x tp={model}"
    if node > 1:
        plan_txt = f"node={node} x " + plan_txt
    if want != n:
        raise ValueError(
            f"parallel plan {plan_txt} = "
            f"{want} devices, but jax.device_count() = {n}. "
            f"Pick factors whose product matches the device count "
            f"(e.g. set XLA_FLAGS=--xla_force_host_platform_device_count={want}).")


def mesh_for_plan(plan, n_devices: int | None = None, *, validate: bool = True):
    """Build the mesh a ParallelPlan asks for.

    ``plan`` is any object with ``pp``/``dp``/``tp`` ints (a
    :class:`repro.runtime.train_loop.ParallelPlan`).  pp == 1 still yields a
    3D mesh with a size-1 pipe axis, so one executor covers every plan.
    Plans with ``node > 1`` get the 4D hierarchical mesh, ``ep > 1`` the
    expert-parallel 4D/5D mesh (``ep == 1`` adds no axis — the expert
    sharding rules then fall back to replication, the pre-EP executor).
    """
    node = int(getattr(plan, "node", 1) or 1)
    ep = int(getattr(plan, "ep", 1) or 1)
    if validate:
        validate_plan_shape(plan.pp, plan.dp, plan.tp, n_devices, node=node,
                            ep=ep)
    if ep > 1:
        if node > 1:
            return make_mesh_5d(node, plan.pp, plan.dp, ep, plan.tp)
        return make_mesh_4d_ep(plan.pp, plan.dp, ep, plan.tp)
    if node > 1:
        return make_mesh_4d(node, plan.pp, plan.dp, plan.tp)
    return make_mesh_3d(plan.pp, plan.dp, plan.tp)
