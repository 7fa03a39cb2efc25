"""Training step builder: the paper's strategy knobs as one declarative plan.

``ParallelPlan`` carries one point of the paper's full 3D search space
(Tables III–V, Fig. 9): the parallel decomposition (``dp`` x ``tp`` x ``pp``
with optional interleaved ``virtual_stages`` and an optional hierarchical
``node`` axis), the sharding strategy (tensor-parallel rule preset), the
ZeRO stage (``zero`` in 0..3, carried as a
:class:`repro.core.memplan.MemoryPlan`; the old ``zero1=`` bool alias has
been removed and raises), micro-batch count via gradient-accumulation steps
(GAS), and precision — plus the compute-path knobs the paper tunes
alongside them: the activation-checkpointing mode (``remat``: full |
selective | none) and the fused Pallas kernel fast path (``kernels``),
carried as a :class:`repro.core.compute.ComputePolicy` and threaded through
every model family and the pipeline stage fn — plus the communication-path
knobs (``qcomm``/``node``/``overlap``, carried as a
:class:`repro.core.commplan.CommPlan` and executed by
``runtime/qcollect.py``): int8 block-quantized zero=3 weight gathers,
two-phase intra/inter-node collectives over the 4D
``("node", "pipe", "data", "model")`` mesh, and per-chunk gather/compute
overlap through the StageProgram scan.

The memory axis is pure shardings (see ``core/memplan.py`` for the stage
semantics): stage >= 1 puts Adam's moments on the data axis, stage >= 2
additionally constrains the fp32 gradient-accumulation scan carry to the
same specs (per-microbatch reduce-scatter instead of a full-gradient
all-reduce), stage 3 shards every parameter leaf over data on its first
divisible free dim with GSPMD all-gather-on-use.

One ``jit_train_step`` serves every plan on the 3D
``("pipe", "data", "model")`` mesh (``launch/mesh.py:mesh_for_plan``):

  * ``pp == 1`` — the classic path: GAS microbatches scanned with fp32
    gradient accumulation, TP via sharding rules, ZeRO-1 over "data".
  * ``pp > 1``  — the same step, but the layer stack (lowered to the
    family-agnostic StageProgram IR — *every* model family pipelines) runs
    through the GSPMD pipeline (``core/pipeline.py:pipeline_spmd``): the
    ``gas`` microbatches become the pipeline's in-flight microbatches (the
    paper's knob that saturates stages — bubble ``(pp-1)/(gas+pp-1)``, or
    the interleaved ``(pp-1)/(v*gas+pp-1)`` when ``virtual_stages > 1``;
    ``core/bubble.py``), accumulated inside one backward pass whose
    pipeline-scan transpose sums per-microbatch parameter cotangents in
    fp32 (the in-body param cast, ``core/stage_program.py``) — matching
    the pp==1 outer scan's fp32 accumulation.  ZeRO-1, loss scaling, and
    the optimizer update are byte-identical between both paths.

``TrainPlan`` remains as a thin alias for existing callers; a 2D plan is
just ``ParallelPlan(pp=1)``.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import commplan as cpl
from repro.core import expertplan as epl
from repro.core import memplan as mpl
from repro.core import precision as prec
from repro.core import sharding as shd
from repro.core.compute import DEFAULT_POLICY, ComputePolicy
from repro.core.memplan import MemoryPlan
from repro.models import moe as moe_mod
from repro.models.common import ModelConfig
from repro.models.model import Model
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.runtime import qcollect as qc


@dataclasses.dataclass(frozen=True)
class ParallelPlan:
    """One point in the paper's 3D (dp, tp, pp) hyperparameter space."""
    dp: int = 1                     # data-parallel ways ("data" mesh axis)
    tp: int = 1                     # tensor-parallel ways ("model" mesh axis)
    pp: int = 1                     # pipeline stages ("pipe" mesh axis)
    virtual_stages: int = 1         # extra stage granularity per pipe rank
                                    # (pp*v logical stages; see pipeline_spmd)
    ep: int = 1                     # expert-parallel ways ("expert" mesh
                                    # axis; core/expertplan.py) — MoE experts
                                    # sharded over their own axis, token
                                    # dispatch as a capacity-C all-to-all
    rules: str = "megatron_tp"      # sharding strategy preset
    zero: int | None = None         # ZeRO stage 0|1|2|3 (core/memplan.py);
                                    # None -> default stage 1
    zero1: Any = None               # REMOVED alias — passing anything but
                                    # None raises, naming zero= (the field
                                    # survives only so dataclasses.replace
                                    # keeps working on stored plans)
    node: int = 1                   # hierarchical ways ("node" mesh axis);
                                    # > 1 selects the 4D mesh + two-phase
                                    # intra/inter-node ZeRO collectives
    qcomm: str = "none"             # none | gather | both — int8 block-
                                    # quantized zero=3 collectives
    overlap: bool = False           # interleave per-chunk weight gathers
                                    # with the StageProgram scan (pp == 1)
    comm_block: int = 32            # quantization block (core/commplan.py)
    gas: int = 1                    # gradient accumulation steps
                                    # (== pipeline microbatches when pp > 1)
    precision: str = "bf16"         # bf16 | fp16 | fp32
    remat: str = "full"             # activation checkpointing:
                                    # full | selective | none (core/compute.py)
    kernels: bool = False           # fused Pallas fast path (norm/MLP/attn/CE)
    multi_segment: bool = False     # hybrid pp>1: lower the alternating
                                    # pattern as an explicit two-segment-kind
                                    # [mamba_i, shared]*n sequence instead of
                                    # one fused "super" segment
    data_axis: str = "data"
    model_axis: str = "model"
    pipe_axis: str = "pipe"
    node_axis: str = "node"
    expert_axis: str = "expert"
    extra_dp_axes: tuple[str, ...] = ()   # e.g. ("pod",) in multi-pod mode
    # hillclimbing hook: ((logical_axis, mesh_axis|None), ...) rule overrides
    rule_overrides: tuple = ()

    def __post_init__(self):
        for name in ("dp", "tp", "pp", "virtual_stages", "gas", "node", "ep"):
            if getattr(self, name) < 1:
                raise ValueError(f"{name} must be >= 1, got {getattr(self, name)}")
        stage = mpl.resolve_stage(self.zero, self.zero1)  # raises on zero1=
        object.__setattr__(self, "zero", stage)
        self.compute_policy()  # validates remat
        self.comm_plan()       # validates qcomm/comm_block/node
        if (self.qcomm != "none" or self.overlap) and stage != 3:
            raise ValueError(
                f"qcomm={self.qcomm!r}/overlap={self.overlap} act on the "
                f"zero=3 weight gathers; this plan has zero={stage}")
        if self.overlap and self.pp > 1:
            raise ValueError(
                "overlap interleaves gathers with the pp==1 StageProgram "
                "scan; pp > 1 already gathers per stage")

    @property
    def n_devices(self) -> int:
        return self.node * self.dp * self.ep * self.tp * self.pp

    @property
    def n_stages(self) -> int:
        """Logical pipeline depth (interleaving included)."""
        return self.pp * self.virtual_stages

    def compute_policy(self) -> ComputePolicy:
        """The compute-path policy (remat + kernels) this plan carries."""
        return ComputePolicy(remat=self.remat, kernels=self.kernels)

    def memory_plan(self) -> MemoryPlan:
        """The memory-axis policy (ZeRO stage) this plan carries."""
        return MemoryPlan(zero=self.zero, data_axis=self.data_axis,
                          node_axis=self.node_axis if self.node > 1 else None)

    def comm_plan(self) -> cpl.CommPlan:
        """The communication-axis policy this plan carries."""
        return cpl.CommPlan(qcomm=self.qcomm, block=self.comm_block,
                            overlap=self.overlap, node=self.node,
                            node_axis=self.node_axis,
                            data_axis=self.data_axis)

    def expert_plan(self) -> epl.ExpertPlan:
        """The expert-parallelism policy this plan carries."""
        return epl.ExpertPlan(ep=self.ep, expert_axis=self.expert_axis,
                              data_axis=self.data_axis,
                              node_axis=self.node_axis)

    def sharding_rules(self) -> shd.ShardingRules:
        preset = shd.PRESETS[self.rules]
        rules = preset(data_axis=self.data_axis,
                       model_axis=self.model_axis,
                       pipe_axis=self.pipe_axis if self.pp > 1 else None)
        # the batch rides every DP-flavored axis, slowest first: extra pod
        # axes, then the hierarchical node axis, then data, then expert —
        # node-major order matches the flat dp = node*dp device order, and
        # expert-last matches the flat dp = dp*ep order, so hierarchical
        # and expert plans reproduce the flat plan's trajectory exactly
        batch_axes = tuple(self.extra_dp_axes)
        if self.node > 1:
            batch_axes += (self.node_axis,)
        if batch_axes or self.ep > 1:
            batch_axes += (self.data_axis,)
            if self.ep > 1:
                batch_axes += (self.expert_axis,)
            rules = rules.with_overrides(
                batch=batch_axes, cache_batch=batch_axes,
                name=rules.name + ("+ep" if self.ep > 1 else "+hier_dp"))
        if self.ep > 1:
            # expert weights move from the data axis (the ep==1 fallback,
            # where "expert parallelism" is just dp-sharded experts) onto
            # their own mesh axis; dispatch is the all-to-all between the
            # composite batch sharding and this one (models/moe.py)
            rules = rules.with_overrides(name=rules.name,
                                         experts=self.expert_axis)
        if self.rule_overrides:
            rules = rules.with_overrides(**dict(self.rule_overrides))
        return rules


# Backwards-compatible name: the pre-3D plan (TP/DP/ZeRO-1 only) is the
# pp == 1 corner of ParallelPlan.
TrainPlan = ParallelPlan


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def plan_state_shardings(model: Model, mesh: Mesh, plan: ParallelPlan):
    """(param shapes, param/optimizer/gradient sharding trees) under the
    plan's :class:`MemoryPlan` — the single source for the executor's
    in/out shardings, the stage-2 scan-carry constraint, and the dry-run's
    byte report."""
    pshapes = model.param_shapes()
    mp = plan.memory_plan()
    psh = shd.tree_shardings(pshapes, model.param_axes(), mesh,
                             plan.sharding_rules())
    psh = mp.param_shardings(pshapes, psh)            # stage 3
    opt_sh = mp.optimizer_shardings(pshapes, psh)     # stage >= 1
    grad_sh = mp.grad_shardings(pshapes, psh)         # stage >= 2
    return pshapes, psh, opt_sh, grad_sh


def _state_sharding_dict(mesh: Mesh, psh: Any, opt_sh: Any) -> dict:
    rep = replicated(mesh)
    return {
        "params": psh,
        "opt": {"mu": opt_sh, "nu": opt_sh, "count": rep},
        "loss_scale": jax.tree.map(lambda _: rep, prec.init_loss_scale(False)),
        "step": rep,
    }


def train_state_shardings(model: Model, mesh: Mesh, plan: ParallelPlan) -> dict:
    _, psh, opt_sh, _ = plan_state_shardings(model, mesh, plan)
    return _state_sharding_dict(mesh, psh, opt_sh)


def train_state_bytes(model: Model, mesh: Mesh, plan: ParallelPlan) -> dict:
    """Per-device bytes of each train-state class under the plan's ZeRO
    stage, measured from the actual sharding specs (``prod(shard_shape) *
    itemsize`` per leaf) — what the dry-run reports next to XLA's peak.

    ``grad_bytes`` is the fp32 accumulation buffer (stage >= 2 shards it);
    ``opt_bytes`` covers both Adam moments (stage >= 1 shards them);
    ``param_bytes`` is the storage-dtype parameter tree (stage 3 shards it).
    """
    pshapes, psh, opt_sh, grad_sh = plan_state_shardings(model, mesh, plan)
    f32 = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                       pshapes)
    return {
        "zero": plan.zero,
        "param_bytes": mpl.sharded_bytes(pshapes, psh),
        "grad_bytes": mpl.sharded_bytes(f32, grad_sh),
        "opt_bytes": 2 * mpl.sharded_bytes(f32, opt_sh),  # mu + nu
    }


def batch_specs(cfg: ModelConfig, global_batch: int, seq_len: int) -> tuple[dict, dict]:
    """(ShapeDtypeStruct tree, logical-axes tree) for one global train batch."""
    specs = {"tokens": jax.ShapeDtypeStruct((global_batch, seq_len), jnp.int32)}
    axes = {"tokens": ("batch", "seq")}
    if cfg.family == "encdec":
        specs["frames"] = jax.ShapeDtypeStruct(
            (global_batch, cfg.enc_seq_len, cfg.frontend_dim), jnp.float32)
        axes["frames"] = ("batch", None, None)
    if cfg.family == "vlm":
        specs["patches"] = jax.ShapeDtypeStruct(
            (global_batch, cfg.num_patches, cfg.frontend_dim), jnp.float32)
        axes["patches"] = ("batch", None, None)
    return specs, axes


def batch_shardings(cfg: ModelConfig, global_batch: int, seq_len: int,
                    mesh: Mesh, plan: ParallelPlan) -> dict:
    specs, axes = batch_specs(cfg, global_batch, seq_len)
    return shd.tree_shardings(specs, axes, mesh, plan.sharding_rules())


def init_train_state(model: Model, key: jax.Array, opt_cfg: AdamWConfig,
                     plan: ParallelPlan, mesh: Mesh | None = None) -> dict:
    """Fresh train state.  With ``mesh`` the state is built inside one jit
    whose outputs already carry the plan's shardings, so no device ever
    holds the whole unsharded state; without it, arrays are built eagerly
    on the default device (small and single-device runs)."""
    def init():
        params = model.init(key)
        return {
            "params": params,
            "opt": adamw_init(params),
            "loss_scale": prec.init_loss_scale(plan.precision == "fp16"),
            "step": jnp.int32(0),
        }

    if mesh is None:
        return init()
    return jax.jit(init, out_shardings=train_state_shardings(
        model, mesh, plan))()


def build_train_step(model: Model, opt_cfg: AdamWConfig, plan: ParallelPlan,
                     mesh: Mesh | None = None, grad_shardings: Any = None):
    """Returns train_step(state, batch) -> (state, metrics).

    pp == 1: the global batch is split into ``gas`` microbatches consumed by
    a ``lax.scan`` that accumulates fp32 gradients — the paper's
    gradient-accumulation knob.

    pp > 1: the ``gas`` microbatches instead flow through the GSPMD pipeline
    inside a single value_and_grad (grads over the summed-loss graph are the
    same mean over microbatches, accumulated in fp32 by the pipeline scan's
    transpose — see ``core/stage_program.py``), so GAS doubles as the
    pipeline-saturation knob exactly as in the paper.
    """
    policy = prec.policy_from_name(plan.precision)
    compute = plan.compute_policy()
    if model.compute not in (DEFAULT_POLICY, compute):
        warnings.warn(
            f"model carries compute policy {model.compute} but the plan "
            f"specifies {compute}; the plan wins inside the executor — set "
            f"remat/kernels on the ParallelPlan instead", stacklevel=2)
    if plan.pp > 1 and mesh is None:
        raise ValueError("pp > 1 requires the mesh at build time "
                         "(pipeline sharding constraints)")

    # CommPlan executor (runtime/qcollect.py): int8 round-trips the zero=3
    # weight gathers and/or hands the model a LayerComm for per-chunk
    # gather/compute overlap.  qcomm=none + overlap=False costs nothing —
    # no CommExec, the step below is byte-identical to before.
    cp = plan.comm_plan()
    comm_exec = None
    if cp.quantizes or cp.overlap:
        if mesh is None:
            raise ValueError("qcomm/overlap require the mesh at build time "
                             "(the comm executor binds sharding specs)")
        _pshapes, _psh, _, _ = plan_state_shardings(model, mesh, plan)
        comm_exec = qc.CommExec(cp, mesh, _pshapes, _psh)

    # ExpertPlan executor (models/moe.py:ExpertDispatch): ep > 1 hands the
    # MoE blocks the mesh + axis names so dispatch/combine become the pair
    # of GSPMD sharding constraints that lower to the token all-to-all.
    # ep == 1 passes nothing — the expert rules resolve to the pre-EP
    # data-axis sharding and the step is byte-identical to before.
    ep_ctx = None
    if plan.ep > 1:
        epl.validate_experts(model.cfg.n_experts, plan.ep,
                             where=f"ParallelPlan(ep={plan.ep}) on "
                                   f"{model.cfg.name}")
        if mesh is None:
            raise ValueError("ep > 1 requires the mesh at build time "
                             "(the dispatch binds sharding constraints)")
        group_axes = tuple(plan.extra_dp_axes)
        if plan.node > 1:
            group_axes += (plan.node_axis,)
        group_axes += (plan.data_axis,)
        ep_ctx = moe_mod.ExpertDispatch(mesh=mesh,
                                        expert_axis=plan.expert_axis,
                                        group_axes=group_axes)
    model = Model(model.cfg, policy.compute_dtype, model.q_chunk,
                  compute=compute,
                  comm=comm_exec.layer_comm if comm_exec else None,
                  ep=ep_ctx)
    # pp > 1 folds all gas microbatches into one pipelined backward pass
    outer_gas = 1 if plan.pp > 1 else plan.gas

    # ZeRO-2: the fp32 accumulator rides the accumulation scan's carry with
    # the optimizer-shard's data-axis spec, so GSPMD reduce-scatters each
    # microbatch's gradients into the owning shard instead of all-reducing
    # full gradients and slicing at the update (core/memplan.py).  Pure
    # shardings only — no manual gather/restack inside jit (the XLA CPU
    # SPMD miscompile documented in core/stage_program.py:Segment.tied).
    if plan.memory_plan().shards_grads and mesh is not None:
        if grad_shardings is None:  # jit_train_step passes its own copy
            _, _, _, grad_shardings = plan_state_shardings(model, mesh, plan)
        gsum_sh = grad_shardings
        constrain_gsum = lambda t: jax.lax.with_sharding_constraint(t, gsum_sh)
    else:
        constrain_gsum = lambda t: t

    def loss_fn(params, micro_batch, scale):
        if comm_exec is not None:
            params = comm_exec.prepare(params)
        if plan.pp > 1:
            loss, metrics = model.loss_pipelined(
                params, micro_batch, mesh=mesh, pp=plan.pp,
                n_micro=plan.gas, virtual_stages=plan.virtual_stages,
                pipe_axis=plan.pipe_axis, data_axis=plan.data_axis,
                multi_segment=plan.multi_segment)
        else:
            loss, metrics = model.loss(params, micro_batch)
        return prec.scale_loss({"scale": scale}, loss), metrics

    def train_step(state, batch):
        params = state["params"]
        scale = state["loss_scale"]["scale"]

        def split(x):
            return x.reshape(outer_gas, x.shape[0] // outer_gas, *x.shape[1:])

        def accum(carry, mb):
            gsum, ce_sum, aux_sum, drop_sum = carry
            (_, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mb, scale)
            gsum = constrain_gsum(jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), gsum, grads))
            return (gsum, ce_sum + metrics["ce"], aux_sum + metrics["moe_aux"],
                    drop_sum + metrics["moe_drop"]), None

        # the two halves carry names into the HLO's op metadata
        with jax.named_scope("loss_and_grad"):
            micro = jax.tree.map(split, batch)
            zero_grads = constrain_gsum(jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params))
            (gsum, ce_sum, aux_sum, drop_sum), _ = jax.lax.scan(
                accum, (zero_grads, jnp.float32(0.0), jnp.float32(0.0),
                        jnp.float32(0.0)), micro)

        with jax.named_scope("optimizer"):
            grads = prec.unscale_grads(
                state["loss_scale"],
                jax.tree.map(lambda g: g / outer_gas, gsum))
            finite = prec.all_finite(grads)
            # global fp32 L2 norm of the unscaled gradient — the telemetry
            # record's training-health signal (sum-of-squares over sharded
            # leaves reduces correctly under GSPMD)
            grad_norm = jnp.sqrt(sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree.leaves(grads)))
            new_params, new_opt = adamw_update(
                opt_cfg, params, grads, state["opt"], skip=~finite)
            new_ls = prec.update_loss_scale(state["loss_scale"], finite)
        metrics = {
            "loss": ce_sum / outer_gas,
            "moe_aux": aux_sum / outer_gas,
            # measured router drop fraction (capacity truncation is never
            # silent — dryrun/bench report it next to the analytic
            # expertplan.predicted_drop_fraction); 0.0 for expert-less models
            "moe_drop": drop_sum / outer_gas,
            "grad_norm": grad_norm,
            "grads_finite": finite,
            "loss_scale": new_ls["scale"],
        }
        new_state = {
            "params": new_params,
            "opt": new_opt,
            "loss_scale": new_ls,
            "step": state["step"] + 1,
        }
        return new_state, metrics

    return train_step


def jit_train_step(model: Model, opt_cfg: AdamWConfig, plan: ParallelPlan,
                   mesh: Mesh, global_batch: int, seq_len: int):
    """jit-compiled unified train step with explicit in/out shardings.

    This is the single executor behind every (dp, tp, pp) plan: TP via the
    plan's sharding rules, PP via ``pipeline_spmd`` in the loss, and the
    ZeRO stage via data-axis shardings of the optimizer states (>= 1), the
    fp32 gradient accumulator (>= 2), and the parameters themselves (3),
    all under one jit.
    """
    _, psh, opt_sh, grad_sh = plan_state_shardings(model, mesh, plan)
    step = build_train_step(model, opt_cfg, plan, mesh, grad_shardings=grad_sh)
    state_sh = _state_sharding_dict(mesh, psh, opt_sh)
    batch_sh = batch_shardings(model.cfg, global_batch, seq_len, mesh, plan)
    rep = replicated(mesh)
    metrics_sh = {"loss": rep, "moe_aux": rep, "moe_drop": rep,
                  "grad_norm": rep, "grads_finite": rep, "loss_scale": rep}
    return jax.jit(
        step,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, metrics_sh),
        donate_argnums=(0,),
    )
