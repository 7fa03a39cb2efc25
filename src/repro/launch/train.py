"""Unified training launcher: one surface for any (dp, tp, pp) plan.

On a v5e deployment each host runs this under the TPU runtime and
``jax.distributed.initialize()`` wires the pod slice together; on a CPU it
drives the same code path on one device (or virtual devices via
XLA_FLAGS), with reduced configs for smoke-scale runs.  :func:`train` is
the same run as a function (``chip_smoke.py`` calls it in-process).

  PYTHONPATH=src python -m repro.launch.train --arch yi-6b --reduced \
      --steps 100 --global-batch 8 --seq-len 128 --ckpt-dir /tmp/ckpt

  # pipeline-parallel point of the 3D space (4 virtual devices):
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  PYTHONPATH=src python -m repro.launch.train --arch yi-6b --reduced \
      --dp 2 --pp 2 --gas 4 --steps 10
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpointing import latest_step, restore_checkpoint, save_checkpoint
from repro.configs import ASSIGNED, PAPER, get_config
from repro.core import telemetry
from repro.data import SyntheticCorpus, make_batch_iterator
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import mesh_for_plan
from repro.models.common import ModelConfig
from repro.models.model import Model
from repro.optim import AdamWConfig, cosine_schedule
from repro.runtime.train_loop import (ParallelPlan, init_train_state,
                                      jit_train_step, train_state_bytes)


def parse_plan(args, n_devices: int) -> ParallelPlan:
    """Resolve (dp, tp, pp) from CLI flags against the device count.

    Unset factors are inferred so dp * tp * pp == n_devices; a plan that
    cannot tile the device count is a hard error (not a silently invalid
    mesh).
    """
    tp = args.tp if args.tp is not None else 1
    pp = args.pp
    node = args.node
    ep = args.ep
    if args.dp is not None:
        dp = args.dp
        if args.tp is None:
            rem = n_devices // max(node * dp * pp * ep, 1)
            tp = max(rem, 1)
    else:
        rem = n_devices // max(node * tp * pp * ep, 1)
        dp = max(rem, 1)
    plan = ParallelPlan(
        dp=dp, tp=tp, pp=pp, ep=ep, node=node,
        virtual_stages=args.virtual_stages,
        rules=args.rules, zero=args.zero, gas=args.gas,
        qcomm=args.qcomm, overlap=args.overlap, comm_block=args.comm_block,
        precision=args.precision, remat=args.remat, kernels=args.kernels)
    if plan.n_devices != n_devices:
        raise SystemExit(
            f"error: node={node} x dp={dp} x ep={ep} x tp={tp} x pp={pp} = "
            f"{plan.n_devices} devices "
            f"but jax.device_count() = {n_devices}; adjust "
            f"--dp/--ep/--tp/--pp "
            f"(or XLA_FLAGS=--xla_force_host_platform_device_count=...)")
    return plan


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ASSIGNED + PAPER), default="yi-6b")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale variant of the architecture")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--gas", type=int, default=1)
    ap.add_argument("--precision", choices=["bf16", "fp16", "fp32"], default="fp32")
    ap.add_argument("--remat", choices=["full", "selective", "none"],
                    default="full",
                    help="activation checkpointing: full = save layer "
                         "boundaries only; selective = also save matmul "
                         "outputs (skip dot recompute in backward); none = "
                         "save everything (fastest, most memory)")
    ap.add_argument("--kernels", action="store_true",
                    help="route norm/MLP-gate/attention/CE through the fused "
                         "Pallas kernels (Mosaic on a TPU, interpret mode on "
                         "the CPU)")
    ap.add_argument("--rules", choices=["megatron_tp", "fsdp", "dp_only", "tp_only"],
                    default="megatron_tp")
    ap.add_argument("--zero", type=int, choices=[0, 1, 2, 3], default=None,
                    help="ZeRO stage (core/memplan.py): 0 = replicated DP, "
                         "1 = shard optimizer states over data (default), "
                         "2 = + shard the fp32 gradient accumulator, "
                         "3 = + shard parameters (all-gather on use)")
    ap.add_argument("--qcomm", choices=["none", "gather", "both"],
                    default="none",
                    help="CommPlan quantized collectives (zero=3 only): "
                         "gather = int8 block-quantize the weight "
                         "all-gathers; both = also fake-quantize the "
                         "gradient path (qgZ precision model)")
    ap.add_argument("--comm-block", type=int, default=32,
                    help="qcomm quantization block size (last-dim elements "
                         "per int8 scale group)")
    ap.add_argument("--overlap", action="store_true",
                    help="overlap zero=3 per-chunk weight gathers with the "
                         "layer-stack compute (pp=1 only)")
    ap.add_argument("--node", type=int, default=1,
                    help="hierarchical node axis ways: data collectives "
                         "split into intra-node + inter-node phases over a "
                         "4D (node, pipe, data, model) mesh")
    ap.add_argument("--dp", "--data-parallel", dest="dp", type=int, default=None,
                    help="data-parallel ways (default: fill remaining devices)")
    ap.add_argument("--tp", "--model-parallel", dest="tp", type=int, default=None,
                    help="tensor-parallel ways")
    ap.add_argument("--pp", type=int, default=1, help="pipeline stages")
    ap.add_argument("--ep", type=int, default=1,
                    help="expert-parallel ways (core/expertplan.py): shard "
                         "MoE expert weights over a dedicated \"expert\" "
                         "mesh axis with capacity-factor token all-to-all "
                         "dispatch; requires n_experts %% ep == 0")
    ap.add_argument("--virtual-stages", type=int, default=1,
                    help="interleaved virtual stages per pipe rank (pp > 1)")
    ap.add_argument("--layers", type=int, default=None,
                    help="override n_layers (with --reduced, lifts the "
                         "2-layer clamp so pp * virtual_stages > 2 plans "
                         "have enough stage units)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-jsonl", default=None, metavar="PATH",
                    help="append one telemetry record per step "
                         "(core/telemetry.py schema: tokens/s, MFU)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of the "
                         "pipeline schedule against measured step times "
                         "(analysis/trace.py; view at chrome://tracing)")
    return ap


def resolve_config(arch: str, *, reduced: bool = False,
                   layers: int | None = None, ep: int = 1) -> ModelConfig:
    """The architecture's config, optionally reduced and/or with its layer
    count overridden (depth is the one dimension cut to fit a chip)."""
    cfg = get_config(arch)
    if reduced:
        # ep-aware clamp: the reduced expert count must stay divisible
        # by the plan's expert ways (no-op for ep=1 / non-moe families)
        overrides = {"n_layers": layers} if layers else {}
        return cfg.reduced(ep=ep, **overrides)
    if layers:
        return dataclasses.replace(cfg, n_layers=layers)
    return cfg


@dataclasses.dataclass
class TrainRun:
    """What one :func:`train` call measured: per-step losses and walls
    (blocked on the step's outputs), AOT compile seconds, the compiled step
    and the final state."""
    losses: list[float]
    step_walls: list[float]
    compile_s: float
    compiled: Any
    state: dict
    telemetry: telemetry.Telemetry


def train(cfg: ModelConfig, plan: ParallelPlan, *, steps: int,
          global_batch: int, seq_len: int, lr: float = 1e-3, seed: int = 0,
          ckpt_dir: str | None = None, ckpt_every: int = 50,
          log_every: int = 10, log_jsonl: str | None = None,
          trace: str | None = None) -> TrainRun:
    """Train ``cfg`` under ``plan`` on this process's devices for ``steps``
    optimizer steps of synthetic data from ``seed``."""
    # --kernels is fully fused on every family now: rmsnorm + layernorm,
    # swiglu + gelu gates, flash attention (softcap native), CE, and the
    # grouped expert MLP (kernels/grouped_mlp.py) for MoE — no fallbacks
    mesh = mesh_for_plan(plan)
    node_s = f"node={plan.node}," if plan.node > 1 else ""
    ep_s = f"ep={plan.ep}," if plan.ep > 1 else ""
    comm_s = (f" qcomm={plan.qcomm} overlap={plan.overlap}"
              if (plan.qcomm != "none" or plan.overlap) else "")
    print(f"arch={cfg.name} params={Model(cfg).n_params():,} "
          f"mesh=({node_s}pp={plan.pp},dp={plan.dp},{ep_s}tp={plan.tp})"
          f"{f' v={plan.virtual_stages}' if plan.virtual_stages > 1 else ''} "
          f"rules={plan.rules} zero={plan.zero} gas={plan.gas} "
          f"precision={plan.precision} remat={plan.remat} "
          f"kernels={plan.kernels}{comm_s}")

    model = Model(cfg, jnp.float32 if plan.precision == "fp32" else jnp.bfloat16)
    opt = AdamWConfig(lr=cosine_schedule(lr, 10, steps))
    state = init_train_state(model, jax.random.PRNGKey(seed), opt, plan,
                             mesh=mesh)
    start = 0
    if ckpt_dir and (s := latest_step(ckpt_dir)) is not None:
        like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
        state = restore_checkpoint(ckpt_dir, s, like)
        start = s
        print(f"restored step {s} from {ckpt_dir}")

    step_fn = jit_train_step(model, opt, plan, mesh, global_batch, seq_len)
    extra = {}
    if cfg.family == "encdec":
        extra["frames"] = ((cfg.enc_seq_len, cfg.frontend_dim), "float32")
    if cfg.family == "vlm":
        extra["patches"] = ((cfg.num_patches, cfg.frontend_dim), "float32")
    it = make_batch_iterator(
        SyntheticCorpus(vocab_size=cfg.vocab_size, seed=seed),
        seq_len=seq_len, global_batch=global_batch,
        extra_specs={k: (sh, np.dtype(dt)) for k, (sh, dt) in extra.items()} or None)

    # telemetry rides every run (records stay in memory unless --log-jsonl);
    # the MFU console suffix appears only when telemetry output was asked
    # for, keeping the documented default step-line format byte-identical
    tele_on = bool(log_jsonl or trace)
    tele = telemetry.Telemetry(cfg, plan, global_batch, seq_len,
                               jsonl=log_jsonl)

    # AOT compile: one .lower().compile() captures the measured collective
    # payload bytes + XLA's peak estimate for the compile record, and the
    # loop below calls the compiled step directly (no second compilation)
    t0 = time.time()
    batch = next(it)
    compiled = step_fn.lower(state, batch).compile()
    compile_s = time.time() - t0
    tele.record_compile(
        compiled, state_bytes=train_state_bytes(model, mesh, plan),
        compile_s=compile_s)

    losses = []
    for i in range(start, steps):
        (state, metrics), wall = telemetry.timed_call(compiled, state, batch)
        rec = tele.step(i + 1, wall, metrics)
        losses.append(float(metrics["loss"]))
        if (i + 1) % log_every == 0:
            print(tele.console_line(rec, window=log_every, with_mfu=tele_on))
        if ckpt_dir and (i + 1) % ckpt_every == 0:
            save_checkpoint(ckpt_dir, i + 1, state)
        batch = next(it)

    if trace:
        from repro.analysis import trace as trace_mod
        tr = trace_mod.build_trace(
            plan.pp, plan.gas, plan.virtual_stages, tele.step_walls,
            meta={"arch": cfg.name, "plan": telemetry.plan_dict(plan)})
        trace_mod.write_trace(tr, trace)
        print(f"wrote pipeline trace to {trace} "
              f"({len(tr['traceEvents'])} events)")
    tele.close()
    return TrainRun(losses=losses, step_walls=list(tele.step_walls),
                    compile_s=compile_s, compiled=compiled, state=state,
                    telemetry=tele)


def main(argv: list[str] | None = None) -> None:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    cfg = resolve_config(args.arch, reduced=args.reduced, layers=args.layers,
                         ep=args.ep)
    plan = parse_plan(args, jax.device_count())
    run = train(cfg, plan, steps=args.steps, global_batch=args.global_batch,
                seq_len=args.seq_len, lr=args.lr, seed=args.seed,
                ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                log_every=args.log_every, log_jsonl=args.log_jsonl,
                trace=args.trace)
    walls = run.step_walls
    if walls:
        med = sorted(walls)[len(walls) // 2]
        u = run.telemetry.mfu(run.telemetry.flops.total, med)
        mfu_s = (f"mfu {telemetry.NOT_MEASURED}" if u is None else
                 f"mfu {100.0 * u:.2f}% ({run.telemetry.machine.name})")
        print(f"done. median step {med * 1e3:.1f} ms, {mfu_s}")
    else:
        print("done.")


if __name__ == "__main__":
    main()
