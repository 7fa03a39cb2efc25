import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes and extract memory / cost / collective analysis.

This is the no-hardware proof that the distribution config is coherent:
a sharding mismatch, an OOM at compile, or an unsupported collective all
fail here.  Results feed EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun.json
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k --multi-pod
"""
import argparse
import json
import time
import traceback
from typing import Any

import jax
import jax.numpy as jnp

from repro.analysis import hlo as hlo_an
from repro.analysis import hlo_cost
from repro.analysis import roofline as rl
from repro.configs import ASSIGNED, get_config
from repro.configs.shapes import SHAPES, InputShape, applicable
from repro.core import compute as cmp
from repro.core import costmodel as cm
from repro.core import expertplan as epl
from repro.core import sharding as shd
from repro.core import telemetry as tel
from repro.launch.mesh import make_production_mesh, mesh_for_plan
from repro.models import moe as moe_mod
from repro.models.common import axes_tree, shape_dtype_tree
from repro.models.model import Model
from repro.optim import AdamWConfig
from repro.runtime.serve_loop import (
    build_decode_step, cache_sds_and_shardings, decode_batch_specs)
from repro.runtime.train_loop import (
    TrainPlan, batch_shardings, batch_specs, jit_train_step,
    train_state_bytes, train_state_shardings)


def train_state_sds(model: Model) -> dict:
    p32 = model.param_shapes(jnp.float32)
    f32 = lambda: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), p32)
    scalar = lambda dt: jax.ShapeDtypeStruct((), dt)
    return {
        "params": p32,
        "opt": {"mu": f32(), "nu": f32(), "count": scalar(jnp.int32)},
        "loss_scale": {"scale": scalar(jnp.float32),
                       "good_steps": scalar(jnp.int32),
                       "enabled": scalar(jnp.bool_)},
        "step": scalar(jnp.int32),
    }


def default_plan(multi_pod: bool, *, zero: int | None = None, gas: int = 1,
                 rules: str = "megatron_tp") -> TrainPlan:
    return TrainPlan(
        rules=rules, zero=zero, gas=gas, precision="bf16",
        extra_dp_axes=("pod",) if multi_pod else (),
    )


def plan_mesh_name(plan: TrainPlan, multi_pod: bool = False) -> str:
    ep = int(getattr(plan, "ep", 1) or 1)
    if plan.node > 1:
        ep_s = f"xep{ep}" if ep > 1 else ""
        return f"node{plan.node}x{plan.pp}x{plan.dp}{ep_s}x{plan.tp}"
    if ep > 1:
        return f"pipe{plan.pp}x{plan.dp}xep{ep}x{plan.tp}"
    if plan.pp > 1:
        return f"pipe{plan.pp}x{plan.dp}x{plan.tp}"
    return "2x16x16" if multi_pod else "16x16"


def lower_step(arch: str, shape_name: str, *, multi_pod: bool,
               plan: TrainPlan | None = None, q_chunk: int = 1024,
               cfg=None):
    """Builds and lowers the right step for (arch, shape). Returns
    (lowered, meta) — meta carries tokens/kind/chips for the roofline."""
    cfg = cfg or get_config(arch)
    shape = SHAPES[shape_name]
    plan = plan or default_plan(multi_pod)
    if plan.pp > 1 or plan.node > 1 or plan.ep > 1:
        # 3D/4D/5D plan: the plan itself defines the ("pipe", "data",
        # "model") — or hierarchical/expert ("node", "pipe", "data",
        # "expert", "model") — mesh; validate against the real device
        # count for a clear error
        mesh = mesh_for_plan(plan)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = plan_mesh_name(plan, multi_pod)
    chips = mesh.devices.size
    # carry the plan's compute policy so prefill/decode dry-runs measure the
    # path the record claims (train shapes get it via jit_train_step anyway)
    model = Model(cfg, jnp.bfloat16, q_chunk=q_chunk,
                  compute=plan.compute_policy())
    meta = {"schema": tel.SCHEMA,
            "arch": arch, "shape": shape_name, "chips": chips,
            "mesh": mesh_name,
            "kind": shape.kind,
            "plan": plan.rules + (f"+zero{plan.zero}" if plan.zero else ""),
            "zero": plan.zero,
            "gas": plan.gas, "remat": plan.remat, "kernels": plan.kernels,
            "node": plan.node, "qcomm": plan.qcomm, "overlap": plan.overlap,
            "ep": plan.ep}

    if shape.kind == "train":
        meta["tokens"] = shape.global_batch * shape.seq_len
        # closed-form expectation of the remat policy's saved-activation
        # bytes per device (paper's Table III axis), to sit next to XLA's
        # measured peak; parallel ways come from the *mesh* (the plan's
        # dp/tp are nominal under the production meshes)
        mesh_dp = (mesh.shape.get("data", 1) or 1) * (mesh.shape.get("pod", 1) or 1)
        meta["activation_bytes_estimate"] = cmp.activation_bytes_estimate(
            cfg, shape.global_batch, shape.seq_len, plan.compute_policy(),
            dp=mesh_dp, tp=mesh.shape.get("model", 1) or 1,
            pp=mesh.shape.get("pipe", 1) or 1, gas=plan.gas)
        # the MemoryPlan byte report: exact per-device bytes of each train-
        # state class under this plan's ZeRO stage, from the sharding specs
        # themselves — optimizer bytes shrink ~1/dp at stage >= 1, gradient
        # bytes at >= 2, parameter bytes at 3; sits next to XLA's measured
        # peak in the record
        meta["state_bytes"] = train_state_bytes(model, mesh, plan)
        # the telemetry record schema's analytic side (core/telemetry.py):
        # family-aware model FLOPs + the costmodel prediction for this
        # shape — lowered-only runs emit the same blocks a live train's
        # records carry
        meta["flops_per_step"] = cm.train_step_flops(
            cfg, shape.global_batch, shape.seq_len).total
        try:
            meta["predicted"] = tel.predicted_block(cm.predict_step(
                cfg, plan, shape.global_batch, shape.seq_len))
        except Exception:
            meta["predicted"] = {}
        if cfg.family == "moe":
            # predicted (ExpertPlan's normal approximation) vs measured
            # (Monte-Carlo over the real router) capacity-overflow drop —
            # the pair BENCH_moe.json validates on live train metrics
            _, g = moe_mod.group_shape(shape.global_batch, shape.seq_len)
            meta["moe_drop_predicted"] = epl.predicted_drop_fraction(
                cfg.top_k, cfg.n_experts, cfg.capacity_factor, g)
            meta["moe_drop_measured"] = moe_mod.simulated_drop_fraction(
                cfg, shape.global_batch, shape.seq_len)
        step = jit_train_step(model, AdamWConfig(), plan, mesh,
                              shape.global_batch, shape.seq_len)
        bsds, _ = batch_specs(cfg, shape.global_batch, shape.seq_len)
        lowered = step.lower(train_state_sds(model), bsds)
    elif shape.kind == "prefill":
        meta["tokens"] = shape.global_batch * shape.seq_len
        rules = plan.sharding_rules()
        psds = model.param_shapes(jnp.float32)
        psh = shd.tree_shardings(psds, model.param_axes(), mesh, rules)
        bsds, baxes = batch_specs(cfg, shape.global_batch, shape.seq_len)
        bsh = shd.tree_shardings(bsds, baxes, mesh, rules)
        fn = jax.jit(lambda p, b: model.prefill(p, b, shape.seq_len),
                     in_shardings=(psh, bsh))
        lowered = fn.lower(psds, bsds)
    elif shape.kind == "decode":
        meta["tokens"] = shape.global_batch
        step = build_decode_step(model, mesh, plan, shape.global_batch, shape.seq_len)
        psds = model.param_shapes(jnp.float32)
        csds, _ = cache_sds_and_shardings(model, shape.global_batch,
                                          shape.seq_len, mesh, plan)
        bsds, _ = decode_batch_specs(cfg, shape.global_batch)
        lowered = step.lower(psds, csds, bsds)
    else:
        raise ValueError(shape.kind)
    return lowered, meta


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool,
               plan: TrainPlan | None = None, verbose: bool = True,
               q_chunk: int = 1024, cfg=None, tag: str = "") -> dict:
    cfg = cfg or get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = applicable(cfg, shape)
    mesh_name = plan_mesh_name(plan or default_plan(multi_pod), multi_pod)
    if not ok:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "skipped", "reason": reason}
        if verbose:
            print(f"[skip] {arch} x {shape_name} ({mesh_name}): {reason}")
        return rec

    rec: dict[str, Any] = {}
    try:
        t0 = time.time()
        lowered, meta = lower_step(arch, shape_name, multi_pod=multi_pod,
                                   plan=plan, q_chunk=q_chunk, cfg=cfg)
        if tag:
            meta["tag"] = tag
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

        cost = compiled.cost_analysis() or {}
        try:
            ma = compiled.memory_analysis()
            mem = {
                "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
                "output_bytes": getattr(ma, "output_size_in_bytes", None),
                "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
                "generated_code_bytes": getattr(ma, "generated_code_size_in_bytes", None),
            }
            # XLA's measured peak (the paper's Table III axis): temps are the
            # live intermediates — exactly what the remat policy trades
            # against recompute; fall back to temps+args when the backend
            # has no dedicated peak counter
            peak = getattr(ma, "peak_memory_in_bytes", None)
            if peak is None and mem["temp_bytes"] is not None:
                peak = (mem["temp_bytes"] or 0) + (mem["argument_bytes"] or 0)
            mem["peak_bytes"] = peak
        except Exception as e:  # backend may not support it
            mem = {"error": str(e)}
        act_est = meta.pop("activation_bytes_estimate", None)
        hlo_text = compiled.as_text()
        # trip-count-corrected cost model (XLA's cost_analysis counts each
        # while body once — useless for scanned layer stacks; see
        # analysis/hlo_cost.py)
        t0 = time.time()
        totals = hlo_cost.analyze(hlo_text)
        t_analyze = time.time() - t0
        flops = totals.flops
        byts = totals.traffic_bytes
        coll = {k: float(v) for k, v in totals.collective_bytes.items()}
        coll_total = totals.collective_total
        # wire-payload views of the same module: trip-count-scaled from the
        # cost walk, plus the flat single-pass measure hlo.comm_bytes (what
        # core/costmodel.py:predict_comm_bytes validates against)
        payload = {k: float(v)
                   for k, v in totals.collective_payload_bytes.items()}
        comm_measured = {k: float(v)
                         for k, v in hlo_an.comm_bytes(hlo_text).items()}
        terms = rl.roofline_terms(flops, byts, coll_total, meta["chips"])
        mf = rl.model_flops(cfg, tokens=meta["tokens"], kind=meta["kind"])
        rec = {
            **meta,
            "status": "ok",
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "analyze_s": round(t_analyze, 2),
            "flops_per_device": flops,
            "dot_flops_per_device": totals.dot_flops,
            "bytes_per_device": byts,
            "xla_cost_analysis": {"flops": float(cost.get("flops", 0.0)),
                                  "bytes_accessed": float(cost.get("bytes accessed", 0.0))},
            "collective_bytes": coll,
            "collective_payload_bytes": payload,
            "comm_bytes": comm_measured,
            "collective_counts": {k: float(v) for k, v in totals.collective_count.items()},
            "collective_bytes_total": coll_total,
            "unknown_trip_loops": totals.unknown_trip_loops,
            "memory_analysis": mem,
            "activation_bytes_estimate": act_est,
            "roofline": terms.as_dict(),
            "model_flops": mf,
            "useful_flops_ratio": (mf / (flops * meta["chips"])) if flops else None,
        }
        if verbose:
            dom = terms.dominant
            peak = mem.get("peak_bytes")
            peak_s = f" | peak {peak/1e9:.2f}GB" if peak else ""
            est_s = (f" (remat={meta['remat']} est. saved-act "
                     f"{act_est/1e9:.2f}GB)" if act_est else "")
            sb = rec.get("state_bytes")
            sb_s = (f" | zero{sb['zero']}: param {sb['param_bytes']/1e9:.2f}GB "
                    f"grad {sb['grad_bytes']/1e9:.2f}GB "
                    f"opt {sb['opt_bytes']/1e9:.2f}GB" if sb else "")
            print(f"[ok] {arch} x {shape_name} ({mesh_name}): "
                  f"lower {t_lower:.1f}s compile {t_compile:.1f}s | "
                  f"compute {terms.compute_s*1e3:.2f}ms mem {terms.memory_s*1e3:.2f}ms "
                  f"coll {terms.collective_s*1e3:.2f}ms -> {dom}-bound | "
                  f"useful-flops ratio {rec['useful_flops_ratio'] and round(rec['useful_flops_ratio'],3)}"
                  f"{peak_s}{est_s}{sb_s}")
    except Exception as e:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()}
        if verbose:
            print(f"[ERROR] {arch} x {shape_name} ({mesh_name}): {e}")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=sorted(set(ASSIGNED) | {"all"}), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES) + ["all"], default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="all archs x shapes (single-pod unless --both-meshes)")
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline stages: any family pipelines via the "
                         "StageProgram IR (pp>1 builds the 3D plan mesh)")
    ap.add_argument("--virtual-stages", type=int, default=1,
                    help="interleaved virtual stages per pipe rank (pp>1)")
    ap.add_argument("--gas", type=int, default=1,
                    help="microbatches (= pipeline in-flight count when pp>1)")
    ap.add_argument("--zero", type=int, choices=(0, 1, 2, 3), default=None,
                    help="ZeRO stage of the MemoryPlan (default 1); the "
                         "record's state_bytes shows the per-class shrink")
    ap.add_argument("--dp", type=int, default=None,
                    help="data-parallel ways of an explicit plan (default 16)")
    ap.add_argument("--tp", type=int, default=None,
                    help="tensor-parallel ways of an explicit plan (default 16)")
    ap.add_argument("--node", type=int, default=1,
                    help="hierarchical node-axis ways (4D CommPlan mesh)")
    ap.add_argument("--ep", type=int, default=1,
                    help="expert-parallel ways (ExpertPlan \"expert\" mesh "
                         "axis; MoE families only)")
    ap.add_argument("--qcomm", choices=("none", "gather", "both"),
                    default="none",
                    help="int8 block-quantized zero=3 collectives")
    ap.add_argument("--overlap", action="store_true",
                    help="overlap zero=3 weight gathers with compute (pp=1)")
    ap.add_argument("--out", default=None, help="append JSON records here")
    ap.add_argument("--print-memory", action="store_true")
    args = ap.parse_args()

    archs = ASSIGNED if (args.all or args.arch in (None, "all")) else [args.arch]
    shapes = sorted(SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    explicit_plan = (args.pp > 1 or args.gas > 1 or args.virtual_stages > 1
                     or args.dp is not None or args.tp is not None
                     or args.zero is not None or args.node > 1
                     or args.ep > 1
                     or args.qcomm != "none" or args.overlap)

    def plan_for(mp: bool):
        if not explicit_plan:
            return None  # default_plan(mp) inside dryrun_one
        # mirror default_plan's pod-as-extra-DP axis so multi-pod records
        # keep the batch sharded over the pod axis of the production mesh
        return TrainPlan(dp=args.dp or 16, tp=args.tp or 16, pp=args.pp,
                         ep=args.ep, node=args.node, qcomm=args.qcomm,
                         overlap=args.overlap,
                         virtual_stages=args.virtual_stages, gas=args.gas,
                         precision="bf16", zero=args.zero,
                         extra_dp_axes=("pod",) if (mp and args.pp == 1) else ())

    records = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = dryrun_one(arch, shape, multi_pod=mp, plan=plan_for(mp))
                records.append(rec)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(tel.sanitize_record(rec)) + "\n")
    n_ok = sum(r["status"] == "ok" for r in records)
    n_skip = sum(r["status"] == "skipped" for r in records)
    n_err = sum(r["status"] == "error" for r in records)
    print(f"\ndry-run summary: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
