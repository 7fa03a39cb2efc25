"""bench_train_step: wall-time of ``jit_train_step`` across the ComputePolicy
and MemoryPlan search space — (remat x kernels x zero x plan) points on a
smoke-sized config.

This starts the repo's measured perf trajectory (as opposed to the analytic
dry-run numbers): every point runs real steps on this machine's backend and
records median step wall time, tokens/s, and the loss trajectory, so remat
policies can be compared *at verified-identical training math*.

  PYTHONPATH=src python benchmarks/bench_train_step.py --out BENCH_train_step.json
  PYTHONPATH=src python benchmarks/bench_train_step.py --validate BENCH_train_step.json

Schema (validated by ``--validate``, wired into ``make bench``):

  {"config": {arch, d_model, n_layers, seq_len, global_batch, steps, devices,
              backend, precision, kernels_interpret_mode},   # _util.run_config
   # each point also carries the telemetry accounting fields
   # (flops_per_step, tflops_per_device, mfu, machine — core/telemetry.py)
   "points": [{"arch": str, "plan": {dp, tp, pp, gas, zero}, "remat": str,
               "kernels": bool, "compile_s": float, "wall_s_per_step": float,
               "tokens_per_s": float, "losses": [float, ...]}, ...]}

Besides the main (dense) matrix, the scan families ride along: zamba2
(mamba2 SSD) and rwkv6 (wkv) run kernels=False vs kernels=True on the base
dp plan — the fused Pallas chunk-scan points — and the validator asserts
each such pair shares one loss trajectory per (arch, plan, remat).

The ``zero`` plan key is the ZeRO stage (core/memplan.py); with more than
one device the base dp plan is swept over stages 0..3 at remat=full, and the
validator asserts every stage reproduces the same loss trajectory — the
MemoryPlan correctness bar (same algorithm, different byte placement).

``backend``/``devices`` record ``jax.default_backend()`` and the device
count of the run; ``kernels_interpret_mode`` flags the CPU caveat
machine-readably: when true, every kernels=True point timed the Pallas
kernels in interpret mode, so those walls are correctness timings, not
kernel perf — consumers must not compare them across backends.

Notes: the smoke shape is matmul-dominated (d=512, ff=2048, S=64) so the
remat tradeoff is visible on CPU — full remat re-runs every projection/MLP
matmul in the backward, which selective skips; ``wall_s_per_step`` is the
min over the timed steps (the standard low-interference estimator on shared
machines).  kernels=True points run the Pallas kernels in interpret mode
here (correctness timing, not kernel perf — that needs a TPU backend).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

POINT_KEYS = {"plan", "remat", "kernels", "compile_s", "wall_s_per_step",
              "tokens_per_s", "losses",
              # telemetry accounting (core/telemetry.py:step_fields)
              "flops_per_step", "tflops_per_device", "mfu", "machine"}
PLAN_KEYS = {"dp", "tp", "pp", "gas", "zero"}
LOSS_TOL = 1e-4


def validate(path: str) -> None:
    """Schema + invariant check: selective must beat full wall time at an
    identical loss trajectory on the base (gas=1, pp=1) plan — the
    acceptance bar for the ComputePolicy fast path.  Other plan points only
    check loss equivalence: pipelined/accumulated steps shift the
    recompute-vs-traffic balance and their timing ordering is reported, not
    asserted (on CPU the pp=2 gap sits inside scheduler noise)."""
    with open(path) as f:
        rec = json.load(f)
    assert {"config", "points"} <= set(rec), f"missing top-level keys in {path}"
    cfgkeys = {"arch", "d_model", "n_layers", "seq_len", "global_batch",
               "steps", "devices", "backend", "precision",
               "kernels_interpret_mode"}
    assert cfgkeys <= set(rec["config"]), (
        f"config keys missing: {cfgkeys - set(rec['config'])}")
    cfg = rec["config"]
    assert isinstance(cfg["devices"], int) and cfg["devices"] >= 1, cfg
    # the CPU-interpret caveat must be recorded consistently with the
    # backend that produced the numbers
    assert cfg["kernels_interpret_mode"] == (cfg["backend"] == "cpu"), cfg
    assert rec["points"], "no benchmark points"
    for p in rec["points"]:
        assert POINT_KEYS <= set(p), f"point keys missing: {POINT_KEYS - set(p)}"
        assert PLAN_KEYS <= set(p["plan"]), p["plan"]
        assert p["remat"] in ("full", "selective", "none"), p["remat"]
        assert p["wall_s_per_step"] > 0 and len(p["losses"]) >= 2, p
        # mfu is None ("not measured") off-chip
        assert p["flops_per_step"] > 0 and (
            p["mfu"] is None or 0.0 <= p["mfu"] <= 1.0), p

    def arch_of(p):
        return p.get("arch", rec["config"]["arch"])

    def key(p):
        return (arch_of(p), tuple(sorted(p["plan"].items())),
                bool(p["kernels"]))

    by_plan: dict = {}
    for p in rec["points"]:
        by_plan.setdefault(key(p), {})[p["remat"]] = p
    checked = False
    for (arch, plan, kernels), modes in by_plan.items():
        if "full" not in modes:
            continue
        ref = modes["full"]["losses"]
        for mode, p in modes.items():
            drift = max(abs(a - b) for a, b in zip(p["losses"], ref))
            assert drift <= LOSS_TOL, (
                f"remat={mode} loss trajectory drifts {drift:.2e} from full "
                f"(arch={arch}, plan={dict(plan)}, kernels={kernels})")
        base_plan = dict(plan)["gas"] == 1 and dict(plan)["pp"] == 1
        if not kernels and base_plan and "selective" in modes:
            full_w = modes["full"]["wall_s_per_step"]
            sel_w = modes["selective"]["wall_s_per_step"]
            assert sel_w < full_w, (
                f"remat=selective ({sel_w:.4f}s) did not beat full "
                f"({full_w:.4f}s) on the base plan={dict(plan)}")
            checked = True
    assert checked, "no (full, selective) pair on a kernels=False base plan"

    # MemoryPlan invariant: the ZeRO stage never changes the training math —
    # points differing only in plan["zero"] must share a loss trajectory
    by_zero: dict = {}
    for p in rec["points"]:
        k = (arch_of(p),
             tuple(sorted((a, b) for a, b in p["plan"].items() if a != "zero")),
             p["remat"], bool(p["kernels"]))
        by_zero.setdefault(k, []).append(p)
    zero_groups = 0
    for k, pts in by_zero.items():
        if len({p["plan"]["zero"] for p in pts}) < 2:
            continue
        zero_groups += 1
        ref = pts[0]["losses"]
        for p in pts[1:]:
            drift = max(abs(a - b) for a, b in zip(p["losses"], ref))
            assert drift <= LOSS_TOL, (
                f"zero={p['plan']['zero']} loss trajectory drifts "
                f"{drift:.2e} from zero={pts[0]['plan']['zero']} ({k})")
    if rec["config"]["devices"] > 1:
        assert zero_groups >= 1, "no multi-stage zero group to validate"

    # kernel-fusion invariant: kernels=True never changes the training math —
    # points differing only in "kernels" must share a loss trajectory (this
    # is what promotes the fused SSD/wkv scan points past correctness)
    by_kern: dict = {}
    for p in rec["points"]:
        k = (arch_of(p), tuple(sorted(p["plan"].items())), p["remat"])
        by_kern.setdefault(k, {})[bool(p["kernels"])] = p
    kernel_pairs = 0
    for k, d in by_kern.items():
        if True not in d or False not in d:
            continue
        kernel_pairs += 1
        drift = max(abs(a - b)
                    for a, b in zip(d[True]["losses"], d[False]["losses"]))
        assert drift <= LOSS_TOL, (
            f"kernels=True loss trajectory drifts {drift:.2e} from the jnp "
            f"path ({k})")
    if any(p["kernels"] for p in rec["points"]):
        assert kernel_pairs >= 1, "no kernels=True/False pair to validate"
        scan_archs = {arch_of(p) for p in rec["points"] if p["kernels"]}
        assert len(scan_archs) >= 2, (
            f"expected scan-family kernels points, got {scan_archs}")
    print(f"{path}: schema + invariants OK "
          f"({len(rec['points'])} points, {zero_groups} zero-equivalence "
          f"groups, {kernel_pairs} kernel-equivalence pairs)")


def run_bench(args) -> dict:
    import time

    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.data import SyntheticCorpus, make_batch_iterator
    from repro.launch.mesh import mesh_for_plan
    from repro.models.model import Model
    from repro.optim import AdamWConfig
    from repro.runtime.train_loop import (ParallelPlan, init_train_state,
                                          jit_train_step)

    n_dev = jax.device_count()
    cfg = get_config(args.arch).reduced(
        n_layers=args.n_layers, d_model=args.d_model, n_heads=4, n_kv_heads=2,
        d_ff=4 * args.d_model, vocab_size=256, head_dim=args.d_model // 4)
    model = Model(cfg, jnp.float32 if args.precision == "fp32" else jnp.bfloat16)
    opt = AdamWConfig(lr=1e-3)
    it = make_batch_iterator(SyntheticCorpus(vocab_size=cfg.vocab_size),
                             seq_len=args.seq_len,
                             global_batch=args.global_batch, prefetch=0)
    batches = [next(it) for _ in range(args.steps + 1)]

    def base_plan(**kw):
        kw.setdefault("zero", 1 if n_dev > 1 else 0)
        return ParallelPlan(precision=args.precision, **kw)

    # the plan axis: dp fills the devices; a gas=2 point and a pp=2 point
    # ride along when the batch/devices/layers tile them, so the matrix
    # covers (remat x kernels x zero x plan)
    plans = [base_plan(dp=n_dev)]
    if args.global_batch % 2 == 0:
        plans.append(base_plan(dp=n_dev, gas=2))
        if n_dev % 2 == 0 and cfg.n_layers % 2 == 0:
            plans.append(base_plan(pp=2, dp=n_dev // 2, gas=2))

    def points_for(plan):
        import dataclasses
        for remat in ("full", "selective", "none"):
            yield dataclasses.replace(plan, remat=remat, kernels=False)
        if plan is plans[0]:
            # the MemoryPlan axis: sweep the ZeRO stage ladder on the base
            # dp plan (remat=full) — the validator asserts all stages share
            # one loss trajectory
            if n_dev > 1:
                for z in (0, 1, 2, 3):
                    if z != plan.zero:
                        yield dataclasses.replace(plan, zero=z)
            if not args.no_kernels:
                for remat in ("full", "selective"):
                    yield dataclasses.replace(plan, remat=remat, kernels=True)

    def bench_point(plan, bmodel, bcfg, arch):
        mesh = mesh_for_plan(plan)
        state = init_train_state(bmodel, jax.random.PRNGKey(0), opt, plan)
        step = jit_train_step(bmodel, opt, plan, mesh,
                              args.global_batch, args.seq_len)
        t0 = time.perf_counter()
        state, m = step(state, batches[0])
        jax.block_until_ready(state)
        compile_s = time.perf_counter() - t0
        losses = [float(m["loss"])]
        walls = []
        for b in batches[1:]:
            t0 = time.perf_counter()
            state, m = step(state, b)
            jax.block_until_ready(state)
            walls.append(time.perf_counter() - t0)
            losses.append(float(m["loss"]))
        wall = float(np.min(walls))  # min-of-N: least-interference estimate
        import _util
        return {
            "arch": arch,
            "plan": {"dp": plan.dp, "tp": plan.tp, "pp": plan.pp,
                     "gas": plan.gas, "zero": plan.zero},
            "remat": plan.remat,
            "kernels": plan.kernels,
            "compile_s": round(compile_s, 3),
            "wall_s_per_step": round(wall, 5),
            "losses": losses,
            # telemetry accounting (core/telemetry.py:step_fields):
            # tokens_per_s + analytic model FLOPs + MFU, same fields as the
            # live train records
            **_util.point_fields(bcfg, args.global_batch, args.seq_len,
                                 wall, n_dev),
        }

    def show(rec, p, arch):
        print(f"{arch:14s} "
              f"plan(dp={p.dp},tp={p.tp},pp={p.pp},gas={p.gas},zero={p.zero}) "
              f"remat={p.remat:9s} kernels={int(p.kernels)} | "
              f"{rec['wall_s_per_step']*1e3:8.2f} ms/step "
              f"{rec['tokens_per_s']:>10,.0f} tok/s "
              f"(compile {rec['compile_s']:.1f}s) loss0 {rec['losses'][0]:.5f}")

    points = []
    for plan in plans:
        for p in points_for(plan):
            rec = bench_point(p, model, cfg, args.arch)
            points.append(rec)
            show(rec, p, args.arch)

    # scan-family rows: the fused SSD (zamba2/mamba2) and wkv (rwkv6) chunk
    # scans vs their jnp paths — kernels=False/True on the base dp plan at
    # remat=full; the validator asserts each pair shares one loss trajectory
    if not args.no_kernels:
        import dataclasses
        for arch in ("zamba2-2.7b", "rwkv6-1.6b"):
            fam_kw = dict(n_layers=4, d_model=128, n_heads=4, n_kv_heads=2,
                          d_ff=256, vocab_size=256, head_dim=32)
            if arch.startswith("zamba"):
                fam_kw["hybrid_attn_every"] = 2
            fam_cfg = get_config(arch).reduced(**fam_kw)
            fam_model = Model(fam_cfg, jnp.float32 if args.precision == "fp32"
                              else jnp.bfloat16)
            for kernels in (False, True):
                p = dataclasses.replace(plans[0], remat="full",
                                        kernels=kernels)
                rec = bench_point(p, fam_model, fam_cfg, arch)
                points.append(rec)
                show(rec, p, arch)

    import _util
    return {
        "config": _util.run_config(
            arch=args.arch, d_model=args.d_model, n_layers=args.n_layers,
            seq_len=args.seq_len, global_batch=args.global_batch,
            steps=args.steps, precision=args.precision),
        "points": points,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--n-layers", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=8,
                    help="timed steps per point (min reported)")
    ap.add_argument("--precision", choices=["bf16", "fp16", "fp32"],
                    default="fp32",
                    help="fp32 keeps remat loss trajectories bit-comparable")
    ap.add_argument("--devices", type=int, default=None,
                    help="force a host-device count (sets XLA_FLAGS; must be "
                         "set before jax is imported)")
    ap.add_argument("--no-kernels", action="store_true",
                    help="skip the Pallas interpret-mode points (faster)")
    ap.add_argument("--out", default="BENCH_train_step.json")
    ap.add_argument("--validate", metavar="PATH", default=None,
                    help="validate an existing result file and exit")
    args = ap.parse_args()

    if args.validate:
        validate(args.validate)
        return
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))
    rec = run_bench(args)
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"wrote {args.out} ({len(rec['points'])} points)")
    validate(args.out)


if __name__ == "__main__":
    main()
