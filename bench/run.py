"""Benchmark entry point: one run of one cell on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json and its files under bench/ by name, makes
the cell's weights and inputs from ``--seed``, warms up every shape the
window uses (set-up), measures for ``--seconds``, then checks what the
timed path produced against the plain reference.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number compared with its limit.
The same numbers close standard error.

Exits non-zero, with no result, when JAX finds no TPU or fewer chips than
the cell asks for, or when the program under test (``src/repro``) is not
beside this directory.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _number(x: float) -> float | str:
    """A compared number as JSON has it: ``"inf"`` or ``"nan"`` where the
    comparison had nothing finite to read."""
    return x if math.isfinite(x) else str(x)


def _device(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def main(argv=None, *, root: pathlib.Path = ROOT,
         on_chip: bool = True) -> int:
    """One run; ``on_chip=False`` (tests only) skips the look for a TPU and
    leaves JAX's persistent compilation cache alone."""
    args = parse_args(argv)
    if not (root / "src" / "repro").is_dir():
        print(f"error: the program under test is not at {root / 'src'}",
              file=sys.stderr)
        return 2
    for p in (str(root / "src"), str(root)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.lib import harness

    cell = harness.load_cell(args.workload, root)
    bench = harness.load_benchmark(root)

    import jax

    devices = jax.devices()
    if on_chip:
        if devices[0].platform != "tpu":
            print(f"error: JAX found no TPU (platform "
                  f"{devices[0].platform!r})", file=sys.stderr)
            return 1
        if len(devices) < cell.chips:
            print(f"error: {cell.name} needs {cell.chips} chips, JAX found "
                  f"{len(devices)}", file=sys.stderr)
            return 1
        harness.use_compile_cache()

    run = harness.Run(cell=cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_process=T_PROCESS,
                      compiles=harness.CompileCounter())
    runner = harness.runner_module(cell, root / "bench")
    try:
        out = runner.run(run)
        gc.collect()
        device = _device(devices)
        device["memory_peak_bytes"] = out["memory_peak_bytes"]
        info = {"cell": cell.name, "seed": args.seed,
                "window_s": run.window_s,
                **run.compile_counts(),
                **out.get("info", {})}
        result = {"correct": harness.judge(out["checks"]),
                  "attempted": out["attempted"],
                  "failed": out["failed"]}
        if run.trace:
            from bench.lib import peaks as peaks_mod
            from bench.lib import trace as trace_mod
            t = trace_mod.load(run.trace_dir)
            red = trace_mod.reduce(t, trace_mod.span_window(t, "bench.window"))
            ctx = {"cell": cell, "counters": out["counters"], "trace": red,
                   "peaks": peaks_mod.peaks_for(devices[0].device_kind),
                   "e2e": out["e2e"], "window_s": run.window_s}
            result["metrics"] = harness.read_per_layer(
                bench, cell.name, ctx, root / "bench")
            device["busy_s"] = red.busy_s
            device["window_s"] = red.window_s
            info["idle_by_device"] = red.idle
            info["exposed_collective_s_by_device"] = red.exposed_collective
            info["kernel_calls"] = {k: len(v) for k, v in red.kernels.items()}
            result["breakdown"] = {"device_ops": red.top_ops,
                                   "idle_gaps": red.idle_gaps}
        else:
            metrics = {"setup_s": {"value": run.setup_s, "unit": "s"}}
            for m in harness.end_to_end_for(bench, cell.name):
                if m["name"] != "setup_s":
                    metrics[m["name"]] = {"value": out["e2e"][m["name"]],
                                          "unit": m["unit"]}
            result["metrics"] = metrics
        result["device"] = device
        result["checks"] = {c["name"]: {"value": _number(c["value"]),
                                        "limit": c["limit"]}
                            for c in out["checks"]}
    finally:
        run.cleanup()
    print(json.dumps({"info": info}), flush=True)
    for c in out["checks"]:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
