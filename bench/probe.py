"""Readings that the limits of ``correct`` are set from, many seeds in one
process (benchmark runs never call this):

  python3 bench/probe.py --workload <cell> --seeds 11,12,13 --seconds 30 \
      --kinds program,control,half_batch

For each seed, one JSON line per kind:

  program      the program's check numbers;
  control      the control: the reference computed with float8 products in
               the program's place (training: its three steps against the
               float32 reference's; serving: the float32 gap of the token
               the float8 reference puts first, over the same served
               tokens, beside the program's own numbers);
  <fault>      the check numbers with that fault of ``bench/lib/faults.py``
               planted under the timed path.

Each line is a JSON object; exits non-zero without a TPU, as run.py does.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def train_control(cell, seed: int, runner) -> dict:
    from bench.lib import compare, corpus, reference, traffic
    mix, wl = cell.traffic, cell.workload
    ks = traffic.key_seed(seed)
    data = corpus.Corpus(vocab_size=cell.config["vocab_size"],
                         seed=traffic.corpus_seed(seed),
                         mean_doc_len=mix["mean_doc_len"], zipf_a=mix["zipf_a"])
    rows = data.batches(mix["global_batch"], mix["seq_len"], 3)
    opt = reference.AdamW.from_dict(wl["optimizer"])
    mesh = runner.reference_mesh(cell)
    ref = reference.train_steps(cell.config, ks, rows, opt, reference.FP32,
                                mesh)
    ctl = reference.train_steps(cell.config, ks, rows, opt, reference.FP8,
                                mesh)
    still = compare.still_leaves(ref["raw_grad"])
    return {"loss_gap": compare.loss_gap(ctl["losses"], ref["losses"]),
            "grad_gap": compare.leaf_gap(ctl["grad"], ref["grad"]),
            "change_gap": compare.leaf_gap(ctl["change"], ref["change"],
                                           still),
            "still_leaves": sorted(still)}


def main(argv=None, *, root: pathlib.Path = ROOT,
         on_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--kinds", default="program",
                    help="comma-separated: program, control, or a fault")
    args = ap.parse_args(argv)
    for p in (str(root / "src"), str(root)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.lib import faults, harness, reference

    cell = harness.load_cell(args.workload, root)
    import jax
    devices = jax.devices()
    if on_chip:
        if devices[0].platform != "tpu" or len(devices) < cell.chips:
            print("error: no TPU with enough chips", file=sys.stderr)
            return 1
        harness.use_compile_cache()
    runner = harness.runner_module(cell, root / "bench")
    kinds = args.kinds.split(",")
    for kind in kinds:
        if kind not in ("program", "control") and kind not in faults.FAULTS:
            print(f"error: unknown kind {kind!r}", file=sys.stderr)
            return 2
    for seed in [int(s) for s in args.seeds.split(",")]:
        for kind in kinds:
            t0 = time.perf_counter()
            if kind == "control" and cell.runner == "train":
                line = train_control(cell, seed, runner)
            else:
                run = harness.Run(cell=cell, seed=seed, seconds=args.seconds,
                                  trace=False, t_process=t0,
                                  probe={"control": reference.FP8}
                                  if kind == "control" else {})
                fault = faults.FAULTS.get(kind)
                with fault() if fault else contextlib.nullcontext():
                    out = runner.run(run)
                line = {c["name"]: c["value"] for c in out["checks"]}
                line.update(setup_s=run.setup_s, e2e=out["e2e"],
                            info=out["info"])
            line.update(seed=seed, seconds=time.perf_counter() - t0,
                        kind=kind)
            print(json.dumps(line), flush=True)
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
