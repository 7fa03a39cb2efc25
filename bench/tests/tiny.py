"""A tiny benchmark tree for CPU tests: the real runners and metric readers
with a small dense configuration and small mixes, in a directory of its
own, so that a test can drive a whole run of ``bench/run.py``."""
from __future__ import annotations

import contextlib
import io
import json
import os
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH.parent

CONFIG = {
    "name": "tiny", "source": "test", "family": "dense", "n_layers": 2,
    "d_model": 64, "n_heads": 4, "n_kv_heads": 4, "head_dim": 16,
    "d_ff": 256, "vocab_size": 512, "norm": "layernorm", "act": "gelu",
    "pos": "rope", "rope_theta": 10000.0, "norm_eps": 1e-5,
    "tie_embeddings": False,
}
TRAIN_MIX = {"kind": "batches", "global_batch": 4, "seq_len": 64,
             "mean_doc_len": 24, "zipf_a": 1.2}
SERVE_MIX = {"kind": "closed_loop", "clients": 4, "size_seed": 0,
             "prompt": {"median": 12, "sigma": 0.5, "min": 4, "max": 40},
             "output": {"median": 6, "sigma": 0.5, "min": 3, "max": 16},
             "temperature": 0.0}
TRAIN = {"runner": "train",
         "plan": {"dp": 1, "tp": 1, "pp": 1, "gas": 1, "precision": "bf16",
                  "remat": "full", "kernels": True},
         "optimizer": {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                       "weight_decay": 0.1, "grad_clip": 1.0},
         # set as the cells' limits are: above the readings of sound runs
         # at this size (loss 4e-5, gradient 7e-3, change 1.5e-3), below
         # the float8 control's (3e-4, 0.015, 0.008)
         "limits": {"loss_gap": 1.5e-4, "grad_gap": 0.012,
                    "change_gap": 0.004}}
TRAIN_TP2PP2 = dict(TRAIN, plan={"dp": 1, "tp": 2, "pp": 2, "gas": 2,
                                 "precision": "bf16", "remat": "full",
                                 "kernels": False})
SERVE = {"runner": "serve", "engine": {"cache_len": 64, "block_size": 8},
         # sound runs read 1e-3 here, the float8 control 0.03
         "check": {"requests": 4}, "limits": {"max_gap": 0.01}}


def write_json(path: pathlib.Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout-shaped directory: the program, BENCHMARK.json with the
    tiny cells, and bench/ with the real runners and readers."""
    root = tmp / "root"
    (root / "bench").mkdir(parents=True)
    os.symlink(REPO / "src", root / "src")
    for d in ("runners", "metrics"):
        os.symlink(BENCH / d, root / "bench" / d)
    write_json(root / "bench/configs/tiny.json", CONFIG)
    write_json(root / "bench/traffic/tiny-batches.json", TRAIN_MIX)
    write_json(root / "bench/traffic/tiny-closed.json", SERVE_MIX)
    write_json(root / "bench/workloads/tiny-train.json", TRAIN)
    write_json(root / "bench/workloads/tiny-serve.json", SERVE)
    write_json(root / "bench/workloads/tiny-train-tp2pp2.json", TRAIN_TP2PP2)
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    bench = dict(real)
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [
        {"name": "tiny-train", "config": "tiny", "traffic": "tiny-batches",
         "chips": 1, "why": "test"},
        {"name": "tiny-serve", "config": "tiny", "traffic": "tiny-closed",
         "chips": 1, "why": "test"},
        {"name": "tiny-train-tp2pp2", "config": "tiny",
         "traffic": "tiny-batches", "chips": 4, "why": "test"}]
    # each real cell's metrics go to the tiny cells of its runner
    tiny_cells = {"train": ["tiny-train", "tiny-train-tp2pp2"],
                  "serve": ["tiny-serve"]}
    runner = {w["name"]: json.loads(
        (REPO / "bench/workloads" / f"{w['name']}.json").read_text())["runner"]
        for w in real["workloads"]}

    def retarget(m):
        m = dict(m)
        if "workloads" in m:
            m["workloads"] = sorted({t for w in m["workloads"]
                                     for t in tiny_cells[runner[w]]})
        return m
    bench["end_to_end"] = [retarget(m) for m in real["end_to_end"]]
    bench["per_layer"] = [retarget(m) for m in real["per_layer"]]
    write_json(root / "BENCHMARK.json", bench)
    return root


def run_cell(root: pathlib.Path, cell: str, seed: int = 2**31 + 11,
             seconds: float = 1.0, trace: int = 0
             ) -> tuple[int, dict | None, str]:
    """Drive ``bench/run.py`` in this process with the chip check skipped;
    returns (exit code, the result line, standard error)."""
    from bench import run as bench_run

    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = bench_run.main(["--workload", cell, "--seed", str(seed),
                             "--seconds", str(seconds), "--trace", str(trace)],
                            root=root, on_chip=False)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()
