"""Whole runs of ``bench/run.py`` on the CPU at a tiny size, the look for a
chip skipped: each runner end to end, and each fault that a cell can have
planted under the timed path, where ``correct`` must come out false."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

from bench.lib import faults, harness, peaks
from bench.tests import tiny

BENCH = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def _result_shape(res: dict, chips: int = 1) -> None:
    assert list(res)[-1] == "checks"
    for k in ("correct", "attempted", "failed", "metrics", "device"):
        assert k in res
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == chips
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}


def test_train_cell_end_to_end(root):
    rc, res, err = tiny.run_cell(root, "tiny-train")
    assert rc == 0, err
    _result_shape(res)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0
    # each number compared closes standard error, beside its limit
    tail = err.strip().splitlines()[-len(res["checks"]):]
    assert [line.split(":")[0] for line in tail] == [
        f"check {name}" for name in res["checks"]]


def test_serve_cell_end_to_end(root):
    rc, res, err = tiny.run_cell(root, "tiny-serve")
    assert rc == 0, err
    _result_shape(res)
    assert res["correct"] is True
    # the serving metrics that BENCHMARK.json gives a serving cell
    bench = harness.load_benchmark(root)
    assert set(res["metrics"]) == {
        m["name"] for m in harness.end_to_end_for(bench, "tiny-serve")}
    assert {"setup_s", "serve_tokens_per_s", "itl_p95_ms"} <= set(res["metrics"])
    assert all(v["value"] > 0 for v in res["metrics"].values())


def test_traced_train_run_reads_its_layers(root, monkeypatch):
    """With ``--trace 1`` the per-layer metrics replace the end-to-end ones;
    on the CPU no device operation is traced, so the device readers find
    nothing, and the peaks are the chip's, borrowed for the test."""
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.TPU_V5E)
    rc, res, err = tiny.run_cell(root, "tiny-train", trace=1)
    assert rc == 0, err
    _result_shape(res)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"mfu.train"}
    assert 0 < res["metrics"]["mfu.train"]["value"]
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("cell,fault", [
    ("tiny-train", "half_batch"),
    ("tiny-train", "frozen_state"),
    ("tiny-serve", "altered_tokens"),
])
def test_fault_is_not_correct(root, cell, fault):
    with faults.FAULTS[fault]():
        rc, res, err = tiny.run_cell(root, cell)
    assert rc == 0, err
    assert res["correct"] is False, res["checks"]


_FOUR_DEVICES = """
import json, pathlib, sys
sys.path[:0] = [{repo!r}, {src!r}]
from bench.lib import faults
from bench.tests import tiny
root = tiny.make_root(pathlib.Path({tmp!r}))
out = {{}}
rc, res, err = tiny.run_cell(root, "tiny-train-tp2pp2")
out["sound"] = (rc, res and res["correct"], res and res["device"]["count"])
with faults.no_stage_exchange():
    rc, res, err = tiny.run_cell(root, "tiny-train-tp2pp2")
out["no_stage_exchange"] = (rc, res and res["correct"], res and res["checks"])
print(json.dumps(out))
"""


def test_pipeline_exchange_left_out_is_not_correct(tmp_path):
    """tp=2 x pp=2 on four CPU devices: a sound run is correct, and one
    whose stages never receive their predecessor's activations is not."""
    code = _FOUR_DEVICES.format(repo=str(BENCH.parent),
                                src=str(BENCH.parent / "src"),
                                tmp=str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["sound"] == [0, True, 4]
    assert out["no_stage_exchange"][:2] == [0, False], out
