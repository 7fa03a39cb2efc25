"""The 22B configuration's file, the collective reader that a four-chip
cell reads, and the faults of a tensor-parallel step over four chips that
a training cell's check has to catch."""
import json
import os
import pathlib
import subprocess
import sys
import types

import pytest

from bench.lib import harness

BENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH.parent
CONFIG = json.loads((BENCH / "configs" / "gpt-22b-l4.json").read_text())


def test_collective_exposed_is_the_mean_share_of_the_window():
    exposed = {"/device:TPU:0": 0.2, "/device:TPU:1": 0.1,
               "/device:TPU:2": 0.0, "/device:TPU:3": 0.1}
    reader = harness.load_module(BENCH / "metrics"
                                 / "collective_exposed.train.py")

    def ctx(exp):
        return {"trace": types.SimpleNamespace(exposed_collective=exp,
                                               window_s=2.0)}
    assert reader.read(ctx(exposed)) == pytest.approx(5.0)
    assert reader.read(ctx({})) is None


@pytest.mark.parametrize("key", sorted(
    k for k, v in CONFIG["published"].items() if isinstance(v, int)))
def test_config_keeps_published_sizes_but_the_reduced(key):
    if key in CONFIG["reduced"]:
        assert CONFIG[key] < CONFIG["published"][key]
    else:
        assert CONFIG[key] == CONFIG["published"][key]


def test_config_assumed_widths_follow_the_published_ones():
    assert sorted(CONFIG["reduced"]) == ["n_layers"]
    assert CONFIG["head_dim"] * CONFIG["n_heads"] == CONFIG["d_model"]
    assert CONFIG["d_ff"] == 4 * CONFIG["d_model"]
    assert CONFIG["n_kv_heads"] == CONFIG["n_heads"]


_FAULTS = """
import contextlib, json, pathlib, sys
sys.path[:0] = [{repo!r}, {src!r}]
from bench.lib import faults
from bench.tests import tiny
root = tiny.make_root(pathlib.Path({tmp!r}))
# the tiny training cell with its layers split tp=4 over four devices
plan = dict(tiny.TRAIN["plan"], tp=4, kernels=False)
tiny.write_json(root / "bench/workloads/tiny-train-tp4.json",
                dict(tiny.TRAIN, plan=plan))
spec = json.loads((root / "BENCHMARK.json").read_text())
spec["workloads"].append({{"name": "tiny-train-tp4", "config": "tiny",
                          "traffic": "tiny-batches", "chips": 4,
                          "why": "test"}})
for m in spec["end_to_end"] + spec["per_layer"]:
    if "tiny-train" in m.get("workloads", []):
        m["workloads"].append("tiny-train-tp4")
tiny.write_json(root / "BENCHMARK.json", spec)
out = {{}}
for name in ("sound", "half_batch", "frozen_state"):
    with (faults.FAULTS[name]() if name in faults.FAULTS
          else contextlib.nullcontext()):
        rc, res, err = tiny.run_cell(root, "tiny-train-tp4")
    out[name] = [rc, res and res["correct"], res and res["device"]["count"],
                 res and res["checks"]]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_device_runs(tmp_path_factory):
    code = _FAULTS.format(repo=str(REPO), src=str(REPO / "src"),
                          tmp=str(tmp_path_factory.mktemp("tp4")))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("kind,correct", [("sound", True),
                                          ("half_batch", False),
                                          ("frozen_state", False)])
def test_tp4_run_is_judged(four_device_runs, kind, correct):
    """A whole tiny run under tp=4 on four CPU devices ends with
    ``correct`` true, and false with each fault planted under the timed
    step."""
    rc, got, count, checks = four_device_runs[kind]
    assert (rc, count) == (0, 4)
    assert got is correct, checks
