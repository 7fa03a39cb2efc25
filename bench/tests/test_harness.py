"""The harness finds everything by name; BENCHMARK.json and the files under
bench/ agree; ``bench/run.py`` refuses to run without a TPU."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench.lib import harness

BENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH.parent
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())


def test_every_cell_has_its_files():
    for w in SPEC["workloads"]:
        cell = harness.load_cell(w["name"], REPO)
        assert (BENCH / "runners" / f"{cell.runner}.py").is_file()
        assert cell.traffic["kind"] in ("batches", "closed_loop")
        assert cell.config["name"] == cell.config_name


def test_every_config_file_is_under_bench_and_its_own():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/configs/")
        cfg = json.loads((REPO / c["file"]).read_text())
        assert sorted(cfg.get("reduced", {})) == sorted(c["reduced"])


def test_metric_readers_declare_what_benchmark_json_says():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        reader = harness.load_module(BENCH / "metrics" / f"{m['name']}.py")
        assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"]), m["name"]
        assert m["moves"] in e2e
        # every cell that reads the metric reports what it moves
        for cell in m["workloads"]:
            assert m["moves"] in {x["name"] for x in
                                  harness.end_to_end_for(SPEC, cell)}, cell


def test_every_cell_reports_setup_another_metric_and_a_layer():
    for w in SPEC["workloads"]:
        names = {m["name"] for m in harness.end_to_end_for(SPEC, w["name"])}
        assert "setup_s" in names and len(names) >= 2
        assert harness.per_layer_for(SPEC, w["name"])


def _copy_tree(tmp: pathlib.Path) -> pathlib.Path:
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def _snapshot(root: pathlib.Path) -> dict:
    return {p.relative_to(root): p.read_bytes()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_cell_and_metric_are_found_by_name(tmp_path):
    """A configuration, a mix, a cell and a per-layer metric dropped in as
    new files plus new entries, and no existing file changes."""
    root = _copy_tree(tmp_path)
    before = _snapshot(root)
    bench = root / "bench"
    cfg = json.loads((bench / "configs/gpt-1.4b.json").read_text())
    (bench / "configs/gpt-1.4b-l12.json").write_text(
        json.dumps(dict(cfg, name="gpt-1.4b-l12", n_layers=12)))
    (bench / "traffic/train-b2-s4096.json").write_text(json.dumps(
        {"kind": "batches", "global_batch": 2, "seq_len": 4096,
         "mean_doc_len": 1024, "zipf_a": 1.2}))
    wl = json.loads((bench / "workloads/train-gpt-1.4b-s2048.json")
                    .read_text())
    (bench / "workloads/train-gpt-1.4b-l12-s4096.json").write_text(
        json.dumps(wl))
    (bench / "metrics/steps_in_window.train.py").write_text(
        'LAYER = "runtime/train_loop"\nUNIT = "steps"\n'
        'SOURCE = "host_clock"\nMOVES = "train_tokens_per_s"\n\n\n'
        'def read(ctx):\n    return ctx["counters"].get("steps")\n')
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "gpt-1.4b-l12", "source": "x",
                            "file": "bench/configs/gpt-1.4b-l12.json",
                            "reduced": ["n_layers"], "why": "x"})
    spec["workloads"].append({"name": "train-gpt-1.4b-l12-s4096",
                              "config": "gpt-1.4b-l12",
                              "traffic": "train-b2-s4096", "chips": 1,
                              "why": "x"})
    for m in spec["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("train-gpt-1.4b-l12-s4096")
    spec["per_layer"].append({"name": "steps_in_window.train",
                              "unit": "steps", "better": "higher",
                              "source": "host_clock",
                              "layer": "runtime/train_loop",
                              "moves": "train_tokens_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    changed = {k for k, v in before.items()
               if k != pathlib.Path("BENCHMARK.json")
               and (root / k).read_bytes() != v}
    assert not changed
    cell = harness.load_cell("train-gpt-1.4b-l12-s4096", root)
    assert (cell.config["n_layers"], cell.traffic["seq_len"],
            cell.runner) == (12, 4096, "train")
    names = [m["name"] for m in harness.per_layer_for(
        spec, "train-gpt-1.4b-l12-s4096")]
    assert "steps_in_window.train" in names
    # a metric without a workloads list is read wherever its end-to-end
    # metric is reported, in the cells already there too
    assert "steps_in_window.train" in [
        m["name"] for m in harness.per_layer_for(spec, "train-gpt-1.4b-s2048")]
    out = harness.read_per_layer(
        {**spec, "per_layer": [spec["per_layer"][-1]]},
        "train-gpt-1.4b-l12-s4096", {"counters": {"steps": 7}}, bench)
    assert out == {"steps_in_window.train": {"value": 7.0, "unit": "steps"}}
    # a reader that finds nothing leaves its metric out of the line
    assert harness.read_per_layer(
        {**spec, "per_layer": [spec["per_layer"][-1]]},
        "train-gpt-1.4b-l12-s4096", {"counters": {}}, bench) == {}


def _run_py(cwd: pathlib.Path, *args: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "train-gpt-1.4b-s2048",
         "--seed", str(2**33 + 5), "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_fails_without_a_tpu():
    r = _run_py(REPO)
    assert r.returncode != 0
    assert r.stdout == ""
    assert "no TPU" in r.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    r = _run_py(_copy_tree(tmp_path))
    assert r.returncode != 0
    assert r.stdout == ""


@pytest.mark.parametrize("flag", ["--workload", "--seed", "--seconds"])
def test_run_needs_each_argument(flag):
    args = ["--workload", "x", "--seed", "1", "--seconds", "1"]
    i = args.index(flag)
    from bench import run as bench_run
    with pytest.raises(SystemExit):
        bench_run.parse_args(args[:i] + args[i + 2:])


NAME = r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}"
UNIT = r"[A-Za-z0-9_/%.\-]{1,16}"


def test_benchmark_json_keeps_its_format():
    import re
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"][1].startswith("bench/")
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    line = lambda s: 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s  # noqa: E731
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert re.fullmatch(NAME, c["name"]) and line(c["why"])
        assert line(c["source"]) and len(c["reduced"]) <= 16
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert re.fullmatch(NAME, w["name"]) and w["chips"] in (1, 4)
        assert re.fullmatch(NAME, w["traffic"]) and line(w["why"])
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert re.fullmatch(NAME, m["name"]) and re.fullmatch(UNIT, m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert line(m["layer"])
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024
