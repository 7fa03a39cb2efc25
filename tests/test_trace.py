"""Pipeline trace export: schedule geometry, Chrome-trace schema, and the
idle-fraction acceptance check.

  * the (stage x microbatch x wave) intervals never double-book a
    (rank, tick) cell and cover exactly the schedule's useful work;
  * the idle fraction integrated from a built trace equals the executor's
    own ``spmd_idle_fraction`` to float precision on a grid of (p, m, v)
    shapes including a partial last wave — and therefore equals
    ``bubble.wave_bubble_fraction`` for v>1 and the GPipe bubble for v==1;
  * ``validate_trace`` / ``check_trace_file`` schema + tolerance behaviour;
  * end-to-end: a real ``launch/train.py --trace --log-jsonl`` run on 4
    virtual devices (pp=2, v=2, gas=4) produces a schema-valid telemetry
    stream and a trace whose measured idle fraction matches the analytic
    wave bubble within the 15% acceptance bound.
"""
import json

import pytest

from repro.analysis import trace as tr
from repro.core import bubble
from repro.core.pipeline import spmd_idle_fraction, spmd_schedule

GRIDS = [
    (2, 4, 1), (4, 8, 1), (3, 6, 1),      # contiguous GPipe-style pass
    (2, 4, 2), (2, 2, 2), (4, 8, 2),      # full interleaved waves
    (3, 7, 2),                            # partial last wave (width 1)
    (2, 8, 4), (4, 4, 4),                 # deeper interleaving
]


@pytest.mark.parametrize("p,m,v", GRIDS)
def test_stage_intervals_geometry(p, m, v):
    ivs = tr.stage_intervals(p, m, v)
    # one interval per useful stage application, no (rank, tick) collision
    _, _, useful = spmd_schedule(p, m, v)
    assert len(ivs) == useful
    cells = [(iv["rank"], iv["tick"]) for iv in ivs]
    assert len(cells) == len(set(cells))
    assert all(0 <= iv["rank"] < p for iv in ivs)
    # interleaved placement: logical stage l runs on rank l % p
    assert all(iv["rank"] == iv["stage"] % p for iv in ivs)


@pytest.mark.parametrize("p,m,v", GRIDS)
def test_trace_idle_matches_schedule(p, m, v):
    trace = tr.build_trace(p, m, v, [1.0, 0.5])
    measured = tr.trace_idle_fraction(trace)
    assert measured == pytest.approx(spmd_idle_fraction(p, m, v), abs=1e-9)
    # and the metadata block carries the same number
    assert trace["metadata"]["idle_fraction_schedule"] == pytest.approx(
        measured, abs=1e-9)


@pytest.mark.parametrize("p,m,v", [g for g in GRIDS if g[2] > 1])
def test_trace_idle_equals_wave_bubble_for_interleaved(p, m, v):
    trace = tr.build_trace(p, m, v, [0.25])
    assert tr.trace_idle_fraction(trace) == pytest.approx(
        bubble.wave_bubble_fraction(p, m, v), abs=1e-9)


@pytest.mark.parametrize("p,m,v", [g for g in GRIDS if g[2] == 1])
def test_trace_idle_equals_gpipe_bubble_for_v1(p, m, v):
    trace = tr.build_trace(p, m, v, [0.25])
    assert tr.trace_idle_fraction(trace) == pytest.approx(
        bubble.bubble_fraction(p, m, schedule="gpipe"), abs=1e-9)


def test_build_trace_event_schema():
    trace = tr.build_trace(2, 4, 2, [1.0, 2.0],
                           meta={"arch": "x", "plan": {"pp": 2}})
    tr.validate_trace(trace)  # no raise
    md = trace["metadata"]
    assert md["schema"] == "repro.trace/1"
    assert md["steps"] == 2 and md["arch"] == "x"
    evs = trace["traceEvents"]
    # two step slices on pid 1, laid end to end
    steps = [e for e in evs if e.get("cat") == "step"]
    assert len(steps) == 2
    assert steps[1]["ts"] == pytest.approx(steps[0]["ts"] + steps[0]["dur"])
    # a stage slice carries (microbatch, stage, wave, step) args
    st = next(e for e in evs if e.get("cat") == "stage")
    assert {"microbatch", "stage", "wave", "step"} <= set(st["args"])
    # lane metadata present for every pipe rank
    tids = {e["tid"] for e in evs if e["ph"] == "M" and "tid" in e}
    assert tids == {0, 1}
    with pytest.raises(ValueError, match="at least one"):
        tr.build_trace(2, 4, 2, [])


def test_validate_trace_rejects_bad():
    with pytest.raises(ValueError, match="traceEvents"):
        tr.validate_trace({"traceEvents": []})
    good = tr.build_trace(2, 4, 2, [1.0])
    bad = dict(good)
    bad["metadata"] = {k: v for k, v in good["metadata"].items()
                       if k != "wave_bubble_fraction"}
    with pytest.raises(ValueError, match="wave_bubble_fraction"):
        tr.validate_trace(bad)
    bad2 = dict(good)
    bad2["metadata"] = {**good["metadata"], "schema": "nope"}
    with pytest.raises(ValueError, match="unknown trace schema"):
        tr.validate_trace(bad2)


def test_check_trace_file(tmp_path):
    path = str(tmp_path / "trace.json")
    tr.write_trace(tr.build_trace(2, 4, 2, [1.0, 0.5]), path)
    summary = tr.check_trace_file(path, tol=0.15)
    assert summary["relative_error"] < 1e-9
    assert summary["analytic_bubble"] == pytest.approx(
        bubble.wave_bubble_fraction(2, 4, 2))
    # tampered analytic anchor -> tolerance failure
    with open(path) as f:
        doc = json.load(f)
    doc["metadata"]["wave_bubble_fraction"] = 0.9
    with open(path, "w") as f:
        json.dump(doc, f)
    with pytest.raises(ValueError, match="relative error"):
        tr.check_trace_file(path, tol=0.15)


# ---------------------------------------------------------------------------
# end-to-end: real instrumented train run on 4 virtual devices
# ---------------------------------------------------------------------------

E2E_CODE = r"""
import runpy, sys
sys.argv = ["train", "--arch", "yi-6b", "--reduced", "--layers", "4",
            "--dp", "2", "--pp", "2", "--virtual-stages", "2", "--gas", "4",
            "--steps", "2", "--global-batch", "8", "--seq-len", "32",
            "--log-every", "1",
            "--log-jsonl", {jsonl!r}, "--trace", {trace!r}]
runpy.run_module("repro.launch.train", run_name="__main__")
"""


def test_train_trace_end_to_end(multidev, tmp_path):
    jsonl = str(tmp_path / "tele.jsonl")
    trace = str(tmp_path / "trace.json")
    multidev(E2E_CODE.format(jsonl=jsonl, trace=trace), n_devices=4)

    from repro.core import telemetry as tel
    recs = tel.validate_jsonl(jsonl)
    comp = next(r for r in recs if r["kind"] == "compile")
    assert comp["plan"]["pp"] == 2 and comp["plan"]["virtual_stages"] == 2
    assert "comm_bytes_measured" in comp and "state_bytes" in comp
    assert "error" not in comp["comm_bytes_measured"]
    steps = [r for r in recs if r["kind"] == "step"]
    assert len(steps) == 2
    # a CPU run records MFU as not measured (no device peak)
    assert all("predicted" in r and "drift" not in r and r["mfu"] is None
               for r in steps)

    # the acceptance bound: measured idle within 15% of the analytic
    # wave bubble for (p=2, m=4, v=2)
    summary = tr.check_trace_file(trace, tol=0.15)
    assert summary["analytic_bubble"] == pytest.approx(
        bubble.wave_bubble_fraction(2, 4, 2))
