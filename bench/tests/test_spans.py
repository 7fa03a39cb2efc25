"""The readers of the program's own spans (``bench/lib/spans.py``): known
values on a span log built by hand, nothing from a program that has no
span log, and on a whole tiny serving run the engine's tick spans agree
with the runner's own stamps and counts."""
import pathlib

import pytest

from bench.lib import harness, peaks, spans
from bench.tests import tiny

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"
SERVE_READERS = ("tick_host_ms.serve", "prefill_ms.serve",
                 "decode_occupancy.serve")


def _reader(name: str):
    return harness.load_module(METRICS / f"{name}.py")


def _tick(i, parent, start, end, admitted, live):
    return (i, parent, "serve.tick", start, end,
            {"admitted": admitted, "live": live, "n_slots": 4})


# (id, parent, name, start, end, attrs); the window is [1, 10]
HAND_BUILT = [
    _tick(1, None, 0.0, 0.5, 0, 4),                  # before the window
    _tick(2, None, 1.0, 1.5, 0, 3),                  # 500 ms, 200 ms waiting
    (3, 2, "serve.wait", 1.2, 1.4, {"for": "decode"}),
    (4, 2, "serve.decode", 1.0, 1.2, {}),
    _tick(5, None, 2.0, 2.6, 0, 4),                  # 600 ms, 400 ms waiting
    (6, 5, "serve.wait", 2.1, 2.5, {"for": "decode"}),
    _tick(7, None, 3.0, 4.0, 1, 2),                  # admits: not a decode tick
    (8, 7, "serve.admit", 3.0, 3.5, {"rid": 0}),
    (9, 8, "serve.wait", 3.3, 3.5, {"for": "prefill"}),
    (10, 7, "serve.wait", 3.8, 3.9, {"for": "decode"}),
    _tick(11, None, 5.0, 5.4, 1, 3),
    (12, 11, "serve.admit", 5.0, 5.3, {"rid": 1}),
    _tick(13, None, 9.5, 10.5, 0, 1),                # ends after the window
]


@pytest.fixture
def hand_built(monkeypatch):
    from repro.core import telemetry as tel
    log = tel.SpanLog()
    log.records.extend(tel.SpanRecord(*s) for s in HAND_BUILT)
    monkeypatch.setattr(tel, "SPANS", log)
    return {"counters": {"window": (1.0, 10.0)}}


def test_readers_on_a_hand_built_log(hand_built):
    ctx = hand_built
    assert [s.id for s in spans.in_window(ctx, "serve.tick")] == [2, 5, 7, 11]
    tick = spans.in_window(ctx, "serve.tick")[0]
    assert spans.self_seconds(tick, spans.in_window(ctx)) == \
        pytest.approx(0.1)
    assert spans.self_seconds(tick, spans.in_window(ctx), "serve.wait") == \
        pytest.approx(0.3)
    # decode ticks 2 and 5: 300 and 200 ms of host time
    assert _reader("tick_host_ms.serve").read(ctx) == pytest.approx(250.0)
    # admissions of 500 and 300 ms
    assert _reader("prefill_ms.serve").read(ctx) == pytest.approx(400.0)
    # 3, 4, 2, 3 of 4 slots live
    assert _reader("decode_occupancy.serve").read(ctx) == pytest.approx(75.0)


def test_readers_find_nothing_outside_the_window(hand_built):
    ctx = {"counters": {"window": (20.0, 30.0)}}
    for name in SERVE_READERS:
        assert _reader(name).read(ctx) is None, name
    # a runner that keeps no window
    assert spans.in_window({"counters": {}}) == []


def test_readers_find_nothing_in_a_program_without_spans(monkeypatch):
    """A program from before the span log: the readers report nothing, and
    raise nothing."""
    from repro.core import telemetry as tel
    monkeypatch.delattr(tel, "SPANS")
    ctx = {"counters": {"window": (0.0, float("inf"))}}
    for name in SERVE_READERS:
        assert _reader(name).read(ctx) is None, name


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def test_tick_spans_agree_with_the_runner(root):
    """The tiny serving cell's runner, called as bench/run.py calls it: one
    engine tick span per ``step()`` the runner stamped, each within 1 ms of
    its stamps, and one prefill span per admission it counted."""
    cell = harness.load_cell("tiny-serve", root)
    run = harness.Run(cell=cell, seed=2**31 + 5, seconds=1.0, trace=False,
                      t_process=0.0)
    out = harness.runner_module(cell, root / "bench").run(run)
    ctx = {"counters": out["counters"]}
    ticks = spans.in_window(ctx, "serve.tick")
    stamped = out["counters"]["ticks"]
    assert len(ticks) == len(stamped) > 0
    for s, (t_a, t_b, n_admitted, _, n_decoded, kv) in zip(ticks, stamped):
        assert abs(s.start - t_a) < 1e-3 and abs(s.end - t_b) < 1e-3
        assert s.attrs["admitted"] == n_admitted
        if not n_admitted:
            assert (s.attrs["live"], s.attrs["kv_positions"]) == (n_decoded, kv)
    assert len(spans.in_window(ctx, "serve.prefill")) == \
        out["counters"]["n_prefills"]


def test_traced_serve_run_reads_the_engine_spans(root, monkeypatch):
    """With ``--trace 1`` the tiny serving cell prints the three readings
    of the engine's spans, each non-null, beside the runner's own; the
    peaks are the chip's, borrowed for the test."""
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.TPU_V5E)
    rc, res, err = tiny.run_cell(root, "tiny-serve", trace=1)
    assert rc == 0, err
    assert res["correct"] is True
    m = res["metrics"]
    assert set(SERVE_READERS) <= set(m)
    assert {"mfu.serve", "decode_tick_ms.serve", "admit_ms.serve"} <= set(m)
    assert m["tick_host_ms.serve"]["value"] > 0
    assert m["prefill_ms.serve"]["value"] > 0
    assert 0 < m["decode_occupancy.serve"]["value"] <= 100
    assert m["decode_occupancy.serve"]["unit"] == "%"
