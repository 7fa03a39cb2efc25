"""Spans inside the serving engine's tick (``core/telemetry.py:span``): the
tree each tick leaves in the span log, its counters against the engine's
own, self time, the log's bound, that spans change no token, and that the
profiler sees them on its host plane."""
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import telemetry as tel
from repro.models.model import Model
from repro.runtime.serve_engine import Request, ServeEngine


@pytest.fixture(scope="module")
def dense():
    cfg = get_config("yi-6b").reduced()
    m = Model(cfg, jnp.float32)
    return cfg, m, m.init(jax.random.PRNGKey(0))


def _requests(cfg, n=3, temperature=0.0):
    key = jax.random.PRNGKey(5)
    return [Request(rid=i, max_new_tokens=10, temperature=temperature,
                    top_p=0.9, seed=i,
                    prompt=np.asarray(jax.random.randint(
                        jax.random.fold_in(key, i), (5 + 2 * i,), 0,
                        cfg.vocab_size), np.int32))
            for i in range(n)]


def _engine(dense, **kw):
    _, m, params = dense
    # 6 usable blocks for 2 slots of growing requests: growth, eviction
    # and replay all happen
    return ServeEngine(m, params, n_slots=2, cache_len=64, block_size=4,
                       n_blocks=7, **kw)


@pytest.fixture(scope="module")
def traced_run(dense):
    """One engine run with the decode call wrapped, so that each tick's
    live slots and attended positions are seen from outside the spans."""
    cfg = dense[0]
    e = _engine(dense)
    seen = []
    decode = e._decode

    def watched(params, cache, batch):
        active = [i for i, s in enumerate(e.slots) if s.req is not None]
        assert int(np.asarray(batch["active"]).sum()) == len(active)
        seen.append((len(active), sum(e.slots[i].pos + 1 for i in active),
                     sum(-(-(e.slots[i].pos + 1) // e.block_size)
                         for i in active)))
        return decode(params, cache, batch)

    e._decode = watched
    t0 = time.perf_counter()
    out = e.run(_requests(cfg))
    spans = tel.SPANS.between(t0, time.perf_counter())
    return e, out, spans, seen


def test_each_tick_leaves_the_right_tree(traced_run):
    e, _, spans, _ = traced_run
    by_id = {s.id: s for s in spans}
    parent = {s.id: by_id.get(s.parent) for s in spans}
    ticks = [s for s in spans if s.name == "serve.tick"]
    assert ticks and all(s.parent is None for s in ticks)
    for s in spans:
        p = parent[s.id]
        if s.name in ("serve.admit", "serve.grow_blocks", "serve.decode",
                      "serve.bookkeep"):
            assert p.name == "serve.tick", s
        elif s.name == "serve.prefill":
            assert p.name == "serve.admit" and p.attrs["rid"] == s.attrs["rid"]
        elif s.name == "serve.wait":
            want = {"prefill": "serve.admit", "decode": "serve.tick"}
            assert p.name == want[s.attrs["for"]], s
        # a child lies inside its parent
        if p is not None:
            assert p.start <= s.start <= s.end <= p.end
    admits = [s for s in spans if s.name == "serve.admit"]
    assert all(s.attrs["rid"] in e.results for s in admits)
    # every decoded tick has one decode, one wait on it, one bookkeeping
    for t in ticks:
        kids = sorted(s.name for s in spans if s.parent == t.id
                      and s.name not in ("serve.admit",))
        assert kids == ["serve.bookkeep", "serve.decode", "serve.grow_blocks",
                        "serve.wait"], kids


def test_tick_counters_match_the_engine(traced_run):
    e, out, spans, seen = traced_run
    ticks = [s for s in spans if s.name == "serve.tick"]
    named = lambda n: [s for s in spans if s.name == n]  # noqa: E731
    assert e.n_evictions >= 1
    assert len(named("serve.prefill")) == e.n_prefills
    assert sum(t.attrs["admitted"] for t in ticks) == e.n_prefills
    assert sum(t.attrs["evicted"] for t in ticks) == e.n_evictions
    assert sum(g.attrs["evictions"] for g in named("serve.grow_blocks")) \
        == e.n_evictions
    assert [t.attrs["tick"] for t in ticks] == list(range(e.n_ticks))
    assert [(t.attrs["live"], t.attrs["kv_positions"]) for t in ticks] == \
        [(live, kv) for live, kv, _ in seen]
    assert all(t.attrs["n_slots"] == 2 and 0 <= t.attrs["free_blocks"] <= 6
               for t in ticks)
    # each admission gives one token, each live slot of a tick one more
    assert sum(t.attrs["live"] for t in ticks) + e.n_prefills == \
        sum(len(v) for v in out.values())
    # an evicted request comes back once, with its generated prefix
    replays = [a for a in named("serve.admit") if a.attrs["replay"] > 0]
    assert len(replays) == e.n_evictions
    assert all(a.attrs["prompt_len"] <= a.attrs["bucket"]
               for a in named("serve.admit"))
    finished = sum(b.attrs["finished"] for b in named("serve.bookkeep"))
    assert finished == sum(r["finish_reason"] is not None
                           for r in e.results.values())


def test_decode_span_names_its_route_and_blocks(traced_run):
    """Each ``serve.decode`` names the attention path the compiled decode
    takes (the gather on the CPU) and counts the pool blocks its slots
    attend: ceil((pos + 1) / block_size) summed over the live slots."""
    e, _, spans, seen = traced_run
    decodes = [s for s in spans if s.name == "serve.decode"]
    assert e.attn_route == "gather"
    assert all(d.attrs["attn"] == "gather" for d in decodes)
    assert [d.attrs["kv_blocks"] for d in decodes] == \
        [blocks for _, _, blocks in seen]


def _log(*spans):
    log = tel.SpanLog()
    log.records.extend(tel.SpanRecord(*s) for s in spans)
    return log


def test_self_seconds():
    log = _log((1, None, "tick", 0.0, 10.0, {}),
               (2, 1, "wait", 1.0, 3.0, {}),
               (3, 1, "admit", 4.0, 8.0, {}),
               (4, 3, "wait", 5.0, 6.0, {}),     # a grandchild: not counted
               (5, None, "wait", 11.0, 12.0, {}))
    tick, admit = log.records[0], log.records[2]
    spans = list(log.records)
    assert tel.self_seconds(tick, spans) == pytest.approx(4.0)
    assert tel.self_seconds(tick, spans, child="wait") == pytest.approx(8.0)
    assert tel.self_seconds(admit, spans) == pytest.approx(3.0)
    assert [s.id for s in log.between(0.0, 10.0, "wait")] == [2, 4]
    assert [s.id for s in log.between(0.5, 12.0)] == [2, 3, 4, 5]


def test_span_log_stays_bounded_and_nests(monkeypatch):
    log = tel.SpanLog(maxlen=5)
    monkeypatch.setattr(tel, "SPANS", log)
    with tel.span("outer", k=1) as attrs:
        attrs["n"] = 2
        for _ in range(11):
            with tel.span("inner"):
                pass
    with pytest.raises(RuntimeError):
        with tel.span("failed"):
            raise RuntimeError("boom")
    names = [s.name for s in log.records]
    assert len(log.records) == 5
    assert names == ["inner"] * 3 + ["outer", "failed"]
    outer = log.records[3]
    assert outer.attrs == {"k": 1, "n": 2} and outer.parent is None
    assert all(s.parent == outer.id for s in list(log.records)[:3])
    # the failed span was recorded and left no parent behind
    assert log.records[4].parent is None and log._stack() == []


class _NoSpan:
    def __init__(self, name, **attrs):
        self.attrs = attrs

    def __enter__(self):
        return self.attrs

    def __exit__(self, *exc):
        return None


@pytest.mark.parametrize("temperature", [0.0, 0.9])
def test_tokens_identical_with_spans_stubbed(dense, monkeypatch, temperature):
    cfg = dense[0]
    with_spans = _engine(dense).run(_requests(cfg, temperature=temperature))
    monkeypatch.setattr(tel, "span", _NoSpan)
    before = len(tel.SPANS.records)
    stubbed = _engine(dense).run(_requests(cfg, temperature=temperature))
    assert len(tel.SPANS.records) == before
    assert with_spans.keys() == stubbed.keys()
    for rid in with_spans:
        np.testing.assert_array_equal(with_spans[rid], stubbed[rid])


def test_profiler_sees_tick_spans_on_the_host_plane(dense, tmp_path):
    from jax.profiler import ProfileData
    cfg = dense[0]
    e = _engine(dense)
    e.run(_requests(cfg, n=1))       # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        e.run([Request(rid=10, prompt=np.arange(6, dtype=np.int32),
                       max_new_tokens=4)])
    files = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert files
    events = {}
    for f in files:
        for plane in ProfileData.from_file(f).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(tel.SPAN_PREFIX):
                        assert plane.name.startswith("/host:")
                        events.setdefault(ev.name, []).append(dict(ev.stats))
    ticks = events["repro.serve.tick"]
    assert len(ticks) == 3          # the admitting tick, then two more
    assert {"repro.serve.admit", "repro.serve.prefill", "repro.serve.decode",
            "repro.serve.wait", "repro.serve.bookkeep"} <= set(events)
    assert all(t["live"] == 1 and t["n_slots"] == 2 for t in ticks)
    assert events["repro.serve.admit"][0]["rid"] == 10


def test_span_records_validate(tmp_path):
    rec = tel.span_record(tel.SpanRecord(3, 1, "serve.wait", 1.0, 1.5,
                                         {"for": "decode"}))
    tel.validate_record(rec)
    assert rec["attrs"] == {"for": "decode"} and rec["parent"] == 1
    with pytest.raises(ValueError, match="ends before"):
        tel.validate_record(dict(rec, start=2.0))
    with pytest.raises(ValueError, match="missing keys"):
        tel.validate_record({k: v for k, v in rec.items() if k != "parent"})


SERVE_CODE = r"""
import runpy, sys
sys.argv = ["serve", "--arch", "yi-6b", "--reduced", "--requests", "3",
            "--n-slots", "2", "--max-new", "4", "--log-jsonl", {path!r}]
runpy.run_module("repro.launch.serve", run_name="__main__")
"""


def test_serve_launcher_writes_span_records(multidev, tmp_path):
    """``launch/serve.py --log-jsonl``: the request records, then the run's
    spans, each a schema-valid ``span`` record whose parent is among them."""
    path = str(tmp_path / "serve.jsonl")
    out = multidev(SERVE_CODE.format(path=path), n_devices=1)
    recs = tel.validate_jsonl(path)
    assert sum(r["kind"] == "request" for r in recs) == 3
    spans = [r for r in recs if r["kind"] == "span"]
    assert f"request records and {len(spans)} spans" in out
    ids = {r["id"] for r in spans}
    ticks = [r for r in spans if r["name"] == "serve.tick"]
    assert ticks and all(r["parent"] is None for r in ticks)
    assert all(r["parent"] in ids for r in spans if r["parent"] is not None)
    assert sum(r["name"] == "serve.admit" for r in spans) == 3
