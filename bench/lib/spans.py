"""How the benchmark reads the program's own spans: the one place it looks
into the process-wide span log of ``repro.core.telemetry`` (``SPANS``),
which the serving engine fills from inside its tick.

A span there has ``id``, ``parent`` (the enclosing span's id), ``name``,
``start`` and ``end`` on ``time.perf_counter`` (the runners' clock), and
``attrs``.  The window and self time are computed here from those fields,
not by the log's own helpers, so that what the metrics read stays with
the benchmark.  A program without that log gives no spans, and the
readers that need them then report nothing."""
from __future__ import annotations


def _log():
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    return getattr(telemetry, "SPANS", None)


def in_window(ctx, name: str | None = None) -> list:
    """The program's spans wholly inside the run's measured window
    (``ctx["counters"]["window"]``), oldest first; only those called
    ``name`` where it is given."""
    log = _log()
    window = ctx["counters"].get("window")
    if log is None or window is None:
        return []
    w0, w1 = window
    return [s for s in list(log.records)
            if w0 <= s.start and s.end <= w1
            and (name is None or s.name == name)]


def self_seconds(span, spans, child: str | None = None) -> float:
    """``span``'s duration less what its direct children among ``spans``
    cover (only those called ``child``, where it is given)."""
    return (span.end - span.start) - sum(
        s.end - s.start for s in spans
        if s.parent == span.id and (child is None or s.name == child))
