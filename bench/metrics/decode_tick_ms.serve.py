"""Median host time of the engine ticks that admitted no request: one
decode step over the slot batch, its sampling and its host round trip."""
import statistics

LAYER = "runtime/serve_engine"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "itl_p95_ms"


def decode_ticks(ticks):
    return [b - a for a, b, n_admitted, *_ in ticks if n_admitted == 0]


def read(ctx):
    ticks = decode_ticks(ctx["counters"].get("ticks", []))
    return 1e3 * statistics.median(ticks) if ticks else None
