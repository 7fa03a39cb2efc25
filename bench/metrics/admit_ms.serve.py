"""Host time of admission and prefill per admitted request: for the ticks
that admitted requests, their time less the median decode-only tick,
summed, over the number of requests they admitted.  A tick that admits is
a long gap for every request in flight, so it sets the ITL tail."""
import statistics

LAYER = "runtime/serve_engine"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "itl_p95_ms"


def read(ctx):
    ticks = ctx["counters"].get("ticks", [])
    plain = [b - a for a, b, n, *_ in ticks if n == 0]
    busy = [(b - a, n) for a, b, n, *_ in ticks if n > 0]
    if not plain or not busy:
        return None
    tick = statistics.median(plain)
    return 1e3 * sum(t - tick for t, _ in busy) / sum(n for _, n in busy)
