"""Host time of a decode tick that waits on no device work: over the
engine's ``serve.tick`` spans in the window that admitted nothing, the
median of the tick's duration less its ``serve.wait`` children (the host
pulls that block on the device).  It is the part of a tick in which the
chip can run dry."""
import statistics

from bench.lib import spans

LAYER = "runtime/serve_engine"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "serve_tokens_per_s"


def read(ctx):
    found = spans.in_window(ctx)
    ticks = [s for s in found
             if s.name == "serve.tick" and s.attrs.get("admitted") == 0
             and s.attrs.get("live", 0) > 0]
    if not ticks:
        return None
    waits = [s for s in found if s.name == "serve.wait"]
    return 1e3 * statistics.median(
        spans.self_seconds(t, waits) for t in ticks)
