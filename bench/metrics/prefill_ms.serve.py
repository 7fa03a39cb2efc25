"""Time of one admission from inside the engine: the mean duration of the
``serve.admit`` spans in the window, which hold the prefill, the scatter
of its KV into the pool and the wait for the first token.  A tick that
admits is a long gap for every request in flight."""
import statistics

from bench.lib import spans

LAYER = "runtime/serve_engine"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "itl_p95_ms"


def read(ctx):
    admits = spans.in_window(ctx, "serve.admit")
    if not admits:
        return None
    return 1e3 * statistics.mean(s.end - s.start for s in admits)
