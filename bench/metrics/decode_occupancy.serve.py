"""Share of the decode batch doing useful work: the mean, over the engine's
``serve.tick`` spans in the window, of its live slots over its slots."""
import statistics

from bench.lib import spans

LAYER = "runtime/serve_engine"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "serve_tokens_per_s"


def read(ctx):
    ticks = spans.in_window(ctx, "serve.tick")
    if not ticks:
        return None
    return 100.0 * statistics.mean(
        s.attrs["live"] / s.attrs["n_slots"] for s in ticks)
