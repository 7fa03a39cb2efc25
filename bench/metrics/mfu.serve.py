"""The serving window's share of the chip's peak by the roofline: for each
prefill and each decode tick in the window, the larger of its required
FLOPs over peak FLOP/s and its required bytes over peak HBM bandwidth
(a decode tick needs the weights and the live KV of its sequences, not
the whole block table), summed, over the window."""
from bench.lib import counts
from bench.lib.peaks import roofline_seconds

LAYER = "runtime/serve_engine"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "serve_tokens_per_s"


def read(ctx):
    c, peaks = ctx["counters"], ctx["peaks"]
    if "ticks" not in c or not c["ticks"]:
        return None
    m = c["dense"]
    w0, w1 = c["window"]
    least = 0.0
    for _, _, _, prefill_lens, n_decoded, kv in c["ticks"]:
        for n in prefill_lens:
            least += roofline_seconds(*counts.prefill_work(m, n), peaks)
        if n_decoded:
            least += roofline_seconds(*counts.decode_work(m, n_decoded, kv),
                                      peaks)
    return 100.0 * least / (w1 - w0)
