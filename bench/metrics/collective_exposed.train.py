"""Share of the traced window in which a collective (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all) runs on a device and no
other operation does, averaged over the cell's devices: the exchange that
compute does not hide."""
LAYER = "core/sharding + core/pipeline collectives"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"


def read(ctx):
    red = ctx["trace"]
    exposed = red.exposed_collective
    if not exposed:
        return None
    return 100.0 * sum(exposed.values()) / len(exposed) / red.window_s
