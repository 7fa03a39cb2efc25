"""Share of the traced window in which no operation ran on the device,
averaged over the cell's devices."""
LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"


def read(ctx):
    idle = ctx["trace"].idle
    if not idle:
        return None
    return 100.0 * sum(idle.values()) / len(idle)
