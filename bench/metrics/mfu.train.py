"""Model FLOP utilization of the training step: model FLOPs per token (the
benchmark's own count, recomputation not counted) times the window's
tokens per second, over the chips' summed bf16 peak."""
LAYER = "runtime/train_loop"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "train_tokens_per_s"


def read(ctx):
    c = ctx["counters"]
    if "model_flops_per_token" not in c:
        return None
    peak = c["chips"] * ctx["peaks"].bf16_flops
    return 100.0 * c["model_flops_per_token"] * c["tokens_per_s"] / peak
