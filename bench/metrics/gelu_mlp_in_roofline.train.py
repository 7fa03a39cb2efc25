"""The fused up-projection and GELU kernel's share of its roofline in
training: for every call in the traced window (forward and the remat
recompute; its backward runs as XLA operations, not as this kernel), the
least time gelu(x @ w1) could take, summed, over the calls' summed device
time."""
from bench.lib import counts
from bench.lib.peaks import roofline_seconds

LAYER = "kernels/gelu_mlp"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"

ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4}


def read(ctx):
    red, peaks = ctx["trace"], ctx["peaks"]
    least = spent = 0.0
    for op in red.kernels.get("gelu_mlp_in", []):
        (dtype, (n, d)), (_, (_, f)) = op.shapes[1]
        least += roofline_seconds(*counts.gelu_mlp_in(n, d, f, ITEMSIZE[dtype]),
                                  peaks)
        spent += op.seconds
    if spent == 0.0:
        return None
    return 100.0 * least / spent
