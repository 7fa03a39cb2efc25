"""Flash attention's share of its roofline in training: for every call of
the forward, dq and dk/dv kernels in the traced window (the forward runs
twice a layer, once more where remat recomputes it, and each run is
counted), the least time its causal-half work could take on the chip,
summed, over the calls' summed device time."""
from bench.lib import counts
from bench.lib.peaks import roofline_seconds

LAYER = "kernels/flash_attention"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"

WORK = {"flash_fwd": counts.flash_fwd, "flash_bwd_dq": counts.flash_bwd_dq,
        "flash_bwd_dkv": counts.flash_bwd_dkv}
ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4}


def read(ctx):
    red, peaks = ctx["trace"], ctx["peaks"]
    least = spent = 0.0
    for kernel, work in WORK.items():
        for op in red.kernels.get(kernel, []):
            dtype, (b, h, s, hd) = op.shapes[1][0]      # q: (B, H, S, hd)
            least += roofline_seconds(*work(b, h, s, hd, ITEMSIZE[dtype]),
                                      peaks)
            spent += op.seconds
    if spent == 0.0:
        return None
    return 100.0 * least / spent
