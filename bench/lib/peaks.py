"""Published peak rates of each accelerator the benchmark may run on.

Keyed by ``jax.Device.device_kind``.  The benchmark's own copy of the
program's ``costmodel.DEVICE_MACHINES`` peaks, so that no change to the
program can move the denominator of a utilization.  A kind that is not in
the table is an error: a share of an assumed peak is not a measurement.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    name: str
    bf16_flops: float     # dense bf16 matmul FLOP/s per chip
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


# Google Cloud documentation, "TPU v5e" (system architecture page): per chip
# 197 TFLOP/s bf16, 394 TOP/s int8, 16 GiB HBM2 at 819 GB/s.
TPU_V5E = Peaks(name="tpu_v5e", bf16_flops=197e12, hbm_bytes_per_s=819e9,
                hbm_bytes=16e9,
                source="Google Cloud documentation, TPU v5e")

PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": TPU_V5E,
}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; raises ``KeyError`` for a kind with no
    published entry."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add them to bench/lib/peaks.py with their source"
                       ) from None


def roofline_seconds(flops: float, nbytes: float, peaks: Peaks) -> float:
    """Least time the chip could take for ``flops`` and ``nbytes``: the
    larger of the compute bound and the memory bound."""
    return max(flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes_per_s)
