"""Shared block/chunk-fitting helpers for the Pallas kernels.

``fit_block`` picks the largest block size <= ``block`` that divides ``n``
(Pallas grids need exact tiling) and is a multiple of ``align``: Mosaic
accepts a block whose last two dims are multiples of (8, 128) or equal to
the array's dims, so sublane (row) dims pass ``align=8`` and lane (minor)
dims ``align=128``; when no aligned divisor exists the whole dim is one
block.  ``pick_chunk`` is the shared chunk-size
heuristic of the two chunked recurrent scans (mamba2 SSD, rwkv wkv): the
largest power-of-two chunk <= ``target`` dividing T — one definition used
by both the jnp reference paths in ``models/{ssm,rwkv}.py`` and the Pallas
chunk-scan kernels, so ``kernels=True`` and the reference path always agree
on the chunk structure (and therefore on the fp32 summation order of the
inter-chunk carry).
"""
from __future__ import annotations

# chunk targets per scan family: SSD wants MXU-sized (Q x Q) intra-chunk
# matmuls; wkv's per-channel (Q, Q, K) decay-gap tensor bounds Q lower
SSD_CHUNK = 128
WKV_CHUNK = 32


def fit_block(block: int, n: int, align: int = 1) -> int:
    if n <= block:
        return n
    for b in range(block - block % align, 0, -align):
        if n % b == 0:
            return b
    return n


def pick_chunk(T: int, target: int) -> int:
    """Largest power-of-two chunk <= min(target, T) that divides T (1 when
    T is odd)."""
    c, q = 1, 2
    while q <= min(target, T):
        if T % q == 0:
            c = q
        q *= 2
    return c
