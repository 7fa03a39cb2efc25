"""Paged decode attention as a Pallas TPU kernel (serving only, no vjp).

One query token per decode slot attends that slot's KV, which lives in a
pool of physical blocks shared by every slot (``Model.paged_cache_specs``):
slot ``b`` owns ``block_table[b, :ceil(lengths[b] / bs)]``.  The XLA path
gathers the whole block table for every slot (``k_pool[block_table]``),
converts it to fp32 and masks by position, so each layer of each tick
moves ``n_slots * max_blocks`` blocks whatever the slots' lengths.  This
kernel moves only the live ones.

The grid is (slot, chunk): a chunk is ``PAGES`` blocks, and each of the
``PAGES`` K operands and ``PAGES`` V operands is the pool itself with a
block index map that reads the physical block from SMEM (a scalar-prefetch
operand, worked out for every step before the call, with the lengths).
Pallas' pipeline then copies each live block HBM->VMEM while the previous
chunk is computed.  Past a slot's last live block an operand's index
repeats the block it already holds, so the pipeline copies nothing there,
and the compute of those blocks is skipped; a slot of length 0 (an
inactive slot) writes zeros.  The copies are the pipeline's because Mosaic
cannot slice the pool's HBM ref for a hand-written DMA: its minor ``hd``
dim (88 in the paper's GPT) is narrower than the 128-lane tiling.

Each block's scores are a VPU multiply and a lane reduction over ``hd``
with the block's ``(bs, Hkv, hd)`` layout as the pool has it; an MXU
formulation (a block-diagonal product, or per-head matmuls over strided
rows) timed slower on the chip, because at one query row per head the MXU
reloads its weights for every block.  Scores, the online-softmax running
max and sum and the accumulator are fp32; K and V stay in the pool's dtype
up to VMEM.  GQA reads query head ``h * G + g`` against KV head ``h`` (no
repeat of K or V), and the logit softcap is applied in-kernel as the flash
kernel does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# blocks per grid step: the cost of a call follows the number of block
# copies, not of steps (at the closed32 pool on one v5e, the copies alone
# took 0.68, 0.70 and 0.71 ms a call at 2, 8 and 16 blocks a step)
PAGES = 4
NEG_INF = -1e30


def _block_index(j, pages, width, b, i, lengths_ref, blocks_ref):
    """Operand ``j``'s physical block at grid step ``(b, i)``."""
    return blocks_ref[b * width + i * pages + j], 0, 0, 0


def _kernel(lengths_ref, blocks_ref, q_ref, *refs, scale: float,
            softcap: float | None, pages: int, bs: int, groups: int):
    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * pages:]
    b, i = pl.program_id(0), pl.program_id(1)
    length = lengths_ref[b]

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)                     # (G, Hkv, hd)
    for j in range(pages):
        first = (i * pages + j) * bs

        @pl.when(first < length)
        def _block(j=j, first=first):
            k = k_refs[j][...].astype(jnp.float32)         # (bs, Hkv, hd)
            v = v_refs[j][...].astype(jnp.float32)
            pos = first + jax.lax.broadcasted_iota(
                jnp.int32, (bs, k.shape[1], 1), 0)
            valid = pos < length
            for g in range(groups):
                s = jnp.sum(k * q[g][None], axis=-1, keepdims=True) * scale
                if softcap is not None:
                    s = jnp.tanh(s / softcap) * softcap
                s = jnp.where(valid, s, NEG_INF)           # (bs, Hkv, 1)
                m_prev = m_ref[g]                          # (Hkv, 1)
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
                p = jnp.where(valid, jnp.exp(s - m_new[None]), 0.0)
                corr = jnp.exp(m_prev - m_new)
                l_ref[g] = l_ref[g] * corr + jnp.sum(p, axis=0)
                acc_ref[g] = acc_ref[g] * corr + jnp.sum(p * v, axis=0)
                m_ref[g] = m_new

    @pl.when(i == pl.num_programs(1) - 1)
    def _finish():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def paged_attention(q, k_pool, v_pool, block_table, lengths, *,
                    softcap=None, interpret=False):
    """q (B, Hq, hd); k_pool/v_pool (n_blocks, bs, Hkv, hd); block_table
    (B, max_blocks) int32; lengths (B,) int32 -> (B, Hq, hd) in q's dtype.
    Query head ``h * G + g`` attends KV head ``h`` over the slot's first
    ``lengths[b]`` positions."""
    B, Hq, hd = q.shape
    _, bs, Hkv, _ = k_pool.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    pages = min(PAGES, block_table.shape[1])
    width = pl.cdiv(block_table.shape[1], pages) * pages
    table = jnp.pad(block_table.astype(jnp.int32),
                    ((0, 0), (0, width - block_table.shape[1])))
    lengths = lengths.astype(jnp.int32)
    # operand j's physical block at each step: logical block i*pages + j
    # while it is live, else the last live block that operand read, so that
    # the pipeline copies nothing new past a slot's length (worked out here
    # once: in the index maps the same scalar work cost ~30% of the call)
    n = pl.cdiv(lengths, bs)[:, None]
    blk = jnp.arange(width, dtype=jnp.int32)[None, :]
    op = blk % pages
    last = jnp.where(op < n, op + pages * ((n - 1 - op) // pages),
                     jnp.maximum(n - 1, 0))
    blocks = jnp.take_along_axis(table, jnp.where(blk < n, blk, last),
                                 axis=1).reshape(-1)
    qg = q.reshape(B, Hkv, G, hd).transpose(0, 2, 1, 3)    # (B, G, Hkv, hd)

    kernel = functools.partial(_kernel, scale=1.0 / np.sqrt(hd),
                               softcap=softcap, pages=pages, bs=bs, groups=G)
    block = pl.BlockSpec((None, G, Hkv, hd), lambda b, i, *_: (b, 0, 0, 0))
    kv = [pl.BlockSpec((None, bs, Hkv, hd),
                       functools.partial(_block_index, j, pages, width))
          for j in range(pages)]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, width // pages),
            in_specs=[block] + kv + kv,
            out_specs=block,
            scratch_shapes=[
                pltpu.VMEM((G, Hkv, 1), jnp.float32),
                pltpu.VMEM((G, Hkv, 1), jnp.float32),
                pltpu.VMEM((G, Hkv, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, G, Hkv, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="paged_decode",
        interpret=interpret,
    )(lengths, blocks, qg, *([k_pool] * pages), *([v_pool] * pages))
    return out.transpose(0, 2, 1, 3).reshape(B, Hq, hd)
