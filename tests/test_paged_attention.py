"""The paged decode kernel (``kernels/paged_attention.py``) in interpret
mode: parity with the gather-and-attend math of ``blocks.paged_attn_decode``
over ragged slots and a shuffled pool, and, with the kernel route forced,
the ServeEngine's token streams equal to the gather route's."""
import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.models import blocks, layers
from repro.models.model import Model
from repro.runtime.serve_engine import Request, ServeEngine

HD, N_BLOCKS, MAX_BLOCKS = 16, 40, 6


@functools.partial(jax.jit, static_argnames="softcap")
def _gather_attend(q, k_pool, v_pool, table, lengths, softcap):
    """The gather path: every slot's whole block table, masked by position
    (fp32 throughout, so it is the exact math the kernel approximates)."""
    B = q.shape[0]
    bs = k_pool.shape[1]
    skv = table.shape[1] * bs
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    gk = f32(k_pool)[table].reshape(B, skv, *k_pool.shape[2:])
    gv = f32(v_pool)[table].reshape(B, skv, *v_pool.shape[2:])
    out = layers.attention(f32(q)[:, None], gk, gv, causal=True,
                           q_offset=jnp.maximum(lengths, 1) - 1,
                           softcap=softcap, kv_positions=jnp.arange(skv))
    return out[:, 0]


def _ragged_pool(seed, hq, hkv, bs, dtype):
    """Slots of length 1, a block, a block + 1, the whole table and 0 (an
    inactive slot), their blocks shuffled over the pool; rows past each
    allocation point at block 0, which holds garbage like every block.  The
    whole-table slot spans two of the kernel's 4-block chunks and ends
    inside the second."""
    rng = np.random.RandomState(seed)
    normal = lambda *shape: jnp.asarray(rng.randn(*shape), dtype)  # noqa: E731
    q = normal(5, hq, HD)
    k_pool = normal(N_BLOCKS, bs, hkv, HD)
    v_pool = normal(N_BLOCKS, bs, hkv, HD)
    lengths = np.array([1, bs, bs + 1, MAX_BLOCKS * bs, 0], np.int32)
    free = list(rng.permutation(np.arange(1, N_BLOCKS)))
    table = np.zeros((5, MAX_BLOCKS), np.int32)
    for b, n in enumerate(lengths):
        for j in range(-(-n // bs)):
            table[b, j] = free.pop()
    return q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(lengths)


# bs 4 runs fp32 (tight parity), bs 16 the serving dtype bf16
@pytest.mark.parametrize("heads,softcap,bs", list(itertools.product(
    [(4, 4), (8, 2)], [None, 30.0], [4, 16])), ids=lambda v: str(v))
def test_paged_attention_matches_gather(heads, softcap, bs):
    hq, hkv = heads
    dtype, tol = (jnp.float32, 1e-5) if bs == 4 else (jnp.bfloat16, 2e-2)
    q, k_pool, v_pool, table, lengths = _ragged_pool(
        hq + bs, hq, hkv, bs, dtype)
    out = ops.paged_attention(q, k_pool, v_pool, table, lengths,
                              softcap=softcap)
    assert out.shape == q.shape and out.dtype == q.dtype
    out = np.asarray(out, np.float32)
    ref = np.asarray(_gather_attend(q, k_pool, v_pool, table, lengths,
                                    softcap=softcap))
    np.testing.assert_allclose(out[:4], ref[:4], atol=tol, rtol=tol)
    assert np.all(out[4] == 0.0)      # inactive slot: zeros, never NaN


def _toks(key, i, length, vocab):
    return np.asarray(jax.random.randint(jax.random.fold_in(key, i),
                                         (length,), 0, vocab), np.int32)


@pytest.mark.parametrize("n_kv_heads", [4, 2])     # MHA, GQA (G = 2)
def test_engine_kernel_route_matches_gather(n_kv_heads, monkeypatch):
    """3 requests over 2 slots (a mid-run refill, an idle slot at the end):
    the engine with the kernel route forced gives the gather route's
    tokens."""
    cfg = get_config("gpt-1.4b").reduced(n_kv_heads=n_kv_heads)
    m = Model(cfg, jnp.float32)
    params = m.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(3)
    reqs = lambda: [Request(rid=i, prompt=_toks(key, i, L, cfg.vocab_size),  # noqa: E731
                            max_new_tokens=5)
                    for i, L in enumerate([5, 9, 7])]

    def run():
        eng = ServeEngine(m, params, n_slots=2, cache_len=32, block_size=4,
                          prefill_buckets=(16,))
        return eng, eng.run(reqs())

    gather, out_gather = run()
    assert gather.attn_route == "gather"     # the CPU route
    monkeypatch.setattr(blocks, "paged_kernel_route",
                        lambda *, quant, unsharded: unsharded and not quant)
    kernel, out_kernel = run()
    assert kernel.attn_route == "paged_kernel"
    for i in range(3):
        np.testing.assert_array_equal(out_kernel[i], out_gather[i])
