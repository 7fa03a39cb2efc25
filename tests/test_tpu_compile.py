"""Every Pallas kernel compiles for a TPU v5e through Mosaic.

The interpret-mode parity tests run the kernel bodies in Python on the CPU,
which accepts blocks the chip's compiler refuses (a block that breaks the
(8, 128) tiling, an in-kernel gather or cumsum, a batched dot, a kernel that
overruns scoped VMEM).  These tests compile each kernel ahead of time for a
*described* ``v5e:2x2`` topology with ``interpret=False`` at published model
widths — nothing runs, no chip is needed — and check that the compiled
module holds the Mosaic kernel (``tpu_custom_call``), not the interpreter.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library, and each pytest worker
imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # the compiler would otherwise log under the system temp directory
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off for these tests
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)


def _compile(fn, shapes, one_chip, *, grad=False):
    """AOT-compile ``fn`` (or its value_and_grad over every float input)
    for the described chip; return the compiled module's text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    step = fn
    if grad:
        floats = tuple(i for i, (_, d) in enumerate(shapes)
                       if jnp.issubdtype(d, jnp.floating))
        step = jax.value_and_grad(lambda *a: jnp.sum(fn(*a).astype(F32)),
                                  argnums=floats)
    return jax.jit(step).lower(*args).compile().as_text()


NOINT = dict(interpret=False)

# gpt-1.4b (the paper's Table I model): d 2112, 24 heads x 88, d_ff 8448,
# vocab 51200; yi-6b: d 4096, 32 heads x 128 over 4 KV heads, d_ff 11008;
# arctic-480b experts: d 7168, d_ff 4864; zamba2-2.7b mamba2: 80 heads x 64,
# state 64; rwkv6-1.6b: 32 heads x 64.  The paged decode runs at the
# closed32 serving cell's pool (32 slots, a 161-block table, 1600 blocks of
# 16 positions) and at yi-6b's GQA heads over the same pool
CASES = {
    "flash_hd88": (
        lambda q, k, v: ops.flash_attention(q, k, v, **NOINT),
        [((1, 2048, 24, 88), BF16)] * 3, True),
    "flash_hd128_gqa": (
        lambda q, k, v: ops.flash_attention(q, k, v, **NOINT),
        [((1, 2048, 32, 128), BF16), ((1, 2048, 4, 128), BF16),
         ((1, 2048, 4, 128), BF16)], True),
    "swiglu": (
        lambda x, a, b: ops.swiglu(x, a, b, **NOINT),
        [((1024, 4096), BF16), ((4096, 11008), BF16),
         ((4096, 11008), BF16)], True),
    "gelu_mlp": (
        lambda x, a: ops.gelu_mlp_in(x, a, **NOINT),
        [((1024, 2112), BF16), ((2112, 8448), BF16)], True),
    "rmsnorm": (
        lambda x, w: ops.rmsnorm(x, w, **NOINT),
        [((2, 2048, 4096), BF16), ((4096,), F32)], True),
    "layernorm": (
        lambda x, w, b: ops.layernorm(x, w, b, **NOINT),
        [((2, 2048, 2112), BF16), ((2112,), F32), ((2112,), F32)], True),
    "cross_entropy": (
        lambda h, w, y: ops.cross_entropy_tokens(h, w, y, **NOINT),
        [((2048, 2112), BF16), ((2112, 51200), BF16), ((2048,), I32)], True),
    "grouped_mlp_gelu": (
        lambda x, w1, w2, m: ops.grouped_mlp(x, w1, None, w2, m, act="gelu",
                                             **NOINT),
        [((2, 256, 7168), BF16), ((2, 7168, 4864), BF16),
         ((2, 4864, 7168), BF16), ((2, 256), F32)], True),
    "grouped_mlp_swiglu": (
        lambda x, w1, w3, w2, m: ops.grouped_mlp(x, w1, w3, w2, m,
                                                 act="swiglu", **NOINT),
        [((2, 256, 7168), BF16), ((2, 7168, 4864), BF16),
         ((2, 7168, 4864), BF16), ((2, 4864, 7168), BF16),
         ((2, 256), F32)], True),
    "ssd_scan": (
        lambda x, dt, b, c, a: ops.ssd_scan(x, dt, b, c, a, chunk=128,
                                            **NOINT)[0],
        [((1, 512, 80, 64), BF16), ((1, 512, 80), F32), ((1, 512, 64), BF16),
         ((1, 512, 64), BF16), ((80,), F32)], True),
    "wkv_scan": (
        lambda r, k, v, w, u, s: ops.wkv_scan(r, k, v, w, u, s, chunk=32,
                                              **NOINT)[0],
        [((1, 512, 32, 64), F32)] * 4 + [((32, 64), F32),
                                          ((1, 32, 64, 64), F32)], True),
    "mamba_decode_step": (
        lambda w, cw, cb, dr, db, al, d, s: ops.mamba_decode_step(
            w, cw, cb, dr, db, al, d, s, n_heads=80, head_dim=64,
            **NOINT)[1],
        [((8, 4, 80 * 64 + 2 * 64), BF16), ((4, 80 * 64 + 2 * 64), BF16),
         ((80 * 64 + 2 * 64,), BF16), ((8, 80), BF16), ((80,), F32),
         ((80,), F32), ((80,), F32), ((8, 80, 64, 64), F32)], False),
    "wkv_decode_step": (
        lambda r, k, v, w, u, s: ops.wkv_decode_step(r, k, v, w, u, s,
                                                     **NOINT)[1],
        [((8, 32, 64), F32)] * 4 + [((32, 64), F32), ((8, 32, 64, 64), F32)],
        False),
    "paged_decode_hd88": (
        lambda q, k, v, t, n: ops.paged_attention(q, k, v, t, n, **NOINT),
        [((32, 24, 88), BF16), ((1600, 16, 24, 88), BF16),
         ((1600, 16, 24, 88), BF16), ((32, 161), I32), ((32,), I32)], False),
    "paged_decode_gqa": (
        lambda q, k, v, t, n: ops.paged_attention(q, k, v, t, n, **NOINT),
        [((32, 32, 128), BF16), ((1600, 16, 4, 128), BF16),
         ((1600, 16, 4, 128), BF16), ((32, 161), I32), ((32,), I32)], False),
}


# the stable ``name=`` each case's Pallas calls carry into the compiled
# module's instruction names (and so into the device trace's op events)
KERNEL_NAMES = {
    "flash_hd88": ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"),
    "flash_hd128_gqa": ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"),
    "swiglu": ("swiglu_in",),
    "gelu_mlp": ("gelu_mlp_in",),
    "rmsnorm": ("rmsnorm",),
    "layernorm": ("layernorm",),
    "cross_entropy": ("cross_entropy",),
    "grouped_mlp_gelu": ("grouped_mlp",),
    "grouped_mlp_swiglu": ("grouped_mlp",),
    "ssd_scan": ("ssd_scan",),
    "wkv_scan": ("wkv_scan",),
    "mamba_decode_step": ("ssd_decode",),
    "wkv_decode_step": ("wkv_decode",),
    "paged_decode_hd88": ("paged_decode",),
    "paged_decode_gqa": ("paged_decode",),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes, with_grad = CASES[name]
    text = _compile(fn, shapes, one_chip, grad=with_grad)
    assert "tpu_custom_call" in text, name
    calls = [line.split(" = ", 1)[0] for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for kernel in KERNEL_NAMES[name]:
        assert any(kernel in c for c in calls), (name, kernel, calls)
