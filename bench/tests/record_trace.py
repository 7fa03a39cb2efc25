"""Records the small device trace that ``test_trace.py`` reads.

Run on one TPU chip from the root of a checkout:

  python3 bench/tests/record_trace.py bench/tests/data/small.xplane.pb

Inside a host span ``bench.window`` it runs, three times over, a bf16
matmul, a 20 ms host sleep with the device idle, and the program's causal
flash-attention kernel on (1, 256, 2, 128), each under a span of its own,
waiting for the device after each.  The test then knows where the device
was busy and idle, and which Pallas kernel ran.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

SLEEP_S = 0.02
REPEATS = 3


def main(out: str) -> int:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, os.path.join(root, "src"))
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    if jax.devices()[0].platform != "tpu":
        print("error: no TPU", file=sys.stderr)
        return 1
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    q = jnp.ones((1, 256, 2, 128), jnp.bfloat16)
    mm = jax.jit(lambda a: a @ a)
    attn = jax.jit(lambda a: ops.flash_attention(a, a, a, causal=True))
    mm(x).block_until_ready()
    attn(q).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="bench-record-")
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(REPEATS):
                with jax.profiler.TraceAnnotation("bench.matmul"):
                    mm(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.sleep"):
                    time.sleep(SLEEP_S)
                with jax.profiler.TraceAnnotation("bench.attn"):
                    attn(q).block_until_ready()
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True)
        shutil.copyfile(path, out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(out, os.path.getsize(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
