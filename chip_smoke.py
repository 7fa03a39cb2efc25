"""Smoke run of the main paths on a TPU, in one process.

  python chip_smoke.py               # one chip: train + serve (below)
  python chip_smoke.py --four-chips  # four chips: parallel training only

One chip:
  1. train: gpt-1.4b (the paper's Table I model) at published widths
     (d_model 2112, 24 heads x 88, d_ff 8448, vocab 51200) with 8 of its
     24 layers, so the fp32 master weights and Adam moments (~16 B/param,
     ~10 GB) fit one v5e's 16 GB; bf16 compute, seq 2048, global batch 4,
     dp = tp = pp = 1.  A few steps with the fused Pallas kernels off, then
     on (compiled by Mosaic: the step must hold ``tpu_custom_call``s); the
     two step-1 losses must agree within a bf16 band.
  2. serve: the ServeEngine serves gpt-1.4b at all 24 layers with bf16
     weights — 8 requests of 64-512 prompt tokens, 32 new tokens each,
     temperature 0 — first with bf16 compute, where every token must be
     ``greedy_generate``'s or an argmax within bf16 rounding (see
     :func:`serve_phase`), then with fp32 compute, where every request's
     tokens must equal ``greedy_generate``'s.

Four chips: gpt-1.4b (8 layers) trained under tp=2 x pp=2 (gas 4) and
under dp=4 at the same global batch, fp32 with the highest matmul
precision, 3 steps; per-step losses must agree and the tp x pp state must
sit on four devices.

Weights come from ``Model.init`` and data from ``--seed``; nothing is read
from disk.  Every phase raises on failure.  Earlier lines report what was
found (losses, step seconds, compile seconds, the token match); the last
line is one JSON object naming the device, printed only when every phase
passed.  Without a TPU, or without the repository's ``src/`` beside this
file, the script exits non-zero before any phase.  Timings here are smoke
readings, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

ARCH = "gpt-1.4b"
TRAIN_LAYERS = 8            # of 24: what fits one chip's HBM with Adam state
SEQ_LEN = 2048
GLOBAL_BATCH = 4
TRAIN_STEPS = 3
# kernels on vs off, step 1 (same weights, same batch): bf16 compute rounds
# at 2^-8 ~ 3.9e-3 relative; allow 2.5x that on the mean loss
KERNEL_LOSS_RTOL = 1e-2
# tp=2 x pp=2 vs dp=4, fp32 at the highest matmul precision: only the
# reduction order differs
PLAN_LOSS_RTOL = 1e-3
SERVE_REQUESTS = 8
SERVE_PROMPT_LENS = (64, 512)
SERVE_NEW_TOKENS = 32
# bf16 keeps 8 significant bits: a logit within |max| * 2^-6 (2-4 bf16
# ulps) of the max is a tie as far as bf16 compute can tell
SERVE_BF16_BAND = 2.0 ** -6


def _kernel_calls(compiled) -> int:
    """Mosaic kernels in a compiled program (interpret mode leaves none)."""
    return compiled.as_text().count("tpu_custom_call")


def _check_losses(label: str, losses: list[float]) -> None:
    if not losses or not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"{label}: non-finite or missing losses {losses}")


def train_phase(cfg, *, seed: int, global_batch: int, seq_len: int,
                steps: int) -> dict:
    """Train ``cfg`` on one device with the fused kernels off, then on."""
    from repro.launch.train import train
    from repro.runtime.train_loop import ParallelPlan

    out = {}
    for kernels in (False, True):
        plan = ParallelPlan(precision="bf16", kernels=kernels)
        run = train(cfg, plan, steps=steps, global_batch=global_batch,
                    seq_len=seq_len, seed=seed, log_every=1)
        label = f"train kernels={kernels}"
        _check_losses(label, run.losses)
        n_kernels = _kernel_calls(run.compiled)
        if kernels and n_kernels == 0:
            raise RuntimeError(f"{label}: the compiled step holds no Mosaic "
                               "kernel (tpu_custom_call)")
        print(f"{label}: losses {run.losses} step_s {run.step_walls} "
              f"compile_s {run.compile_s} tpu_custom_calls {n_kernels}",
              flush=True)
        out[kernels] = run.losses
        del run
        gc.collect()
    ref, fused = out[False][0], out[True][0]
    rel = abs(fused - ref) / abs(ref)
    print(f"train step-1 loss kernels off {ref} on {fused}: rel diff {rel} "
          f"(band {KERNEL_LOSS_RTOL})", flush=True)
    if rel > KERNEL_LOSS_RTOL:
        raise RuntimeError(f"kernels=True step-1 loss {fused} differs from "
                           f"kernels=False {ref} by {rel} > {KERNEL_LOSS_RTOL}")
    return out


def _serve(model, params, reqs, *, cache_len: int, n_slots: int,
           block_size: int, label: str) -> dict:
    """Drive ``reqs`` through a fresh ServeEngine; every request must run
    to its token budget.  Returns ``{rid: tokens}``."""
    from repro.runtime.serve_engine import ServeEngine

    engine = ServeEngine(model, params, n_slots=n_slots, cache_len=cache_len,
                         block_size=block_size)
    t0 = time.perf_counter()
    got = engine.run(reqs)
    wall = time.perf_counter() - t0
    unfinished = [r["rid"] for r in engine.records
                  if r["finish_reason"] != "max_new_tokens"]
    if len(engine.records) != len(reqs) or unfinished:
        raise RuntimeError(f"{label}: {len(engine.records)}/{len(reqs)} "
                           f"requests finished; cut short: {unfinished}")
    print(f"{label}: {len(reqs)} requests, prompts "
          f"{[len(r.prompt) for r in reqs]}, "
          f"{sum(len(v) for v in got.values())} tokens, {engine.n_ticks} "
          f"decode ticks, {engine.n_prefills} prefills, wall_s {wall} "
          f"(compiles included)", flush=True)
    return got


def _greedy_mismatches(model, params, reqs, got, *, new_tokens: int,
                       cache_len: int) -> list[tuple[int, int]]:
    """(rid, first differing token) for each request whose engine tokens
    differ from ``greedy_generate``'s."""
    import jax.numpy as jnp
    import numpy as np

    from repro.runtime.serve_loop import greedy_generate

    out = []
    for r in reqs:
        ref = np.asarray(greedy_generate(model, params,
                                         jnp.asarray(r.prompt)[None],
                                         new_tokens, cache_len))[0]
        if not np.array_equal(ref, got[r.rid]):
            out.append((r.rid, int(np.argmax(ref != got[r.rid]))))
    return out


def _off_argmax(model, params, prompt, toks) -> list[tuple[int, float, float]]:
    """Teacher-force ``toks`` after ``prompt`` through the model's plain
    forward and return (position, chosen logit, max logit) wherever the
    chosen token is not an argmax within the bf16 band."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    seq = np.concatenate([prompt, toks[:-1]]).astype(np.int32)[None]
    logits = np.asarray(jax.jit(model.logits)(
        params, {"tokens": jnp.asarray(seq)})[0, len(prompt) - 1:])
    top = logits.max(axis=-1)
    chosen = logits[np.arange(len(toks)), toks]
    band = np.abs(top) * SERVE_BF16_BAND
    return [(int(i), float(chosen[i]), float(top[i]))
            for i in np.nonzero(chosen < top - band)[0]]


def serve_phase(cfg, *, seed: int, n_requests: int,
                prompt_lens: tuple[int, int], new_tokens: int,
                n_slots: int = 4, block_size: int = 16) -> None:
    """Serve ``n_requests`` greedy requests with bf16 weights through the
    ServeEngine, twice:

    1. bf16 compute, as deployed.  The engine prefills right-padded
       length buckets and decodes a batch of slots over paged blocks;
       ``greedy_generate`` prefills the exact prompt and decodes one
       sequence.  Those shapes order bf16 reductions differently, so an
       argmax whose top two logits lie within bf16 rounding can flip.
       Each request must equal ``greedy_generate``, or else every engine
       token must be an argmax, within the bf16 band, of the model's
       teacher-forced logits.
    2. fp32 compute at the highest matmul precision (same bf16 weights):
       every request must equal ``greedy_generate`` token for token.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import synthetic_requests
    from repro.models.model import Model

    params = Model(cfg).init(jax.random.PRNGKey(seed), jnp.bfloat16)
    cache_len = prompt_lens[1] + new_tokens
    reqs = synthetic_requests(cfg, n_requests, prompt_lens=prompt_lens,
                              max_new=(new_tokens, new_tokens), seed=seed)
    kw = dict(cache_len=cache_len, n_slots=n_slots, block_size=block_size)

    model = Model(cfg, jnp.bfloat16)
    got = _serve(model, params, reqs, label="serve bf16", **kw)
    diff = _greedy_mismatches(model, params, reqs, got,
                              new_tokens=new_tokens, cache_len=cache_len)
    by_rid = {r.rid: r for r in reqs}
    off = {rid: _off_argmax(model, params, np.asarray(by_rid[rid].prompt),
                            got[rid]) for rid, _ in diff}
    print(f"serve bf16 token match vs greedy_generate: "
          f"{n_requests - len(diff)}/{n_requests} requests; first divergence "
          f"(rid, token) {diff}; engine tokens off the teacher-forced argmax "
          f"beyond the bf16 band ({SERVE_BF16_BAND} x |max logit|): "
          f"{ {rid: v for rid, v in off.items() if v} }", flush=True)
    if any(off.values()):
        raise RuntimeError(f"serve bf16: engine tokens that are not an "
                           f"argmax of the reference logits: {off}")
    del got
    gc.collect()

    with jax.default_matmul_precision("highest"):
        model = Model(cfg, jnp.float32)
        got = _serve(model, params, reqs, label="serve fp32", **kw)
        diff = _greedy_mismatches(model, params, reqs, got,
                                  new_tokens=new_tokens, cache_len=cache_len)
    print(f"serve fp32 token match vs greedy_generate: "
          f"{n_requests - len(diff)}/{n_requests} requests", flush=True)
    if diff:
        raise RuntimeError(f"serve fp32 tokens differ from greedy_generate "
                           f"(rid, first token): {diff}")


def four_chip_phase(cfg, *, seed: int, global_batch: int, seq_len: int,
                    steps: int) -> None:
    """tp=2 x pp=2 against dp=4 at one global batch, fp32, highest matmul
    precision: the same loss per step, and a state spread over 4 chips."""
    import jax

    from repro.launch.train import train
    from repro.runtime.train_loop import ParallelPlan

    plans = {"tp2xpp2": ParallelPlan(tp=2, pp=2, gas=4, precision="fp32"),
             "dp4": ParallelPlan(dp=4, precision="fp32")}
    losses = {}
    with jax.default_matmul_precision("highest"):
        for label, plan in plans.items():
            run = train(cfg, plan, steps=steps, global_batch=global_batch,
                        seq_len=seq_len, seed=seed, log_every=1)
            _check_losses(label, run.losses)
            print(f"{label}: losses {run.losses} step_s {run.step_walls} "
                  f"compile_s {run.compile_s}", flush=True)
            losses[label] = run.losses
            if label == "tp2xpp2":
                leaves = jax.tree.leaves(run.state)
                devices = set().union(*(x.sharding.device_set
                                        for x in leaves))
                spread = max(len({str(s.index) for s in x.addressable_shards})
                             for x in leaves)
                print(f"{label}: state on {len(devices)} devices, largest "
                      f"leaf split into {spread} distinct shards", flush=True)
                if len(devices) != 4 or spread != 4:
                    raise RuntimeError(f"{label}: state not spread over 4 "
                                       f"devices ({len(devices)} devices, "
                                       f"{spread} shards)")
            del run
            gc.collect()
    a, b = losses["tp2xpp2"], losses["dp4"]
    rel = [abs(x - y) / abs(y) for x, y in zip(a, b)]
    print(f"tp2xpp2 vs dp4 per-step rel diff {rel} (tol {PLAN_LOSS_RTOL})",
          flush=True)
    if len(a) != len(b) or max(rel) > PLAN_LOSS_RTOL:
        raise RuntimeError(f"tp2xpp2 losses {a} differ from dp4 {b}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tp=2 x pp=2 vs dp=4 training check "
                         "(needs four chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"error: no repro package under {SRC}; run from a checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"error: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    n_chips = len(jax.devices())
    want = 4 if args.four_chips else 1
    if n_chips < want:
        print(f"error: {want} chips needed, JAX found {n_chips}",
              file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.train import resolve_config

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    train_cfg = resolve_config(ARCH, layers=TRAIN_LAYERS)
    if args.four_chips:
        four_chip_phase(train_cfg, seed=args.seed, global_batch=GLOBAL_BATCH,
                        seq_len=SEQ_LEN, steps=TRAIN_STEPS)
    else:
        train_phase(train_cfg, seed=args.seed, global_batch=GLOBAL_BATCH,
                    seq_len=SEQ_LEN, steps=TRAIN_STEPS)
        serve_phase(resolve_config(ARCH), seed=args.seed,
                    n_requests=SERVE_REQUESTS,
                    prompt_lens=SERVE_PROMPT_LENS,
                    new_tokens=SERVE_NEW_TOKENS)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
