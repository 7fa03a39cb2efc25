"""Serving runner: the program's ``ServeEngine`` with bf16 weights made on
the device from the seed, driven by a closed loop of clients for a time
window through ``submit`` and ``step``.

Set-up makes the weights, builds the engine at the cell's slot count,
cache length, block size and pool, and sends one short request of every
prefill length bucket that the mix can reach (evicted requests replay
with their generated prefix, so the longest prompt plus the longest
output counts), which compiles or loads every program the window calls.

The window: every client submits a request at once, and each submits its
next request as soon as the previous one has finished.  The harness
stamps each ``step()`` return, so a token's time is the return of the
tick that produced it: time to first token runs from ``submit`` to that
stamp, and the gaps between a request's tokens are the differences of
its stamps.

Check: once the window has closed and the engine is freed, a sample of
the finished requests drawn from the seed, the longest among them, is run
through the reference over prompt plus served tokens; ``max_gap`` is the
widest gap by which a served token's reference logit lies below the
reference's best.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import counts, reference, traffic
from bench.lib.harness import memory_peak_bytes
from bench.lib.program import model_config

WARM_RID = -1


def _buckets(engine, lo: int, hi: int) -> list[int]:
    """The engine's prefill buckets that lengths in [lo, hi] fall in."""
    bs = engine.prefill_buckets
    first = next(i for i, b in enumerate(bs) if b >= lo)
    last = next(i for i, b in enumerate(bs) if b >= hi)
    return list(bs[first:last + 1])


def _percentile(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else \
        float("nan")


def run(run) -> dict:
    from repro.models.model import Model
    from repro.runtime.serve_engine import Request, ServeEngine

    cell = run.cell
    cfg, mix, wl = cell.config, cell.traffic, cell.workload
    eng_cfg = wl["engine"]
    mcfg = model_config(cfg)
    model = Model(mcfg, jnp.bfloat16)
    ks = traffic.key_seed(run.seed)
    params = jax.jit(lambda k: model.init(k, jnp.bfloat16))(
        jax.random.PRNGKey(ks))
    loop = traffic.ClosedLoop(mix, run.seed, mcfg.vocab_size)
    engine = ServeEngine(model, params, n_slots=loop.clients,
                         cache_len=eng_cfg["cache_len"],
                         block_size=eng_cfg["block_size"],
                         n_blocks=eng_cfg.get("n_blocks"),
                         clock=time.perf_counter)

    # set-up: one request per reachable prefill bucket
    warm = _buckets(engine, mix["prompt"]["min"], loop.longest)
    for i, b in enumerate(warm):
        length = min(b, engine.capacity - 2)
        engine.submit(Request(rid=WARM_RID - i, max_new_tokens=2,
                              prompt=np.ones(length, np.int32)))
    while engine.queue or any(s.req is not None for s in engine.slots):
        engine.step()

    reqs: dict[int, traffic.RequestSpec] = {}
    submitted: dict[int, float] = {}
    stamps: dict[int, list[float]] = {}
    ticks = []          # (start, end, n_admitted, prefill lens, decoded, kv)

    def submit():
        spec = loop.next()
        reqs[spec.index] = spec
        with run.span("submit"):
            submitted[spec.index] = time.perf_counter()
            engine.submit(Request(rid=spec.index, prompt=spec.prompt,
                                  max_new_tokens=spec.max_new_tokens))
        stamps[spec.index] = []

    def live() -> dict[int, int]:
        return {s.req.rid: s.pos for s in engine.slots if s.req is not None}

    with run.window():
        for _ in range(loop.clients):
            submit()
        while True:
            before = live()
            n_pre = engine.n_prefills
            t_a = time.perf_counter()
            with run.span("engine_step"):
                finished = engine.step()
            t_b = time.perf_counter()
            after = live()
            admitted = [r for r in after if r not in before]
            # positions each decoded sequence attended over: its position
            # after the tick, or for one that finished in it, one past its
            # position before
            attended = dict(after)
            attended.update({r: before[r] + 1 for r in finished
                             if r in before})
            ticks.append((t_a, t_b, engine.n_prefills - n_pre,
                          [after[r] - 1 for r in admitted],
                          len(attended), sum(attended.values())))
            for r in set(before) | set(admitted):
                n = len(engine.results[r]["generated"])
                stamps[r].extend([t_b] * (n - len(stamps[r])))
            if t_b - run.window_start >= run.seconds:
                break
            for _ in finished:
                submit()
    w0, w1 = run.window_start, run.window_end
    peak = memory_peak_bytes(jax.devices()[:cell.chips])

    done = [r for r, st in engine.results.items()
            if r >= 0 and st["finish_reason"] is not None]
    failed = sum(engine.results[r]["finish_reason"] != "max_new_tokens"
                 for r in done)
    served = {r: np.asarray(engine.results[r]["generated"], np.int32)
              for r in done}
    n_tokens = sum(sum(w0 <= t <= w1 for t in ts) for ts in stamps.values())
    ttft = [ts[0] - submitted[r] for r, ts in stamps.items()
            if ts and w0 <= ts[0] <= w1]
    itl = [b - a for ts in stamps.values() for a, b in zip(ts, ts[1:])
           if w0 <= b <= w1]
    walls = [b - a for a, b, *_ in ticks]
    half = len(walls) // 2
    info = {"tick_ms_halves": [1e3 * float(np.mean(w)) if w else None
                               for w in (walls[:half], walls[half:])],
            "requests_submitted": len(submitted), "requests_done": len(done),
            "ticks": len(ticks), "first_tokens": len(ttft),
            "gaps": len(itl), "evictions": engine.n_evictions,
            "tokens": n_tokens}
    del engine, params
    gc.collect()

    t_ref = time.perf_counter()
    sample = _sample(done, served, reqs, run.seed, wl["check"]["requests"])
    ref_params = reference.init_params(cfg, ks, jnp.bfloat16)
    pad = -(-loop.longest // 256) * 256
    control = run.probe.get("control")
    gaps, control_gaps = [], []
    for r in sample:
        g = reference.served_gaps(ref_params, cfg, reqs[r].prompt, served[r],
                                  pad, control)
        gaps.append(float(g["gap"].max()))
        if control is not None:
            control_gaps.append(float(g["control_gap"].max()))
    del ref_params
    info["reference_s"] = time.perf_counter() - t_ref
    info["checked_requests"] = len(sample)
    info["checked_tokens"] = int(sum(len(served[r]) for r in sample))
    if control is not None:
        info["control_max_gap"] = max(control_gaps)
    checks = [{"name": "max_gap", "value": max(gaps) if gaps else float("inf"),
               "limit": wl["limits"]["max_gap"]}]

    m = counts.Dense.from_config(cfg)
    return {
        "attempted": len(submitted), "failed": failed,
        "e2e": {"serve_tokens_per_s": n_tokens / (w1 - w0),
                "ttft_p95_ms": 1e3 * _percentile(ttft, 95),
                "itl_p95_ms": 1e3 * _percentile(itl, 95)},
        "counters": {"ticks": ticks, "window": (w0, w1), "dense": m,
                     "n_prefills": sum(t[2] for t in ticks)},
        "checks": checks, "memory_peak_bytes": peak, "info": info,
    }


def _sample(done, served, reqs, seed: int, n: int) -> list[int]:
    """``n`` finished requests drawn from the seed, the longest (prompt
    plus served tokens) always among them."""
    if not done:
        return []
    longest = max(done, key=lambda r: len(reqs[r].prompt) + len(served[r]))
    rest = sorted(r for r in done if r != longest)
    rng = np.random.default_rng(traffic.seed_sequence(seed, 37))
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]
