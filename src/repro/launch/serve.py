"""Serving launcher: the continuous-batching ServeEngine over synthetic
Poisson traffic, reporting per-request latency / TTFT percentiles and
goodput.  ``--static`` runs the static-batch baseline (admission only when
every decode slot has drained) for an apples-to-apples comparison;
``--log-jsonl`` streams one ``repro.telemetry/1`` ``request`` record per
completed request, then writes the run's engine spans (``serve.tick`` and
its parts, ``runtime/serve_engine.py``) as ``span`` records: where a tick's
host time went, and its counters (live slots, admissions, free blocks).

  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \
      --requests 16 --rate 4 --n-slots 4 --log-jsonl serve_requests.jsonl
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ASSIGNED, PAPER, get_config
from repro.core.telemetry import SPANS, JsonlSink, span_record
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model
from repro.runtime.serve_engine import Request, ServeEngine


def synthetic_requests(cfg, n: int, *, rate: float | None = None,
                       prompt_lens: tuple[int, int] = (4, 16),
                       max_new: tuple[int, int] = (4, 16),
                       temperature: float = 0.0, top_p: float = 1.0,
                       seed: int = 0) -> list[Request]:
    """Synthetic workload: Poisson arrivals at ``rate`` req/s (all at t=0
    when ``rate`` is None), uniform prompt/new-token lengths, per-request
    seeds, and the family's non-token extras (frames / patches)."""
    rng = np.random.RandomState(seed)
    t = 0.0
    reqs = []
    for rid in range(n):
        if rate:
            t += float(rng.exponential(1.0 / rate))
        length = int(rng.randint(prompt_lens[0], prompt_lens[1] + 1))
        n_new = int(rng.randint(max_new[0], max_new[1] + 1))
        prompt = rng.randint(0, cfg.vocab_size, size=length).astype(np.int32)
        extras = None
        if cfg.family == "encdec":
            extras = {"frames": 0.1 * rng.randn(
                cfg.enc_seq_len, cfg.frontend_dim).astype(np.float32)}
        elif cfg.family == "vlm":
            extras = {"patches": 0.1 * rng.randn(
                cfg.num_patches, cfg.frontend_dim).astype(np.float32)}
        reqs.append(Request(
            rid=rid, prompt=prompt, max_new_tokens=n_new,
            temperature=temperature, top_p=top_p, seed=seed + rid,
            arrival=t, extras=extras))
    return reqs


def summarize(records: list[dict]) -> dict:
    """Latency/TTFT percentiles + goodput over a run's request records.
    Goodput is completed tokens over the makespan (first arrival to last
    completion) — the quantity continuous batching exists to raise."""
    lat = [r["t_done"] - r["t_arrival"] for r in records]
    ttft = [r["t_first_token"] - r["t_arrival"] for r in records]
    total = sum(r["n_generated"] for r in records)
    makespan = max(r["t_done"] for r in records) - \
        min(r["t_arrival"] for r in records)
    return {
        "n_requests": len(records),
        "completed_tokens": int(total),
        "makespan_s": float(makespan),
        "goodput_tok_s": float(total / makespan) if makespan > 0 else 0.0,
        "latency_p50_s": float(np.percentile(lat, 50)),
        "latency_p99_s": float(np.percentile(lat, 99)),
        "ttft_p50_s": float(np.percentile(ttft, 50)),
        "ttft_p99_s": float(np.percentile(ttft, 99)),
        "evictions": int(sum(r["evictions"] for r in records)),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ASSIGNED + PAPER), default="yi-6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=None,
                    help="Poisson arrival rate (req/s); default: all at t=0")
    ap.add_argument("--n-slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=64)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--static", action="store_true",
                    help="static-batch baseline (no slot refill mid-flight)")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel slots over a dp-way mesh")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-jsonl", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Model(cfg, jnp.float32)
    params = model.init(jax.random.PRNGKey(args.seed))

    mesh = plan = None
    if args.dp > 1:
        from repro.launch.mesh import mesh_for_plan
        from repro.runtime.train_loop import ParallelPlan
        plan = ParallelPlan(dp=args.dp, precision="fp32", zero=0)
        mesh = mesh_for_plan(plan)

    sink = JsonlSink(args.log_jsonl) if args.log_jsonl else None
    engine = ServeEngine(
        model, params, n_slots=args.n_slots, cache_len=args.cache_len,
        block_size=args.block_size, continuous=not args.static,
        mesh=mesh, plan=plan, telemetry_sink=sink)
    reqs = synthetic_requests(
        cfg, args.requests, rate=args.rate,
        prompt_lens=(4, args.cache_len // 4),
        max_new=(2, args.max_new), temperature=args.temperature,
        top_p=args.top_p, seed=args.seed)

    mode = "static" if args.static else "continuous"
    pool = (f"paged pool: {engine.n_blocks}x{engine.block_size} blocks"
            if engine.paged else "slot-swap cache")
    print(f"{cfg.name} [{cfg.family}] {mode} batching, "
          f"{args.n_slots} slots, {pool}")
    t0 = time.monotonic()
    t_spans = time.perf_counter()
    engine.run(reqs)
    wall = time.monotonic() - t0
    spans = SPANS.between(t_spans, time.perf_counter())
    s = summarize(engine.records)
    print(f"{s['n_requests']} requests, {s['completed_tokens']} tokens in "
          f"{wall:.2f}s wall ({engine.n_ticks} decode ticks, "
          f"{engine.n_prefills} prefills, {s['evictions']} evictions)")
    print(f"goodput {s['goodput_tok_s']:,.1f} tok/s | latency p50 "
          f"{s['latency_p50_s'] * 1e3:.0f} ms p99 "
          f"{s['latency_p99_s'] * 1e3:.0f} ms | ttft p50 "
          f"{s['ttft_p50_s'] * 1e3:.0f} ms p99 {s['ttft_p99_s'] * 1e3:.0f} ms")
    if sink is not None:
        for sp in spans:
            sink.write(span_record(sp))
        sink.close()
        print(f"request records and {len(spans)} spans -> {args.log_jsonl}")


if __name__ == "__main__":
    main()
