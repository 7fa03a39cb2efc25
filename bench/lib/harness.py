"""The harness: finds a cell's files by name, hands runners what they share
(spans, the measured window, the compile count), and runs the per-layer
metric readers.

Layout under the benchmark's directory (``bench/``), every file found by a
name that ``BENCHMARK.json`` gives:

  configs/<config>.json     one model configuration, as it is run
  traffic/<traffic>.json    one traffic mix, read by lib/traffic.py
  workloads/<cell>.json     one cell: its runner, plan, engine and limits
  runners/<runner>.py       one job kind; ``run(run: Run) -> dict``
  metrics/<metric>.py       one per-layer metric; ``read(ctx) -> float|None``

A new configuration, mix, cell, runner or metric is a new file plus an
entry in ``BENCHMARK.json``; no existing file changes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import pathlib
import shutil
import tempfile
import time
from typing import Any

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict
    workload: dict

    @property
    def runner(self) -> str:
        return self.workload["runner"]


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell called ``name`` in ``root``'s BENCHMARK.json, with its
    configuration, traffic and workload files."""
    bench_dir = root / "bench"
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return Cell(
        name=name, chips=int(entry["chips"]),
        config_name=entry["config"],
        config=load_json(root / cfg_entry["file"]),
        traffic=load_json(bench_dir / "traffic" / f"{entry['traffic']}.json"),
        workload=load_json(bench_dir / "workloads" / f"{name}.json"))


def load_module(path: pathlib.Path):
    """Import a file by path; its name may hold dots."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def runner_module(cell: Cell, bench_dir: pathlib.Path = BENCH_DIR):
    return load_module(bench_dir / "runners" / f"{cell.runner}.py")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def end_to_end_for(bench: dict, cell: str) -> list[dict]:
    return [m for m in bench["end_to_end"] if _applies(m, cell)]


def per_layer_for(bench: dict, cell: str) -> list[dict]:
    """Per-layer metrics read in ``cell``: those that list it, and those
    without a list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_for(bench, cell)}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def read_per_layer(bench: dict, cell: str, ctx: dict,
                   bench_dir: pathlib.Path = BENCH_DIR) -> dict:
    """{name: {"value", "unit"}} for each per-layer metric of the cell
    whose reader finds something to read."""
    out = {}
    for m in per_layer_for(bench, cell):
        reader = load_module(bench_dir / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# What a runner is handed
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts, by time, through JAX's monitoring hooks: programs compiled or
    loaded from the persistent cache (JAX times both as a backend
    compile), hits of that cache, and traces of Python functions."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring
        self.times: dict[str, list[float]] = {
            self.COMPILE: [], self.TRACE: [], self.HIT: []}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, *_: Any, **__: Any) -> None:
        if event in self.times:
            self.times[event].append(time.perf_counter())

    def close(self) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on_event)
        jax.monitoring.unregister_event_listener(self._on_event)

    def between(self, t0: float, t1: float, event: str = COMPILE) -> int:
        return sum(t0 <= t <= t1 for t in self.times[event])


@dataclasses.dataclass
class Run:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_process: float                       # perf_counter at process start
    probe: dict = dataclasses.field(default_factory=dict)
    window_start: float | None = None
    window_end: float | None = None
    trace_dir: str | None = None
    compiles: CompileCounter | None = None

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span on the profiler's clock (``bench.<name>``)."""
        if self.trace:
            import jax
            with jax.profiler.TraceAnnotation("bench." + name):
                yield
        else:
            yield

    @contextlib.contextmanager
    def window(self):
        """The measured window: set-up ends where it opens.  With tracing
        on, the profiler records exactly this span."""
        import jax
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self.trace_dir)
        self.window_start = time.perf_counter()
        try:
            with self.span("window"):
                yield
        finally:
            self.window_end = time.perf_counter()
            if self.trace:
                jax.profiler.stop_trace()

    @property
    def setup_s(self) -> float:
        return self.window_start - self.t_process

    @property
    def window_s(self) -> float:
        return self.window_end - self.window_start

    def compile_counts(self) -> dict:
        """Programs compiled in set-up (not found in the persistent cache),
        and programs compiled or loaded, and functions traced, in the
        window: there should be none."""
        c = self.compiles
        if c is None:
            return {}
        setup = (self.t_process, self.window_start)
        window = (self.window_start, self.window_end)
        return {"compiles_in_setup": c.between(*setup)
                - c.between(*setup, c.HIT),
                "cache_hits_in_setup": c.between(*setup, c.HIT),
                "compiles_in_window": c.between(*window),
                "traces_in_window": c.between(*window, c.TRACE)}

    def cleanup(self) -> None:
        if self.compiles is not None:
            self.compiles.close()
        if self.trace_dir:
            shutil.rmtree(self.trace_dir, ignore_errors=True)


def judge(checks: list[dict]) -> bool:
    """Correct when there is something to compare and every number lies
    within its limit."""
    return bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks)


def memory_peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest device, where the backend says."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def use_compile_cache() -> None:
    """Keep every compiled program in JAX's persistent cache: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else a fixed directory inside the
    checkout, so that only a cell's first run there compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
