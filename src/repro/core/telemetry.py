"""Telemetry: per-step structured records, MFU accounting, host spans.

The paper's headline results are *measurements* — 38.38%/36.14%/31.96% GPU
throughput (MFU) for 22B/175B/1T, bubble fractions, comm latency, memory
footprints.  This module is the measurement layer of the reproduction: a
:class:`Telemetry` recorder that turns every training run into a stream of
schema-tagged JSONL records (``SCHEMA``) carrying

  * throughput — wall time, tokens/sec, achieved FLOPs and **MFU** from the
    costmodel-shared analytic per-family counter
    (``core/costmodel.py:train_step_flops``; model FLOPs, remat replay
    excluded, so the number is comparable to the paper's),
  * training signals — loss / moe_aux / moe_drop / grad_norm / loss_scale,
  * one compile-time record with the per-class memory watermarks from
    ``runtime/train_loop.py:train_state_bytes``, XLA's peak-bytes estimate,
    the measured collective payload bytes from ``analysis/hlo.py:comm_bytes``
    on the compiled module, and the costmodel's ``predicted`` block.

Beside the records, :func:`span` times named, nested host intervals (the
serving engine's tick and its parts, ``runtime/serve_engine.py``).  Each
span is a ``jax.profiler.TraceAnnotation`` called ``repro.<name>`` on the
profiler's host plane, on the device trace's timeline, and on exit a
:class:`SpanRecord` in the process-wide bounded log :data:`SPANS`, stamped
with ``time.perf_counter``.  The log is always on; ``launch/serve.py
--log-jsonl`` writes a run's spans as ``span`` records.

Every record is passed through :func:`sanitize_record` (the shared helper
dryrun/hillclimb also use): tracebacks stripped, numpy/jax scalars coerced
to plain JSON types.  ``launch/train.py --log-jsonl`` writes the stream,
``launch/dryrun.py`` emits the same schema for lowered-only runs,
``benchmarks/*`` reuse the record fields (:func:`step_fields`), and
``analysis/report.py --telemetry`` renders the step table.  The
pipeline-timeline view of the same run lives in ``analysis/trace.py``.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import json
import threading
import time
from typing import Any, IO, Iterable, Mapping

from jax.profiler import TraceAnnotation

from repro.core import costmodel as cm

SCHEMA = "repro.telemetry/1"

NOT_MEASURED = "not measured"

# required keys per record kind — the contract ``validate_record`` enforces
# and the CI telemetry job checks on real artifacts
_STEP_KEYS = frozenset({
    "schema", "kind", "step", "wall_s", "tokens", "tokens_per_s",
    "flops_per_step", "tflops_per_device", "mfu", "loss", "loss_scale",
    "predicted",
})
_COMPILE_KEYS = frozenset({
    "schema", "kind", "arch", "family", "plan", "global_batch", "seq_len",
    "devices", "backend", "kernels_interpret_mode", "machine", "peak_flops",
    "flops_per_step", "predicted",
})
# dryrun records keep their shape kind (launch/dryrun.py lowers train /
# prefill / decode shapes) but share the schema tag + predicted block
_DRYRUN_KINDS = frozenset({"train", "prefill", "decode"})
_DRYRUN_KEYS = frozenset({"schema", "kind", "arch", "status"})
# per-request serving records (runtime/serve_engine.py emits one per
# completed request; launch/serve.py --log-jsonl and bench_serve write them)
_REQUEST_KEYS = frozenset({
    "schema", "kind", "rid", "arch", "t_arrival", "t_admit",
    "t_first_token", "t_done", "n_prompt", "n_generated", "finish_reason",
    "evictions",
})
# host spans (``span``), written by launch/serve.py --log-jsonl
_SPAN_KEYS = frozenset({
    "schema", "kind", "id", "parent", "name", "start", "end", "attrs",
})


def sanitize_record(rec: Mapping[str, Any], *,
                    drop: tuple[str, ...] = ("traceback",)) -> dict:
    """JSON-safe copy of a record: ``drop`` keys removed at every nesting
    level, numpy/jax scalars coerced to Python floats/ints/bools.

    The one shared sanitizer behind the telemetry sink, ``launch/dryrun.py
    --out`` and ``launch/hillclimb.py --out`` (previously three copies of
    the same traceback-stripping dict comprehension).
    """
    def clean(x):
        if isinstance(x, Mapping):
            return {str(k): clean(v) for k, v in x.items() if k not in drop}
        if isinstance(x, (list, tuple)):
            return [clean(v) for v in x]
        if isinstance(x, (str, int, float, bool)) or x is None:
            return x
        if hasattr(x, "item") and getattr(x, "ndim", None) in (0, None):
            try:
                return x.item()      # numpy / 0-d jax scalar
            except Exception:
                pass
        if hasattr(x, "tolist"):
            return x.tolist()        # small arrays (e.g. loss curves)
        return str(x)
    return clean(dict(rec))


def device_machine() -> cm.Machine | None:
    """Peak rates of the device this process runs on, looked up by
    ``device_kind`` in ``costmodel.DEVICE_MACHINES``; ``None`` on the CPU,
    where no utilization is measured.  An accelerator the table does not
    know raises."""
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    return cm.machine_for_device_kind(dev.device_kind)


def mfu(flops_per_step: float, step_time_s: float, n_devices: int,
        peak_flops: float) -> float:
    """Model-FLOPs utilization: analytic step FLOPs over what the machine
    could have done in the measured wall time."""
    denom = step_time_s * max(n_devices, 1) * peak_flops
    return flops_per_step / denom if denom > 0 else 0.0


def step_fields(cfg, global_batch: int, seq_len: int, wall_s: float,
                n_devices: int, machine: cm.Machine | None = None) -> dict:
    """Throughput fields for one measured step — the fragment the BENCH_*
    writers merge into their point records so bench artifacts share the
    telemetry schema's accounting.  ``machine`` defaults to this process's
    device (:func:`device_machine`); with none, ``mfu`` is ``None``."""
    machine = machine or device_machine()
    flops = cm.train_step_flops(cfg, global_batch, seq_len).total
    tokens = global_batch * seq_len
    return {
        "tokens_per_s": tokens / wall_s if wall_s > 0 else 0.0,
        "flops_per_step": flops,
        "tflops_per_device": (flops / (wall_s * max(n_devices, 1)) / 1e12
                              if wall_s > 0 else 0.0),
        "mfu": (mfu(flops, wall_s, n_devices, machine.peak_flops)
                if machine else None),
        "machine": machine.name if machine else None,
    }


class JsonlSink:
    """Append-only JSONL writer; every record goes through
    :func:`sanitize_record` and is flushed immediately (crash-safe tail)."""

    def __init__(self, path: str):
        self.path = path
        self._f: IO[str] | None = open(path, "a")

    def write(self, rec: Mapping[str, Any]) -> None:
        if self._f is None:
            raise ValueError(f"sink {self.path} is closed")
        self._f.write(json.dumps(sanitize_record(rec)) + "\n")
        self._f.flush()

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class Telemetry:
    """Per-run recorder: one compile record, then one record per step.

    ``cfg`` is the ``ModelConfig`` actually trained, ``plan`` the
    ``ParallelPlan`` (or duck-typed equivalent).  ``machine`` (default: the
    device's own, :func:`device_machine`) supplies the MFU peak and the
    machine the costmodel prices the plan on; without one — on the CPU —
    ``mfu`` is recorded as ``None`` ("not measured") and the prediction is
    for the costmodel's calibrated Frontier machine.  The analytic FLOPs
    and the costmodel prediction are computed once here; each
    :meth:`step` call only does O(1) bookkeeping on top of the metrics the
    executor already returns.
    """

    def __init__(self, cfg, plan, global_batch: int, seq_len: int, *,
                 machine: cm.Machine | None = None,
                 jsonl: str | None = None):
        self.cfg, self.plan = cfg, plan
        self.global_batch, self.seq_len = global_batch, seq_len
        self.machine = machine or device_machine()
        self.flops = cm.train_step_flops(cfg, global_batch, seq_len)
        try:
            self.prediction = cm.predict_step(cfg, plan, global_batch,
                                              seq_len,
                                              self.machine or cm.FRONTIER)
        except Exception:                   # exotic plan the model can't price
            self.prediction = None
        self.sink = JsonlSink(jsonl) if jsonl else None
        self.step_walls: list[float] = []
        self.records: list[dict] = []

    # ---------------------------------------------------------------
    def _predicted_block(self) -> dict:
        return predicted_block(self.prediction)

    def record_compile(self, compiled=None, *, state_bytes: dict | None = None,
                       compile_s: float | None = None,
                       extra: dict | None = None) -> dict:
        """One-time record at compile: measured collective payloads from the
        *compiled* module (``hlo.comm_bytes``; unoptimized StableHLO has no
        collectives), XLA's peak-bytes estimate, and the per-class state
        watermarks from the plan's sharding specs."""
        import jax
        rec: dict[str, Any] = {
            "schema": SCHEMA, "kind": "compile",
            "arch": self.cfg.name, "family": self.cfg.family,
            "plan": plan_dict(self.plan),
            "global_batch": self.global_batch, "seq_len": self.seq_len,
            "devices": jax.device_count(),
            "backend": jax.default_backend(),
            "kernels_interpret_mode": jax.default_backend() == "cpu",
            "machine": self.machine.name if self.machine else None,
            "peak_flops": self.machine.peak_flops if self.machine else None,
            "flops_per_step": self.flops.total,
            "flops_breakdown": {"matmul": self.flops.matmul,
                                "attn": self.flops.attn,
                                "scan": self.flops.scan},
            "predicted": self._predicted_block(),
        }
        if compiled is not None:
            from repro.analysis import hlo
            try:
                rec["comm_bytes_measured"] = {
                    k: int(v) for k, v in hlo.comm_bytes(compiled).items()}
            except Exception as e:
                rec["comm_bytes_measured"] = {"error": str(e)}
            try:
                ma = compiled.memory_analysis()
                if ma is not None:
                    rec["xla_peak_bytes"] = int(
                        ma.temp_size_in_bytes + ma.argument_size_in_bytes
                        + ma.output_size_in_bytes - ma.alias_size_in_bytes)
            except Exception:
                pass
        if state_bytes is not None:
            rec["state_bytes"] = state_bytes
        if compile_s is not None:
            rec["compile_s"] = compile_s
        if extra:
            rec.update(extra)
        return self._emit(rec)

    def step(self, step: int, wall_s: float, metrics: Mapping[str, Any],
             *, tokens: int | None = None) -> dict:
        """Record one optimizer step from its measured wall time + the
        executor's metrics dict; returns the sanitized record."""
        tokens = tokens if tokens is not None else \
            self.global_batch * self.seq_len
        n_dev = self.plan.n_devices
        self.step_walls.append(wall_s)
        rec: dict[str, Any] = {
            "schema": SCHEMA, "kind": "step", "step": step,
            "wall_s": wall_s, "tokens": tokens,
            "tokens_per_s": tokens / wall_s if wall_s > 0 else 0.0,
            "flops_per_step": self.flops.total,
            "tflops_per_device": (self.flops.total / (wall_s * n_dev) / 1e12
                                  if wall_s > 0 else 0.0),
            "mfu": self.mfu(self.flops.total, wall_s),
            "predicted": self._predicted_block(),
        }
        for k in ("loss", "moe_aux", "moe_drop", "grad_norm", "loss_scale",
                  "grads_finite"):
            if k in metrics:
                rec[k] = metrics[k]
        return self._emit(rec)

    def mfu(self, flops: float, wall_s: float) -> float | None:
        """MFU against this run's device peak; ``None`` without one."""
        if self.machine is None:
            return None
        return mfu(flops, wall_s, self.plan.n_devices,
                   self.machine.peak_flops)

    def _emit(self, rec: dict) -> dict:
        rec = sanitize_record(rec)
        validate_record(rec)
        self.records.append(rec)
        if self.sink is not None:
            self.sink.write(rec)
        return rec

    def console_line(self, rec: Mapping[str, Any], *,
                     window: int = 1, with_mfu: bool = True) -> str:
        """The launcher's human step line.  The prefix is byte-identical to
        the pre-telemetry format (examples/docs depend on it); ``with_mfu``
        appends the utilization suffix.  ``window`` averages throughput
        over the last N recorded steps (the old ``--log-every`` cadence)."""
        walls = self.step_walls[-window:] or [rec["wall_s"]]
        dt = sum(walls)
        tok_s = self.global_batch * self.seq_len * len(walls) / dt if dt else 0.0
        line = (f"step {rec['step']:5d} loss {rec['loss']:.4f} "
                f"scale {rec['loss_scale']:.0f} "
                f"{tok_s:,.0f} tok/s")
        if with_mfu:
            w_mfu = self.mfu(self.flops.total * len(walls), dt)
            line += (f" mfu {NOT_MEASURED}" if w_mfu is None
                     else f" mfu {100.0 * w_mfu:.2f}%")
        return line

    def close(self) -> None:
        if self.sink is not None:
            self.sink.close()


def predicted_block(prediction: cm.Prediction | None) -> dict:
    """Costmodel prediction as the record's ``predicted`` sub-dict — the
    analytic side of a record (dryrun emits the same block on lowered-only
    runs)."""
    if prediction is None:
        return {}
    return {
        "step_time_s": prediction.step_time_s,
        "memory_per_gpu": prediction.memory_per_gpu,
        "comm_bytes": dict(prediction.comm_bytes),
        "bubble": prediction.bubble,
        "tflops_per_device": prediction.tflops_per_gpu,
        "moe_drop": prediction.moe_drop,
    }


def plan_dict(plan) -> dict:
    """JSON view of a ParallelPlan (duck-typed; only the schema fields)."""
    out = {}
    for k in ("dp", "tp", "pp", "ep", "node", "virtual_stages", "zero",
              "gas", "qcomm", "overlap", "comm_block", "precision", "remat",
              "kernels", "rules"):
        if hasattr(plan, k):
            out[k] = getattr(plan, k)
    return out


def timed_call(fn, *args):
    """Call ``fn`` and block until every output is ready; returns
    ``(outputs, wall_seconds)`` — the launcher's per-step timing hook."""
    import jax
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# Host spans: named, nested intervals on the profiler's timeline and in a
# bounded in-process log
# ---------------------------------------------------------------------------

SPAN_PREFIX = "repro."
SPAN_LOG_LEN = 65536


@dataclasses.dataclass(slots=True)
class SpanRecord:
    """One finished span: ``start``/``end`` on ``time.perf_counter``,
    ``parent`` the id of the span that enclosed it (``None`` at the top),
    ``attrs`` its attributes, counters set inside the span included."""
    id: int
    parent: int | None
    name: str
    start: float
    end: float
    attrs: dict

    @property
    def seconds(self) -> float:
        return self.end - self.start


class SpanLog:
    """The process-wide log of finished spans, newest last, holding at most
    ``maxlen`` (the oldest fall out)."""

    def __init__(self, maxlen: int = SPAN_LOG_LEN):
        self.records: collections.deque[SpanRecord] = \
            collections.deque(maxlen=maxlen)
        self._ids = itertools.count(1)
        self._local = threading.local()

    def _stack(self) -> list[int]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def between(self, t0: float, t1: float,
                name: str | None = None) -> list[SpanRecord]:
        """The spans that lie wholly inside ``[t0, t1]``, oldest first;
        only those called ``name`` where it is given."""
        return [s for s in list(self.records)
                if t0 <= s.start and s.end <= t1
                and (name is None or s.name == name)]


SPANS = SpanLog()


def self_seconds(span: SpanRecord, spans: Iterable[SpanRecord],
                 child: str | None = None) -> float:
    """``span``'s duration less what its direct children among ``spans``
    cover (only those called ``child``, where it is given)."""
    return span.seconds - sum(
        s.seconds for s in spans
        if s.parent == span.id and (child is None or s.name == child))


class span:
    """``with span(name, **attrs) as attrs:`` times the enclosed block.

    The block may add counters to the yielded ``attrs`` dict.  While the
    profiler records, the span is also a ``TraceAnnotation`` called
    ``repro.<name>`` carrying the attributes on exit; with it off a span
    costs two clock reads and one append to :data:`SPANS`."""

    __slots__ = ("name", "attrs", "_id", "_parent", "_start", "_ann")

    def __init__(self, name: str, **attrs: Any):
        self.name, self.attrs = name, attrs

    def __enter__(self) -> dict:
        stack = SPANS._stack()
        self._parent = stack[-1] if stack else None
        self._id = next(SPANS._ids)
        stack.append(self._id)
        if TraceAnnotation.is_enabled():
            self._ann = TraceAnnotation(SPAN_PREFIX + self.name)
            self._ann.__enter__()
        else:
            self._ann = None
        self._start = time.perf_counter()
        return self.attrs

    def __exit__(self, *exc) -> None:
        end = time.perf_counter()
        if self._ann is not None:
            self._ann.set_metadata(**self.attrs)
            self._ann.__exit__(*exc)
        SPANS._stack().pop()
        SPANS.records.append(SpanRecord(self._id, self._parent, self.name,
                                        self._start, end, self.attrs))


def span_record(s: SpanRecord) -> dict:
    """A span as a ``span`` record of the telemetry schema."""
    return sanitize_record({
        "schema": SCHEMA, "kind": "span", "id": s.id, "parent": s.parent,
        "name": s.name, "start": s.start, "end": s.end, "attrs": s.attrs})


# ---------------------------------------------------------------------------
# Schema validation (tests + the CI telemetry job run this on real files)
# ---------------------------------------------------------------------------

def validate_record(rec: Mapping[str, Any]) -> None:
    """Raise ``ValueError`` on a record that violates the schema contract."""
    if rec.get("schema") != SCHEMA:
        raise ValueError(f"record schema {rec.get('schema')!r} != {SCHEMA!r}")
    kind = rec.get("kind")
    if kind == "step":
        missing = _STEP_KEYS - rec.keys()
    elif kind == "compile":
        missing = _COMPILE_KEYS - rec.keys()
    elif kind in _DRYRUN_KINDS:
        missing = _DRYRUN_KEYS - rec.keys()
        if rec.get("status") == "ok" and kind == "train" \
                and "predicted" not in rec:
            raise ValueError("ok train dryrun record missing 'predicted'")
    elif kind == "request":
        missing = _REQUEST_KEYS - rec.keys()
    elif kind == "span":
        missing = _SPAN_KEYS - rec.keys()
    else:
        raise ValueError(f"unknown record kind {kind!r}")
    if missing:
        raise ValueError(f"{kind} record missing keys: {sorted(missing)}")
    if kind == "request" and not missing:
        if rec["n_generated"] < 0 or rec["n_prompt"] <= 0:
            raise ValueError("request record with non-positive token counts")
        t = [rec["t_arrival"], rec["t_admit"], rec["t_first_token"],
             rec["t_done"]]
        if any(x is None for x in t) or not all(
                a <= b + 1e-9 for a, b in zip(t, t[1:])):
            raise ValueError(
                f"request timestamps not monotone: {t}")
    if kind == "span" and not rec["start"] <= rec["end"]:
        raise ValueError(f"span {rec['name']!r} ends before it starts")
    if kind == "step":
        if rec["mfu"] is not None and not (0.0 <= rec["mfu"] <= 1.0):
            raise ValueError(f"mfu {rec['mfu']} outside [0, 1]")
    if kind == "compile":
        if rec["kernels_interpret_mode"] != (rec["backend"] == "cpu"):
            raise ValueError("kernels_interpret_mode must equal "
                             "(backend == 'cpu')")


def validate_jsonl(path: str, *, require_step: bool = True) -> list[dict]:
    """Parse + validate a telemetry JSONL file; returns the records.
    By default requires at least one step or request record (a run that
    never stepped / completed nothing is not a valid telemetry artifact);
    pass ``require_step=False`` for dryrun streams, which are
    compile-time only."""
    records = []
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                raise ValueError(f"{path}:{i + 1}: not JSON: {e}") from e
            validate_record(rec)
            records.append(rec)
    if require_step and not any(r["kind"] in ("step", "request")
                                for r in records):
        raise ValueError(f"{path}: no step or request records")
    return records
