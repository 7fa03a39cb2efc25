"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

What it reads: for every TPU device plane, the events of its "XLA Ops"
line (one event per HLO operation, the device's own clock mapped onto the
host's), and the host spans that the benchmark writes with
``jax.profiler.TraceAnnotation`` (names starting ``bench.``).

What it computes, inside a window given on the host clock:

* busy time: the union of the operation intervals of a device (control
  flow such as a ``while`` holds the operations of its body, so the union,
  not the sum, is the busy time); idle share is 1 - busy / window;
* kernel time: Pallas kernels carry no name in the trace today (their
  events are ``custom-call`` operations with ``custom_call_target=
  "tpu_custom_call"``), so :func:`classify_kernel` recognises each kernel
  of the dense path by its operand and result shapes;
* exposed collective time: collective operations during which no other
  operation runs on that device;
* the operations that took the most time, and the longest idle gaps, each
  labelled with the innermost host span that covers its middle.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

CONTAINER_OPS = frozenset({"while", "conditional", "call"})
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")
SPAN_PREFIX = "bench."

_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|s64|f16|bf16|f32|f64)"
                    r"\[([0-9,]*)\]")


@dataclasses.dataclass(frozen=True)
class Op:
    start: float          # seconds on the host clock
    end: float
    name: str             # HLO instruction name without its leading '%'
    kind: str             # HLO operation: fusion, custom-call, while, ...
    kernel: str | None    # kernel of the dense path, for a Pallas call
    shapes: tuple = ()    # (results, operands) of a Pallas call

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass(frozen=True)
class Span:
    start: float
    end: float
    name: str


@dataclasses.dataclass
class Trace:
    devices: dict[str, list[Op]]   # plane name -> its ops, by start time
    spans: list[Span]              # host spans the benchmark wrote


# ---------------------------------------------------------------------------
# Parsing one HLO instruction's text
# ---------------------------------------------------------------------------

def _split_result(text: str) -> tuple[str, str, str]:
    """'%name = <result shape> <op>(<operands>), attrs' -> (name, result
    text, rest starting at the op)."""
    name, _, rhs = text.partition(" = ")
    name = name.strip().lstrip("%")
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                return name, rhs[:i + 1], rhs[i + 1:].lstrip()
        return name, rhs, ""
    result, _, rest = rhs.partition(" ")
    return name, result, rest


def _shapes(text: str) -> list[tuple[str, tuple[int, ...]]]:
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(text)]


def parse_op(text: str) -> tuple[str, str, tuple]:
    """(name, kind, (result shapes, operand shapes)) of an event's HLO
    text; shapes only for Pallas calls."""
    if " = " not in text:
        return text, text, ()
    name, result, rest = _split_result(text)
    kind = rest.split("(", 1)[0].strip()
    if kind != "custom-call" or 'custom_call_target="tpu_custom_call"' \
            not in rest:
        return name, kind, ()
    operands = rest.split("(", 1)[1].split("), custom_call_target", 1)[0]
    return name, kind, (tuple(_shapes(result)), tuple(_shapes(operands)))


def classify_kernel(shapes: tuple) -> str | None:
    """The dense-path kernel a Pallas call is, from its shapes, or None.

    flash_fwd:     q, k, v (B, H, S, hd)        -> o, lse (B, H, S, 1) f32
    flash_bwd_dq:  q, k, v, do, lse, delta       -> dq
    flash_bwd_dkv: q, k, v, do, lse, delta       -> dk, dv
    gelu_mlp_in:   x (N, d), w1 (d, F)           -> (N, F)
    layernorm:     x (N, d), scale (d), bias (d) -> (N, d)
    cross_entropy: h (N, d), w (d, V), y (N, 1)  -> lse, y-logit (N, 1) f32
    """
    if not shapes:
        return None
    res, ops = shapes
    dims = [s for _, s in ops]
    if len(ops) == 3 and all(len(d) == 4 for d in dims) and len(res) == 2 \
            and res[0][1] == dims[0] and res[1][1] == dims[0][:3] + (1,):
        return "flash_fwd"
    if len(ops) == 6 and all(len(d) == 4 for d in dims) \
            and dims[4][-1] == 1 and dims[5][-1] == 1:
        if len(res) == 1 and res[0][1] == dims[0]:
            return "flash_bwd_dq"
        if len(res) == 2 and res[0][1] == dims[1] and res[1][1] == dims[2]:
            return "flash_bwd_dkv"
    if len(ops) == 2 and len(res) == 1 and len(dims[0]) == 2 \
            and len(dims[1]) == 2 and dims[0][1] == dims[1][0] \
            and res[0][1] == (dims[0][0], dims[1][1]):
        return "gelu_mlp_in"
    if len(ops) == 3 and len(res) == 1 and len(dims[0]) == 2 \
            and dims[1] == dims[2] == (dims[0][1],) and res[0][1] == dims[0]:
        return "layernorm"
    if len(ops) == 3 and len(res) == 2 and len(dims[0]) == 2 \
            and len(dims[1]) == 2 and dims[2] == (dims[0][0], 1):
        return "cross_entropy"
    return None


# ---------------------------------------------------------------------------
# Loading
# ---------------------------------------------------------------------------

def find_xplane(trace_dir: str) -> list[str]:
    return sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` file, or every one under a directory."""
    from jax.profiler import ProfileData

    files = [path] if os.path.isfile(path) else find_xplane(path)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return from_profiles([ProfileData.from_file(f) for f in files])


def from_profiles(profiles) -> Trace:
    """The device operations and benchmark spans of ``ProfileData``s."""
    devices: dict[str, list[Op]] = {}
    spans: list[Span] = []
    for pd in profiles:
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                for line in plane.lines:
                    if line.name != "XLA Ops":
                        continue
                    ops = devices.setdefault(plane.name, [])
                    for e in line.events:
                        name, kind, shapes = parse_op(e.name)
                        t0 = e.start_ns * 1e-9
                        ops.append(Op(t0, t0 + e.duration_ns * 1e-9, name,
                                      kind, classify_kernel(shapes), shapes))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(SPAN_PREFIX):
                            t0 = e.start_ns * 1e-9
                            spans.append(Span(t0, t0 + e.duration_ns * 1e-9,
                                              e.name))
    for ops in devices.values():
        ops.sort(key=lambda o: o.start)
    spans.sort(key=lambda s: s.start)
    return Trace(devices=devices, spans=spans)


# ---------------------------------------------------------------------------
# Reductions
# ---------------------------------------------------------------------------

def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, window) -> list[tuple[float, float]]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def busy_seconds(ops: list[Op], window: tuple[float, float]) -> float:
    return _length(_clip(union((o.start, o.end) for o in ops), window))


def idle_share(trace: Trace, window: tuple[float, float]) -> dict[str, float]:
    """Per device: 1 - busy / window."""
    length = window[1] - window[0]
    return {d: 1.0 - busy_seconds(ops, window) / length
            for d, ops in trace.devices.items()}


def in_window(ops: list[Op], window: tuple[float, float]) -> list[Op]:
    return [o for o in ops if o.start >= window[0] and o.end <= window[1]]


def kernel_calls(trace: Trace, window: tuple[float, float]
                 ) -> dict[str, list[Op]]:
    """Pallas calls of the dense path inside the window, by kernel."""
    out: dict[str, list[Op]] = collections.defaultdict(list)
    for ops in trace.devices.values():
        for o in in_window(ops, window):
            if o.kernel:
                out[o.kernel].append(o)
    return dict(out)


def exposed_collective_seconds(trace: Trace, window: tuple[float, float]
                               ) -> dict[str, float]:
    """Per device: time inside collective operations during which no
    other (non-container) operation runs."""
    out = {}
    for d, ops in trace.devices.items():
        coll, other = [], []
        for o in ops:
            if o.kind in CONTAINER_OPS:
                continue
            is_coll = any(o.kind.startswith(c) for c in COLLECTIVE_OPS)
            (coll if is_coll else other).append((o.start, o.end))
        coll = _clip(union(coll), window)
        busy = union(_clip(other, window))
        exposed, j = 0.0, 0
        for s, e in coll:
            covered = 0.0
            while j < len(busy) and busy[j][1] <= s:
                j += 1
            k = j
            while k < len(busy) and busy[k][0] < e:
                covered += min(e, busy[k][1]) - max(s, busy[k][0])
                k += 1
            exposed += (e - s) - covered
        out[d] = exposed
    return out


def _label(spans: list[Span], t: float) -> str:
    """The innermost host span that covers ``t``."""
    best = None
    for s in spans:
        if s.start <= t <= s.end and (best is None
                                      or s.end - s.start < best.end - best.start):
            best = s
    return best.name if best else "no host span"


def _group(name: str) -> str:
    """An HLO instruction name without its numeric suffix."""
    return re.sub(r"\.\d+$", "", name)


def top_ops(trace: Trace, window: tuple[float, float], n: int = 10
            ) -> list[list]:
    """The device operations that took the most time in the window, summed
    over devices and averaged per device: Pallas calls by kernel, other
    operations by instruction name without its suffix; control flow
    (whose events hold their bodies) is left out."""
    tot: dict[str, float] = collections.Counter()
    for ops in trace.devices.values():
        for o in in_window(ops, window):
            if o.kind in CONTAINER_OPS:
                continue
            tot[o.kernel or _group(o.name)] += o.seconds
    n_dev = max(len(trace.devices), 1)
    return [[k, v / n_dev] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, window: tuple[float, float], n: int = 10
              ) -> list[list]:
    """The ``n`` longest idle gaps on any device inside the window, each
    labelled with the host span that covers its middle."""
    gaps = []
    for ops in trace.devices.values():
        busy = _clip(union((o.start, o.end) for o in ops), window)
        edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, _label(trace.spans, (s + e) / 2)))
    gaps.sort(key=lambda g: -g[0])
    return [[label, sec] for sec, label in gaps[:n]]


def span_window(trace: Trace, name: str) -> tuple[float, float]:
    """The extent of the first host span called ``name``."""
    for s in trace.spans:
        if s.name == name:
            return (s.start, s.end)
    raise KeyError(f"no host span {name!r} in the trace")


@dataclasses.dataclass
class Reduction:
    """What the per-layer metric readers get from a traced window."""
    window: tuple[float, float]
    idle: dict[str, float]
    busy_s: float                         # averaged over devices
    kernels: dict[str, list[Op]]
    exposed_collective: dict[str, float]
    top_ops: list
    idle_gaps: list

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def reduce(trace: Trace, window: tuple[float, float]) -> Reduction:
    idle = idle_share(trace, window)
    length = window[1] - window[0]
    busy = [(1.0 - v) * length for v in idle.values()]
    return Reduction(
        window=window, idle=idle,
        busy_s=sum(busy) / max(len(busy), 1),
        kernels=kernel_calls(trace, window),
        exposed_collective=exposed_collective_seconds(trace, window),
        top_ops=top_ops(trace, window), idle_gaps=idle_gaps(trace, window))
