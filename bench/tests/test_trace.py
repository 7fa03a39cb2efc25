"""The reduction from a profiler trace to device metrics, on a trace whose
busy and idle intervals are known: built from a text profile here, and
recorded on a TPU v5e by ``record_trace.py`` (``data/small.xplane.pb``)."""
import importlib.util
import pathlib

import pytest

from bench.lib import peaks, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"
T0_NS = 1_000_000_000          # the lines' time stamp: 1 s

FLASH = ('%custom-call.5 = (bf16[1,2,256,128]{3,2,1,0}, '
         'f32[1,2,256,1]{3,2,1,0}) custom-call(bf16[1,2,256,128]{3,2,1,0} '
         '%q, bf16[1,2,256,128]{3,2,1,0} %k, bf16[1,2,256,128]{3,2,1,0} %v), '
         'custom_call_target="tpu_custom_call"')
GELU = ('%custom-call.7 = bf16[16,512]{1,0} custom-call(bf16[16,128]{1,0} '
        '%x, bf16[128,512]{1,0} %w), custom_call_target="tpu_custom_call"')

# (name, start ms, end ms) from the lines' time stamp
DEVICES = {
    "/device:TPU:0": [
        ("%while.1 = (s32[], bf16[8]{0}) while((s32[], bf16[8]{0}) %t)", 0, 60),
        ("%fusion.2 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p)", 0, 20),
        (FLASH, 20, 40),
        ("%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %x)", 50, 70),
        (GELU, 80, 90),
        ("%fusion.9 = bf16[8]{0} fusion(bf16[8]{0} %p)", 120, 130),
    ],
    "/device:TPU:1": [
        ("%fusion.1 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p)", 10, 50),
        ("%collective-permute.2 = bf16[8]{0} collective-permute("
         "bf16[8]{0} %y)", 40, 60),
    ],
}
SPANS = [("bench.window", 0, 100), ("bench.step", 0, 45),
         ("bench.next_batch", 70, 85), ("bench.step", 85, 100),
         ("other.span", 0, 100)]


def _line(name: str, events: list) -> str:
    meta = {n: i + 1 for i, n in enumerate(dict.fromkeys(e[0] for e in events))}
    ev = "".join(
        f"    events {{ metadata_id: {meta[n]} offset_ps: {int(a * 1e9)} "
        f"duration_ps: {int((b - a) * 1e9)} }}\n" for n, a, b in events)
    md = "".join(
        f"  event_metadata {{ key: {i} value {{ id: {i} name: "
        f"{_quote(n)} }} }}\n" for n, i in meta.items())
    return (f"  lines {{\n    id: 1\n    name: \"{name}\"\n"
            f"    timestamp_ns: {T0_NS}\n{ev}  }}\n{md}")


def _quote(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'


def _text_profile() -> str:
    planes = [(name, "XLA Ops", ops) for name, ops in DEVICES.items()]
    planes.append(("/host:CPU", "python", SPANS))
    return "".join(f"planes {{\n  id: {i + 1}\n  name: \"{name}\"\n"
                   f"{_line(line, ev)}}}\n"
                   for i, (name, line, ev) in enumerate(planes))


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData
    t = trace.from_profiles([ProfileData.from_text_proto(_text_profile())])
    return t, trace.reduce(t, trace.span_window(t, "bench.window"))


def _ms(x: float) -> float:
    return round(x * 1e3, 6)


def test_window_and_spans(synthetic):
    t, red = synthetic
    assert _ms(red.window[0] - T0_NS * 1e-9) == 0.0
    assert _ms(red.window_s) == 100.0
    assert [s.name for s in t.spans].count("bench.step") == 2
    assert all(s.name.startswith("bench.") for s in t.spans)


def test_busy_union_and_idle_share(synthetic):
    _, red = synthetic
    # TPU:0 busy [0, 70] and [80, 90] (the while holds its body; the op at
    # 120 ms lies outside the window); TPU:1 busy [10, 60]
    assert red.idle["/device:TPU:0"] == pytest.approx(0.2)
    assert red.idle["/device:TPU:1"] == pytest.approx(0.5)
    assert _ms(red.busy_s) == 65.0


def test_kernels_found_by_shape(synthetic):
    _, red = synthetic
    assert sorted(red.kernels) == ["flash_fwd", "gelu_mlp_in"]
    (fwd,) = red.kernels["flash_fwd"]
    assert _ms(fwd.seconds) == 20.0
    assert fwd.shapes[1][0] == ("bf16", (1, 2, 256, 128))


def test_exposed_collectives(synthetic):
    _, red = synthetic
    # the all-reduce overlaps only the while (a container); the permute
    # overlaps the fusion for 10 of its 20 ms
    assert _ms(red.exposed_collective["/device:TPU:0"]) == 20.0
    assert _ms(red.exposed_collective["/device:TPU:1"]) == 10.0


def test_top_ops_and_labelled_gaps(synthetic):
    _, red = synthetic
    top = {k: _ms(v) for k, v in red.top_ops}
    assert top == {"fusion": 30.0, "flash_fwd": 10.0, "all-reduce": 10.0,
                   "collective-permute": 10.0, "gelu_mlp_in": 5.0}
    gaps = [(label, _ms(sec)) for label, sec in red.idle_gaps]
    assert gaps[0] == ("bench.next_batch", 40.0)
    assert sorted(gaps[1:]) == [("bench.next_batch", 10.0),
                                ("bench.step", 10.0), ("bench.step", 10.0)]


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_kernel_roofline_readers(synthetic):
    from bench.lib import counts
    _, red = synthetic
    v5e = peaks.peaks_for("TPU v5 lite")
    ctx = {"trace": red, "peaks": v5e}
    least = peaks.roofline_seconds(*counts.flash_fwd(1, 2, 256, 128), v5e)
    assert _reader("flash_attention_roofline.train").read(ctx) == \
        pytest.approx(100.0 * least / 0.020)
    least = peaks.roofline_seconds(*counts.gelu_mlp_in(16, 128, 512), v5e)
    assert _reader("gelu_mlp_in_roofline.train").read(ctx) == \
        pytest.approx(100.0 * least / 0.010)


def test_readers_find_nothing_in_an_empty_window(synthetic):
    t, _ = synthetic
    red = trace.reduce(t, (0.0, 0.5))      # before any event
    ctx = {"trace": red, "peaks": peaks.peaks_for("TPU v5 lite")}
    assert _reader("flash_attention_roofline.train").read(ctx) is None
    assert _reader("gelu_mlp_in_roofline.train").read(ctx) is None


def test_parse_op_keeps_shapes_of_pallas_calls_only():
    name, kind, shapes = trace.parse_op(FLASH)
    assert (name, kind) == ("custom-call.5", "custom-call")
    assert trace.classify_kernel(shapes) == "flash_fwd"
    assert trace.parse_op("%fusion.2 = bf16[8]{0} fusion(bf16[8]{0} %p)") \
        == ("fusion.2", "fusion", ())


@pytest.fixture(scope="module")
def recorded():
    """Recorded on one TPU v5e by ``record_trace.py``: three times a
    2048^3 bf16 matmul, a 20 ms host sleep, and flash attention on
    (1, 256, 2, 128)."""
    t = trace.load(str(DATA / "small.xplane.pb"))
    return t, trace.reduce(t, trace.span_window(t, "bench.window"))


def test_recorded_trace_planes_and_spans(recorded):
    t, red = recorded
    assert list(t.devices) == ["/device:TPU:0"]
    names = [s.name for s in t.spans]
    assert names.count("bench.sleep") == names.count("bench.attn") == 3
    assert 0.060 < red.window_s < 0.080


def test_recorded_trace_kernels_and_idle(recorded):
    _, red = recorded
    fwd = red.kernels["flash_fwd"]
    assert len(fwd) == 3 and list(red.kernels) == ["flash_fwd"]
    assert all(o.shapes[1][0] == ("bf16", (1, 2, 256, 128)) for o in fwd)
    # the device ran for well under a millisecond of the ~67 ms window:
    # the matmuls (~91 us each) and three ~4 us kernels
    assert 1e-4 < red.busy_s < 1e-3
    assert red.idle["/device:TPU:0"] > 0.98
    assert red.top_ops[0][0] == "fusion"
    # the three sleeps are the three longest gaps
    assert [label for label, _ in red.idle_gaps[:3]] == ["bench.sleep"] * 3
    assert all(sec > 0.019 for _, sec in red.idle_gaps[:3])
    assert red.exposed_collective == {"/device:TPU:0": 0.0}
