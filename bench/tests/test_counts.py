"""The benchmark's own counts: model FLOPs, kernel work, peaks."""
import json
import pathlib

import pytest

from bench.lib import counts, peaks

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def _config(name: str) -> dict:
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name,global_batch", [("gpt-1.4b-l8", 4),
                                               ("gpt-22b-l4", 8),
                                               ("gpt-1.4b", 4)])
def test_train_step_flops_matches_program_cost_model(name, global_batch):
    """The copy counts what the program's ``costmodel.train_step_flops``
    counts, but for one term: the program bills the layer-stacked
    LayerNorm scales and biases, (n_layers, d) arrays, as matmul weights."""
    from bench.lib.program import model_config
    from repro.core import costmodel

    cfg, seq = _config(name), 2048
    ours = counts.train_step_flops(counts.Dense.from_config(cfg),
                                   global_batch, seq)
    theirs = costmodel.train_step_flops(model_config(cfg), global_batch, seq)
    norm_vectors = 4 * cfg["n_layers"] * cfg["d_model"]
    assert theirs.total - ours == pytest.approx(
        6.0 * global_batch * seq * norm_vectors, rel=1e-9)


def test_train_step_flops_by_hand():
    m = counts.Dense(n_layers=2, d_model=8, n_heads=2, n_kv_heads=2,
                     head_dim=4, d_ff=32, vocab_size=16)
    # per layer: q, k, v, o 4 * 8 * 8, w1 and w2 2 * 8 * 32; head 8 * 16
    assert m.matmul_params == 2 * (256 + 512) + 128
    tokens = 3 * 5
    attn = 3.0 * 4.0 * 3 * 2 * 4 * 2 * 5 * 5
    assert counts.train_step_flops(m, 3, 5) == 6.0 * 1664 * tokens + attn


def test_flash_counts_by_hand():
    b, h, s, hd = 2, 3, 4, 8
    pairs = 10                                    # 4 * 5 / 2
    assert counts.flash_fwd(b, h, s, hd) == (
        4.0 * b * h * pairs * hd, 4.0 * b * h * s * hd * 2 + 4.0 * b * h * s)
    assert counts.flash_bwd_dq(b, h, s, hd) == (
        6.0 * b * h * pairs * hd, 5.0 * b * h * s * hd * 2 + 8.0 * b * h * s)
    assert counts.flash_bwd_dkv(b, h, s, hd) == (
        8.0 * b * h * pairs * hd, 6.0 * b * h * s * hd * 2 + 8.0 * b * h * s)
    # the causal half: a kernel that skips masked blocks cannot beat it
    full = 4.0 * b * h * s * s * hd
    assert counts.flash_fwd(b, h, s, hd)[0] < full


def test_gelu_mlp_in_counts_by_hand():
    assert counts.gelu_mlp_in(16, 128, 512) == (
        2.0 * 16 * 128 * 512, 2.0 * (16 * 128 + 128 * 512 + 16 * 512))


def test_serving_work_by_hand():
    m = counts.Dense(n_layers=1, d_model=4, n_heads=1, n_kv_heads=1,
                     head_dim=4, d_ff=8, vocab_size=10)
    # matmul params: 4 * 16 + 2 * 32 = 128 in the layer, 40 in the head
    flops, nbytes = counts.decode_work(m, n_active=2, kv_tokens=6)
    assert flops == 2.0 * 168 * 2 + 4.0 * 1 * 1 * 4 * 6
    assert nbytes == 2 * 168 + 2 * 1 * 1 * 4 * 2 * 8
    flops, nbytes = counts.prefill_work(m, 3)
    assert flops == 2.0 * 128 * 3 + 4.0 * 4 * 6 + 2.0 * 4 * 10
    assert nbytes == 2 * 168 + 2 * 1 * 1 * 4 * 2 * 3


def test_peaks_table():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.hbm_bytes_per_s) == (197e12, 819e9)
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v99")
    # the larger of the compute and the memory bound
    assert peaks.roofline_seconds(197e12, 1.0, v5e) == pytest.approx(1.0)
    assert peaks.roofline_seconds(1.0, 819e9, v5e) == pytest.approx(1.0)


def test_peaks_agree_with_program_table():
    from repro.core import costmodel
    prog = costmodel.DEVICE_MACHINES["TPU v5 lite"]
    ours = peaks.peaks_for("TPU v5 lite")
    assert set(peaks.PEAKS) == set(costmodel.DEVICE_MACHINES)
    assert ours.bf16_flops == pytest.approx(prog.peak_flops)
    assert ours.hbm_bytes_per_s == pytest.approx(prog.hbm_bw)


def test_every_seed_asks_for_the_same_waves():
    from bench.lib import traffic
    mix = {"kind": "closed_loop", "clients": 8, "size_seed": 3,
           "prompt": {"median": 100, "sigma": 0.8, "min": 4, "max": 400},
           "output": {"median": 20, "sigma": 0.8, "min": 2, "max": 80}}

    def waves(seed, n):
        loop = traffic.ClosedLoop(mix, seed, vocab_size=1000)
        specs = [loop.next() for _ in range(8 * n)]
        return [sorted((len(s.prompt), s.max_new_tokens)
                       for s in specs[8 * w:8 * w + 8]) for w in range(n)]

    assert waves(2**40 + 1, 3) == waves(5, 3)
    a = traffic.ClosedLoop(mix, 5, 1000).next()
    b = traffic.ClosedLoop(mix, 6, 1000).next()
    assert not (len(a.prompt) == len(b.prompt)
                and (a.prompt == b.prompt).all())
