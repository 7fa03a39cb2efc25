"""Telemetry: analytic FLOPs counter, MFU accounting, schema.

Covers the telemetry PR's acceptance bar:
  * the per-family analytic FLOPs counter (costmodel.train_step_flops)
    agrees with the costmodel's attention pricing *exactly* and with an
    independent spec-tree matmul count exactly; scales linearly in tokens
    and quadratically in seq for attention; forward-only is total/3;
  * every assigned family prices to a positive total with the right
    attn/scan structure (rwkv scan-only, hybrid both, encdec encoder);
  * MFU / step_fields / sanitize_record unit behaviour;
  * Telemetry end-to-end: compile + step records through a JSONL sink,
    schema-validated on re-read; the console line keeps the documented
    pre-telemetry prefix byte-identically;
  * plan-invariance (8 virtual devices): loss and moe_drop recorded by
    telemetry are identical across a dp=4 x tp=2 and a dp=2 x ep=2 x tp=2
    re-plan of the same MoE model — the recorder measures the model, not
    the layout.
"""
import json

import numpy as np
import pytest

from repro.configs import get_config
from repro.core import costmodel as cm
from repro.core import telemetry as tel
from repro.models.common import is_spec
from repro.models.model import Model

REDUCE = dict(d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
              vocab_size=256, head_dim=32)


def _dense_cfg(**kw):
    return get_config("yi-6b").reduced(**{**REDUCE, **kw})


def _matmul_params(subtree) -> float:
    """Independent matmul-param count: every rank>=2 Spec leaf."""
    import jax
    return float(sum(np.prod(s.shape)
                     for s in jax.tree.leaves(subtree, is_leaf=is_spec)
                     if len(s.shape) >= 2))


# ---------------------------------------------------------------------------
# train_step_flops: pricing agreement + scaling
# ---------------------------------------------------------------------------

def test_attn_flops_match_costmodel_pricing_exactly():
    # h * hd == d here, so the counter's 4*T*T_kv*h*hd forward per layer
    # must equal the costmodel's 2*factor*s^2*d per-layer pricing with
    # factor=6 (fwd 2 + bwd 4, remat replay excluded — MFU, not HFU)
    cfg = _dense_cfg()
    B, s = 4, 16
    f = cm.train_step_flops(cfg, B, s)
    d, L = cfg.d_model, cfg.n_layers
    assert cfg.n_heads * cfg.resolved_head_dim == d
    assert f.attn == pytest.approx(2 * 6 * B * s * s * d * L, rel=0, abs=0)


def test_matmul_flops_match_spec_tree_exactly():
    # dense untied model: billed matmul params are exactly the rank>=2
    # leaves of the layer stack + lm_head (+ final_norm has no matmuls);
    # the embed lookup is a gather and must not be billed
    cfg = _dense_cfg()
    assert cfg.family == "dense" and not cfg.tie_embeddings
    specs = Model(cfg).param_specs()
    expected_params = (_matmul_params(specs["layers"])
                       + _matmul_params(specs["lm_head"]))
    B, s = 4, 16
    f = cm.train_step_flops(cfg, B, s)
    assert f.matmul == pytest.approx(6.0 * B * s * expected_params)
    assert f.scan == 0.0
    assert f.tokens == B * s
    assert f.total == f.matmul + f.attn


def test_flops_scaling():
    cfg = _dense_cfg()
    f1 = cm.train_step_flops(cfg, 4, 16)
    # matmul is linear in tokens (batch and seq alike)
    assert cm.train_step_flops(cfg, 8, 16).matmul == pytest.approx(
        2 * f1.matmul)
    assert cm.train_step_flops(cfg, 4, 32).matmul == pytest.approx(
        2 * f1.matmul)
    # attention is quadratic in seq, linear in batch
    assert cm.train_step_flops(cfg, 4, 32).attn == pytest.approx(4 * f1.attn)
    assert cm.train_step_flops(cfg, 8, 16).attn == pytest.approx(2 * f1.attn)
    # forward-only (prefill) is exactly a third of fwd+bwd
    fwd = cm.train_step_flops(cfg, 4, 16, backward=False)
    assert fwd.total == pytest.approx(f1.total / 3.0)


FAMILY_CASES = {
    "dense": ("yi-6b", {}),
    "moe": ("llama4-maverick-400b-a17b", {}),
    "rwkv": ("rwkv6-1.6b", {}),
    "hybrid": ("zamba2-2.7b", dict(n_layers=4, hybrid_attn_every=2)),
    "encdec": ("seamless-m4t-medium", dict(enc_seq_len=16)),
    "vlm": ("internvl2-2b", dict(num_patches=8)),
}


@pytest.mark.parametrize("fam", sorted(FAMILY_CASES))
def test_per_family_flops_structure(fam):
    arch, kw = FAMILY_CASES[fam]
    cfg = get_config(arch).reduced(**{**REDUCE, **kw})
    f = cm.train_step_flops(cfg, 4, 16)
    assert f.total > 0 and f.matmul > 0, fam
    if fam == "rwkv":
        assert f.attn == 0.0 and f.scan > 0.0
    elif fam == "hybrid":
        assert f.attn > 0.0 and f.scan > 0.0
    elif fam in ("dense", "moe", "encdec", "vlm"):
        assert f.attn > 0.0 and f.scan == 0.0
    if fam == "moe":
        # expert leaves billed at the routed top_k/E active fraction:
        # strictly fewer matmul flops than a full-expert count would give
        specs = Model(cfg).param_specs()
        full = 6.0 * 4 * 16 * _matmul_params(specs["layers"])
        assert f.matmul < full


def test_moe_active_fraction_scales_with_top_k():
    cfg = get_config("llama4-maverick-400b-a17b").reduced(**REDUCE)
    import dataclasses
    more = dataclasses.replace(cfg, top_k=min(2, cfg.n_experts))
    if more.top_k > cfg.top_k:
        assert cm.train_step_flops(more, 4, 16).matmul > \
            cm.train_step_flops(cfg, 4, 16).matmul


@pytest.mark.parametrize("fam", ["rwkv", "hybrid"])
def test_scan_flops_counted_once_and_kernels_invariant(fam):
    """The analytic scan term for the attention-free mixers is the closed
    form, counted exactly once — fusing the scans behind kernels=True must
    not change MFU accounting (the counter has no plan/kernels input at
    all, and the scan FLOPs are not double-billed into matmul)."""
    import inspect

    arch, kw = FAMILY_CASES[fam]
    cfg = get_config(arch).reduced(**{**REDUCE, **kw})
    B, s = 4, 16
    f = cm.train_step_flops(cfg, B, s)
    if fam == "rwkv":
        per_tok = 4.0 * cfg.d_model * cfg.resolved_head_dim
    else:
        from repro.models.ssm import d_inner
        per_tok = 6.0 * d_inner(cfg) * max(cfg.ssm_state, 1)
    # mult 3.0 = fwd 1 + bwd 2 (remat replay excluded): exactly once
    assert f.scan == pytest.approx(3.0 * B * s * cfg.n_layers * per_tok)
    assert f.total == f.matmul + f.attn + f.scan
    # invariance by construction: the counter cannot even see the plan
    sig = inspect.signature(cm.train_step_flops)
    assert "plan" not in sig.parameters and "kernels" not in sig.parameters


@pytest.mark.parametrize("fam", ["rwkv", "hybrid"])
def test_scan_telemetry_plan_and_kernels_invariant(fam):
    """MFU and drift telemetry for the scan families measure the model,
    not the execution path: identical flops_per_step / mfu / predicted
    anchor across re-plans and across kernels=True vs the jnp path."""
    from repro.runtime.train_loop import ParallelPlan

    arch, kw = FAMILY_CASES[fam]
    cfg = get_config(arch).reduced(**{**REDUCE, **kw})
    GB, S = 8, 16
    plans = [ParallelPlan(precision="fp32", zero=0),
             ParallelPlan(precision="fp32", zero=0, kernels=True),
             ParallelPlan(dp=2, gas=2, precision="fp32", zero=3,
                          kernels=True)]
    recs = []
    for plan in plans:
        t = tel.Telemetry(cfg, plan, GB, S, machine=cm.TPU_V5E)
        t.step(1, 0.5, {"loss": np.float32(2.0), "loss_scale": 1.0,
                        "grad_norm": np.float32(0.5)})
        recs.append((plan, t.records[-1]))
    (_, r0) = recs[0]
    for plan, r in recs[1:]:
        assert r["flops_per_step"] == r0["flops_per_step"]
        # MFU is per-device: device-normalized utilization is plan-invariant
        assert r["mfu"] * plan.n_devices == pytest.approx(
            r0["mfu"] * plans[0].n_devices)
    # the costmodel's predicted anchor (the drift denominator) ignores the
    # kernels flag: same step-time prediction fused or not
    base, fused = plans[0], plans[1]
    pa = cm.predict_step(cfg, base, GB, S)
    pb = cm.predict_step(cfg, fused, GB, S)
    assert pa.step_time_s == pb.step_time_s
    assert pa.comm_bytes == pb.comm_bytes


# ---------------------------------------------------------------------------
# plan mapping + prediction anchor
# ---------------------------------------------------------------------------

def test_plan_parallel_cfg_reconstructs_global_batch():
    from repro.runtime.train_loop import ParallelPlan
    cfg = _dense_cfg()
    plan = ParallelPlan(dp=2, tp=2, gas=2, precision="fp32", zero=0)
    pc = cm.plan_parallel_cfg(cfg, plan, 8, 16)
    assert pc.mbs == 2 and pc.gbs == 8
    assert pc.n_gpus == plan.n_devices


def test_predict_step_returns_anchor():
    from repro.runtime.train_loop import ParallelPlan
    cfg = _dense_cfg()
    for plan in (ParallelPlan(precision="fp32"),
                 ParallelPlan(dp=2, tp=2, pp=2, gas=4, zero=3,
                              precision="fp32"),
                 ParallelPlan(dp=2, ep=2, tp=2, gas=2, precision="fp32",
                              zero=0)):
        pred = cm.predict_step(cfg, plan, 8, 16)
        assert pred.step_time_s > 0
        assert "total" in pred.comm_bytes
        blk = tel.predicted_block(pred)
        assert blk["step_time_s"] == pred.step_time_s
        assert blk["comm_bytes"]["total"] == pred.comm_bytes["total"]
    assert tel.predicted_block(None) == {}


# ---------------------------------------------------------------------------
# mfu / step_fields / sanitize_record
# ---------------------------------------------------------------------------

def test_mfu():
    assert tel.mfu(600.0, 1.0, 2, 300.0) == pytest.approx(1.0)
    assert tel.mfu(150.0, 1.0, 2, 300.0) == pytest.approx(0.25)
    assert tel.mfu(1.0, 0.0, 2, 300.0) == 0.0


def test_step_fields():
    cfg = _dense_cfg()
    f = tel.step_fields(cfg, 4, 16, wall_s=0.5, n_devices=2)
    flops = cm.train_step_flops(cfg, 4, 16).total
    assert f["tokens_per_s"] == pytest.approx(64 / 0.5)
    assert f["flops_per_step"] == flops
    assert f["tflops_per_device"] == pytest.approx(flops / (0.5 * 2) / 1e12)
    # the CPU has no peak on record: MFU is not measured, no number given
    assert f["mfu"] is None and f["machine"] is None
    # an explicit machine supplies the peak
    g = tel.step_fields(cfg, 4, 16, 0.5, 2, machine=cm.TPU_V5E)
    assert g["machine"] == cm.TPU_V5E.name
    assert g["mfu"] == pytest.approx(
        tel.mfu(flops, 0.5, 2, cm.TPU_V5E.peak_flops))
    assert 0.0 <= g["mfu"] <= 1.0


def test_device_peak_table():
    # one table keyed by device_kind; an unknown kind is an error, not a
    # default peak
    assert cm.machine_for_device_kind("TPU v5 lite") is cm.TPU_V5E
    assert cm.TPU_V5E.peak_flops == 197e12
    with pytest.raises(ValueError, match="no peak rates on record"):
        cm.machine_for_device_kind("TPU v99")
    assert tel.device_machine() is None     # this suite runs on the CPU


def test_sanitize_record():
    rec = {
        "a": np.float32(1.5), "b": np.int64(3), "c": np.array([1.0, 2.0]),
        "traceback": "Traceback (most recent call last): ...",
        "nested": {"traceback": "x", "ok": (1, 2)},
        "obj": object(),
    }
    out = tel.sanitize_record(rec)
    assert out["a"] == 1.5 and isinstance(out["a"], float)
    assert out["b"] == 3 and isinstance(out["b"], int)
    assert out["c"] == [1.0, 2.0]
    assert "traceback" not in out and "traceback" not in out["nested"]
    assert out["nested"]["ok"] == [1, 2]
    assert isinstance(out["obj"], str)
    json.dumps(out)  # JSON-safe by construction


# ---------------------------------------------------------------------------
# Telemetry end-to-end (single device) + schema validation
# ---------------------------------------------------------------------------

def test_telemetry_records_roundtrip(tmp_path):
    from repro.runtime.train_loop import ParallelPlan
    cfg = _dense_cfg()
    plan = ParallelPlan(precision="fp32")
    path = str(tmp_path / "tele.jsonl")
    t = tel.Telemetry(cfg, plan, 4, 16, jsonl=path)
    t.record_compile(None, state_bytes={"params": 1000}, compile_s=1.0)
    for i in range(3):
        t.step(i + 1, 0.25, {"loss": np.float32(2.0), "loss_scale": 1.0,
                             "grad_norm": np.float32(0.5)})
    t.close()
    recs = tel.validate_jsonl(path)
    assert [r["kind"] for r in recs] == ["compile", "step", "step", "step"]
    comp, step = recs[0], recs[1]
    assert comp["state_bytes"] == {"params": 1000}
    assert comp["flops_per_step"] == t.flops.total
    assert comp["kernels_interpret_mode"] == (comp["backend"] == "cpu")
    assert step["tokens"] == 64 and step["grad_norm"] == 0.5
    # the costmodel's prediction rides every step record, next to the
    # measured wall; no measured-over-predicted block is derived from it
    assert step["predicted"] == tel.predicted_block(t.prediction)
    assert "drift" not in step
    # CPU run: no device peak, so MFU is recorded as not measured
    assert comp["machine"] is None and comp["peak_flops"] is None
    assert step["mfu"] is None
    # console line: prefix byte-identical to the pre-telemetry format
    line = t.console_line(step, with_mfu=False)
    assert line == "step     1 loss 2.0000 scale 1 256 tok/s"
    assert t.console_line(step, with_mfu=True).endswith(" mfu not measured")
    # with a device peak the same record carries a utilization
    tv = tel.Telemetry(cfg, plan, 4, 16, machine=cm.TPU_V5E)
    rv = tv.step(1, 0.25, {"loss": np.float32(2.0), "loss_scale": 1.0})
    assert 0.0 <= rv["mfu"] <= 1.0
    assert " mfu " in tv.console_line(rv, with_mfu=True)


def test_validate_record_rejects_bad_records():
    with pytest.raises(ValueError, match="schema"):
        tel.validate_record({"schema": "nope", "kind": "step"})
    with pytest.raises(ValueError, match="unknown record kind"):
        tel.validate_record({"schema": tel.SCHEMA, "kind": "bogus"})
    with pytest.raises(ValueError, match="missing keys"):
        tel.validate_record({"schema": tel.SCHEMA, "kind": "step", "step": 1})


def test_validate_jsonl_requires_steps(tmp_path):
    p = tmp_path / "only_compile.jsonl"
    p.write_text(json.dumps({
        "schema": tel.SCHEMA, "kind": "train", "arch": "x",
        "status": "error"}) + "\n")
    with pytest.raises(ValueError, match="no step or request records"):
        tel.validate_jsonl(str(p))
    assert len(tel.validate_jsonl(str(p), require_step=False)) == 1
    # a serve stream (request records only) is a valid artifact
    q = tmp_path / "serve.jsonl"
    q.write_text(json.dumps({
        "schema": tel.SCHEMA, "kind": "request", "rid": 0, "arch": "x",
        "t_arrival": 0.0, "t_admit": 0.1, "t_first_token": 0.2,
        "t_done": 0.3, "n_prompt": 4, "n_generated": 2,
        "finish_reason": "max_new_tokens", "evictions": 0}) + "\n")
    assert len(tel.validate_jsonl(str(q))) == 1


# ---------------------------------------------------------------------------
# plan invariance on 8 virtual devices: telemetry measures the model,
# not the layout — loss and moe_drop agree across a dp/ep re-plan
# ---------------------------------------------------------------------------

PLAN_INVARIANCE_CODE = r"""
import json
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.core import telemetry as tel
from repro.data import SyntheticCorpus, make_batch_iterator
from repro.launch.mesh import mesh_for_plan
from repro.models.model import Model
from repro.optim import AdamWConfig
from repro.runtime.train_loop import (ParallelPlan, init_train_state,
                                      jit_train_step)

GB, S, STEPS = 8, 32, 2
cfg = get_config("llama4-maverick-400b-a17b").reduced(
    ep=2, d_model=128, n_heads=4, n_kv_heads=2, d_ff=256, vocab_size=256,
    head_dim=32, n_layers=4)
model = Model(cfg, jnp.float32)
opt = AdamWConfig(lr=1e-3)
it = make_batch_iterator(SyntheticCorpus(vocab_size=cfg.vocab_size),
                         seq_len=S, global_batch=GB, prefetch=0)
batches = [next(it) for _ in range(STEPS)]

out = {}
for label, plan in [
    ("dp4", ParallelPlan(dp=4, tp=2, gas=2, precision="fp32", zero=0)),
    ("ep2", ParallelPlan(dp=2, ep=2, tp=2, gas=2, precision="fp32", zero=0)),
]:
    mesh = mesh_for_plan(plan)
    state = init_train_state(model, jax.random.PRNGKey(0), opt, plan)
    step = jit_train_step(model, opt, plan, mesh, GB, S)
    t = tel.Telemetry(cfg, plan, GB, S)
    for i, b in enumerate(batches):
        (state, m), wall = tel.timed_call(step, state, b)
        t.step(i + 1, wall, m)
    out[label] = {
        "loss": [r["loss"] for r in t.records],
        "moe_drop": [r["moe_drop"] for r in t.records],
        "flops": t.flops.total,
    }
print("RESULT " + json.dumps(out))
"""


def test_telemetry_plan_invariance_multidev(multidev):
    stdout = multidev(PLAN_INVARIANCE_CODE, n_devices=8)
    line = next(l for l in stdout.splitlines() if l.startswith("RESULT "))
    out = json.loads(line[len("RESULT "):])
    a, b = out["dp4"], out["ep2"]
    # the analytic FLOPs counter is plan-invariant by construction
    assert a["flops"] == b["flops"]
    for la, lb in zip(a["loss"], b["loss"]):
        assert abs(la - lb) <= 1e-4, (a["loss"], b["loss"])
    for da, db in zip(a["moe_drop"], b["moe_drop"]):
        assert abs(da - db) <= 1e-6, (a["moe_drop"], b["moe_drop"])
