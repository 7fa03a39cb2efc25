"""Unified 3D executor: a ParallelPlan(pp>1) train step on a
("pipe", "data", "model") mesh matches the single-device dp=tp=pp=1 loss
trajectory, ZeRO-1 optimizer-state shardings stay correct under pp>1, and
the HPO bridge emits real 3D plans."""
import pytest

from repro.core import hpo

PLAN_EQUIV_CODE = '''
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models.model import Model
from repro.optim import AdamWConfig
from repro.runtime.train_loop import ParallelPlan, init_train_state, jit_train_step
from repro.launch.mesh import mesh_for_plan, single_device_mesh
from repro.data import SyntheticCorpus, make_batch_iterator

cfg = get_config("yi-6b").reduced(n_layers=4, d_model=128, n_heads=4,
                                  n_kv_heads=2, d_ff=256, vocab_size=256,
                                  head_dim=32)
model = Model(cfg, jnp.float32)
opt = AdamWConfig(lr=1e-3)
it = make_batch_iterator(SyntheticCorpus(vocab_size=cfg.vocab_size),
                         seq_len=32, global_batch=8, prefetch=0)
batches = [next(it) for _ in range(3)]

def run(plan, mesh):
    state = init_train_state(model, jax.random.PRNGKey(0), opt, plan)
    step = jit_train_step(model, opt, plan, mesh, 8, 32)
    losses = []
    for b in batches:
        state, m = step(state, b)
        losses.append(float(m["loss"]))
    return losses, jax.device_get(state["params"]["embed"]), state

ref_losses, ref_embed, _ = run(
    ParallelPlan(gas=1, precision="fp32", zero=0, rules="dp_only"),
    single_device_mesh())

# the acceptance-criteria plan: pp=2 with dp=2 ZeRO-1 and gas=2 microbatches
plan = ParallelPlan(dp=2, tp=1, pp=2, gas=2, precision="fp32", zero=1)
mesh = mesh_for_plan(plan)
assert set(mesh.axis_names) == {"pipe", "data", "model"}
pp_losses, pp_embed, pp_state = run(plan, mesh)
np.testing.assert_allclose(pp_losses, ref_losses, rtol=1e-5, atol=1e-4)
np.testing.assert_allclose(pp_embed, ref_embed, rtol=2e-3, atol=2e-4)

# layer stack sharded over the pipe axis
lspec = jax.tree.leaves(pp_state["params"]["layers"])[0].sharding.spec
assert "pipe" in str(lspec), lspec

# ZeRO-1 under pp>1: optimizer moments sharded over data, and no spec ever
# reuses a mesh axis across two dims (pipe on the stage dim stays intact)
mu_specs = [l.sharding.spec for l in jax.tree.leaves(pp_state["opt"]["mu"])]
assert any("data" in str(s) for s in mu_specs), mu_specs
assert any("pipe" in str(s) and "data" in str(s) for s in mu_specs), mu_specs
for spec in mu_specs:
    flat = [a for e in spec if e is not None
            for a in (e if isinstance(e, tuple) else (e,))]
    assert len(flat) == len(set(flat)), f"mesh axis reused in {spec}"

# interleaved virtual stages: 4 logical stages on 2 pipe ranks
vplan = ParallelPlan(dp=2, tp=1, pp=2, virtual_stages=2, gas=2,
                     precision="fp32")
v_losses, _, _ = run(vplan, mesh_for_plan(vplan))
np.testing.assert_allclose(v_losses, ref_losses, rtol=1e-5, atol=1e-4)

# mixed precision end-to-end under pp>1 (fp16 loss scaling engages)
fplan = ParallelPlan(dp=2, tp=1, pp=2, gas=2, precision="fp16")
state = init_train_state(model, jax.random.PRNGKey(0), opt, fplan)
step = jit_train_step(model, opt, fplan, mesh_for_plan(fplan), 8, 32)
state, m = step(state, batches[0])
assert bool(m["grads_finite"]) and float(m["loss_scale"]) > 1.0
np.testing.assert_allclose(float(m["loss"]), ref_losses[0], rtol=2e-2)
print("PLAN_EQUIV_OK")
'''


def test_parallel_plan_pp_matches_single_device(multidev):
    out = multidev(PLAN_EQUIV_CODE, n_devices=4)
    assert "PLAN_EQUIV_OK" in out


TP_PP_CODE = '''
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models.model import Model
from repro.optim import AdamWConfig
from repro.runtime.train_loop import ParallelPlan, init_train_state, jit_train_step
from repro.launch.mesh import mesh_for_plan, single_device_mesh
from repro.data import SyntheticCorpus, make_batch_iterator

cfg = get_config("yi-6b").reduced(n_layers=4, d_model=128, n_heads=4,
                                  n_kv_heads=2, d_ff=256, vocab_size=256,
                                  head_dim=32)
model = Model(cfg, jnp.float32)
opt = AdamWConfig(lr=1e-3)
it = make_batch_iterator(SyntheticCorpus(vocab_size=cfg.vocab_size),
                         seq_len=32, global_batch=8, prefetch=0)
batches = [next(it) for _ in range(2)]

def run(plan, mesh):
    state = init_train_state(model, jax.random.PRNGKey(0), opt, plan)
    step = jit_train_step(model, opt, plan, mesh, 8, 32)
    losses = []
    for b in batches:
        state, m = step(state, b)
        losses.append(float(m["loss"]))
    return losses

# full 3D point: pp=2 x dp=2 x tp=2 on 8 devices, megatron TP + ZeRO-1
losses = run(ParallelPlan(dp=2, tp=2, pp=2, gas=4, precision="fp32"),
             mesh_for_plan(ParallelPlan(dp=2, tp=2, pp=2)))
ref = run(ParallelPlan(gas=1, precision="fp32", zero=0, rules="dp_only"),
          single_device_mesh())
np.testing.assert_allclose(losses, ref, rtol=1e-5, atol=1e-4)
print("TP_PP_OK")
'''


def test_parallel_plan_3d_tp_pp(multidev):
    out = multidev(TP_PP_CODE, n_devices=8)
    assert "TP_PP_OK" in out


def test_trial_plan_bridges_search_space_to_real_plans():
    plan = hpo.trial_plan({"pp": 4, "tp": 8, "mbs": 8, "gas": 10,
                           "zero": 1, "nnodes": 16})
    assert plan is not None
    assert (plan.pp, plan.tp, plan.dp) == (4, 8, 4)  # 16*8 / (4*8) = 4
    assert plan.gas == 10 and plan.zero == 1
    assert plan.n_devices == 16 * 8

    # untileable config -> None (penalized as the paper's F-objective failure)
    assert hpo.trial_plan({"pp": 12, "tp": 8, "nnodes": 16}) is None


def test_plan_objective_penalizes_untileable():
    seen = []

    def score(plan, cfg):
        seen.append(plan)
        return 40.0

    obj = hpo.plan_objective(score)
    assert obj({"pp": 2, "tp": 4, "gas": 5, "zero": 0, "nnodes": 16}) == 40.0
    assert obj({"pp": 12, "tp": 8, "nnodes": 16}) == -1.0
    assert len(seen) == 1 and seen[0].pp == 2


def test_parallel_plan_validation():
    from repro.runtime.train_loop import ParallelPlan

    with pytest.raises(ValueError):
        ParallelPlan(pp=0)
    with pytest.raises(ValueError):
        ParallelPlan(gas=-1)
    p = ParallelPlan(dp=2, tp=4, pp=2, virtual_stages=3)
    assert p.n_devices == 16 and p.n_stages == 6
    # pp>1 plans route "layers" onto the pipe axis; pp==1 plans do not
    assert p.sharding_rules().mesh_axis("layers") == "pipe"
    assert ParallelPlan().sharding_rules().mesh_axis("layers") is None


SHARDED_INIT_CODE = '''
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models.model import Model
from repro.optim import AdamWConfig
from repro.runtime.train_loop import (ParallelPlan, init_train_state,
                                      train_state_shardings)
from repro.launch.mesh import mesh_for_plan

cfg = get_config("yi-6b").reduced(n_layers=4, d_model=128, n_heads=4,
                                  n_kv_heads=2, d_ff=256, vocab_size=256,
                                  head_dim=32)
model = Model(cfg, jnp.float32)
opt = AdamWConfig(lr=1e-3)
plan = ParallelPlan(tp=2, pp=2, gas=2, precision="fp32")
mesh = mesh_for_plan(plan)
eager = init_train_state(model, jax.random.PRNGKey(0), opt, plan)
sharded = init_train_state(model, jax.random.PRNGKey(0), opt, plan, mesh=mesh)
want = train_state_shardings(model, mesh, plan)
for a, b, sh in zip(jax.tree.leaves(eager), jax.tree.leaves(sharded),
                    jax.tree.leaves(want)):
    assert b.sharding.is_equivalent_to(sh, b.ndim), (b.sharding, sh)
    # jit may fuse the init's scale into the sampler: equal to fp32 rounding
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                               atol=0)
# the stacked layer weights really are split over all four devices
qkv = jax.tree.leaves(sharded["params"]["layers"])
assert max(len({str(s.index) for s in x.addressable_shards}) for x in qkv) == 4
print("SHARDED_INIT_OK")
'''


def test_init_train_state_builds_sharded_under_mesh(multidev):
    """init_train_state(mesh=) returns the eager init's values already in
    the plan's shardings (no device holds the whole unsharded state)."""
    out = multidev(SHARDED_INIT_CODE, n_devices=4)
    assert "SHARDED_INIT_OK" in out
