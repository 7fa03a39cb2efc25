"""Training runner: the program's compiled train step under the cell's
``ParallelPlan``, fed by the program's batch iterator from the benchmark's
own corpus, for a time window.

Set-up builds the state (``init_train_state`` on the plan's mesh, weights
from the seed), compiles the step ahead of time, and drives that same
compiled step through its first three steps on the window's own feed:
after step 1 it reads the first gradient from the optimizer state (Adam's
first moment is (1 - b1) times the clipped gradient), after step 3 the
norm of each weight's change.  The window then runs further steps of the
same object.  After the window the state is freed and the reference runs
the same three steps from the same seed.

Checks: ``loss_gap`` (worst relative gap of the three losses),
``grad_gap`` (worst leaf's gap of the first gradient's norm) and
``change_gap`` (worst leaf's gap of the change after three steps, leaves
whose reference gradient is nought to rounding left out).
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import compare, corpus, counts, reference, traffic
from bench.lib.harness import memory_peak_bytes
from bench.lib.program import model_config

N_CHECKED = 3


def _flat(tree) -> dict:
    return {"/".join(str(getattr(p, "key", p)) for p in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _norms(tree) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in _flat(tree).items()}


def reference_mesh(cell):
    """The cell's chips as one flat mesh for a reference that one chip
    cannot hold; None on one chip."""
    if cell.chips == 1:
        return None
    return reference.flat_mesh(jax.devices()[:cell.chips])


def run(run) -> dict:
    from repro.data import make_batch_iterator
    from repro.launch.mesh import mesh_for_plan
    from repro.models.model import Model
    from repro.optim import AdamWConfig
    from repro.runtime import train_loop

    cell = run.cell
    cfg, mix, wl = cell.config, cell.traffic, cell.workload
    gb, seq = mix["global_batch"], mix["seq_len"]
    mcfg = model_config(cfg)
    plan = train_loop.ParallelPlan(**wl["plan"])
    mesh = mesh_for_plan(plan)
    model = Model(mcfg, jnp.float32 if plan.precision == "fp32"
                  else jnp.bfloat16)
    opt = AdamWConfig(**wl["optimizer"])
    ks = traffic.key_seed(run.seed)
    key = jax.random.PRNGKey(ks)

    marks = {"start": time.perf_counter()}
    # the key is an argument, not a constant, so that a new seed finds the
    # program in the compile cache
    state = jax.jit(
        lambda k: train_loop.init_train_state(model, k, opt, plan, mesh=mesh),
        out_shardings=train_loop.train_state_shardings(model, mesh, plan))(key)
    step = train_loop.jit_train_step(model, opt, plan, mesh, gb, seq)
    data = corpus.Corpus(vocab_size=mcfg.vocab_size,
                         seed=traffic.corpus_seed(run.seed),
                         mean_doc_len=mix["mean_doc_len"],
                         zipf_a=mix["zipf_a"])
    batches = make_batch_iterator(data, seq_len=seq, global_batch=gb)
    batch = next(batches)
    jax.block_until_ready(state)
    marks["state"] = time.perf_counter()
    compiled = step.lower(state, batch).compile()
    marks["compiled"] = time.perf_counter()

    leaf_norms = jax.jit(_norms)

    @jax.jit
    def change_norms(params, k):
        return _norms(jax.tree.map(lambda a, b: a.astype(jnp.float32) - b,
                                   params, model.init(k)))

    # the first steps go through the window's own call and feed
    losses = []
    for i in range(N_CHECKED):
        with run.span("step"):
            state, metrics = compiled(state, batch)
            losses.append(float(metrics["loss"]))
        if i == 0:
            mu = jax.device_get(leaf_norms(state["opt"]["mu"]))
            grad = {k: float(v) / (1.0 - opt.b1) for k, v in mu.items()}
        batch = next(batches)
    change = {k: float(v) for k, v in
              jax.device_get(change_norms(state["params"], key)).items()}
    marks["checked_steps"] = time.perf_counter()

    n_steps = 0
    ends = []
    with run.window():
        t0 = time.perf_counter()
        while True:
            with run.span("step"):
                state, metrics = compiled(state, batch)
                jax.block_until_ready(metrics)
            n_steps += 1
            t1 = time.perf_counter()
            ends.append(t1)
            if t1 - t0 >= run.seconds:
                break
            with run.span("next_batch"):
                batch = next(batches)
    tokens_per_s = n_steps * gb * seq / (t1 - t0)
    finite = bool(np.isfinite(float(metrics["loss"])))
    peak = memory_peak_bytes(jax.devices()[:cell.chips])

    del state, metrics, compiled, step, batches, batch
    gc.collect()

    t_ref = time.perf_counter()
    rows = data.batches(gb, seq, N_CHECKED)
    ref = reference.train_steps(cfg, ks, rows,
                                reference.AdamW.from_dict(wl["optimizer"]),
                                mesh=reference_mesh(cell))
    t_ref = time.perf_counter() - t_ref
    still = compare.still_leaves(ref["raw_grad"])
    limits = wl["limits"]
    checks = [
        {"name": "loss_gap", "value": compare.loss_gap(losses, ref["losses"]),
         "limit": limits["loss_gap"]},
        {"name": "grad_gap", "value": compare.leaf_gap(grad, ref["grad"]),
         "limit": limits["grad_gap"]},
        {"name": "change_gap",
         "value": compare.leaf_gap(change, ref["change"], still),
         "limit": limits["change_gap"]},
    ]
    m = counts.Dense.from_config(cfg)
    return {
        "attempted": n_steps, "failed": 0 if finite else n_steps,
        "e2e": {"train_tokens_per_s": tokens_per_s},
        "counters": {"steps": n_steps, "tokens_per_s": tokens_per_s,
                     "model_flops_per_token":
                         counts.train_step_flops(m, gb, seq) / (gb * seq),
                     "chips": cell.chips},
        "checks": checks, "memory_peak_bytes": peak,
        "info": {"steps": n_steps, "losses": losses,
                 "reference_s": t_ref,
                 "step_walls": np.diff([t0] + ends).tolist(),
                 "setup_phases_s": dict(zip(list(marks)[1:], np.diff(
                     list(marks.values())).tolist())),
                 "reference_losses": ref["losses"],
                 "still_leaves": sorted(still)},
    }
