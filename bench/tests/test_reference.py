"""The plain reference against the program's model, kernels off, float32,
at a small size on the CPU: the same weights from the same seed, the same
logits, loss and gradients."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import compare, reference
from bench.tests import tiny

SEED = 1234


@pytest.fixture(scope="module")
def both():
    from bench.lib.program import model_config
    from repro.models.model import Model

    cfg = tiny.CONFIG
    model = Model(model_config(cfg), jnp.float32)
    tokens = np.random.default_rng(0).integers(
        1, cfg["vocab_size"], (2, 48)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        prog = model.init(jax.random.PRNGKey(SEED))
        ref = reference.init_params(cfg, SEED)
        batch = {"tokens": jnp.asarray(tokens)}
        out = {
            "prog_logits": model.logits(prog, batch),
            "ref_logits": reference.logits(ref, jnp.asarray(tokens), cfg),
            "prog_loss": model.loss(prog, batch)[0],
            "ref_loss": reference.mean_loss(ref, jnp.asarray(tokens), cfg),
            "prog_grad": jax.grad(lambda p: model.loss(p, batch)[0])(prog),
            "ref_grad": jax.grad(reference.mean_loss)(
                ref, jnp.asarray(tokens), cfg),
        }
    return cfg, prog, ref, out


def _flat(tree) -> dict:
    return {"/".join(str(getattr(p, "key", p)) for p in path): np.asarray(v)
            for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_same_weights_from_the_seed(both):
    _, prog, ref, _ = both
    flat = _flat(prog)
    assert sorted(flat) == sorted(ref)
    for k, v in ref.items():
        np.testing.assert_allclose(flat[k], np.asarray(v), rtol=1e-6,
                                   atol=0, err_msg=k)


def test_same_logits_and_loss(both):
    _, _, _, out = both
    np.testing.assert_allclose(out["prog_logits"], out["ref_logits"],
                               rtol=1e-4, atol=1e-4)
    assert float(out["prog_loss"]) == pytest.approx(float(out["ref_loss"]),
                                                    rel=1e-5)


def test_same_gradients(both):
    _, _, _, out = both
    g = _flat(out["prog_grad"])
    for k, v in out["ref_grad"].items():
        np.testing.assert_allclose(g[k], np.asarray(v), rtol=1e-3, atol=1e-6,
                                   err_msg=k)


def test_train_steps_match_one_device_when_laid_out():
    """A reference laid out over a mesh gives the numbers of one device."""
    cfg = dict(tiny.CONFIG, n_layers=1)
    rows = [np.random.default_rng(i).integers(1, cfg["vocab_size"], (2, 32))
            .astype(np.int32) for i in range(2)]
    opt = reference.AdamW(lr=1e-3, b1=0.9, b2=0.95, eps=1e-8,
                          weight_decay=0.1, grad_clip=1.0)
    one = reference.train_steps(cfg, SEED, rows, opt)
    laid = reference.train_steps(cfg, SEED, rows, opt,
                                 mesh=reference.flat_mesh(jax.devices()[:1]))
    assert laid["losses"] == pytest.approx(one["losses"], rel=1e-6)
    assert compare.leaf_gap(laid["change"], one["change"]) < 1e-5


def test_control_departs_from_the_reference():
    """Float8 products move the loss and gradients by more than float32
    rounding does."""
    cfg = tiny.CONFIG
    rows = [np.random.default_rng(7).integers(1, cfg["vocab_size"], (2, 32))
            .astype(np.int32)]
    opt = reference.AdamW(lr=1e-3, b1=0.9, b2=0.95, eps=1e-8,
                          weight_decay=0.1, grad_clip=1.0)
    ref = reference.train_steps(cfg, SEED, rows, opt)
    ctl = reference.train_steps(cfg, SEED, rows, opt, reference.FP8)
    assert compare.leaf_gap(ctl["grad"], ref["grad"]) > 1e-3
