"""Faults planted under the timed path, for the readings that set the
limits and for the tests that see ``correct`` come out false.  Each is a
context manager that patches the program's module for its duration; none
is used by a benchmark run."""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp


class _Step:
    """Stands in for the jitted train step: ``lower(...).compile()`` gives
    ``wrap(compiled)``."""

    def __init__(self, real, wrap):
        self.real, self.wrap = real, wrap

    def lower(self, *args):
        lowered = self.real.lower(*args)
        wrap = self.wrap

        class _Lowered:
            def compile(self):
                return wrap(lowered.compile())
        return _Lowered()


@contextlib.contextmanager
def _patch_step(wrap):
    from repro.runtime import train_loop
    real = train_loop.jit_train_step
    train_loop.jit_train_step = lambda *a, **k: _Step(real(*a, **k), wrap)
    try:
        yield
    finally:
        train_loop.jit_train_step = real


def half_batch():
    """Half of every batch left out, the mean taken over the rest: the
    second half of the rows is replaced by the first."""
    def wrap(step):
        def run(state, batch):
            def dup(x):
                h = x.shape[0] // 2
                return jnp.concatenate([x[:h], x[:h]] + ([x[2 * h:]]
                                                         if x.shape[0] % 2
                                                         else []))
            return step(state, jax.tree.map(dup, batch))
        return run
    return _patch_step(wrap)


def frozen_state():
    """A step that returns its state unchanged (its metrics are the real
    step's, run on a copy)."""
    def wrap(step):
        def run(state, batch):
            _, metrics = step(jax.tree.map(jnp.copy, state), batch)
            return state, metrics
        return run
    return _patch_step(wrap)


@contextlib.contextmanager
def altered_tokens():
    """Every token the engine samples for its first slot is replaced by the
    next id."""
    from repro.runtime import serve_engine
    real = serve_engine.sample_tokens

    def sample(logits, *args):
        toks = real(logits, *args)
        return toks.at[0].set((toks[0] + 1) % logits.shape[-1])
    serve_engine.sample_tokens = sample
    try:
        yield
    finally:
        serve_engine.sample_tokens = real


@contextlib.contextmanager
def no_stage_exchange():
    """The pipeline's transfer between stages left out: the ring advance
    across the pipe axis (``jnp.roll`` over axis 0 of the stage buffer,
    which XLA lowers to a collective-permute) keeps each stage's own
    output, so a stage never receives its predecessor's activations."""
    from repro.core import pipeline
    real = pipeline.jnp

    class _NoExchange:
        def __getattr__(self, name):
            return getattr(real, name)

        @staticmethod
        def roll(a, shift, axis=None):
            return a if axis == 0 else real.roll(a, shift, axis=axis)

    pipeline.jnp = _NoExchange()
    try:
        yield
    finally:
        pipeline.jnp = real


FAULTS = {"half_batch": half_batch, "frozen_state": frozen_state,
          "altered_tokens": altered_tokens,
          "no_stage_exchange": no_stage_exchange}
