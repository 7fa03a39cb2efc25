"""The one traffic generator: it reads a mix from ``bench/traffic/<name>.json``
and turns it, with the run's seed, into the work of a run.

Seeds change the order and the token content, never the sizes: every seed
draws from the same list of (prompt length, output length) pairs, made
from the mix's own ``size_seed``, a block of ``clients`` pairs at a time in
the seed's order within the block, so that two seeds offer the same work.

Kinds of mix:

* ``batches``: training rows of ``seq_len`` tokens, ``global_batch`` to a
  step, packed from the synthetic corpus (``bench/lib/corpus.py``).
* ``closed_loop``: ``clients`` callers, each of which sends its next
  request as soon as the previous one has finished (zero think time);
  prompt and output lengths are lognormal with the given median and
  sigma, clipped to [min, max]; tokens are uniform over the vocabulary
  but the reserved id 0; every request is greedy (temperature 0).
"""
from __future__ import annotations

import dataclasses

import numpy as np

SIZE_POOL = 4096


def seed_sequence(seed: int, *salt: int) -> np.random.SeedSequence:
    """A numpy seed sequence for any whole-number seed, negative or past
    64 bits included."""
    return np.random.SeedSequence([seed % (1 << 64), *salt])


def key_seed(seed: int) -> int:
    """A 31-bit seed for ``jax.random.PRNGKey``, which keeps only 32 bits
    of what it is given; derived so that seeds differing above bit 32
    still give different keys."""
    return int(seed_sequence(seed, 17).generate_state(1)[0] & 0x7FFFFFFF)


def corpus_seed(seed: int) -> int:
    return int(seed_sequence(seed, 23).generate_state(1)[0])


def _lognormal(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    index: int
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int


class ClosedLoop:
    """An endless, seed-ordered stream of requests for a closed loop."""

    def __init__(self, mix: dict, seed: int, vocab_size: int):
        if mix["kind"] != "closed_loop":
            raise ValueError(f"not a closed-loop mix: {mix['kind']!r}")
        self.clients = int(mix["clients"])
        sizes = np.random.default_rng(mix["size_seed"])
        self.prompt_lens = _lognormal(sizes, mix["prompt"], SIZE_POOL)
        self.output_lens = _lognormal(sizes, mix["output"], SIZE_POOL)
        # the seed orders each block of ``clients`` sizes, and the blocks
        # follow one another: every seed's first wave, and every whole wave
        # after it, asks for the same work
        rng = np.random.default_rng(seed_sequence(seed, 29))
        self.order = np.concatenate([
            start + rng.permutation(min(self.clients, SIZE_POOL - start))
            for start in range(0, SIZE_POOL, self.clients)])
        self.seed = seed
        self.vocab_size = vocab_size
        self.n = 0

    def next(self) -> RequestSpec:
        i = self.n
        j = self.order[i % SIZE_POOL]
        rng = np.random.default_rng(seed_sequence(self.seed, 31, i))
        prompt = rng.integers(1, self.vocab_size, int(self.prompt_lens[j]),
                              dtype=np.int32)
        self.n += 1
        return RequestSpec(i, prompt, int(self.output_lens[j]))

    @property
    def longest(self) -> int:
        """Most positions any request of the mix holds."""
        return int(self.prompt_lens.max() + self.output_lens.max())
