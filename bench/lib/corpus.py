"""The benchmark's copy of the synthetic token corpus.

A Zipf-distributed, Markov-flavoured token stream, deterministic in
(seed, document index), packed into fixed-length rows with EOS separators:
the same stream as the program's ``data/synthetic.SyntheticCorpus``, kept
here so that the reference regenerates every row it checks without taking
anything from the program.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# row i of every batch packs documents from 10_000 * i onwards, as the
# program's host-sharded batch iterator lays rows out on one host
ROW_DOC_STRIDE = 10_000


@dataclasses.dataclass(frozen=True)
class Corpus:
    vocab_size: int
    seed: int
    mean_doc_len: int = 512
    zipf_a: float = 1.2
    eos_id: int = 0

    def document(self, idx: int) -> np.ndarray:
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, idx]))
        length = max(8, int(rng.poisson(self.mean_doc_len)))
        base = rng.zipf(self.zipf_a, size=length).astype(np.int64)
        base = (base - 1) % max(self.vocab_size - 2, 1) + 1
        toks = base.copy()
        flips = rng.random(length) < 0.5
        for i in range(1, length):
            if flips[i]:
                toks[i] = (toks[i - 1] * 7 + 3) % (self.vocab_size - 1) + 1
        return toks.astype(np.int32)

    def packed_sequences(self, seq_len: int, start_doc: int = 0):
        """Endless fixed-length rows of documents from ``start_doc`` on,
        each document followed by EOS: the stream the program's batch
        iterator packs a row from."""
        buf: list[int] = []
        doc = start_doc
        while True:
            while len(buf) < seq_len:
                buf.extend(self.document(doc).tolist())
                buf.append(self.eos_id)
                doc += 1
            yield np.asarray(buf[:seq_len], np.int32)
            buf = buf[seq_len:]

    def rows(self, row: int, seq_len: int, n: int) -> list[np.ndarray]:
        """The first ``n`` packed sequences of batch row ``row``."""
        it = self.packed_sequences(seq_len, ROW_DOC_STRIDE * row)
        return [next(it) for _ in range(n)]

    def batches(self, global_batch: int, seq_len: int,
                n: int) -> list[np.ndarray]:
        """The first ``n`` global batches, each (global_batch, seq_len)."""
        per_row = [self.rows(r, seq_len, n) for r in range(global_batch)]
        return [np.stack([per_row[r][s] for r in range(global_batch)])
                for s in range(n)]
