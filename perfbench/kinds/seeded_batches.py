"""Pretraining batches: ``batch`` sequences of ``seq`` token ids, uniform over
the vocabulary, a fresh batch every step from the run's seed.  A job does
not reuse a batch, so neither does the benchmark; every seed offers the same
amount of work (the same shapes), only the ids differ.
"""
from __future__ import annotations

import numpy as np


class Batches:
    def __init__(self, params: dict, seed: int, vocab: int):
        self.batch = int(params["batch"])
        self.seq = int(params["seq"])
        self.vocab = int(vocab)
        self._rng = np.random.default_rng(np.random.SeedSequence(
            [int(seed), 0x7061]))

    @property
    def tokens_per_step(self) -> int:
        return self.batch * self.seq

    def next_batch(self) -> np.ndarray:
        return self._rng.integers(0, self.vocab, (self.batch, self.seq),
                                  dtype=np.int32)


def make(params: dict, seed: int, vocab: int) -> Batches:
    return Batches(params, seed, vocab)
