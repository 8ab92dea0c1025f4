"""A backlog: batch work that is all there already.  The generator keeps
``keep_waiting`` or more requests in the engine's waiting queue at all
times, so the engine never runs dry and arrival timing plays no part; a
request falls due at the moment it is handed over.

The multiset of ``documents`` (prompt length, output length) pairs is fixed
(``_lengths.py``) and handed out in the seed's order, again from the start
when it runs out.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from perfbench.kinds import _lengths
from perfbench.kinds.open_loop import Request


class Backlog:
    closed = True

    def __init__(self, params: dict, seed: int, vocab: int, horizon_s: float):
        self.keep = int(params["keep_waiting"])
        self.vocab = int(vocab)
        self._rng = np.random.default_rng(np.random.SeedSequence(
            [int(seed), 0x626C]))
        n = int(params["documents"])
        self._pairs = _lengths.shuffled(
            _lengths.request_blocks(params, n), self._rng)
        self._next = 0

    def next_due(self) -> Optional[float]:
        return None

    def poll(self, now: float, waiting: int) -> List[Request]:
        out = []
        for _ in range(max(0, self.keep - waiting)):
            p, o = self._pairs[self._next % len(self._pairs)]
            self._next += 1
            out.append(Request(now, self._rng.integers(
                0, self.vocab, p, dtype=np.int32), o))
        return out


def make(params: dict, seed: int, vocab: int, horizon_s: float) -> Backlog:
    return Backlog(params, seed, vocab, horizon_s)
