"""Open loop: independent users.  Requests fall due on a schedule fixed
before the run, whatever the system does, so a slow system builds a queue.

Parameters: ``rate_rps`` (mean arrivals a second), ``gap_cv`` (1 = Poisson:
exponential gaps; above 1 = bursty, gamma gaps with that coefficient of
variation at the same mean), ``prompt`` / ``output`` length distributions,
``stratify_block``.  The gaps are stratified like the lengths: the inverse
CDF on a fixed grid, dealt into the same blocks, permuted by the seed and
scaled so that they sum to the horizon — every seed offers the same number
of requests and the same rate over the run, in another order.
"""
from __future__ import annotations

import math
from typing import List, Optional

import numpy as np

from perfbench.kinds import _lengths


class Request:
    __slots__ = ("due", "prompt", "out_len")

    def __init__(self, due: float, prompt: np.ndarray, out_len: int):
        self.due, self.prompt, self.out_len = due, prompt, out_len


def quantile_gaps(n: int, cv: float) -> List[float]:
    """``n`` gaps of mean 1 at the mid-points of ``n`` equal slices of the
    gap distribution, ascending."""
    us = [(i + 0.5) / n for i in range(n)]
    if abs(cv - 1.0) < 1e-9:
        gaps = [-math.log(1.0 - u) for u in us]
    else:
        from scipy.stats import gamma
        shape = 1.0 / (cv * cv)
        gaps = [float(g) for g in gamma.ppf(us, shape, scale=1.0 / shape)]
    mean = sum(gaps) / n
    return [g / mean for g in gaps]


class OpenLoop:
    closed = False

    def __init__(self, params: dict, seed: int, vocab: int, horizon_s: float):
        rate = float(params["rate_rps"])
        n = max(1, int(round(rate * horizon_s)))
        rng = np.random.default_rng(np.random.SeedSequence(
            [int(seed), 0x6F70]))
        pairs = _lengths.shuffled(_lengths.request_blocks(params, n), rng)
        gaps = quantile_gaps(n, float(params.get("gap_cv", 1.0)))
        gap_blocks = [[gaps[i] for i in idx] for idx in _lengths.deal(
            n, int(params.get("stratify_block", 16)))]
        gaps = _lengths.shuffled(gap_blocks, rng)
        scale = horizon_s / sum(gaps)
        due, t = [], 0.0
        for g in gaps:
            t += g * scale
            due.append(t)
        # the first request falls due after its gap, the last at the horizon
        self.requests = [
            Request(d, rng.integers(0, vocab, p, dtype=np.int32), o)
            for d, (p, o) in zip(due, pairs)]
        self.horizon_s = horizon_s
        self._next = 0

    def next_due(self) -> Optional[float]:
        if self._next < len(self.requests):
            return self.requests[self._next].due
        return None

    def poll(self, now: float, waiting: int) -> List[Request]:
        out = []
        while (self._next < len(self.requests)
               and self.requests[self._next].due <= now):
            out.append(self.requests[self._next])
            self._next += 1
        return out


def make(params: dict, seed: int, vocab: int, horizon_s: float) -> OpenLoop:
    return OpenLoop(params, seed, vocab, horizon_s)
