"""Shared by the serving generators: the fixed multiset of requests.

Every seed offers the same work.  The multiset of (prompt length, output
length) pairs is drawn by inverse CDF on a fixed stratified grid
(``u_i = (i + 0.5) / n``), never at random; the seed only permutes the
order, draws the token ids and (open loop) permutes the arrival gaps.  The
requests are dealt into blocks of ``stratify_block`` so that each block
holds one request of every quantile band: the seed shuffles the blocks and
the requests inside a block, and any stretch of a run sees the same mix.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import List, Tuple

import numpy as np

PAIRING_SEED = 0x5EED            # fixed: which output goes with which prompt


def quantile_lengths(spec: dict, n: int) -> List[int]:
    """``n`` lengths at the mid-points of ``n`` equal slices of the
    distribution, ascending.  ``spec``: ``{"dist": "lognormal", "median",
    "sigma", "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``."""
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if spec["dist"] == "lognormal":
            x = spec["median"] * math.exp(
                spec["sigma"] * NormalDist().inv_cdf(u))
        elif spec["dist"] == "uniform":
            x = spec["min"] + u * (spec["max"] - spec["min"])
        else:
            raise ValueError(f"unknown length distribution {spec['dist']!r}")
        out.append(int(min(max(round(x), spec["min"]), spec["max"])))
    return out


def deal(n: int, block: int) -> List[List[int]]:
    """Indices ``0..n-1`` (ascending quantiles) dealt round-robin into
    ``ceil(n / block)`` blocks, so each block spans the whole range."""
    blocks = max(1, math.ceil(n / block))
    return [list(range(b, n, blocks)) for b in range(blocks)]


def request_blocks(params: dict, n: int) -> List[List[Tuple[int, int]]]:
    """The fixed multiset as blocks of (prompt length, output length); the
    same for every seed."""
    prompts = quantile_lengths(params["prompt"], n)
    outputs = quantile_lengths(params["output"], n)
    pair_rng = np.random.default_rng(PAIRING_SEED)
    blocks = []
    for idx in deal(n, int(params.get("stratify_block", 16))):
        outs = [outputs[i] for i in idx]
        pair_rng.shuffle(outs)
        blocks.append([(prompts[i], o) for i, o in zip(idx, outs)])
    return blocks


def shuffled(blocks: List[list], rng: np.random.Generator) -> list:
    """The blocks in a seeded order, each block's items in a seeded order,
    flattened."""
    order = rng.permutation(len(blocks))
    out = []
    for b in order:
        items = list(blocks[b])
        rng.shuffle(items)
        out.extend(items)
    return out
