"""The traffic generators: every seed offers the same work, in another order."""
import collections

import numpy as np
import pytest

from perfbench.kinds import _lengths, backlog, open_loop, seeded_batches

CHAT = {"rate_rps": 3.5, "gap_cv": 1.0, "stratify_block": 16,
        "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.8,
                   "min": 32, "max": 1024},
        "output": {"dist": "lognormal", "median": 96, "sigma": 0.6,
                   "min": 16, "max": 256}}
DOCS = {"keep_waiting": 32, "documents": 256, "stratify_block": 16,
        "prompt": {"dist": "lognormal", "median": 1024, "sigma": 0.5,
                   "min": 512, "max": 1792},
        "output": {"dist": "lognormal", "median": 64, "sigma": 0.4,
                   "min": 32, "max": 128}}
SEEDS = [0, 1, 7, 2 ** 31 + 11, 3000000019]
HORIZON = 47.0


def multiset(requests):
    return collections.Counter((len(r.prompt), r.out_len) for r in requests)


@pytest.fixture(scope="module")
def chats():
    return {s: open_loop.make(CHAT, s, 50304, HORIZON) for s in SEEDS}


@pytest.mark.parametrize("seed", SEEDS[1:])
def test_open_loop_same_multiset_for_every_seed(chats, seed):
    assert multiset(chats[seed].requests) == multiset(chats[0].requests)


@pytest.mark.parametrize("seed", SEEDS[1:])
def test_open_loop_order_gaps_and_ids_differ_by_seed(chats, seed):
    a, b = chats[0].requests, chats[seed].requests
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert [r.due for r in a] != [r.due for r in b]
    assert not np.array_equal(a[0].prompt[:16], b[0].prompt[:16])


@pytest.mark.parametrize("seed", SEEDS)
def test_open_loop_same_offered_rate_and_monotone_due(chats, seed):
    reqs = chats[seed].requests
    assert len(reqs) == round(CHAT["rate_rps"] * HORIZON)
    due = [r.due for r in reqs]
    assert all(b > a for a, b in zip(due, due[1:]))
    assert due[-1] == pytest.approx(HORIZON)
    # every block of arrivals spans nearly the same time, so the rate
    # offered to any stretch of 32 arrivals is within a third of the mean
    # for every seed (independent exponential gaps stray by over a half)
    spans = [due[i + 32] - due[i] for i in range(len(due) - 32)]
    mean = 32 / CHAT["rate_rps"]
    assert max(abs(s - mean) for s in spans) < 0.33 * mean


def test_open_loop_same_seed_same_inputs():
    a = open_loop.make(CHAT, 5, 50304, HORIZON).requests
    b = open_loop.make(CHAT, 5, 50304, HORIZON).requests
    assert [r.due for r in a] == [r.due for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_open_loop_lengths_follow_the_distribution():
    reqs = open_loop.make(CHAT, 0, 50304, 400.0).requests
    prompts = sorted(len(r.prompt) for r in reqs)
    outs = sorted(r.out_len for r in reqs)
    assert prompts[0] >= 32 and prompts[-1] <= 1024
    assert outs[0] >= 16 and outs[-1] <= 256
    assert prompts[len(prompts) // 2] == pytest.approx(256, rel=0.03)
    assert outs[len(outs) // 2] == pytest.approx(96, rel=0.03)
    assert all(0 <= int(r.prompt.min()) and int(r.prompt.max()) < 50304
               for r in reqs[:20])


def test_open_loop_poll_hands_over_what_is_due():
    src = open_loop.make(CHAT, 3, 50304, HORIZON)
    first = src.next_due()
    assert src.poll(first - 1e-6, 0) == []
    got = src.poll(10.0, 0)
    assert got and all(r.due <= 10.0 for r in got)
    assert src.next_due() > 10.0
    rest = src.poll(1e9, 0)
    assert len(got) + len(rest) == len(src.requests)
    assert src.next_due() is None


@pytest.mark.parametrize("cv", [1.0, 3.0])
def test_gap_quantiles_have_the_asked_mean_and_spread(cv):
    gaps = np.asarray(open_loop.quantile_gaps(4000, cv))
    assert gaps.mean() == pytest.approx(1.0)
    assert gaps.std() / gaps.mean() == pytest.approx(cv, rel=0.12)


@pytest.mark.parametrize("seed", SEEDS)
def test_backlog_never_below_keep_waiting(seed):
    src = backlog.make(DOCS, seed, 50304, 60.0)
    waiting = 0
    rng = np.random.default_rng(seed % 1000)
    for step in range(200):
        waiting += len(src.poll(step * 0.1, waiting))
        assert waiting >= DOCS["keep_waiting"]
        waiting -= int(rng.integers(0, 4))      # the engine admits some
    assert src.next_due() is None


@pytest.mark.parametrize("seed", SEEDS[1:])
def test_backlog_same_documents_for_every_seed(seed):
    def first_cycle(s):
        src = backlog.make(DOCS, s, 50304, 60.0)
        out = []
        while len(out) < DOCS["documents"]:
            out += src.poll(0.0, 0)
        return out[:DOCS["documents"]]
    a, b = first_cycle(0), first_cycle(seed)
    assert multiset(a) == multiset(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    lens = sorted(len(r.prompt) for r in a)
    assert lens[0] >= 512 and lens[-1] <= 1792


def test_blocks_each_span_the_whole_range():
    blocks = _lengths.request_blocks(CHAT, 160)
    assert sum(len(b) for b in blocks) == 160
    for b in blocks:
        prompts = sorted(p for p, _ in b)
        assert prompts[0] < 120 and prompts[-1] > 500


def test_seeded_batches_same_shapes_other_ids():
    p = {"batch": 4, "seq": 64}
    a, b = seeded_batches.make(p, 1, 1000), seeded_batches.make(p, 2, 1000)
    x, y = a.next_batch(), b.next_batch()
    assert x.shape == y.shape == (4, 64) and x.dtype == np.int32
    assert not np.array_equal(x, y)
    assert not np.array_equal(x, a.next_batch())      # fresh every step
    assert np.array_equal(seeded_batches.make(p, 1, 1000).next_batch(), x)
    assert a.tokens_per_step == 256
    assert 0 <= x.min() and x.max() < 1000
