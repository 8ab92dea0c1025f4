"""The serving engine launches unit n+1 before it reads unit n (ISSUE 36):
a decode row takes its id from the previous program's output on the device.
Here, on the CPU at tiny sizes: tokens against the dense reference with the
run-ahead engaged, one unit's events a call, and every way a unit in flight
can meet something the host did not foresee — an end-of-sequence token, a
cancel, a deadline, a non-finite page, a raising step, a pool too small, a
window block let go for a unit that is dropped, drain / spill / resume /
defrag / stop, a hung step — and that where an id comes from is data of the
one decode program.  Engine-level cases run once a family of
``serving_families.py``; the cases that need a window kind of layer build a
tiny MiMo-V2-Flash."""
import numpy as np
import pytest
from serving_families import (dense_continuation, dense_forward,  # noqa: F401
                              family, tiny_model)

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.inference import PagedKVCache, ServingEngine
from paddle_tpu.inference.engine import pack_step_inputs
from paddle_tpu.inference.kv_cache import WindowLayer
from paddle_tpu.inference.scheduler import (ContinuousBatchingScheduler,
                                            SequenceState)
from paddle_tpu.observability.compilation import CompileTracker
from paddle_tpu.observability.registry import MetricsRegistry
from paddle_tpu.testing import faults

pytestmark = pytest.mark.serving

PROMPTS = ([1, 2, 3], [4, 5], [6, 7, 8, 9, 10, 11, 12, 13, 14], [15])


def engine(model=None, **kw):
    kw.setdefault("registry", MetricsRegistry())
    kw.setdefault("max_seqs", 4)
    kw.setdefault("kv_block_size", 4)
    return ServingEngine(model or tiny_model(), **kw)


def assert_no_block_aliasing(cache):
    for kind, pool in cache.pools.items():
        seen = {}
        owned = [(sid, b) for sid in cache.live_seqs()
                 for b in cache.table(sid, kind)]
        owned += [(f"held:{sid}", b) for sid, bs in pool.held for b in bs]
        for sid, b in owned:
            assert b not in seen, \
                f"{kind} block {b} aliased by {sid} and {seen[b]}"
            seen[b] = sid


def units(eng):
    """Units landed so far, by the counters the harness classifies a call
    by."""
    reg = eng._reg()
    return (reg.counter("serve.prefills").value
            + reg.counter("serve.decode_steps").value)


def step_all(eng, check=None):
    """Drive the engine dry; the events a call, after ``check(engine)``."""
    calls = []
    while eng.has_work():
        before = units(eng)
        events = eng.step()
        assert units(eng) - before <= 1       # one unit's events a call
        if check is not None:
            check(eng)
        calls.append(events)
    return calls


# ---------------------------------------------------------------------------
# (a) tokens, and what a call returns
# ---------------------------------------------------------------------------
@pytest.mark.usefixtures("family")
class TestTokensAndCalls:
    def test_greedy_tokens_are_the_dense_references_with_units_ahead(self):
        model = tiny_model()
        new = (6, 3, 5, 7)
        want = [dense_continuation(model, p, n)
                for p, n in zip(PROMPTS, new)]
        eng = engine(model, capture_logits=True)
        rids = [eng.submit(p, max_new_tokens=n)
                for p, n in zip(PROMPTS, new)]
        calls = step_all(eng)
        for rid, p, w in zip(rids, PROMPTS, want):
            got = eng.collect(rid)
            assert got["tokens"] == w
            ref = dense_forward(model)(p + got["tokens"])
            for i, row in enumerate(got["logits"]):
                np.testing.assert_allclose(row, ref[len(p) - 1 + i],
                                           atol=1e-4)
        ahead = eng.stats()["ahead"]
        # 4 prefills and the decode units of the longest request but one
        # (its first token is the prefill's); every unit but the first was
        # launched while another was in flight
        assert ahead["units_launched"] == len(calls) == 4 + max(new) - 1
        assert ahead["units_ahead"] == ahead["units_launched"] - 1
        assert ahead["ahead_rows_discarded"] == 0
        assert ahead["ahead_units_dropped"] == 0
        assert ahead["ahead_breaks"] == {"idle": 1, "preempt": 0,
                                         "fault": 0, "drain": 0}
        snap = eng._reg().snapshot()
        assert snap["serve.units_ahead"]["value"] == ahead["units_ahead"]
        assert snap["serve.units_launched"]["value"] == len(calls)
        assert snap["serve.ahead_breaks.idle"]["value"] == 1
        # a call's events are one unit's: a prefill's one first token, or
        # a decode batch's one token a row
        for events in calls:
            ids = [e["request_id"] for e in events]
            assert len(ids) == len(set(ids)) and 1 <= len(ids) <= 4
        assert [len(c) for c in calls[:4]] == [1, 1, 1, 1]

    def test_has_work_while_a_unit_is_in_flight_and_tokens_in_order(self):
        eng = engine()
        got = []
        rid = eng.submit([1, 2, 3], max_new_tokens=4,
                         on_token=lambda r, t, fin: got.append((t, fin)))
        eng.step()                     # launches two, lands the prefill
        assert eng._in_flight is not None and eng._in_flight.kind == "decode"
        seq = eng.sched.running[0]
        # the landed token is the host's; the unit in flight feeds it and
        # samples the next, which the host has not read
        assert len(seq.output) == 1 and seq.pending == seq.output[0]
        assert seq.in_flight == 1 and seq.computed_len == 3 + 1
        while eng.has_work():
            eng.step()
        assert eng._in_flight is None and not eng.sched.has_work()
        assert eng.drain_callbacks(timeout=10.0)
        tokens = eng.collect(rid)["tokens"]
        assert [t for t, _ in got] == tokens and len(tokens) == 4
        assert [fin for _, fin in got] == [False, False, False, True]


# ---------------------------------------------------------------------------
# (b), (c) a row that ends while the next unit holds it
# ---------------------------------------------------------------------------
@pytest.mark.usefixtures("family")
class TestARowEndsInFlight:
    def test_an_end_of_sequence_token_discards_the_row_computed_ahead(self):
        model = tiny_model()
        want = dense_continuation(model, [1, 2, 3], 6)
        other = dense_continuation(model, [4, 5], 6)
        # ends on its third token: the decode unit launched before that
        # token was read holds the row once more
        eos = want[2]
        stop = want.index(eos) + 1
        eng = engine(model)
        rid = eng.submit([1, 2, 3], max_new_tokens=6, eos_token_id=eos)
        calls = [eng.step()]           # its prefill lands, a decode is out
        peer = eng.submit([4, 5], max_new_tokens=6)
        calls += step_all(eng)
        out = eng.collect(rid)
        assert out["finish_reason"] == "eos" and out["tokens"] == want[:stop]
        assert eng.collect(peer)["tokens"] == other
        mine = [e for events in calls for e in events
                if e["request_id"] == rid]
        assert len(mine) == stop and mine[-1]["finished"]   # none after it
        ahead = eng.stats()["ahead"]
        assert ahead["ahead_rows_discarded"] == 1
        assert eng._reg().counter("serve.ahead_rows_discarded").value == 1
        report = eng.cache.leak_report()
        assert report["num_used"] == 0 and report["balanced"]
        assert report["leaked_blocks"] == 0

    @pytest.mark.parametrize("how", ["cancel", "deadline"])
    def test_a_cancel_or_a_deadline_strikes_a_row_in_flight(self, how):
        model = tiny_model()
        other = dense_continuation(model, [4, 5], 8)
        clk = faults.expire_clock()
        eng = engine(model, clock=clk)
        doomed = eng.submit([1, 2, 3], max_new_tokens=20,
                            deadline_ms=50.0 if how == "deadline" else None)
        peer = eng.submit([4, 5], max_new_tokens=8)
        for _ in range(4):             # two prefills, two decode units
            eng.step()
        assert doomed in [s.request_id for s in eng._in_flight.seqs]
        had = len(eng.sched.running[0].output)
        if how == "cancel":
            assert eng.cancel(doomed)
        else:
            clk.advance(1.0)
        events = eng.step()
        # the reaper's terminal event, then the landed unit's tokens: none
        # of them the doomed row's
        mine = [e for e in events if e["request_id"] == doomed]
        assert mine == [{"request_id": doomed, "token": None,
                         "finished": True,
                         "reason": "cancelled" if how == "cancel"
                         else "deadline"}]
        assert [e["request_id"] for e in events
                if e["token"] is not None] == [peer]
        calls = step_all(eng)
        assert not [e for c in calls for e in c if e["request_id"] == doomed]
        out = eng.collect(doomed)
        assert out["finish_reason"] == mine[0]["reason"]
        assert len(out["tokens"]) == had
        assert eng.collect(peer)["tokens"] == other
        assert eng.stats()["ahead"]["ahead_rows_discarded"] == 1
        report = eng.cache.leak_report()
        assert report["num_used"] == 0 and report["balanced"]


# ---------------------------------------------------------------------------
# (d) a landing that raises with the next unit in flight
# ---------------------------------------------------------------------------
@pytest.mark.usefixtures("family")
class TestAFaultWithAUnitInFlight:
    @pytest.mark.parametrize("route", ["page", "raise", "nan"])
    def test_the_unit_launched_after_it_is_dropped_and_survivors_exact(
            self, route):
        model = tiny_model()
        want = [dense_continuation(model, p, 6) for p in PROMPTS]
        hook = {"page": None,
                "raise": faults.poison_request(1, mode="raise",
                                               kinds=("decode",)),
                "nan": faults.poison_request(1, mode="nan",
                                             kinds=("decode",))}[route]
        eng = engine(model, nan_guard=route != "raise", step_fault=hook)
        rids = [eng.submit(p, max_new_tokens=6) for p in PROMPTS]
        for _ in rids:
            eng.step()                 # the four prefills land
        assert eng._in_flight.kind == "decode"
        if route == "page":
            # the decode unit in flight has consumed the pool: the NaN
            # meets the unit launched after it
            block = eng.cache.table(rids[1])[0]
            pages = [list(layer) for layer in eng.cache.pages]
            pages[0][0] = pages[0][0].at[block].set(np.nan)
            eng.cache.update_pages(pages)
        step_all(eng)
        assert list(eng.quarantined) == [rids[1]]
        assert eng.collect(rids[1])["finish_reason"] == "poisoned"
        for i in (0, 2, 3):
            assert eng.collect(rids[i])["tokens"] == want[i]
        ahead = eng.stats()["ahead"]
        assert ahead["ahead_units_dropped"] == 1
        assert ahead["ahead_breaks"]["fault"] == 1
        assert eng._reg().counter("serve.ahead_units_dropped").value == 1
        # every sequence's marks were taken back and moved again: nothing
        # is in flight, nothing is held
        assert eng._in_flight is None
        assert all(s.in_flight == 0 for s in eng.sched.finished.values())
        report = eng.cache.leak_report()
        assert report["num_used"] == 0 and report["balanced"]

    def test_a_dropped_prefill_goes_back_to_the_head_of_the_queue(self):
        """The unit launched after the faulting decode unit is a prefill:
        its sequence was admitted for nothing, and is admitted again."""
        model = tiny_model()
        prompts = [[1, 2, 3], [4, 5], [6, 7, 8]]
        want = [dense_continuation(model, p, 5) for p in prompts]
        inj = faults.poison_request(0, mode="raise", kinds=("decode",))
        eng = engine(model, step_fault=inj)
        first = eng.submit(prompts[0], max_new_tokens=5)
        eng.step()                     # its prefill lands, a decode is out
        late = [eng.submit(p, max_new_tokens=5) for p in prompts[1:]]
        eng.step()         # launches late[0]'s prefill; the decode faults
        assert list(eng.quarantined) == [first]
        assert eng._in_flight is None
        assert [s.request_id for s in eng.sched.waiting] == late
        assert eng.sched.preemptions == 0 and eng.cache.blocks_used() == 0
        step_all(eng)
        for rid, w in zip(late, want[1:]):
            assert eng.collect(rid)["tokens"] == w
            assert eng.collect(rid)["preemptions"] == 0
        assert eng.stats()["ahead"]["ahead_units_dropped"] == 1

    def test_a_sampling_engine_replays_under_the_units_own_number(self):
        kw = dict(temperature=0.8, seed=9)
        want = engine(**kw).generate(PROMPTS, max_new_tokens=6)
        inj = faults.poison_request(3, mode="raise", kinds=("decode",))
        eng = engine(step_fault=inj, **kw)
        rids = [eng.submit(p, max_new_tokens=6) for p in PROMPTS]
        step_all(eng)
        assert list(eng.quarantined) == [rids[3]]
        # the dropped unit's number was given to the unit launched in its
        # place, so the rows that kept their places drew the same noise
        for rid, tokens in zip(rids[:3], want):
            assert eng.collect(rid)["tokens"] == tokens


# ---------------------------------------------------------------------------
# (e) a pool so small that growing preempts
# ---------------------------------------------------------------------------
@pytest.mark.usefixtures("family")
class TestATightPool:
    def test_running_ahead_breaks_where_a_plan_would_preempt(self):
        model = tiny_model()
        prompts = [[1, 2, 3, 4], [5, 6, 7], [8, 9], [10, 11, 12, 13, 14]]
        want = [dense_continuation(model, p, 6) for p in prompts]
        eng = engine(model, num_kv_blocks=5)
        rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        step_all(eng, check=lambda e: assert_no_block_aliasing(e.cache))
        assert eng.sched.preemptions > 0
        ahead = eng.stats()["ahead"]
        # a victim is only ever chosen with nothing in flight
        assert ahead["ahead_breaks"]["preempt"] >= eng.sched.preemptions
        assert ahead["units_ahead"] > 0
        for rid, w in zip(rids, want):
            assert eng.collect(rid)["tokens"] == w
        assert eng.cache.allocator.num_used == 0


# ---------------------------------------------------------------------------
# (f) the window kind: blocks let go behind a row for a unit that is dropped
# ---------------------------------------------------------------------------
def mimo_engine(**kw):
    from paddle_tpu.models.mimo_v2 import MimoV2ForCausalLM, mimo_v2_tiny
    pt.seed(35)
    model = MimoV2ForCausalLM(mimo_v2_tiny(initializer_range=0.2))
    kw.setdefault("max_model_len", 96)
    return model, engine(model, capture_logits=True, **kw)


class TestWindowBlocksHeldBack:
    def cache(self, window_blocks=6):
        # a window of 6 in blocks of 4: a ring of 3
        return PagedKVCache([((1, 4),), WindowLayer(((1, 4),), 6)],
                            {"full": 16, "window": window_blocks},
                            block_size=4)

    def test_a_held_block_is_not_free_until_the_unit_has_landed(self):
        c = self.cache()
        pool = c.pools["window"]
        assert c.ensure_capacity("a", 10)       # tokens 4..9: blocks 1, 2
        first = list(pool.tables["a"])
        assert pool.first["a"] == 1 and len(first) == 2
        used = pool.allocator.num_used
        # 14 tokens: a query at 13 reaches back to 8, so block 1 goes
        # and block 3 comes
        assert c.ensure_capacity("a", 14, hold=True)
        assert pool.held == [("a", first[:1])]
        assert pool.tables["a"][0] == first[1] and pool.first["a"] == 2
        assert pool.allocator.num_used == used + 1 and pool.freed_behind == 0
        report = c.leak_report()
        assert report["leaked_blocks"] == 0 and report["balanced"]
        c.release_held()
        assert not pool.held and pool.freed_behind == 1
        assert pool.allocator.num_used == used
        assert c.leak_report()["leaked_blocks"] == 0

    def test_restoring_puts_it_back_and_lets_go_of_what_grew_ahead(self):
        c = self.cache()
        pool = c.pools["window"]
        assert c.ensure_capacity("a", 10)
        before = (list(pool.tables["a"]), pool.first["a"],
                  pool.allocator.num_used)
        assert c.ensure_capacity("a", 14, hold=True)
        assert c.ensure_capacity("a", 18, hold=True)     # another goes
        assert len(pool.held) == 2
        c.restore_held()
        # the ring is 3 wide: both come back, and the two blocks grown
        # for the units that are dropped are given up for them
        assert pool.first["a"] == before[1] and not pool.held
        assert pool.tables["a"][:2] == before[0]
        assert len(pool.tables["a"]) == pool.table_width == 3
        report = c.leak_report()
        assert report["leaked_blocks"] == 0 and report["balanced"]
        assert pool.freed_behind == 0
        c.free_seq("a")
        assert c.blocks_used() == 0

    def test_a_sequence_that_left_frees_its_held_blocks_either_way(self):
        for way in ("release_held", "restore_held"):
            c = self.cache()
            assert c.ensure_capacity("a", 10)
            assert c.ensure_capacity("a", 14, hold=True)
            c.free_seq("a")
            getattr(c, way)()
            assert c.blocks_used() == 0
            report = c.leak_report()
            assert report["leaked_blocks"] == 0 and report["balanced"]

    def test_a_pool_short_of_the_held_block_makes_the_plan_wait(self):
        """A window of 5 in blocks of 4, two window blocks in all: the
        ninth token lets go of block 0 and needs block 2 in one plan.  The
        block that would be free is held back, so the table cannot grow
        ahead of the landing."""
        c = PagedKVCache([((1, 4),), WindowLayer(((1, 4),), 5)],
                         {"full": 16, "window": 2}, block_size=4)
        pool = c.pools["window"]
        sch = ContinuousBatchingScheduler(c, 2, 32)
        seq = SequenceState("a", list(range(1, 9)), max_new_tokens=4)
        sch.submit(seq)
        assert sch.schedule().kind == "prefill"          # tokens 0..7
        sch.mark_launched("prefill", [seq], [True])
        seq.output, seq.pending, seq.in_flight = [7], 7, 0
        assert sch.schedule(ahead=True).kind == "wait"
        assert seq in sch.running and sch.preemptions == 0
        assert len(pool.held) == 1 and pool.allocator.num_free == 0
        c.release_held()                                 # the unit landed
        plan = sch.schedule()
        assert plan.kind == "decode" and plan.seqs == [seq]
        assert pool.first["a"] == 1 and len(pool.tables["a"]) == 2

    def test_a_row_crosses_a_block_boundary_in_a_unit_that_is_dropped(self):
        """A window of 8 in blocks of 4.  The survivor's prompt of 10
        tokens puts its first decode unit at position 10 (it attends
        3..10: block 0) and the next at 11 (4..11: block 0 is let go).
        The first unit faults with the second in flight: the replay finds
        block 0 where it was, and the logits are the plain forward's."""
        model, clean = mimo_engine()
        rng = np.random.default_rng(36)
        prompts = [rng.integers(0, 96, n).tolist() for n in (10, 7)]
        want = [clean.collect(clean.submit(p, max_new_tokens=8))
                for p in prompts]
        inj = faults.poison_request(1, mode="raise", kinds=("decode",))
        _, eng = mimo_engine(step_fault=inj)
        pool = eng.cache.pools["window"]
        seen = []
        restore = eng.cache.restore_held

        def spy():
            seen.append([(sid, list(b)) for sid, b in pool.held])
            restore()
            seen.append((list(pool.tables[rids[0]]), pool.first[rids[0]]))
        eng.cache.restore_held = spy
        rids = [eng.submit(p, max_new_tokens=8) for p in prompts]
        eng.step()
        eng.step()                      # both prefills landed
        block0 = pool.tables[rids[0]][0]
        assert pool.first[rids[0]] == 0
        step_all(eng, check=lambda e: assert_no_block_aliasing(e.cache))
        assert list(eng.quarantined) == [rids[1]]
        assert eng.stats()["ahead"]["ahead_units_dropped"] == 1
        # the plan of the dropped unit had let go of the survivor's block
        # 0; it came back to the front of the table for the replay
        assert seen[0] == [(rids[0], [block0])]
        assert seen[1][0][0] == block0 and seen[1][1] == 0
        got = eng.collect(rids[0])
        assert got["tokens"] == want[0]["tokens"]
        np.testing.assert_allclose(np.stack(got["logits"]),
                                   np.stack(want[0]["logits"]), atol=2e-5)
        assert pool.freed_behind >= 1 and not pool.held
        report = eng.cache.leak_report()
        assert report["leaked_blocks"] == 0 and report["balanced"]
        assert all(r["num_used"] == 0 for r in report["pools"].values())

    def test_two_kinds_of_pool_run_ahead_token_exact_under_preemption(self):
        model, roomy = mimo_engine()
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 96, n).tolist() for n in (22, 19, 17)]
        want = [roomy.collect(roomy.submit(p, max_new_tokens=12))["tokens"]
                for p in prompts]
        _, tight = mimo_engine(num_kv_blocks={"full": 20})
        rids = [tight.submit(p, max_new_tokens=12) for p in prompts]
        step_all(tight, check=lambda e: assert_no_block_aliasing(e.cache))
        assert tight.sched.preemptions > 0
        ahead = tight.stats()["ahead"]
        assert ahead["ahead_breaks"]["preempt"] > 0
        assert ahead["units_ahead"] > 0
        assert [tight.collect(r)["tokens"] for r in rids] == want
        assert tight.cache.pools["window"].freed_behind > 0
        report = tight.cache.leak_report()
        assert report["leaked_blocks"] == 0 and report["balanced"]


# ---------------------------------------------------------------------------
# (g) drain, spill, resume, defrag, stop with a unit in flight
# ---------------------------------------------------------------------------
@pytest.mark.usefixtures("family")
class TestLandedStateOnly:
    def busy(self, model, **kw):
        eng = engine(model, **kw)
        rids = [eng.submit(p, max_new_tokens=7) for p in PROMPTS]
        for _ in range(5):             # four prefills, a decode unit
            eng.step()
        assert eng._in_flight is not None
        return eng, rids

    def test_begin_drain_lands_it_and_a_draining_engine_is_serial(self):
        model = tiny_model()
        want = [dense_continuation(model, p, 7) for p in PROMPTS]
        eng, rids = self.busy(model)
        had = [len(s.output) for s in eng.sched.running]
        eng.begin_drain()
        assert eng._in_flight is None and eng.state == "draining"
        assert [len(s.output) for s in eng.sched.running] == \
            [n + 1 for n in had]
        assert eng.has_work()
        events = eng.step()            # what landed early, then one unit
        assert len(events) == 2 * len(rids)
        assert eng._in_flight is None
        step_all(eng)
        ahead = eng.stats()["ahead"]
        assert ahead["ahead_breaks"]["drain"] >= 1
        assert ahead["units_ahead"] == 5       # none since the drain began
        assert [eng.collect(r)["tokens"] for r in rids] == want

    def test_a_spill_holds_landed_tokens_and_resumes_token_exact(
            self, tmp_path):
        model = tiny_model()
        want = [dense_continuation(model, p, 7) for p in PROMPTS]
        eng, rids = self.busy(model, run_dir=str(tmp_path))
        report = eng.drain(timeout=0.0)
        assert report["spilled"] == len(rids) and report["timed_out"]
        assert eng._in_flight is None and eng.cache.blocks_used() == 0
        for rec, w in zip(report["spilled_records"], want):
            # every spilled token is one that landed, none is lost
            assert rec["output"] == w[:len(rec["output"])]
            assert len(rec["output"]) >= 2
        fresh = engine(model, run_dir=str(tmp_path))
        resumed = fresh.resume(report["spill_path"])
        step_all(fresh)
        assert [fresh.collect(r)["tokens"] for r in resumed] == want
        assert fresh.cache.leak_report()["leaked_blocks"] == 0

    def test_defrag_renumbers_landed_tables(self):
        model = tiny_model()
        want = [dense_continuation(model, p, 7) for p in PROMPTS]
        eng, rids = self.busy(model)
        eng.cancel(rids[0])
        eng.cancel(rids[2])
        eng.step()                     # holes at the pool's start
        assert eng._in_flight is not None
        assert eng.defrag()
        assert eng._in_flight is None  # it landed first
        tokens = {}
        for events in step_all(eng):   # its events were not lost
            for e in events:
                if e["token"] is not None:
                    tokens.setdefault(e["request_id"], []).append(e["token"])
        for rid, w in ((rids[1], want[1]), (rids[3], want[3])):
            assert eng.collect(rid)["tokens"] == w
            assert tokens[rid] == w[-len(tokens[rid]):]
            assert len(tokens[rid]) >= 7 - 3
        assert eng.cache.leak_report()["leaked_blocks"] == 0

    def test_stop_lands_what_is_in_flight(self):
        eng, rids = self.busy(tiny_model())
        had = [len(s.output) for s in eng.sched.running]
        eng.stop()
        assert eng._in_flight is None and eng.state == "stopped"
        assert [len(s.output) for s in eng.sched.running] == \
            [n + 1 for n in had]
        assert eng.stats()["kv_blocks"]["balanced"]


# ---------------------------------------------------------------------------
# (h) a hung step with a unit in flight
# ---------------------------------------------------------------------------
@pytest.mark.faults
@pytest.mark.usefixtures("family")
class TestAHungStep:
    def test_both_units_are_abandoned_and_the_rows_recomputed(self):
        model = tiny_model()
        prompts = [[2, 3, 4], [5, 6]]
        want = [dense_continuation(model, p, 6) for p in prompts]
        injector = faults.poison_request(1, mode="hang", seconds=30.0,
                                         kinds=("decode",), count=1)
        eng = engine(model, max_seqs=2, step_timeout=120.0,
                     step_fault=injector)
        try:
            eng.submit([1, 2, 3], max_new_tokens=6)      # warm (index 0)
            eng.run(max_steps=100)
            eng.step_timeout = 2.0
            rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
            step_all(eng)
            assert eng.watchdog_restarts == 1 and injector.fired == 1
            assert eng._in_flight is None
            got = [eng.collect(r) for r in rids]
            assert [g["tokens"] for g in got] == want
            assert all(g["preemptions"] >= 1 for g in got)
            report = eng.cache.leak_report()
            assert report["num_used"] == 0 and report["balanced"]
        finally:
            eng.stop()


# ---------------------------------------------------------------------------
# (i) where an id comes from is data of the one program
# ---------------------------------------------------------------------------
@pytest.mark.usefixtures("family")
class TestWhereAnIdComesFromIsData:
    def test_one_decode_program_and_one_a_bucket_whatever_the_order(
            self, monkeypatch):
        import paddle_tpu.observability.compilation as comp
        tracker = CompileTracker(registry=MetricsRegistry())
        monkeypatch.setattr(comp, "get_tracker", lambda: tracker)
        eng = engine(max_seqs=3)
        # decode after decode, decode after a prefill, a prefill after a
        # decode, a serial start and a start over: arrivals between calls
        eng.submit([1, 2], max_new_tokens=5)
        for _ in range(3):
            eng.step()
        eng.submit([3, 4, 5, 6, 7, 8, 9], max_new_tokens=4)
        eng.step()
        eng.submit([1, 2, 3], max_new_tokens=3)
        step_all(eng)
        eng.generate([[4, 5, 6, 7, 8, 9, 10, 11, 12]], max_new_tokens=3)
        names = sorted(f for f in tracker.functions()
                       if f.startswith("serve"))
        assert names == ["serve_decode", "serve_prefill_b16",
                         "serve_prefill_b8"]
        for fn in names:
            st = tracker.stats(fn)
            assert st["traces"] == 1 and st["retraces"] == 0, (fn, st)
            assert st["walks"] == 1, (fn, st)
        assert tracker.stats("serve_decode")["calls"] > 4
        assert eng.stats()["ahead"]["units_ahead"] > 0

    def test_a_row_takes_its_id_from_the_previous_programs_tokens(self):
        """The decode program fed ``prev`` and ``src`` against the same
        program fed the ids in the buffer: the same tokens and logits; a
        negative ``src`` keeps the buffer's id."""
        eng = engine()
        fn, key = eng._build_step_fn(), jax.random.PRNGKey(0)
        rng = np.random.default_rng(5)
        pool = [tuple(jnp.asarray(rng.normal(size=a.shape) * 0.3, a.dtype)
                      for a in layer) for layer in eng.cache.pages]
        sids = [f"s{i}" for i in range(4)]
        lens = np.asarray([3, 6, 4, 9], np.int32)
        for sid, n in zip(sids, lens):
            assert eng.cache.ensure_capacity(sid, int(n))
        tables = eng.cache.table_array(sids, eng.sched.max_blocks_per_seq)
        slots = eng.cache.slot_array(sids, list(lens - 1), 1)
        ids = np.asarray([[7], [11], [2], [5]], np.int32)

        def run(ids, prev, src):
            eng.cache.update_pages([tuple(jnp.array(a) for a in layer)
                                    for layer in pool])
            nxt, _, logits, pages, _, carry = fn(
                eng._params,
                pack_step_inputs(ids, lens - 1, 0, tables, lens, slots,
                                 src=src),
                eng.cache.pages, key, prev, rows=4, chunk=1)
            eng.cache.update_pages(pages)
            return np.asarray(nxt), np.asarray(logits), np.asarray(carry)
        want = run(ids, eng._no_prev, None)
        # rows 0 and 2 read rows 3 and 1 of the previous tokens; the ids
        # in their own words are junk that nobody reads
        prev = jnp.asarray([9, 2, 30, 7], jnp.int32)
        mixed = ids.copy()
        mixed[0, 0] = mixed[2, 0] = 0
        got = run(mixed, prev, np.asarray([3, -1, 1, -1], np.int32))
        assert got[0].tolist() == want[0].tolist()
        np.testing.assert_allclose(got[1], want[1], atol=1e-6)
        assert got[2].tolist() == got[0].tolist()        # carry: (max_seqs,)

    def test_a_prefills_tokens_are_carried_at_the_decode_programs_shape(
            self):
        eng = engine()
        assert eng.cache.ensure_capacity("s", 5)
        ids = np.zeros((1, 8), np.int32)
        ids[0, :5] = [1, 2, 3, 4, 5]
        nxt, _, _, pages, _, carry = eng._build_step_fn()(
            eng._params,
            pack_step_inputs(ids, np.zeros((1,)), 4,
                             eng.cache.table_array(
                                 ["s"], eng.sched.max_blocks_per_seq),
                             np.asarray([5]),
                             eng.cache.slot_array(["s"], [0], 8)),
            eng.cache.pages, jax.random.PRNGKey(0), eng._no_prev,
            rows=1, chunk=8)
        eng.cache.update_pages(pages)
        assert carry.shape == (4,) and carry.dtype == jnp.int32
        assert np.asarray(carry).tolist() == [int(nxt[0]), 0, 0, 0]

    def test_a_sampling_engine_repeats_itself_for_a_seed(self):
        streams = [engine(temperature=0.8, seed=seed).generate(
            PROMPTS, max_new_tokens=8) for seed in (3, 3, 4)]
        assert all(len(t) == 8 for s in streams for t in s)
        assert streams[0] == streams[1] and streams[0] != streams[2]
        assert streams[0] != engine().generate(PROMPTS, max_new_tokens=8)


# ---------------------------------------------------------------------------
# the scheduler plans one unit ahead of the accept
# ---------------------------------------------------------------------------
class TestTheSchedulerPlansAhead:
    def make(self, blocks=8, max_seqs=3):
        cache = PagedKVCache([((1, 4), (1, 4))], blocks, block_size=4)
        return cache, ContinuousBatchingScheduler(cache, max_seqs, 16)

    @staticmethod
    def admit(sch, rid, prompt_len, max_new):
        seq = SequenceState(rid, list(range(1, prompt_len + 1)),
                            max_new_tokens=max_new)
        sch.submit(seq)
        plan = sch.schedule()
        assert plan.kind == "prefill" and plan.seqs == [seq]
        return seq, sch.mark_launched("prefill", [seq], [True])

    def test_marks_move_at_the_launch_and_come_back(self):
        cache, sch = self.make()
        seq, marks = self.admit(sch, "a", 5, 4)
        assert (seq.computed_len, seq.in_flight, seq.output) == (5, 1, [])
        plan = sch.schedule(ahead=True)        # its first token is unread
        assert plan.kind == "decode" and plan.seqs == [seq]
        more = sch.mark_launched("decode", [seq], [True])
        assert (seq.computed_len, seq.in_flight) == (6, 2)
        sch.unmark(more)
        assert (seq.computed_len, seq.in_flight) == (5, 1)
        sch.unmark(marks)
        assert (seq.computed_len, seq.in_flight) == (0, 0)
        sch.unadmit(seq)
        assert list(sch.waiting) == [seq] and not sch.running
        assert seq.state == "waiting" and seq.preemptions == 0
        assert sch.preemptions == 0 and cache.blocks_used() == 0

    def test_a_recompute_prefill_samples_nothing_new(self):
        cache, sch = self.make()
        seq = SequenceState("a", [1, 2, 3], max_new_tokens=4)
        seq.output, seq.pending = [9, 8], 8
        sch.submit(seq)
        assert sch.schedule().kind == "prefill"
        sch.mark_launched("prefill", [seq], [False])
        assert (seq.computed_len, seq.in_flight) == (4, 0)

    def test_a_row_that_fills_up_in_flight_is_left_out(self):
        cache, sch = self.make()
        a, _ = self.admit(sch, "a", 3, 2)
        b, _ = self.admit(sch, "b", 3, 5)
        for s in (a, b):                       # both first tokens landed
            s.output, s.pending, s.in_flight = [7], 7, 0
        plan = sch.schedule(ahead=True)
        assert plan.seqs == [a, b]
        sch.mark_launched("decode", plan.seqs, [True, True])
        # a's second token, its last, is on its way
        assert a.fills_up_in_flight() and not b.fills_up_in_flight()
        assert sch.schedule(ahead=True).seqs == [b]
        assert a in sch.running                # it leaves at its landing

    def test_a_plan_that_would_preempt_waits_for_the_landing(self):
        cache, sch = self.make(blocks=3)
        a, _ = self.admit(sch, "a", 4, 4)
        b, _ = self.admit(sch, "b", 4, 4)
        for s in (a, b):
            s.output, s.pending, s.in_flight = [7], 7, 0
        plan = sch.schedule(ahead=True)        # each needs a second block
        assert plan.kind == "wait" and not plan.seqs and not plan.preempted
        assert sch.running == [a, b] and sch.preemptions == 0
        plan = sch.schedule()                  # landed: now a victim goes
        assert plan.kind == "decode" and plan.seqs == [a]
        assert plan.preempted == [b] and b.in_flight == 0
