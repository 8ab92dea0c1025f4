"""The serving engine's ledger of its units (ISSUE 37): when each was
handed to the device and seen done; why the device had nothing to run; how
long the host waited; how long a request queued.  Here, on the CPU
at tiny sizes: the sums from stamps put in by hand where a time matters,
the real engine where an order or a count does, the spans that say whose
unit they work for, and the span ring that holds a whole run."""
import json
import time

import pytest
from serving_families import family, tiny_model  # noqa: F401

from paddle_tpu.inference import ServingEngine
from paddle_tpu.inference import engine as engine_module
from paddle_tpu.inference.engine import LATE_EPS_S, _Unit
from paddle_tpu.observability import tracing
from paddle_tpu.observability.registry import MetricsRegistry
from paddle_tpu.testing import faults

pytestmark = pytest.mark.serving

PROMPTS = ([1, 2, 3], [4, 5], [6, 7, 8, 9, 10, 11, 12, 13, 14], [15])
LAUNCH = ("schedule", "tables", "h2d", "dispatch")
LANDING = ("device_wait", "logits_copy", "guard", "accept")
INF = float("inf")


@pytest.fixture(autouse=True)
def fresh_tracing():
    tracing.reset_tracing()
    yield
    tracing.reset_tracing()


def engine(model=None, **kw):
    kw.setdefault("registry", MetricsRegistry())
    kw.setdefault("max_seqs", 4)
    kw.setdefault("kv_block_size", 4)
    return ServingEngine(model or tiny_model(), **kw)


def run_dry(eng):
    calls = 0
    while eng.has_work():
        eng.step()
        calls += 1
    return calls


def spans(name=None):
    """``(path, start, end, attrs)`` of every span kept, or of one leaf."""
    return [s for s in tracing.spans_between(0.0, INF)
            if name is None or s[0].rsplit("/", 1)[-1] == name]


def roots():
    return [s for s in spans() if s[0] == "engine.step"]


def accounted(u):
    """Seconds the ledger has put somewhere: on the device or starved."""
    return (sum(v["device_s"] + v["device_s_bound"]
                for v in u["by_kind"].values())
            + sum(s for _, s in u["starved"].values()))


def open_interval(u):
    """Seconds of the starved interval that is open at the snapshot."""
    return u["now_s"] - u["last_done_s"] if u["starving"] else 0.0


# ---------------------------------------------------------------------------
# the sums, from stamps put in by hand
# ---------------------------------------------------------------------------
class Waited:
    """What ``_note_done`` reads of a ``device_wait`` span."""

    def __init__(self, start, end):
        self.start, self.end, self.elapsed = start, end, end - start


class TestTheSumsFromInjectedStamps:
    def rig(self, monkeypatch, born=100.0):
        """An engine whose launches are the test's: ``launch(number,
        enqueued, prev)`` goes through ``_start`` as a real one would."""
        eng = engine()
        eng._born = born
        made = {}

        def _launch(kind, seqs, bucket, prev, number):
            return made[number]

        monkeypatch.setattr(eng, "_launch", _launch)
        monkeypatch.setattr(eng.sched, "mark_launched", lambda *a: [])

        def launch(number, enqueued, prev=None, kind="decode", bucket=0,
                   rows=2):
            u = made[number] = _Unit(kind, [object()] * rows, bucket,
                                     number, 0.0)
            u.enqueued = enqueued
            return eng._start(kind, u.seqs, bucket, prev, number)

        def land(u, wait_from, done, ahead=None):
            eng._in_flight = ahead
            eng._note_done(u, Waited(wait_from, done))
            eng._book_landing(u)
            eng._in_flight = None

        return eng, launch, land

    def test_a_stretch_of_units_telescopes_to_the_wall(self, monkeypatch):
        eng, launch, land = self.rig(monkeypatch)
        root = eng._step_root = tracing.span("engine.step")
        u0 = launch(0, 100.5, kind="prefill", bucket=16, rows=1)
        assert root.attrs == {"starved_t0": 100.0, "starved_t1": 100.5,
                              "starved_why": "start"}
        u1 = launch(1, 100.55, prev=u0)
        land(u0, 100.6, 101.0, ahead=u1)
        assert (u0.wait_s, u0.late, u0.exact) == (pytest.approx(0.4), False,
                                                  True)
        assert u0.device_s == pytest.approx(0.5)       # from its hand-over
        u2 = launch(2, 101.05, prev=u1)
        land(u1, 101.1, 101.8, ahead=u2)
        assert u1.device_s == pytest.approx(0.8) and u1.exact  # from done(0)
        # the host comes late to unit 2: the device had finished it
        u3 = launch(3, 102.4, prev=u2)
        land(u2, 102.5, 102.5 + LATE_EPS_S / 2, ahead=u3)
        assert u2.late and not u2.exact
        land(u3, 102.6, 103.0)
        assert not u3.late and not u3.exact            # its start is unsure
        eng._note_break("idle")
        root.attrs.clear()
        u4 = launch(4, 104.0)
        assert root.attrs == {"starved_t0": 103.0, "starved_t1": 104.0,
                              "starved_why": "idle"}
        land(u4, 104.1, 104.5)
        assert u4.exact and u4.device_s == pytest.approx(0.5)
        u = eng.stats()["units"]
        assert u["host_late"] == 1
        assert u["starved"]["start"] == [1, pytest.approx(0.5)]
        assert u["starved"]["idle"] == [1, pytest.approx(1.0)]
        d, p = u["by_kind"]["decode"], u["by_kind"]["prefill"]
        assert (p["units"], p["rows"], p["units_bound"]) == (1, 1, 0)
        assert u["prefill_by_bucket"] == {16: p}
        assert (d["units"], d["rows"], d["units_bound"]) == (4, 8, 2)
        assert d["device_s"] == pytest.approx(0.8 + 0.5)
        assert d["device_s_bound"] == pytest.approx(103.0 - 101.8)
        assert d["wait_s"] == pytest.approx(0.7 + LATE_EPS_S / 2 + 0.4 + 0.4)
        # everything between the engine's birth and the last landing is
        # either a unit's time on the device or a starved interval
        assert accounted(u) == pytest.approx(104.5 - 100.0)
        assert (u["starving"], u["last_done_s"]) == ("idle", 104.5)
        assert u["eps_s"] == LATE_EPS_S

    @pytest.mark.parametrize("why", ["idle", "preempt", "fault", "drain"])
    def test_starvation_is_booked_under_why_nothing_was_ahead(
            self, monkeypatch, why):
        eng, launch, land = self.rig(monkeypatch)
        land(launch(0, 100.25), 100.3, 101.0)
        eng._note_break(why)
        u = eng.stats()["units"]
        assert (u["starving"], u["last_done_s"]) == (why, 101.0)
        launch(1, 103.0)
        starved = eng.stats()["units"]["starved"]
        assert starved.pop("start") == [1, pytest.approx(0.25)]
        assert starved.pop(why) == [1, pytest.approx(2.0)]
        assert all(v == [0, 0.0] for v in starved.values())
        assert eng.stats()["ahead"]["ahead_breaks"][why] == 1

    def test_a_unit_whose_launch_raised_books_nothing(self, monkeypatch):
        eng, launch, land = self.rig(monkeypatch)
        launch(0, None)                 # no ``enqueued``: it never left
        assert accounted(eng.stats()["units"]) == 0.0
        u = eng.stats()["units"]
        assert (u["starving"], u["last_done_s"]) == ("start", 100.0)

    def test_a_wait_of_eps_is_late_and_a_longer_one_is_not(
            self, monkeypatch):
        eng, launch, land = self.rig(monkeypatch, born=-1.0)
        a = launch(0, -0.5)
        land(a, 0.0, LATE_EPS_S)           # from 0: the difference is exact
        b = launch(1, 0.5)
        land(b, 0.0, 2 * LATE_EPS_S)
        assert a.late and not b.late
        # launched with nothing in flight: no unit ahead of the late one
        assert eng.stats()["units"]["host_late"] == 0


# ---------------------------------------------------------------------------
# the real engine: what is booked, and under which word
# ---------------------------------------------------------------------------
@pytest.mark.usefixtures("family")
class TestWhatTheEngineBooks:
    def test_a_unit_launched_ahead_books_no_starvation(self):
        eng = engine()
        for p in PROMPTS:
            eng.submit(p, max_new_tokens=5)
        calls = run_dry(eng)
        s, u = eng.stats(), eng.stats()["units"]
        assert s["ahead"]["units_ahead"] == calls - 1
        # the one interval is the one before the engine's first unit
        assert [n for n, _ in u["starved"].values()] == [1, 0, 0, 0, 0]
        assert u["starved"]["start"][1] > 0.0
        assert u["by_kind"]["prefill"]["units"] == 4
        assert u["by_kind"]["decode"]["units"] == calls - 4
        assert u["by_kind"]["prefill"]["rows"] == 4
        assert sum(v["units"] for v in u["prefill_by_bucket"].values()) == 4
        assert set(u["prefill_by_bucket"]) == {8, 16}
        assert [r for r in roots() if "starved_why" in r[3]] == roots()[:1]
        assert u["step_s"] == pytest.approx(
            sum(t1 - t0 for _, t0, t1, _ in roots()))
        json.dumps(u)                    # /statusz serialises it

    def test_an_empty_queue_is_idle_for_its_whole_length(self):
        eng = engine()
        eng.submit([1, 2, 3], max_new_tokens=3)
        run_dry(eng)
        eng.step()                       # a call with nothing to do
        last_done = spans("device_wait")[-1][2]
        t_a = time.perf_counter()
        time.sleep(0.05)
        eng.step()
        t_b = time.perf_counter()
        before = eng.stats()["units"]
        assert (before["starving"], before["last_done_s"]) == ("idle",
                                                               last_done)
        assert before["starved"]["idle"] == [0, 0.0]   # not closed yet
        eng.submit([4, 5], max_new_tokens=3)
        run_dry(eng)
        n, s = eng.stats()["units"]["starved"]["idle"]
        assert n == 1 and t_b - t_a <= s < t_b - t_a + 1.0
        (_, _, _, at), = [r for r in roots()
                          if r[3].get("starved_why") == "idle"]
        first_dispatch = [d for d in spans("dispatch")
                          if d[1] > t_b][0]
        # from the moment the host saw the device done to the hand-over
        assert at["starved_t0"] == last_done
        assert at["starved_t1"] == first_dispatch[2]
        assert s == pytest.approx(at["starved_t1"] - at["starved_t0"])

    def test_a_plan_that_would_preempt_books_preempt(self):
        eng = engine(num_kv_blocks=5)
        for p in ([1, 2, 3, 4], [5, 6, 7], [8, 9], [10, 11, 12, 13, 14]):
            eng.submit(p, max_new_tokens=6)
        run_dry(eng)
        s = eng.stats()
        assert eng.sched.preemptions > 0
        n, secs = s["units"]["starved"]["preempt"]
        # every such break was followed by a unit launched after the
        # landing, with a victim chosen from landed state
        assert n == s["ahead"]["ahead_breaks"]["preempt"] > 0
        assert secs > 0.0
        assert len([r for r in roots()
                    if r[3].get("starved_why") == "preempt"]) == n

    def test_a_faulted_landing_books_fault_and_a_dropped_unit_nothing(self):
        inj = faults.poison_request(1, mode="raise", kinds=("decode",))
        eng = engine(step_fault=inj)
        rids = [eng.submit(p, max_new_tokens=6) for p in PROMPTS]
        run_dry(eng)
        assert list(eng.quarantined) == [rids[1]]
        s, u = eng.stats(), eng.stats()["units"]
        assert s["ahead"]["ahead_units_dropped"] == 1
        assert u["starved"]["fault"][0] >= 1       # the replay, at least
        reg = eng._reg()
        accepted = (reg.counter("serve.prefills").value
                    + reg.counter("serve.decode_steps").value)
        assert sum(v["units"] for v in u["by_kind"].values()) == accepted
        # a number is read once: the faulted unit's by its replay, the
        # dropped unit's by the unit that took its number
        read = [at["unit"] for _, _, _, at in spans("accept")][::2]
        assert len(read) == len(set(read)) == accepted
        launched = [at["unit"] for _, _, _, at in spans("dispatch")]
        assert len(launched) > len(set(launched))   # numbers taken again
        assert set(read) <= set(launched)

    def test_a_draining_engine_starves_under_drain(self):
        eng = engine()
        for p in PROMPTS[:2]:
            eng.submit(p, max_new_tokens=6)
        eng.step()
        eng.step()
        eng.begin_drain()
        run_dry(eng)
        u = eng.stats()["units"]
        assert u["starved"]["drain"][0] >= 2        # nothing runs ahead
        assert u["starved"]["idle"][0] == u["starved"]["fault"][0] == 0

    def test_the_sums_cover_the_wall(self):
        eng = engine()
        eng.submit([1, 2], max_new_tokens=2)
        run_dry(eng)
        a = eng.stats()["units"]
        for p in PROMPTS:
            eng.submit(p, max_new_tokens=8)
        run_dry(eng)
        time.sleep(0.02)
        for p in PROMPTS[:2]:
            eng.submit(p, max_new_tokens=4)
        run_dry(eng)
        b = eng.stats()["units"]
        wall = b["now_s"] - a["now_s"]
        covered = (accounted(b) + open_interval(b)
                   - accounted(a) - open_interval(a)) / wall
        assert covered == pytest.approx(1.0, abs=0.01)
        assert b["starved"]["idle"][0] == 2


class TestLateOnASlowHost:
    @pytest.mark.parametrize("slow_host", [True, False])
    def test_a_host_that_dawdles_finds_the_device_done(self, monkeypatch,
                                                       slow_host):
        eng = engine()
        for p in PROMPTS:                 # compile every program first
            eng.submit(p, max_new_tokens=3)
        run_dry(eng)
        before = eng.stats()
        if slow_host:
            launch_next = eng._launch_next

            def dawdle(prev):
                unit = launch_next(prev)
                time.sleep(0.02)          # the tiny program is long done
                return unit

            monkeypatch.setattr(eng, "_launch_next", dawdle)
            # the constant is the chip's; a wait on this CPU after a sleep
            # takes about as long, so the test brings its own
            monkeypatch.setattr(engine_module, "LATE_EPS_S", 5e-3)
        else:                             # no wait is short enough
            monkeypatch.setattr(engine_module, "LATE_EPS_S", -1.0)
        for p in PROMPTS:
            eng.submit(p, max_new_tokens=6)
        run_dry(eng)
        s, u = eng.stats(), eng.stats()["units"]
        ahead = s["ahead"]["units_ahead"] - before["ahead"]["units_ahead"]
        late = u["host_late"] - before["units"]["host_late"]
        bound = sum(v["units_bound"] for v in u["by_kind"].values()) - sum(
            v["units_bound"] for v in before["units"]["by_kind"].values())
        landed = s["ahead"]["units_launched"] \
            - before["ahead"]["units_launched"]
        assert ahead == landed - 1
        if slow_host:
            # every landing is late, so every unit launched ahead is one
            # whose gap the host cannot bound, and no time is exact
            assert late == ahead and bound == landed
        else:                             # but for the unit after the last
            assert late == 0 and bound <= 1   # late one of the warm-up


# ---------------------------------------------------------------------------
# spans: whose unit a child works for, and a request's wait
# ---------------------------------------------------------------------------
@pytest.mark.usefixtures("family")
class TestTheSpansSayWhoseTheyAre:
    def test_every_child_carries_the_right_unit(self):
        eng = engine()
        for p in PROMPTS:
            eng.submit(p, max_new_tokens=4)
        calls = run_dry(eng)
        eng.step()                        # and a call that plans nothing
        by_step = {}
        for path, _, _, at in spans():
            if path.startswith("engine.step"):
                by_step.setdefault(at["step"], []).append(
                    (path.rpartition("/")[2], at))
        assert sorted(by_step) == list(range(calls + 1))
        for step, members in by_step.items():
            (_, root), = [m for m in members if m[0] == "engine.step"]
            for name, at in members:
                if name in LANDING:
                    assert at["unit"] == root["unit"] == step
                elif name in LAUNCH and step < calls:
                    # the first call launches the unit it lands, too
                    ok = {root["ahead_unit"]} | ({0} if step == 0 else set())
                    assert at["unit"] in ok, (step, name, at)
                elif name in ("reap", "gauges"):
                    assert "unit" not in at
        # running ahead: unit n is launched in call n-1 and landed in call n
        last = by_step[calls - 1]
        assert dict(last)["engine.step"]["ahead_unit"] is None
        assert [at for n, at in last if n == "schedule"] == [
            {"step": calls - 1, "unit": None}]
        idle = dict(by_step[calls])
        assert idle["engine.step"]["kind"] == "other"
        assert idle["schedule"]["unit"] is None

    def test_a_request_queues_once_though_it_is_preempted(self):
        eng = engine(num_kv_blocks=5)
        prompts = ([1, 2, 3, 4], [5, 6, 7], [8, 9], [10, 11, 12, 13, 14])
        t_submit = time.perf_counter()
        rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        run_dry(eng)
        queue = spans("queue")
        assert [p for p, *_ in queue] == ["engine.request/queue"] * 4
        assert sorted(at["request_id"] for *_, at in queue) == sorted(rids)
        # a recompute prefill is no second wait of the request's: the
        # request trace's ``preempt`` component has that
        assert eng.sched.preemptions > 0
        assert not [p for p, *_ in spans() if p.startswith("engine.request/")
                    and p != "engine.request/queue"]
        plans = {at["unit"]: t0 for _, t0, _, at in spans("schedule")
                 if at["unit"] is not None}
        prefills = {at["unit"] for _, _, _, at in roots()
                    if at.get("kind") == "prefill"}
        for _, t0, t1, at in queue:
            # it ends where the plan that took the request began, and the
            # unit it names is that request's prefill
            assert t1 == plans[at["unit"]] and at["unit"] in prefills
            assert t_submit <= t0 <= t1
        tree = tracing.span_tree_totals()
        assert tree["engine.request/queue"]["count"] == 4
        # the phases of a step are the children of ``engine.step`` alone
        assert "queue" not in eng.stats()["phases"]


def test_a_chrome_export_follows_one_unit_on_one_clock(tmp_path):
    """A short chat: for one unit number the export shows its launch in
    one call, its landing in the next, and the wait of the request it
    prefilled, all on one clock."""
    eng = engine()
    eng.submit([1, 2, 3], max_new_tokens=4)
    eng.step()
    rid = eng.submit([4, 5, 6, 7], max_new_tokens=4)
    run_dry(eng)
    out = tmp_path / "trace.json"
    n = tracing.export_chrome_trace(str(out))
    events = json.loads(out.read_text())["traceEvents"]
    assert n == len(events) == len(spans())
    wait, = [e for e in events if e["name"] == "engine.request/queue"
             and e["args"]["request_id"] == rid]
    number = wait["args"]["unit"]
    mine = [e for e in events if e.get("args", {}).get("unit") == number
            and e["name"].startswith("engine.step/")]
    leaf = lambda e: e["name"].rpartition("/")[2]
    launch = [e for e in mine if leaf(e) in LAUNCH]
    landing = [e for e in mine if leaf(e) in LANDING]
    assert [leaf(e) for e in launch] == list(LAUNCH)
    assert [leaf(e) for e in landing] == list(LANDING) + ["accept"]
    call, = {e["args"]["step"] for e in launch}
    assert {e["args"]["step"] for e in landing} == {call + 1}
    root, = [e for e in events if e["name"] == "engine.step"
             and e["args"].get("unit") == number]
    assert root["args"]["step"] == call + 1
    assert root["args"]["kind"] == "prefill"
    # one clock: the wait ends where the unit's plan begins, the launch
    # lies before the landing, and both inside their calls
    end = lambda e: e["ts"] + e["dur"]
    assert end(wait) == pytest.approx(launch[0]["ts"], abs=1.0)   # us
    assert end(launch[-1]) <= landing[0]["ts"]
    assert root["ts"] <= landing[0]["ts"] and end(landing[-1]) <= end(root)
    assert abs(root["ts"] / 1e6 - time.time()) < 60.0    # and it is wall


# ---------------------------------------------------------------------------
# the instruments: what is there, what went
# ---------------------------------------------------------------------------
def test_the_ledger_is_in_stats_alone_and_unread_instruments_are_gone():
    eng = engine()
    for p in PROMPTS:
        eng.submit(p, max_new_tokens=4)
    run_dry(eng)
    eng.admit_record({"request_id": "again", "prompt": [1, 2],
                      "output": [3], "max_new_tokens": 3})
    run_dry(eng)
    snap = eng._reg().snapshot()
    for gone in ("serve.decode_batch", "serve.shed", "serve.resumed"):
        assert gone not in snap
    # the ledger has one copy of its sums: ``stats()["units"]``, which
    # ``/statusz`` shows whole; no instrument doubles it
    assert not [n for n in snap if n.startswith(("serve.starved_s",
                                                 "serve.device_wait_s"))]
    u = eng.stats()["units"]
    assert set(u["by_kind"]["decode"]) == {
        "units", "rows", "device_s", "units_bound", "device_s_bound",
        "wait_s"}
    assert set(u) == {"by_kind", "prefill_by_bucket", "starved",
                      "host_late", "step_s", "eps_s", "now_s",
                      "last_done_s", "starving"}
    assert set(u["starved"]) == {"start", "idle", "preempt", "fault",
                                 "drain"}
    assert eng.stats()["load_shed"] == {"active": False,
                                        "queue_threshold": 64}
    # an admitted record waits like a submitted request
    assert "again" in {at["request_id"] for *_, at in spans("queue")}


# ---------------------------------------------------------------------------
# the span facility: stamps, records, and a ring that holds a run
# ---------------------------------------------------------------------------
class TestTheSpanFacility:
    def test_a_span_shows_its_two_stamps(self):
        before = time.perf_counter()
        with tracing.span("x") as sp:
            assert before <= sp.start <= time.perf_counter()
        assert sp.end - sp.start == sp.elapsed
        (_, t0, t1, _), = spans("x")
        assert (t0, t1) == (sp.start, pytest.approx(sp.end))

    def test_a_record_feeds_ring_tree_and_export(self, tmp_path):
        with tracing.span("engine.step", step=0):
            pass
        tracing.record("engine.request/queue", 5.0, 5.25, request_id="r",
                       unit=3)
        tracing.record("engine.request/queue", 6.0, 6.5, request_id="s",
                       unit=4)
        tracing.record("bare", 7.0, 6.0)              # ends before it starts
        got = tracing.spans_between(4.0, 8.0)
        assert got == [
            ("engine.request/queue", 5.0, 5.25,
             {"request_id": "r", "unit": 3}),
            ("engine.request/queue", 6.0, 6.5,
             {"request_id": "s", "unit": 4}),
            ("bare", 7.0, 7.0, {})]
        tree = tracing.span_tree_totals()
        assert tree["engine.request/queue"] == {
            "count": 2, "total_ms": 750.0, "self_ms": 750.0}
        out = tmp_path / "t.json"
        assert tracing.export_chrome_trace(str(out)) == 4
        ev = [e for e in json.loads(out.read_text())["traceEvents"]
              if e["name"] == "engine.request/queue"]
        assert [e["args"] for e in ev] == [{"request_id": "r", "unit": 3},
                                           {"request_id": "s", "unit": 4}]
        assert ev[0]["dur"] == pytest.approx(0.25e6)
        assert ev[0]["ts"] == pytest.approx(
            (5.0 + tracing._WALL_OFFSET) * 1e6)
        assert tracing.dropped() == 0

    def test_what_a_reader_gets_is_its_own(self):
        with tracing.span("a", step=1) as sp:
            sp.set(kind="decode")
        (_, _, _, at), = spans("a")
        at["step"] = 99
        assert spans("a")[0][3] == {"step": 1, "kind": "decode"}

    def test_the_ring_holds_a_chat_run(self):
        """80 s of the chat cell's calls: 215 a second, 11 spans each."""
        assert tracing.BUFFER_SPANS >= 1 << 18
        t, dt = 1000.0, 1.0 / 215
        names = ["engine.step/" + n for n in
                 ("reap",) + LAUNCH + LANDING + ("accept", "gauges")]
        for call in range(215 * 80):
            for k, name in enumerate(names):
                tracing.record(name, t + k * 1e-4, t + (k + 1) * 1e-4,
                               step=call, unit=call)
            t += dt
        assert len(names) == 11
        assert tracing.dropped(1000.0) == 0
        assert len(tracing.spans_between(1000.0, INF)) == 215 * 80 * 11
