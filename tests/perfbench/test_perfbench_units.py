"""The readers of the engine's unit ledger (``harness/unit_reads.py``, ISSUE
37) on run records built by hand (a program without the ledger reads None),
the fifteen manifest entries held by NAME, and a tiny chat and a tiny
backlog cell end to end on the CPU, where every new reader finds something
to read."""
import json
import os
import types

import pytest

from perfbench.harness import phase_reads, unit_reads
from perfbench.harness.manifest import Manifest, validate

from test_perfbench_cells import DATA, run_cell

LAYER = "serving loop (inference/engine.py, scheduler.py, kv_cache.py)"
CELLS = {"chat": ("gpt3-xl.chat", "tpot_ms_p90"),
         "backlog": ("gpt3-xl.doc-backlog", "serve_tok_s"),
         "reason": ("deepseek-v2-ep4-l5.reason-backlog", "serve_tok_s"),
         "longctx": ("glm-5-ep16-l5.longctx-backlog", "serve_tok_s"),
         "mixedctx": ("mimo-v2-flash-ep16-l7.mixedctx-backlog",
                      "serve_tok_s")}
FAMILIES = {
    "device_starved_share": ("%", "lower", "program_span",
                             unit_reads.device_starved_share),
    "host_slack_share": ("%", "higher", "program_counter",
                         unit_reads.host_slack_share),
    "prefill_device_share": ("%", "lower", "program_counter",
                             unit_reads.prefill_device_share)}
T_START = 1000.0        # perf_counter() at the harness's time zero


def sums(units=0, rows=0, device_s=0.0, bound=0, device_s_bound=0.0,
         wait_s=0.0):
    return {"units": units, "rows": rows, "device_s": device_s,
            "units_bound": bound, "device_s_bound": device_s_bound,
            "wait_s": wait_s}


def ledger(prefill, decode, buckets=None, starved=None, host_late=0,
           step_s=0.0, now_s=0.0, last_done_s=0.0, starving=None):
    whys = {"start": [1, 0.5], "idle": [0, 0.0], "preempt": [0, 0.0],
            "fault": [0, 0.0], "drain": [0, 0.0]}
    return {"by_kind": {"prefill": prefill, "decode": decode},
            "prefill_by_bucket": buckets or {},
            "starved": dict(whys, **(starved or {})),
            "host_late": host_late, "step_s": step_s, "eps_s": 1e-4,
            "now_s": now_s, "last_done_s": last_done_s,
            "starving": starving}


AT_OPEN = ledger(sums(10, 10, 1.0, wait_s=0.9),
                 sums(100, 800, 2.0, wait_s=1.6),
                 buckets={64: sums(10, 10, 1.0, wait_s=0.9)},
                 step_s=4.0, now_s=T_START + 10.2,
                 last_done_s=T_START + 10.0)     # a unit was in flight
# ten seconds later: 10 s of wall = 3 s of prefill + 6.5 s of decode on the
# device + 0.25 s idle + 0.15 s waiting to preempt + 0.1 s still open
AT_END = ledger(sums(20, 20, 3.0, 2, 1.0, wait_s=3.6),
                sums(500, 3600, 8.0, 4, 0.5, wait_s=6.9),
                buckets={64: sums(15, 15, 2.0, wait_s=1.9),
                         128: sums(5, 5, 1.0, 2, 1.0, wait_s=1.7)},
                starved={"idle": [2, 0.25], "preempt": [3, 0.15]},
                host_late=1, step_s=14.0, now_s=T_START + 20.0,
                last_done_s=T_START + 19.9, starving="idle")


def record(at_open=AT_OPEN, at_end=AT_END, job="serve", **more):
    wrap = lambda u: None if u is None else dict(
        {"steps": 1}, **({} if u is False else {"units": u}))
    return dict({"job": job, "trace": None,
                 "serve": {"stats_at_open": wrap(at_open),
                           "stats_at_end": wrap(at_end)}}, **more)


# ---------------------------------------------------------------------------
# the two readers of the counters
# ---------------------------------------------------------------------------
def test_slack_is_wait_over_seconds_inside_step_since_the_opening():
    # the host waited 8.0 of the 10.0 s it spent inside step()
    assert unit_reads.host_slack_share(record()) == pytest.approx(80.0)
    # the window never opened: from the engine's first call
    assert unit_reads.host_slack_share(record(at_open=None)) == \
        pytest.approx(100 * 10.5 / 14.0)
    # no call since the opening: nothing to divide by
    assert unit_reads.host_slack_share(record(at_end=AT_OPEN)) is None


def test_prefill_share_is_by_unit_and_the_units_are_said_once(capsys):
    run = record()
    # prefill 3.0 of 9.5 s on the device, exact and bound together
    assert unit_reads.prefill_device_share(run) == pytest.approx(
        100 * 3.0 / 9.5)
    assert unit_reads.prefill_device_share(run) == pytest.approx(
        100 * 3.0 / 9.5)
    line, = capsys.readouterr().out.strip().splitlines()
    label, _, body = line.partition(": ")
    said = json.loads(body)
    assert label == "engine_units" and said["host_late"] == 1
    assert said["by_kind"]["decode"] == {
        "units": 400, "units_bound": 4, "rows": pytest.approx(7.0),
        "device_ms": pytest.approx(1e3 * 6.0 / 396),
        "wait_ms": pytest.approx(1e3 * 5.3 / 400)}
    assert said["by_kind"]["prefill"]["device_ms"] == pytest.approx(
        1e3 * 2.0 / 8)
    assert said["prefill_by_bucket"]["64"]["units"] == 5
    assert said["prefill_by_bucket"]["128"] == {
        "units": 5, "units_bound": 2, "rows": 1.0,
        "device_ms": pytest.approx(1e3 / 3), "wait_ms": pytest.approx(340.0)}
    # 10 s inside step(), 8 s of them waiting, 410 units landed
    assert said["host_ms_a_unit"] == pytest.approx(1e3 * 2.0 / 410)
    # 9.5 s on the device + 0.4 s starved + 0.1 s open, of 10 s
    assert said["wall_s"] == pytest.approx(10.0)
    assert said["covered"] == pytest.approx(100.0)


@pytest.mark.parametrize("reader", [
    unit_reads.host_slack_share, unit_reads.prefill_device_share,
    unit_reads.device_starved_share,
    lambda run: unit_reads.queue_wait_ms(run, 90.0, "q")],
    ids=["slack", "prefill", "starved", "queue"])
@pytest.mark.parametrize("run", [
    record(at_open=False, at_end=False),         # a program without it
    record(at_end=None),                         # a run without stats
    {"job": "train", "train": {}, "trace": None}],
    ids=["older_program", "no_stats", "training"])
def test_none_where_there_is_no_ledger(reader, run, monkeypatch, capsys):
    older = types.SimpleNamespace(spans_between=lambda a, b: [],
                                  dropped=lambda since=0.0: 0)
    monkeypatch.setattr(phase_reads, "_source", lambda: older)
    run = dict(run, spans=types.SimpleNamespace(records=[]),
               window={"t0": 0.0, "t1": 1.0, "seconds": 1.0})
    assert reader(run) is None
    assert capsys.readouterr().out == ""


def test_the_starved_line_is_said_with_or_without_a_trace(capsys):
    run = record(spans=types.SimpleNamespace(records=[]))
    assert unit_reads.device_starved_share(run) is None      # no trace
    assert capsys.readouterr().out.startswith("engine_starved: ")


# ---------------------------------------------------------------------------
# the two readers of the spans
# ---------------------------------------------------------------------------
class FakeTracing:
    def __init__(self, spans, dropped_until=float("-inf")):
        self.spans, self.dropped_until = spans, dropped_until

    def spans_between(self, t0, t1):
        return [s for s in self.spans if s[1] < t1 and s[2] > t0]

    def dropped(self, since=float("-inf")):
        return 5 if self.dropped_until > since else 0

    def record(self, *a, **kw):
        raise AssertionError("a reader records nothing")


def root(t0, t1, **at):
    return ("engine.step", T_START + t0, T_START + t1, dict(at, step=0))


def starved_root(t0, t1, s0, s1, why="idle"):
    return root(t0, t1, starved_t0=T_START + s0, starved_t1=T_START + s1,
                starved_why=why)


def traced_run(a, b, window_s, at_end=AT_END):
    return record(
        at_end=at_end, trace={"window_s": window_s, "busy_s": 0.1},
        spans=types.SimpleNamespace(
            records=[("traced", T_START + a, T_START + b)]))


def test_starved_intervals_are_cut_to_the_traced_stretch(monkeypatch,
                                                         capsys):
    fake = FakeTracing([
        starved_root(14.90, 15.00, 14.0, 14.95),       # before the stretch
        starved_root(16.40, 16.50, 15.5, 16.45),       # began before it
        root(16.50, 16.60, kind="decode"),             # launched ahead
        ("engine.step/device_wait", T_START + 16.5, T_START + 16.6,
         {"step": 0, "unit": 1, "starved_t0": 0.0}),   # not a root
        starved_root(17.00, 17.10, 16.8, 17.05, "preempt"),
        starved_root(19.50, 19.60, 18.9, 19.55)])      # ended after it
    monkeypatch.setattr(phase_reads, "_source", lambda: fake)
    # traced [16, 19) measured as 3.2 s: 0.45 + 0.25 + 0.1 s inside it
    run = traced_run(16.0, 19.0, 3.2, at_end=dict(AT_END, starving=None))
    assert unit_reads.device_starved_share(run) == pytest.approx(
        100 * 0.80 / 3.2)
    line, = capsys.readouterr().out.strip().splitlines()
    assert line.startswith("engine_starved: ")
    assert json.loads(line.partition(": ")[2]) == {
        "start": [0, 0.0], "idle": [2, 0.25], "preempt": [3, 0.15],
        "fault": [0, 0.0], "drain": [0, 0.0]}
    # the interval that is open when the run ends has no root yet: the
    # ledger says since when (19.9 of a stretch that ends at 20.0)
    run = traced_run(17.5, 20.0, 2.5)
    assert unit_reads.device_starved_share(run) == pytest.approx(
        100 * (0.65 + 0.1) / 2.5)
    # and one that began before the stretch is cut like any other
    run = traced_run(19.95, 20.0, 0.05)
    assert unit_reads.device_starved_share(run) == pytest.approx(100.0)


def test_starved_share_needs_a_trace_and_a_whole_ring(monkeypatch):
    fake = FakeTracing([starved_root(16.4, 16.5, 15.5, 16.45)],
                       dropped_until=T_START + 16.2)
    monkeypatch.setattr(phase_reads, "_source", lambda: fake)
    assert unit_reads.device_starved_share(
        traced_run(16.0, 19.0, 3.0)) is None            # the ring let go
    fake.dropped_until = T_START + 15.0
    assert unit_reads.device_starved_share(
        traced_run(16.0, 19.0, 3.0)) is not None
    run = traced_run(16.0, 19.0, 3.0)
    run["trace"] = None                                 # an untraced run
    assert unit_reads.device_starved_share(run) is None
    run = traced_run(16.0, 19.0, 3.0)
    run["spans"].records.clear()                        # no stretch
    assert unit_reads.device_starved_share(run) is None


def queue_run(waits_ending_at, window=(1.0, 9.0)):
    """A run whose harness steps give the offset, and a program whose
    requests waited ``w`` seconds until ``t``."""
    records = [("engine.step", T_START + i, T_START + i + 0.5)
               for i in range(10)]
    steps = [(i, i + 0.5, "decode", 1, 1) for i in range(10)]
    spans = [("engine.request/queue", T_START + t - w, T_START + t,
              {"request_id": f"r{i}", "unit": i})
             for i, (t, w) in enumerate(waits_ending_at)]
    spans.append(("engine.request/other", T_START + 2.0, T_START + 5.0,
                  {"request_id": "again", "unit": 99}))
    run = record(spans=types.SimpleNamespace(records=records),
                 window={"t0": window[0], "t1": window[1],
                         "seconds": window[1] - window[0]})
    run["serve"]["steps"] = steps
    return run, FakeTracing(spans)


def test_queue_wait_is_over_the_waits_that_ended_in_the_window(
        monkeypatch, capsys):
    waits = [(0.5, 0.4)] + [(1.5 + 0.5 * i, 0.001 * (i + 1))
                            for i in range(10)] + [(9.5, 0.3)]
    run, fake = queue_run(waits)
    monkeypatch.setattr(phase_reads, "_source", lambda: fake)
    # ten waits of 1..10 ms ended inside [1, 9); another span under
    # ``engine.request`` is not mixed in
    got = unit_reads.queue_wait_ms(run, 90.0, "queue_wait_ms_p90.chat")
    assert 9.0 <= got <= 10.0
    assert unit_reads.queue_wait_ms(run, 50.0, "q") == pytest.approx(
        5.5, abs=0.51)
    assert capsys.readouterr().out.splitlines()[0] == \
        "samples_behind: queue_wait_ms_p90.chat n=10"
    fake.dropped_until = T_START + 2.0                  # the ring let go
    assert unit_reads.queue_wait_ms(run, 90.0, "q") is None
    fake.dropped_until = float("-inf")
    run["serve"]["steps"].pop()                         # no offset
    assert unit_reads.queue_wait_ms(run, 90.0, "q") is None


def test_no_wait_in_the_window_reads_none(monkeypatch):
    run, fake = queue_run([(0.5, 0.1)])
    monkeypatch.setattr(phase_reads, "_source", lambda: fake)
    assert unit_reads.queue_wait_ms(run, 90.0, "q") is None


# ---------------------------------------------------------------------------
# the manifest
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("sfx", sorted(CELLS))
def test_an_entry_a_cell_held_by_name(family, sfx):
    m = Manifest()
    unit, better, source, reader = FAMILIES[family]
    cell, moves = CELLS[sfx]
    name = f"{family}.{sfx}"
    x, = [x for x in m.data["per_layer"] if x["name"] == name]
    assert x == {"name": name, "unit": unit, "better": better,
                 "source": source, "layer": LAYER, "moves": moves,
                 "workloads": [cell]}
    assert x in m.metrics_for("per_layer", cell)
    assert moves in {e["name"] for e in m.metrics_for("end_to_end", cell)}
    assert m.load_module("layer_metrics", name).read is reader


def test_the_whole_manifest_and_what_the_entries_leave_alone():
    m = Manifest()
    assert validate(m) == []
    mine = [x["name"] for x in m.data["per_layer"]
            if x["name"].rpartition(".")[0] in FAMILIES]
    # by name, however many a later cell appends
    assert len(mine) == len(set(mine)) and {
        f"{family}.{sfx}" for family in FAMILIES for sfx in CELLS} \
        <= set(mine)
    # the queue wait has a reader and no entry yet: in the one cell with
    # arrivals nothing queues inside the engine (PERF.md section 7)
    assert not [x for x in m.data["per_layer"]
                if x["name"].startswith("queue_wait")]
    # what was there keeps its place, and no training cell reports these
    names = [x["name"] for x in m.data["per_layer"]]
    assert names.index("step_ahead_share.mixedctx") < min(
        names.index(n) for n in mine)
    for cell in ("gpt3-125m.train", "gpt3-xl-l16.train-hybrid4"):
        assert not {x["name"] for x in m.metrics_for("per_layer", cell)} \
            & set(mine)


# ---------------------------------------------------------------------------
# the real engine under the real harness, on the CPU
# ---------------------------------------------------------------------------
def tiny_manifest(tmp_path, sfx):
    with open(os.path.join(DATA, "manifest.json")) as f:
        d = json.load(f)
    for c in d["configs"]:
        c["file"] = os.path.join(DATA, c["file"])
    like = next(x for x in d["per_layer"]
                if x["name"] == f"decode_batch_occupancy.{sfx}")
    names = [f"{family}.{sfx}" for family in FAMILIES]
    for name in names:
        d["per_layer"].append(dict(like, name=name))
    path = tmp_path / "manifest.json"
    path.write_text(json.dumps(d))
    return str(path), names


@pytest.mark.parametrize("sfx", ["chat", "backlog"])
def test_a_tiny_cell_reads_the_ledger(tmp_path, sfx):
    """Every new reader finds something to read (a CPU prints no value
    under a metric's name, but the name is there only if the reader
    returned one), and the lines say what the ledger held."""
    path, names = tiny_manifest(tmp_path, sfx)
    out = run_cell(f"tiny.{sfx}", "--trace", "1", "--manifest", path)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0
    for name in names:
        if name.startswith("device_starved_share"):
            continue            # over the DEVICE trace's stretch: no CPU has
        assert name in last["metrics"], (name, sorted(last["metrics"]))
        assert last["metrics"][name]["value"] is None
    said = {x.partition(": ")[0]: json.loads(x.partition(": ")[2])
            for x in lines
            if x.startswith(("engine_units: ", "engine_starved: "))}
    units = said["engine_units"]
    assert units["by_kind"]["decode"]["units"] > 10
    assert units["by_kind"]["prefill"]["units"] >= 1
    assert sum(v["units"] for v in units["prefill_by_bucket"].values()) \
        == units["by_kind"]["prefill"]["units"]
    # on the device or starved: all of the wall between the two snapshots
    assert units["covered"] == pytest.approx(100.0, abs=1.0)
    starved = said["engine_starved"]
    assert set(starved) == {"start", "idle", "preempt", "fault", "drain"}
    assert starved["start"] == [0, 0.0]         # long before the window
    assert starved["fault"] == [0, 0.0] and starved["drain"] == [0, 0.0]
    if sfx == "chat":
        assert starved["idle"][0] >= 1          # the queue ran dry
