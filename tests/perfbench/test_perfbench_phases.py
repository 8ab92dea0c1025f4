"""The step-phase readers (``harness/phase_reads.py``) on a run record built
by hand: the shares by hand arithmetic, None where there is nothing sound
to read, the ten manifest entries, and one tiny chat cell end to end."""
import json
import os
import types

import pytest

from perfbench.harness import phase_reads
from perfbench.harness.manifest import Manifest, validate

from test_perfbench_cells import DATA, run_cell

T_START = 1000.0        # perf_counter() at the harness's time zero
PHASES = [("reap", 0.001), ("schedule", 0.002), ("dispatch", 0.001),
          ("tables", 0.004), ("h2d", 0.006), ("dispatch", 0.010),
          ("device_wait", 0.060), ("logits_copy", 0.008), ("guard", 0.002),
          ("accept", 0.003), ("gauges", 0.001)]
STEP_S = 0.100          # the phases sum to 0.098: 2 ms are unattributed


def program_spans(n_steps, first_step=0):
    """What ``spans_between`` would hand out for ``n_steps`` steps that
    start every 0.125 s from the harness's zero."""
    spans = []
    for i in range(n_steps):
        t = T_START + 0.125 * i
        at = {"step": first_step + i}
        cursor = t + 0.001
        for name, dur in PHASES:
            spans.append((f"engine.step/{name}", cursor, cursor + dur, at))
            cursor += dur
        spans.append(("engine.step", t, t + STEP_S,
                      dict(at, kind="decode", rows=2, bucket=0)))
    return spans


class FakeTracing:
    def __init__(self, spans, dropped_until=float("-inf")):
        self.spans, self.dropped_until = spans, dropped_until

    def spans_between(self, t0, t1):
        return [s for s in self.spans if s[1] < t1 and s[2] > t0]

    def dropped(self, since=float("-inf")):
        return 5 if self.dropped_until > since else 0


def run_record(n_steps, window, traced=None, trace=None):
    """The harness's side: its own ``engine.step`` records lie a little
    outside the program's spans (it stamps before and after the call)."""
    records, steps = [], []
    for i in range(n_steps):
        ts, te = 0.125 * i - 2e-5, 0.125 * i + STEP_S + 2e-5
        records.append(("engine.step", T_START + ts + 1e-5,
                        T_START + te - 1e-5))
        steps.append((ts, te, "decode", 2, 100))
    if traced:
        records.append(("traced", T_START + traced[0], T_START + traced[1]))
    return {"job": "serve", "spans": types.SimpleNamespace(records=records),
            "serve": {"steps": steps, "traced": traced},
            "window": {"t0": window[0], "t1": window[1],
                       "seconds": window[1] - window[0]},
            "trace": trace}


@pytest.fixture()
def eight_steps(monkeypatch):
    fake = FakeTracing(program_spans(8))
    monkeypatch.setattr(phase_reads, "_source", lambda: fake)
    return fake


def test_the_offset_comes_from_the_harness_own_step_records():
    run = run_record(8, (0.0, 1.0))
    assert phase_reads.clock_offset(run) == pytest.approx(T_START, abs=1e-9)
    run["serve"]["steps"].pop()         # not one record a step: no offset
    assert phase_reads.clock_offset(run) is None


def test_shares_by_hand(eight_steps, capsys):
    # steps end at 0.1, 0.225, ..., 0.975: the window [0.2, 0.9) holds
    # those that END in it, steps 1..6, whole (step 1 began at 0.125)
    run = run_record(8, (0.2, 0.9))
    assert phase_reads.plan_share(run) == pytest.approx(100 * 0.003 / 0.1)
    assert phase_reads.inputs_share(run) == pytest.approx(100 * 0.010 / 0.1)
    assert phase_reads.logits_copy_share(run) == pytest.approx(
        100 * 0.008 / 0.1)
    assert phase_reads.accept_share(run) == pytest.approx(100 * 0.006 / 0.1)
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1                # the first reader prints, once
    label, _, body = out[0].partition(": ")
    assert label == "engine_phases"
    ph = json.loads(body)
    assert ph["engine.step"] == [6, pytest.approx(0.6),
                                 pytest.approx(0.012)]      # 2 ms a step
    assert ph["dispatch"] == [12, pytest.approx(0.066),
                              pytest.approx(0.066)]
    assert ph["device_wait"][1] == pytest.approx(0.36)
    assert set(ph) == {n for n, _ in PHASES} | {"engine.step"}


def test_host_only_share_is_over_the_traced_stretch(eight_steps, capsys):
    # traced [0.5, 0.75): step 4 (0.5 - 0.6) and step 5 (0.625 - 0.725)
    # whole; the trace measured the stretch as 0.26 s
    run = run_record(8, (0.0, 0.5), traced=(0.5, 0.75),
                     trace={"window_s": 0.26, "busy_s": 0.2})
    host = 0.098 - 0.011 - 0.060        # all but dispatch and device_wait
    assert phase_reads.host_only_share(run) == pytest.approx(
        100 * 2 * host / 0.26)
    line, = capsys.readouterr().out.strip().splitlines()
    assert line.startswith("engine_phases_traced: ")
    assert json.loads(line.partition(": ")[2])["engine.step"][0] == 2
    # spans are cut to the stretch: traced from the middle of step 4's
    # device_wait (0.525 - 0.585) on
    run = run_record(8, (0.0, 0.5), traced=(0.555, 0.75),
                     trace={"window_s": 0.2, "busy_s": 0.1})
    assert phase_reads.host_only_share(run) == pytest.approx(
        100 * (host + 0.014) / 0.2)
    # without a trace there is no denominator
    assert phase_reads.host_only_share(run_record(8, (0.0, 0.5))) is None


def test_none_when_the_buffer_dropped_the_window(monkeypatch, capsys):
    # the buffer let go of steps 0..2, the last of which ended at 0.35 s: a
    # window from 0.2 s is no longer whole, nor is one from 0.4 s, whose
    # first step began at 0.375 s; one from 0.5 s is
    fake = FakeTracing(program_spans(8)[36:], dropped_until=T_START + 0.35)
    monkeypatch.setattr(phase_reads, "_source", lambda: fake)
    assert phase_reads.plan_share(run_record(8, (0.2, 0.9))) is None
    assert phase_reads.accept_share(run_record(8, (0.2, 0.9))) is None
    fake.dropped_until = T_START + 0.38
    assert phase_reads.plan_share(run_record(8, (0.4, 0.9))) is None
    assert phase_reads.plan_share(run_record(8, (0.5, 0.9))) == \
        pytest.approx(3.0)
    run = run_record(8, (0.0, 0.2), traced=(0.2, 0.9),
                     trace={"window_s": 0.7, "busy_s": 0.5})
    assert phase_reads.host_only_share(run) is None
    assert "engine_phases_traced" not in capsys.readouterr().out


def test_none_on_a_program_without_spans_between(monkeypatch):
    # the parent commit's tracing module: no reader may raise over it
    monkeypatch.setattr(phase_reads, "_source", types.SimpleNamespace)
    run = run_record(8, (0.2, 0.9), traced=(0.9, 1.0),
                     trace={"window_s": 0.1, "busy_s": 0.05})
    for read in (phase_reads.plan_share, phase_reads.inputs_share,
                 phase_reads.logits_copy_share, phase_reads.accept_share,
                 phase_reads.host_only_share):
        assert read(run) is None


def test_none_where_no_step_ended_or_the_job_is_training(eight_steps):
    assert phase_reads.plan_share(run_record(8, (5.0, 6.0))) is None
    train = {"job": "train", "trace": {"window_s": 1.0}}
    assert phase_reads.plan_share(train) is None
    assert phase_reads.host_only_share(train) is None


def test_a_quarantined_step_keeps_its_probes_under_quarantine(monkeypatch):
    spans = program_spans(1)
    at = {"step": 0}
    spans[-1:-1] = [
        ("engine.step/quarantine/dispatch", T_START + 0.0985,
         T_START + 0.0990, at),
        ("engine.step/quarantine", T_START + 0.0982, T_START + 0.0995, at)]
    monkeypatch.setattr(phase_reads, "_source", lambda: FakeTracing(spans))
    ph = phase_reads.window_phases(run_record(1, (0.0, 1.0)))
    assert ph["quarantine"] == [1, pytest.approx(0.0013),
                                pytest.approx(0.0008)]
    assert ph["dispatch"][0] == 2       # the probe's is not the step's
    assert ph["engine.step"][2] == pytest.approx(0.002 - 0.0013)


NEW = ["step_plan_share", "step_inputs_share", "step_logits_copy_share",
       "step_accept_share", "step_host_only_share"]


def test_the_ten_entries_and_their_readers():
    m = Manifest()
    assert validate(m) == []
    by_name = {x["name"]: x for x in m.data["per_layer"]}
    loop = by_name["prefill_time_share.chat"]["layer"]
    for stem in NEW:
        for suffix, cell, moves in (
                ("chat", "gpt3-xl.chat", "tpot_ms_p90"),
                ("backlog", "gpt3-xl.doc-backlog", "serve_tok_s")):
            x = by_name[f"{stem}.{suffix}"]
            assert x == {"name": f"{stem}.{suffix}", "unit": "%",
                         "better": "lower", "source": "program_span",
                         "layer": loop, "moves": moves, "workloads": [cell]}
            assert callable(m.load_module("layer_metrics", x["name"]).read)
    # appended: what was there keeps its place
    assert [x["name"] for x in m.data["per_layer"]][-10:] == [
        f"{stem}.{suffix}" for stem in NEW for suffix in ("chat", "backlog")]
    assert not [x for x in m.data["per_layer"][:-10]
                if x["source"] == "program_span"]


def test_a_tiny_chat_cell_reads_the_engines_spans(tmp_path):
    """The real engine under the real harness, on the CPU: the readers find
    the program's spans and the line of phases accounts for every step."""
    with open(os.path.join(DATA, "manifest.json")) as f:
        d = json.load(f)
    for c in d["configs"]:
        c["file"] = os.path.join(DATA, c["file"])
    like = next(x for x in d["per_layer"]
                if x["name"] == "prefill_time_share.chat")
    d["per_layer"] += [dict(like, name=f"{stem}.chat", source="program_span")
                       for stem in NEW]
    path = tmp_path / "manifest.json"
    path.write_text(json.dumps(d))
    out = run_cell("tiny.chat", "--trace", "1", "--manifest", str(path))
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True
    # a CPU prints no value; the four window shares are there all the same
    # (a CPU trace has no device plane, so the fifth has no denominator)
    for stem in NEW[:4]:
        assert last["metrics"][f"{stem}.chat"] == {"value": None,
                                                   "unit": "%"}
    assert "step_host_only_share.chat" not in last["metrics"]
    body, = [x.partition(": ")[2] for x in lines
             if x.startswith("engine_phases: ")]
    ph = json.loads(body)
    assert {"reap", "schedule", "tables", "h2d", "dispatch", "device_wait",
            "logits_copy", "guard", "accept", "gauges",
            "engine.step"} == set(ph)
    steps = ph["engine.step"][0]
    assert steps > 0 and ph["tables"][0] == steps
    assert ph["dispatch"][0] == 2 * steps
    covered = sum(v[1] for k, v in ph.items() if k != "engine.step")
    assert covered == pytest.approx(
        ph["engine.step"][1] - ph["engine.step"][2], rel=1e-3)
