"""``step_ahead_share.*`` (ISSUE 36): the reader on run records built by
hand, the five manifest entries held by NAME, and one tiny backlog cell end
to end on the CPU, where the engine's own counts say that it ran ahead."""
import json
import os

import pytest

from perfbench.harness import ahead_reads
from perfbench.harness.manifest import Manifest, validate

from test_perfbench_cells import DATA, run_cell

LAYER = "serving loop (inference/engine.py, scheduler.py, kv_cache.py)"
CELLS = {"chat": ("gpt3-xl.chat", "tpot_ms_p90"),
         "backlog": ("gpt3-xl.doc-backlog", "serve_tok_s"),
         "reason": ("deepseek-v2-ep4-l5.reason-backlog", "serve_tok_s"),
         "longctx": ("glm-5-ep16-l5.longctx-backlog", "serve_tok_s"),
         "mixedctx": ("mimo-v2-flash-ep16-l7.mixedctx-backlog",
                      "serve_tok_s")}


def record(at_open, at_end, job="serve"):
    wrap = lambda a: None if a is None else {"steps": 1, **(
        {} if a is False else {"ahead": a})}
    return {"job": job, "serve": {"stats_at_open": wrap(at_open),
                                  "stats_at_end": wrap(at_end)}}


def counts(launched, ahead, **more):
    return dict({"units_launched": launched, "units_ahead": ahead,
                 "ahead_rows_discarded": 0, "ahead_units_dropped": 0,
                 "ahead_breaks": {"idle": 0, "preempt": 0, "fault": 0,
                                  "drain": 0}}, **more)


@pytest.mark.parametrize("at_open,at_end,want", [
    (counts(100, 90), counts(600, 565), 95.0),   # 475 of 500 in the window
    (counts(0, 0), counts(8, 6), 75.0),
    (None, counts(8, 8), 100.0),                 # the window never opened
    (counts(40, 30), counts(40, 30), None),      # nothing launched since
    (counts(40, 30), False, None),               # a program without it
    (False, False, None),
], ids=["window", "from_zero", "no_opening", "idle", "older_end",
        "older_program"])
def test_the_share_is_units_ahead_over_units_launched_since_the_opening(
        at_open, at_end, want, capsys):
    got = ahead_reads.step_ahead_share(record(at_open, at_end))
    assert got == (want if want is None else pytest.approx(want))
    said = [x for x in capsys.readouterr().out.splitlines()
            if x.startswith("engine_ahead: ")]
    assert len(said) == (1 if at_end else 0)
    if said:
        assert json.loads(said[0].partition(": ")[2]) == at_end


def test_a_training_run_reads_nothing():
    assert ahead_reads.step_ahead_share(
        {"job": "train", "train": {}}) is None


def test_the_five_entries_are_appended_and_name_their_cells():
    m = Manifest()
    assert validate(m) == []
    names = [x["name"] for x in m.data["per_layer"]]
    mine = [f"step_ahead_share.{sfx}" for sfx in CELLS]
    assert names[-5:] == mine
    for sfx, (cell, moves) in CELLS.items():
        x = next(x for x in m.data["per_layer"]
                 if x["name"] == f"step_ahead_share.{sfx}")
        assert x == {"name": f"step_ahead_share.{sfx}", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": LAYER, "moves": moves, "workloads": [cell]}
        assert x in m.metrics_for("per_layer", cell)
        assert moves in {e["name"]
                         for e in m.metrics_for("end_to_end", cell)}
        assert m.load_module("layer_metrics", x["name"]).read \
            is ahead_reads.step_ahead_share
    # no training cell reports it
    for cell in ("gpt3-125m.train", "gpt3-xl-l16.train-hybrid4"):
        assert not [x for x in m.metrics_for("per_layer", cell)
                    if x["name"].startswith("step_ahead_share")]


def test_a_tiny_backlog_cell_runs_ahead_and_says_so(tmp_path):
    """The real engine under the real harness, on the CPU: a backlog keeps
    a queue, so nearly every unit is launched while another is in flight;
    the metric is on the traced line (a CPU prints no value under it)."""
    with open(os.path.join(DATA, "manifest.json")) as f:
        d = json.load(f)
    for c in d["configs"]:
        c["file"] = os.path.join(DATA, c["file"])
    like = next(x for x in d["per_layer"]
                if x["name"] == "decode_batch_occupancy.backlog")
    d["per_layer"].append(dict(like, name="step_ahead_share.backlog"))
    path = tmp_path / "manifest.json"
    path.write_text(json.dumps(d))
    out = run_cell("tiny.backlog", "--trace", "1", "--manifest", str(path))
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["metrics"]["step_ahead_share.backlog"] == {
        "value": None, "unit": "%"}
    body, = [x.partition(": ")[2] for x in lines
             if x.startswith("engine_ahead: ")]
    ahead = json.loads(body)
    assert ahead["units_launched"] > 20
    assert ahead["units_ahead"] >= 0.8 * ahead["units_launched"]
    assert ahead["ahead_units_dropped"] == 0
    assert ahead["ahead_breaks"]["fault"] == 0
