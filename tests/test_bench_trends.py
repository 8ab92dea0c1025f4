"""Perf trend engine (ISSUE 14): series view of the ledger, changepoint
detection + attribution, the noise-aware gate, trailing-median perfdiff,
the compactor, and the self-contained HTML dashboard."""
import json
import os

import pytest

from paddle_tpu.bench import diff as perfdiff
from paddle_tpu.bench import gate, ledger, report, schema, trends
from paddle_tpu.observability import interconnect, roofline
from paddle_tpu.utils import fsio

_FP = {"platform": "cpu", "device_kind": "cpu", "device_count": 8,
       "jax": "0.0-test", "python": "3.10.0"}


def _row(scenario="moe", mode="smoke", p50=50.0, phases=None, sha="aaaa1111",
         ts=1.0, fingerprint=None, mfu=0.1, compile_wall=100.0):
    """A schema-valid row with *controlled* sha/ts/fingerprint (new_row
    stamps the real repo sha, which these drills must not depend on)."""
    phases = phases or {"data": 5.0, "compute": p50 - 10.0,
                        "readback": 3.0, "collective": 2.0}
    roof = roofline.degraded_block(
        p50, {k: float(v) for k, v in phases.items()},
        reason="trends drill row")
    return {
        "schema_version": schema.SCHEMA_VERSION,
        "scenario": scenario, "mode": mode, "ts": float(ts),
        "git_sha": sha, "device_kind": "cpu",
        "fingerprint": dict(fingerprint or _FP), "config": {}, "steps": 4,
        "step_time_ms": {"p50": p50, "p99": p50 * 1.05, "mean": p50,
                         "min": p50 * 0.95},
        "phases_ms": {k: float(v) for k, v in phases.items()},
        "tokens_per_sec": 1000.0, "mfu": mfu,
        "compile": {"wall_ms": compile_wall},
        "bytes_on_wire": 0, "peak_hbm_bytes": 1 << 20,
        # schema v2: every row carries a gap budget; the degraded
        # phase-only block keeps these drills schema-valid
        "roofline": roof,
        # schema v3: every row carries a comm sub-budget; bucket must
        # match the roofline comm bucket for _validate_interconnect
        "interconnect": interconnect.degraded_block(
            float(roof["buckets_ms"].get("comm", 0.0)),
            reason="trends drill row"),
        "extra": {},
    }


def _moe_drill_rows(jitter=None, shift=True):
    """The acceptance drill: 12 rows across 3 shas; sha B inflates the
    moe compute phase by 1.2x (and C keeps it).  ``shift=False`` drops
    the inflation (the flat variant); ``jitter`` (len 12) multiplies
    each row's times."""
    base = {"data": 5.0, "compute": 40.0, "readback": 3.0,
            "collective": 2.0}
    infl = dict(base, compute=48.0) if shift else base
    rows = []
    ts = 0.0
    for sha, ph in (("aaaa1111", base), ("bbbb2222", infl),
                    ("cccc3333", infl)):
        for _ in range(4):
            ts += 1.0
            j = jitter[len(rows)] if jitter else 1.0
            rows.append(_row(p50=sum(ph.values()) * j,
                             phases={k: v * j for k, v in ph.items()},
                             sha=sha, ts=ts))
    return rows


# -- read_series ------------------------------------------------------------
def test_read_series_dedupes_sha_newest_wins(tmp_path):
    lpath = str(tmp_path / "l.jsonl")
    for i, (sha, p50) in enumerate([("a", 50.0), ("a", 52.0),
                                    ("b", 60.0)]):
        ledger.append_row(_row(p50=p50, sha=sha, ts=float(i)), lpath)
    pts = ledger.read_series("moe", "smoke", path=lpath)
    assert [(p["sha"], p["value"]) for p in pts] == [("a", 52.0),
                                                     ("b", 60.0)]
    # run-level view keeps every row (the gate's statistics need reruns)
    pts = ledger.read_series("moe", "smoke", path=lpath,
                             dedupe_sha=False)
    assert [p["value"] for p in pts] == [50.0, 52.0, 60.0]


def test_read_series_partitions_by_fingerprint(tmp_path):
    lpath = str(tmp_path / "l.jsonl")
    tpu_fp = dict(_FP, platform="tpu", device_kind="TPU v5e",
                  device_count=64)
    ledger.append_row(_row(p50=5.0, sha="t1", ts=1.0,
                           fingerprint=tpu_fp), lpath)
    ledger.append_row(_row(p50=50.0, sha="c1", ts=2.0), lpath)
    ledger.append_row(_row(p50=51.0, sha="c2", ts=3.0), lpath)
    # default partition = the newest row's (cpu): the TPU point is out
    pts = ledger.read_series("moe", "smoke", path=lpath)
    assert [p["value"] for p in pts] == [50.0, 51.0]
    # explicit partition selects the TPU series
    pts = ledger.read_series("moe", "smoke", path=lpath,
                             partition="tpu/TPU v5e/x64")
    assert [p["value"] for p in pts] == [5.0]


def test_read_series_skips_rows_missing_the_metric(tmp_path):
    lpath = str(tmp_path / "l.jsonl")
    r1 = _row(p50=50.0, sha="a", ts=1.0, mfu=None)
    r2 = _row(p50=51.0, sha="b", ts=2.0, mfu=0.2)
    ledger.append_row(r1, lpath)
    ledger.append_row(r2, lpath)
    assert len(ledger.read_series("moe", "smoke", "step_p50",
                                  path=lpath)) == 2
    mfu = ledger.read_series("moe", "smoke", "mfu", path=lpath)
    assert [(p["sha"], p["value"]) for p in mfu] == [("b", 0.2)]
    with pytest.raises(KeyError):
        schema.metric_value(r1, "bogus_metric")


# -- compaction -------------------------------------------------------------
def test_compact_ledger_bounds_per_scenario_history(tmp_path):
    lpath = str(tmp_path / "l.jsonl")
    for i in range(10):
        ledger.append_row(_row(scenario="a", p50=40.0 + i, ts=float(i)),
                          lpath)
    for i in range(3):
        ledger.append_row(_row(scenario="b", p50=90.0 + i,
                               ts=float(100 + i)), lpath)
    kept, dropped = ledger.compact_ledger(lpath, keep=4)
    assert (kept, dropped) == (7, 6)
    rows = ledger.read_ledger(lpath)
    a = [r for r in rows if r["scenario"] == "a"]
    assert [r["step_time_ms"]["p50"] for r in a] == [46.0, 47.0, 48.0,
                                                     49.0]  # newest 4
    assert len([r for r in rows if r["scenario"] == "b"]) == 3


def test_compact_ledger_env_knob_and_validation(tmp_path, monkeypatch):
    lpath = str(tmp_path / "l.jsonl")
    for i in range(5):
        ledger.append_row(_row(p50=40.0, ts=float(i)), lpath)
    monkeypatch.setenv("PTPU_LEDGER_KEEP", "2")
    assert ledger.compact_ledger(lpath) == (2, 3)
    with pytest.raises(ValueError):
        ledger.compact_ledger(lpath, keep=0)
    # an absent ledger compacts to nothing and is NOT created
    missing = str(tmp_path / "nope.jsonl")
    assert ledger.compact_ledger(missing) == (0, 0)
    assert not os.path.exists(missing)


def test_ledger_cli_compact_and_summary(tmp_path, capsys):
    lpath = str(tmp_path / "l.jsonl")
    for i in range(4):
        ledger.append_row(_row(p50=40.0, ts=float(i)), lpath)
    assert ledger.main(["--ledger", lpath]) == 0
    assert "4 row(s)" in capsys.readouterr().out
    assert ledger.main(["--ledger", lpath, "--compact",
                        "--keep", "1"]) == 0
    assert "dropped 3" in capsys.readouterr().out
    assert len(ledger.read_ledger(lpath)) == 1


# -- robust statistics ------------------------------------------------------
def test_median_mad_theil_sen():
    assert trends.median([3.0, 1.0, 2.0]) == 2.0
    assert trends.median([1.0, 2.0, 3.0, 4.0]) == 2.5
    assert trends.median([]) is None
    assert trends.mad([1.0, 1.0, 5.0]) == 0.0  # median dev from 1.0
    assert trends.mad([1.0, 2.0, 3.0, 100.0]) == 1.0
    assert trends.theil_sen([1.0, 2.0, 3.0, 4.0]) == pytest.approx(1.0)
    # one outlier does not move the Theil-Sen slope much
    assert trends.theil_sen([1.0, 2.0, 50.0, 4.0, 5.0]) == pytest.approx(
        1.0, abs=0.5)


def test_sigma_from_diffs_is_shift_immune():
    flat = [50.0, 50.4, 49.8, 50.2, 49.9, 50.1]
    sigma = trends.sigma_from_diffs(flat)
    assert sigma is not None and sigma < 1.0
    # a 20% mean shift contaminates one diff; the MAD shrugs it off
    shifted = flat + [60.0, 60.3, 59.8, 60.1]
    assert trends.sigma_from_diffs(shifted) < 1.0
    assert trends.sigma_from_diffs([1.0, 2.0]) is None  # too short


# -- changepoints -----------------------------------------------------------
def test_changepoint_detected_on_clean_step():
    cps = trends.detect_changepoints([50.0, 60.0, 60.0])
    assert len(cps) == 1 and cps[0]["index"] == 1
    assert cps[0]["delta_frac"] == pytest.approx(0.20)
    assert cps[0]["direction"] == "up"
    cps = trends.detect_changepoints([50.0, 50.0, 50.0, 40.0, 40.0])
    assert len(cps) == 1 and cps[0]["index"] == 3
    assert cps[0]["direction"] == "down"


def test_changepoint_detected_under_jitter():
    vals = ([50.0, 51.2, 49.1, 50.6, 48.9, 50.3, 49.5, 51.0]
            + [60.4, 59.2, 61.1, 60.0, 59.5, 60.8])
    cps = trends.detect_changepoints(vals)
    assert len(cps) == 1 and cps[0]["index"] == 8
    assert cps[0]["delta_frac"] == pytest.approx(0.20, abs=0.04)


def test_pure_noise_yields_zero_changepoints():
    # hand-picked +-8% zero-mean jitter around 50 (deterministic)
    mults = [1.03, 0.95, 1.06, 0.97, 1.01, 0.94, 1.05, 0.99,
             1.02, 0.96, 1.07, 0.93, 1.00, 1.04, 0.98]
    vals = [50.0 * m for m in mults]
    assert trends.detect_changepoints(vals) == []
    # the tiny-series variant (3 deduped shas, jittered, no shift)
    assert trends.detect_changepoints([51.5, 47.5, 53.0]) == []


def test_small_series_demands_a_loud_shift():
    # below the small-series floor (12%): not evidence on 3 points
    assert trends.detect_changepoints([50.0, 55.0, 55.0]) == []
    # above it: evidence
    assert trends.detect_changepoints([50.0, 57.0, 57.0]) != []


def test_slow_linear_drift_is_flagged_not_missed():
    # +1.2%/point over 16 points crosses the floor; residual noise tiny
    vals = [50.0 * (1 + 0.012 * i) for i in range(16)]
    pts = [{"sha": f"s{i:02d}", "ts": float(i), "value": v, "row": {}}
           for i, v in enumerate(vals)]
    a = trends.analyze_series(pts)
    assert a["drift"] is not None and a["drift"]["flagged"]
    assert a["drift"]["direction"] == "up"
    assert a["drift"]["total_frac"] == pytest.approx(0.18, abs=0.03)
    # a flat jittery series has no flagged drift
    flat = [{"sha": f"s{i}", "ts": float(i), "value": 50.0 + (i % 3),
             "row": {}} for i in range(16)]
    flat_a = trends.analyze_series(flat)
    assert not (flat_a["drift"] and flat_a["drift"]["flagged"])


def test_analyze_series_trend_direction_and_sha_range():
    pts = [{"sha": f"s{i}", "ts": float(i), "value": v, "row": {}}
           for i, v in enumerate([50.0, 50.2, 49.8, 50.1, 60.0])]
    a = trends.analyze_series(pts)
    assert a["trend"] == "up"
    assert a["changepoints"], "the jump must register"
    assert a["changepoints"][-1]["sha_range"] == ("s3", "s4")
    down = [{"sha": f"s{i}", "ts": float(i), "value": v, "row": {}}
            for i, v in enumerate([50.0, 50.2, 49.8, 50.1, 40.0])]
    assert trends.analyze_series(down)["trend"] == "down"
    flat = [{"sha": f"s{i}", "ts": float(i), "value": 50.0, "row": {}}
            for i in range(5)]
    assert trends.analyze_series(flat)["trend"] == "flat"


def test_median_row_carries_perfdiff_fields():
    rows = [_row(p50=p, sha=s, ts=t,
                 phases={"data": d, "compute": p - d - 5.0,
                         "readback": 3.0, "collective": 2.0})
            for p, d, s, t in [(40.0, 4.0, "a", 1.0),
                               (50.0, 5.0, "b", 2.0),
                               (60.0, 6.0, "c", 3.0)]]
    mr = trends.median_row(rows)
    assert mr["step_time_ms"]["p50"] == 50.0
    assert mr["phases_ms"]["data"] == 5.0
    assert mr["git_sha"] == "median:3"
    assert mr["scenario"] == "moe" and mr["device_kind"] == "cpu"
    with pytest.raises(ValueError):
        trends.median_row([])


# -- the acceptance drill ---------------------------------------------------
def test_drill_shift_named_with_sha_range_and_phase(tmp_path, capsys):
    lpath = str(tmp_path / "l.jsonl")
    for r in _moe_drill_rows():
        ledger.append_row(r, lpath)
    analyses = trends.scan_ledger(path=lpath)
    assert [a["scenario"] for a in analyses] == ["moe"]
    step = analyses[0]["metrics"]["step_p50"]
    assert step["n"] == 3  # 12 rows, 3 shas, deduped
    cps = step["changepoints"]
    assert len(cps) == 1
    assert cps[0]["sha_range"] == ("aaaa1111", "bbbb2222")
    assert cps[0]["delta_frac"] == pytest.approx(0.16, abs=0.02)
    assert cps[0]["dominant_phase"] == "compute"
    # the CLI names all of it
    assert trends.main(["--ledger", lpath]) == 0
    out = capsys.readouterr().out
    assert "moe" in out and "aaaa1111..bbbb2222" in out
    assert "compute" in out and "+16" in out


def test_drill_jitter_no_shift_is_quiet_and_gate_green(tmp_path, capsys):
    # +-8% zero-mean jitter, no real shift anywhere
    jitter = [1.03, 0.95, 1.06, 0.97, 0.92, 1.01, 1.08, 0.99,
              1.02, 0.96, 1.05, 0.94]
    lpath = str(tmp_path / "l.jsonl")
    gpath = str(tmp_path / "g.json")
    rows = _moe_drill_rows(jitter=jitter, shift=False)
    for r in rows:
        ledger.append_row(r, lpath)
    analyses = trends.scan_ledger(path=lpath)
    assert analyses[0]["metrics"]["step_p50"]["changepoints"] == []
    # noise-aware gate: green (the trailing median + k*MAD absorbs it)
    ledger.write_golden(ledger.golden_from_rows(
        {"moe": rows[0]}), gpath)
    assert gate.run_gate(lpath, gpath) == 0
    assert "ok" in capsys.readouterr().out


def test_report_html_renders_both_series_self_contained(tmp_path):
    lpath = str(tmp_path / "l.jsonl")
    for r in _moe_drill_rows():                       # shifted series
        ledger.append_row(r, lpath)
    for i, m in enumerate([1.03, 0.95, 1.06, 0.97, 1.01, 0.99]):
        ledger.append_row(_row(scenario="gpt_pretrain_fused",
                               p50=40.0 * m, sha=f"sha{i}",
                               ts=100.0 + i), lpath)  # jittery-flat
    out = str(tmp_path / "report.html")
    assert report.write_report(path=out, ledger_path=lpath) == out
    doc = fsio.read_bytes(out).decode("utf-8")
    assert doc.strip()
    assert "moe" in doc and "gpt_pretrain_fused" in doc
    assert "<svg" in doc and "<polyline" in doc
    # the changepoint marker (dashed rule + dot) is drawn
    assert "stroke-dasharray" in doc and "<circle" in doc
    assert "aaaa1111..bbbb2222" in doc
    # self-contained: no network fetches, no scripts, no imports
    for banned in ("http://", "https://", "<script", "@import",
                   "url(", "src="):
        assert banned not in doc, banned
    # CLI round-trip
    assert report.main(["--ledger", lpath, "--out", out]) == 0


# -- the noise-aware gate ---------------------------------------------------
def _seed_gate(tmp_path, prior_p50s, cur_p50, scenario="moe"):
    lpath = str(tmp_path / "l.jsonl")
    gpath = str(tmp_path / "g.json")
    for i, p in enumerate(prior_p50s):
        ledger.append_row(_row(scenario=scenario, p50=p, ts=float(i)),
                          lpath)
    ledger.append_row(_row(scenario=scenario, p50=cur_p50,
                           ts=float(len(prior_p50s))), lpath)
    ledger.write_golden(ledger.golden_from_rows(
        {scenario: _row(scenario=scenario, p50=prior_p50s[0])}), gpath)
    return lpath, gpath


def test_gate_noise_aware_passes_jittery_but_flat(tmp_path, capsys):
    # priors jitter +-8% around 50 (MAD 3ms); the newest lands 12% above
    # the trailing median — the fixed 10% rule WOULD fail this
    priors = [46.0, 47.0, 48.0, 49.0, 50.0, 51.0, 52.0, 53.0, 54.0,
              46.5, 53.5]
    med = trends.median(priors)
    cur = 56.0
    assert cur > 1.10 * med           # the fixed rule's verdict: FAIL
    lpath, gpath = _seed_gate(tmp_path, priors, cur)
    assert gate.run_gate(lpath, gpath) == 0      # noise-aware: green
    out = capsys.readouterr().out
    assert "noise-raised" in out
    # ... and an explicit --threshold still means what it says
    assert gate.run_gate(lpath, gpath, threshold_frac=0.10) == 1


def test_gate_quiet_scenario_still_fails_on_regression(tmp_path, capsys):
    lpath, gpath = _seed_gate(tmp_path, [50.0, 50.1, 49.9, 50.0], 58.0)
    assert gate.run_gate(lpath, gpath) == 1
    out = capsys.readouterr().out
    assert "REGRESSION" in out and "FAIL" in out


def test_gate_insufficient_history_is_advisory_rc0(tmp_path, capsys):
    # 2 rows < MIN_HISTORY: advisory, NOT a silent golden comparison —
    # even though the newest row is 50% up (would fail any raw compare)
    lpath, gpath = _seed_gate(tmp_path, [40.0], 60.0)
    assert gate.run_gate(lpath, gpath) == 0
    out = capsys.readouterr().out
    assert "insufficient history" in out
    assert "REGRESSION" not in out


# -- perfdiff --baseline median:N ------------------------------------------
def test_diff_baseline_median_compares_vs_trailing_median(tmp_path,
                                                          capsys):
    lpath = str(tmp_path / "l.jsonl")
    for i, p in enumerate([40.0, 41.0, 39.0, 40.5, 39.5]):
        ledger.append_row(_row(p50=p, sha=f"s{i}", ts=float(i)), lpath)
    ledger.append_row(_row(p50=48.0, sha="s9", ts=9.0), lpath)
    rc = perfdiff.main(["--baseline", "median:4", "--ledger", lpath])
    out = capsys.readouterr().out
    assert rc == 1                      # 48 vs ~40 median: regression
    assert "median:4" in out            # the pseudo-row names itself
    assert "REGRESSION" in out
    # median window of 1 = newest prior row only
    rc = perfdiff.main(["--baseline", "median:1", "--ledger", lpath,
                        "--scenario", "moe"])
    assert rc == 1
    capsys.readouterr()


def test_diff_baseline_median_needs_two_rows(tmp_path, capsys):
    lpath = str(tmp_path / "l.jsonl")
    ledger.append_row(_row(p50=40.0, ts=1.0), lpath)
    rc = perfdiff.main(["--baseline", "median:4", "--ledger", lpath])
    assert rc == 0
    assert "fewer than 2 rows" in capsys.readouterr().err
    with pytest.raises(SystemExit):
        perfdiff.main(["--baseline", "median:0"])


# -- doctor / statusz wiring ------------------------------------------------
def test_doctor_perf_trend_names_scenario_sha_and_phase():
    from paddle_tpu.observability.doctor import check_perf_trend
    rows = _moe_drill_rows()
    workers = {0: [{"kind": "bench.row", "scenario": "moe",
                    "ts": 1.0}]}
    findings = check_perf_trend(workers, rows=rows)
    assert len(findings) == 1
    f = findings[0]
    assert f["kind"] == "perf_trend"
    assert "moe" in f["title"] and "bbbb2222" in f["title"]
    assert f["data"]["dominant"] == "compute"
    assert f["data"]["sha_range"] == ("aaaa1111", "bbbb2222")
    assert f["data"]["delta_frac"] == pytest.approx(0.16, abs=0.02)
    assert any("compute" in ev for ev in f["evidence"])


def test_doctor_perf_trend_gated_on_bench_rows():
    from paddle_tpu.observability.doctor import check_perf_trend
    rows = _moe_drill_rows()
    # no bench.row records in the window: the global ledger is someone
    # else's history — no findings
    workers = {0: [{"kind": "step", "step_time_ms": 50.0}]}
    assert check_perf_trend(workers, rows=rows) == []
    # benched a different scenario: still quiet
    workers = {0: [{"kind": "bench.row", "scenario": "mnist"}]}
    assert check_perf_trend(workers, rows=rows) == []


def test_trend_knobs_read_from_env(monkeypatch):
    monkeypatch.setenv("PTPU_TREND_WINDOW", "4")
    monkeypatch.setenv("PTPU_TREND_K", "9.0")
    assert trends.trend_window() == 4
    assert trends.trend_k() == 9.0
    monkeypatch.delenv("PTPU_TREND_WINDOW")
    monkeypatch.delenv("PTPU_TREND_K")
    assert trends.trend_window() == trends.DEFAULT_WINDOW
    assert trends.trend_k() == trends.DEFAULT_K


def test_trends_cli_json_mode(tmp_path, capsys):
    lpath = str(tmp_path / "l.jsonl")
    for r in _moe_drill_rows():
        ledger.append_row(r, lpath)
    assert trends.main(["--ledger", lpath, "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload[0]["scenario"] == "moe"
    assert payload[0]["metrics"]["step_p50"]["n"] == 3
    # an empty ledger renders the hint, not a crash
    assert trends.main(["--ledger", str(tmp_path / "empty.jsonl")]) == 0
    assert "no ledger series" in capsys.readouterr().out
