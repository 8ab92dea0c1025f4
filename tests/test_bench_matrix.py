"""Performance observatory (ISSUE 13): row schema, ledger semantics,
perfdiff attribution, and the CI gate's edge cases."""
import json
import os

import pytest

from paddle_tpu.bench import diff as perfdiff
from paddle_tpu.bench import gate, harness, ledger, schema
from paddle_tpu.utils import fsio


def _mk_row(scenario="gpt_pretrain_fused", mode="smoke", p50=40.0,
            phases=None, **kw):
    """A synthetic but schema-valid row (steady step series around p50)."""
    kw.setdefault("compile_stats",
                  {"wall_ms": 2000.0, "traces": 1, "retraces": 0,
                   "storms": 0, "cache_hits": 3,
                   "persistent_hits": 0, "persistent_requests": 0})
    return schema.new_row(
        scenario, mode,
        step_times_ms=[p50 * 0.98, p50, p50 * 1.02, p50],
        phases_ms=phases or {"data": 1.0, "compute": p50 - 2.0,
                             "readback": 0.5, "collective": 0.5},
        config={"batch": 2},
        tokens_per_sec=1000.0, mfu=0.01,
        bytes_on_wire=0, peak_hbm_bytes=1 << 20, **kw)


# -- schema -----------------------------------------------------------------
def test_new_row_is_schema_valid():
    row = _mk_row()
    assert schema.validate_row(row) == []
    assert row["schema_version"] == schema.SCHEMA_VERSION
    assert row["steps"] == 4
    assert row["step_time_ms"]["p50"] == pytest.approx(40.0)
    assert set(row["phases_ms"]) == set(schema.PHASES)
    assert row["fingerprint"]["platform"] == "cpu"
    assert row["device_kind"]


def test_validate_row_catches_violations():
    assert schema.validate_row("nope") == ["row is not an object"]
    row = _mk_row()
    bad = dict(row, schema_version=99)
    assert any("schema_version" in e for e in schema.validate_row(bad))
    bad = dict(row, mode="bogus")
    assert any("mode" in e for e in schema.validate_row(bad))
    bad = dict(row, phases_ms={"data": 1.0})  # missing phases
    assert any("phases_ms.compute" in e for e in schema.validate_row(bad))
    bad = dict(row, step_time_ms={})
    assert any("p50" in e for e in schema.validate_row(bad))
    bad = dict(row, bytes_on_wire="lots")
    assert any("bytes_on_wire" in e for e in schema.validate_row(bad))


def test_no_cpu_unless_asked(monkeypatch):
    """A run that was not told to use a CPU never continues on one: with
    neither BENCH_CPU=1 nor JAX_PLATFORMS=cpu, finding no TPU is fatal."""
    from paddle_tpu.bench import runner
    monkeypatch.delenv("BENCH_CPU", raising=False)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit, match="not a TPU"):
        runner.ensure_devices()
    monkeypatch.setenv("BENCH_CPU", "1")
    assert runner.ensure_devices() == "cpu"
    assert _mk_row()["device_kind"]  # what actually ran is always stamped


def test_pct_matches_aggregate_definition():
    from paddle_tpu.observability.aggregate import _pct
    series = sorted([5.0, 1.0, 3.0, 2.0, 4.0])
    for p in (0, 50, 90, 99, 100):
        assert harness.pct(series, p) == _pct(series, p)


# -- ledger -----------------------------------------------------------------
def test_append_round_trip(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    r1, r2 = _mk_row(p50=40.0), _mk_row(scenario="moe", p50=60.0)
    ledger.append_row(r1, path)
    ledger.append_row(r2, path)
    rows = ledger.read_ledger(path)
    assert [r["scenario"] for r in rows] == ["gpt_pretrain_fused", "moe"]
    assert rows[0]["step_time_ms"]["p50"] == pytest.approx(40.0)


def test_append_rejects_invalid_row(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    with pytest.raises(ValueError, match="invalid ledger row"):
        ledger.append_row({"scenario": "x"}, path)
    assert not os.path.exists(path)  # nothing poisoned the history


def test_torn_tail_and_foreign_schema_tolerated(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    good = _mk_row()
    foreign = dict(_mk_row(scenario="from_the_future"), schema_version=99)
    fsio.append_bytes(path, (json.dumps(good) + "\n").encode())
    fsio.append_bytes(path, (json.dumps(foreign) + "\n").encode())
    # a mid-append death leaves a torn trailing line
    fsio.append_bytes(path, json.dumps(good)[: 40].encode())
    drops = {}
    rows = ledger.read_ledger(path, drops=drops)
    assert len(rows) == 1 and rows[0]["scenario"] == good["scenario"]
    assert drops == {"torn_lines": 1, "unknown_schema": 1}


def test_latest_rows_newest_wins(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    ledger.append_row(_mk_row(p50=40.0), path)
    ledger.append_row(_mk_row(p50=44.0), path)
    ledger.append_row(_mk_row(scenario="moe", mode="full", p50=9.0), path)
    latest = ledger.latest_rows(ledger.read_ledger(path))
    assert latest["gpt_pretrain_fused"]["step_time_ms"]["p50"] == \
        pytest.approx(44.0)
    assert ledger.latest_rows(ledger.read_ledger(path),
                              mode="smoke").keys() == {"gpt_pretrain_fused"}


def test_golden_round_trip_and_thresholds(tmp_path):
    gpath = str(tmp_path / "golden.json")
    golden = ledger.golden_from_rows({"moe": _mk_row(scenario="moe")},
                                     thresholds={"step_time_regression_frac":
                                                 0.25})
    ledger.write_golden(golden, gpath)
    loaded = ledger.load_golden(gpath)
    assert loaded["scenarios"]["moe"]["scenario"] == "moe"
    # explicit override wins; unknown name raises; default backfills
    assert ledger.threshold(loaded, "step_time_regression_frac") == 0.25
    assert ledger.threshold(loaded, "comm_min_compress_ratio") == 3.0
    with pytest.raises(KeyError):
        ledger.threshold(loaded, "not_a_threshold")
    assert ledger.load_golden(str(tmp_path / "absent.json")) is None


# -- perfdiff attribution ---------------------------------------------------
@pytest.mark.parametrize("phase", schema.PHASES)
def test_attribution_names_the_inflated_phase(phase):
    base = _mk_row(phases={"data": 5.0, "compute": 30.0, "readback": 2.0,
                           "collective": 3.0})
    cur_phases = dict(base["phases_ms"])
    cur_phases[phase] *= 2.0  # inflate exactly one phase
    cur = _mk_row(p50=40.0 + cur_phases[phase] / 2.0, phases=cur_phases)
    att = perfdiff.attribute(base, cur)
    assert att["dominant"] == phase
    assert att["movers"][0]["phase"] == phase
    assert att["movers"][0]["delta_ms"] == pytest.approx(
        base["phases_ms"][phase])


def test_diff_rows_regression_verdict_and_render():
    base = _mk_row(p50=40.0)
    cur = _mk_row(p50=48.0,
                  phases={"data": 1.0, "compute": 46.0, "readback": 0.5,
                          "collective": 0.5})
    rep = perfdiff.diff_rows(base, cur, 0.10)
    assert rep["regression"] and rep["ratio"] == pytest.approx(1.2)
    assert rep["attribution"]["dominant"] == "compute"
    text = perfdiff.render(rep)
    assert "REGRESSION" in text and "compute" in text
    assert "dominant" in text
    # improvement: no regression, no dominant mover
    rep2 = perfdiff.diff_rows(cur, base, 0.10)
    assert not rep2["regression"]


def test_diff_compile_wall_reported_separately():
    base = _mk_row()
    cur = _mk_row(compile_stats={"wall_ms": 9000.0, "traces": 3,
                                 "retraces": 2, "storms": 0,
                                 "cache_hits": 0, "persistent_hits": 0,
                                 "persistent_requests": 0})
    att = perfdiff.attribute(base, cur)
    assert att["compile_wall_delta_ms"] == pytest.approx(7000.0)
    # compile is not a step phase: it never becomes the dominant mover
    assert att["dominant"] is None


def test_diff_cli_two_row_files(tmp_path):
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps(_mk_row(p50=40.0)))
    b.write_text(json.dumps(_mk_row(p50=60.0, phases={
        "data": 1.0, "compute": 58.0, "readback": 0.5, "collective": 0.5})))
    assert perfdiff.main([str(a), str(b)]) == 1  # regression → rc 1
    assert perfdiff.main([str(b), str(a)]) == 0


# -- gate edge cases --------------------------------------------------------
def _setup_gate(tmp_path, base_p50=40.0, cur_p50=40.0, scenario="moe"):
    lpath = str(tmp_path / "ledger.jsonl")
    gpath = str(tmp_path / "golden.json")
    ledger.write_golden(ledger.golden_from_rows(
        {scenario: _mk_row(scenario=scenario, p50=base_p50)}), gpath)
    # three identical prior rows give the noise-aware gate its history:
    # trailing median = base_p50, MAD = 0, so the threshold collapses to
    # the golden fraction and the edge-case contracts below stay exact
    for _ in range(3):
        ledger.append_row(_mk_row(scenario=scenario, p50=base_p50), lpath)
    ledger.append_row(_mk_row(scenario=scenario, p50=cur_p50), lpath)
    return lpath, gpath


def test_gate_passes_when_flat(tmp_path, capsys):
    lpath, gpath = _setup_gate(tmp_path)
    assert gate.run_gate(lpath, gpath) == 0
    assert "ok" in capsys.readouterr().out


def test_gate_exactly_at_threshold_passes(tmp_path):
    # strictly-greater contract: exactly +10% is NOT a regression
    lpath, gpath = _setup_gate(tmp_path, base_p50=40.0, cur_p50=44.0)
    assert gate.run_gate(lpath, gpath) == 0
    lpath2, gpath2 = _setup_gate(tmp_path / "b", base_p50=40.0,
                                 cur_p50=44.01)
    assert gate.run_gate(lpath2, gpath2) == 1


def test_gate_regression_fails_with_attribution(tmp_path, capsys):
    lpath, gpath = _setup_gate(tmp_path, base_p50=40.0, cur_p50=48.0)
    assert gate.run_gate(lpath, gpath) == 1
    out = capsys.readouterr().out
    assert "REGRESSION" in out and "moe" in out
    assert "dominant" in out  # the perfdiff report names the mover
    assert "FAIL" in out


def test_gate_golden_missing_passes_advisory(tmp_path, capsys):
    lpath = str(tmp_path / "ledger.jsonl")
    ledger.append_row(_mk_row(), lpath)
    rc = gate.run_gate(lpath, str(tmp_path / "no_golden.json"))
    assert rc == 0
    assert "--write-golden" in capsys.readouterr().out


def test_gate_new_scenario_passes_until_blessed(tmp_path, capsys):
    lpath, gpath = _setup_gate(tmp_path)
    # a scenario in the ledger but absent from golden: pass with a note
    ledger.append_row(_mk_row(scenario="brand_new", p50=500.0), lpath)
    assert gate.run_gate(lpath, gpath) == 0
    assert "not in golden" in capsys.readouterr().out


def test_gate_write_golden_blesses_latest(tmp_path, capsys):
    lpath = str(tmp_path / "ledger.jsonl")
    gpath = str(tmp_path / "golden.json")
    ledger.append_row(_mk_row(p50=40.0), lpath)
    ledger.append_row(_mk_row(p50=42.0), lpath)
    assert gate.run_gate(lpath, gpath, write_golden=True) == 0
    golden = ledger.load_golden(gpath)
    assert golden["scenarios"]["gpt_pretrain_fused"]["step_time_ms"][
        "p50"] == pytest.approx(42.0)
    assert golden["thresholds"]["step_time_regression_frac"] == 0.10
    # re-blessing preserves threshold overrides already in the file
    golden["thresholds"]["step_time_regression_frac"] = 0.33
    ledger.write_golden(golden, gpath)
    assert gate.run_gate(lpath, gpath, write_golden=True) == 0
    assert ledger.load_golden(gpath)["thresholds"][
        "step_time_regression_frac"] == 0.33


def test_gate_empty_ledger_advisory(tmp_path):
    _, gpath = _setup_gate(tmp_path)
    assert gate.run_gate(str(tmp_path / "empty.jsonl"), gpath) == 0
    assert gate.run_gate(str(tmp_path / "empty.jsonl"),
                         str(tmp_path / "x.json"), write_golden=True) == 2


def test_gate_tolerates_torn_ledger_tail(tmp_path, capsys):
    lpath, gpath = _setup_gate(tmp_path)
    fsio.append_bytes(lpath, b'{"torn...')
    assert gate.run_gate(lpath, gpath) == 0
    assert "torn" in capsys.readouterr().out


# -- doctor / statusz verdict ----------------------------------------------
def test_doctor_check_perf_regression_names_dominant_mover():
    from paddle_tpu.observability.doctor import check_perf_regression
    base = _mk_row(scenario="moe", p50=40.0)
    golden = ledger.golden_from_rows({"moe": base})
    rec = {"kind": "bench.row", "scenario": "moe",
           "step_time_p50_ms": 55.0,
           "phases_ms": {"data": 1.0, "compute": 53.0, "readback": 0.5,
                         "collective": 0.5},
           "compile_wall_ms": 2000.0, "device_kind": "cpu"}
    findings = check_perf_regression({0: [rec]}, golden=golden)
    assert len(findings) == 1
    f = findings[0]
    assert f["kind"] == "perf_regression"
    assert f["data"]["scenario"] == "moe"
    assert f["data"]["dominant"] == "compute"
    assert any("dominant mover: compute" in e for e in f["evidence"])
    # within threshold → silent; no golden → silent
    ok = dict(rec, step_time_p50_ms=41.0)
    assert check_perf_regression({0: [ok]}, golden=golden) == []
    assert check_perf_regression({0: [rec]}, golden={}) == []


def test_statusz_surfaces_perf_section(tmp_path, monkeypatch):
    from paddle_tpu.bench import runner
    from paddle_tpu.observability.monitor import StatusServer
    from paddle_tpu.observability.registry import get_registry
    reg = get_registry()
    reg.gauge("perf.step_time_ms[scenario=moe]").set(55.0)
    reg.gauge("perf.phase_ms[scenario=moe,phase=compute]").set(53.0)
    gpath = str(tmp_path / "golden.json")
    ledger.write_golden(ledger.golden_from_rows(
        {"moe": _mk_row(scenario="moe", p50=40.0)}), gpath)
    monkeypatch.setattr(ledger, "default_golden_path", lambda: gpath)
    try:
        status = StatusServer(port=0).statusz()
        perf = status["perf"]
        assert perf["scenarios"]["moe"]["step_time_ms"] == 55.0
        assert perf["scenarios"]["moe"]["phases_ms"]["compute"] == 53.0
        verdicts = perf["perf_regression"]
        assert verdicts and verdicts[0]["scenario"] == "moe"
        assert verdicts[0]["dominant"] == "compute"
    finally:
        reg.gauge("perf.step_time_ms[scenario=moe]").set(0.0)


# -- the matrix itself ------------------------------------------------------
def test_scenario_registry_covers_the_matrix():
    from paddle_tpu.bench import scenarios
    have = set(scenarios.names())
    assert {"gpt_pretrain_fused", "gpt_pretrain_unfused", "moe",
            "long_context", "resnet", "mnist", "serve"} <= have
    with pytest.raises(KeyError, match="unknown scenario"):
        scenarios.get("nope")


def test_run_scenario_emits_valid_row(tmp_path):
    # one in-process matrix entry end to end: scenario → row → ledger.
    # mnist is the cheapest registered scenario.
    from paddle_tpu.bench.runner import run_scenario
    row = run_scenario("mnist", "smoke")
    assert schema.validate_row(row) == []
    assert row["scenario"] == "mnist"
    assert row["compile"]["traces"] >= 1
    assert row["extra"]["images_per_sec"] > 0
    path = ledger.append_row(row, str(tmp_path / "ledger.jsonl"))
    assert ledger.read_ledger(path)[0]["scenario"] == "mnist"
