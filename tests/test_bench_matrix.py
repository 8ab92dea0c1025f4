"""The scenario matrix (ISSUE 13): row schema, registry, one run end to
end."""
import pytest

from paddle_tpu.bench import harness, schema


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


# -- the matrix itself ------------------------------------------------------
def test_scenario_registry_covers_the_matrix():
    from paddle_tpu.bench import scenarios
    have = set(scenarios.names())
    assert {"gpt_pretrain_fused", "gpt_pretrain_unfused", "moe",
            "long_context", "resnet", "mnist", "serve"} <= have
    with pytest.raises(KeyError, match="unknown scenario"):
        scenarios.get("nope")


def test_run_scenario_emits_valid_row():
    # one in-process matrix entry end to end: scenario → row.
    # mnist is the cheapest registered scenario.
    from paddle_tpu.bench.runner import run_scenario
    row = run_scenario("mnist", "smoke")
    assert schema.validate_row(row) == []
    assert row["scenario"] == "mnist"
    assert row["compile"]["traces"] >= 1
    assert row["extra"]["images_per_sec"] > 0
