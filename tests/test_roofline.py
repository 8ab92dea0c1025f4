"""MFU microscope (ISSUE 19): HLO parsing, the per-device roofline fit,
the gap budget's sum-to-measured invariant, schema v2 plumbing, the
synthetic drill, HLO dumping, and the doctor's ``mfu_gap`` verdict."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.bench import harness, schema
from paddle_tpu.observability import doctor, roofline
from paddle_tpu.observability.compilation import get_tracker, track_jit
from paddle_tpu.observability.mfu import DEVICE_SPECS, device_spec


@pytest.fixture(autouse=True)
def _clean_observatory():
    roofline.reset_observatory()
    yield
    roofline.reset_observatory()


def _mk_row(p50=10.0, roofline_block=None, **kw):
    phases = kw.pop("phases_ms", {"data": 1.0, "compute": 7.0,
                                  "readback": 1.0, "collective": 1.0})
    return schema.new_row(
        kw.pop("scenario", "gpt_pretrain_fused"), kw.pop("mode", "smoke"),
        step_times_ms=[p50 * 0.99, p50, p50 * 1.01],
        phases_ms=phases, config={"batch": 2},
        tokens_per_sec=1000.0, mfu=0.01,
        roofline=roofline_block, **kw)


# -- taxonomy pins ----------------------------------------------------------
def test_sink_taxonomy_is_pinned_across_modules():
    # schema.GAP_SINKS is a literal (no bench→observability import at
    # module scope); this is the cross-check that keeps them identical
    assert schema.GAP_SINKS == roofline.SINKS
    assert "mxu" in roofline.SINKS and "residual" in roofline.SINKS


def test_device_spec_known_and_unknown():
    # the table is keyed by the device_kind string the chip reports — a
    # v5e says "TPU v5 lite", and the marketing name is NOT a key
    spec = device_spec("TPU v5 lite")
    assert spec["known"] and spec["gen"] == "v5e"
    assert (spec["bf16_tflops"], spec["int8_tops"], spec["hbm_gbps"]) == (
        197.0, 393.0, 819.0)
    assert spec["bf16_tflops"] == DEVICE_SPECS["TPU v5 lite"]["bf16_tflops"]
    for kind in ("TPU v5e", "Frobnicator 9000", "cpu"):
        unk = device_spec(kind)
        assert not unk["known"] and unk["gen"] is None
        # an unknown device carries NO peaks — nothing to divide by
        assert not {"bf16_tflops", "int8_tops", "hbm_gbps"} & set(unk)


def test_mfu_is_none_without_a_known_peak():
    from paddle_tpu.observability.mfu import mfu, peak_flops_per_sec
    import jax
    assert jax.devices()[0].device_kind not in DEVICE_SPECS   # the CPU mesh
    assert peak_flops_per_sec() is None
    assert mfu(1e5, 1e9) is None
    assert mfu(1e5, 1e9, peak=2e14) == pytest.approx(0.5)


# -- HLO parsing ------------------------------------------------------------
def test_parse_hlo_ops_on_real_compiled_dot():
    @jax.jit
    def f(a, b):
        return jnp.tanh(a @ b)

    a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 32), jnp.float32)
    text = f.lower(a, b).compile().as_text()
    ops = roofline.parse_hlo_ops(text)
    assert ops, "no ops parsed from compiled HLO"
    dots = [o for o in ops if o["klass"] == "mxu"]
    assert dots, f"no MXU op found in {[o['opcode'] for o in ops]}"
    # 2 * M*N*K exactly, from lhs_contracting_dims
    assert any(o["flops"] == 2 * 64 * 32 * 128 for o in dots)
    assert all(o["bytes"] is None or o["bytes"] >= 0 for o in ops)
    classes = {o["klass"] for o in ops}
    assert classes <= {"mxu", "hbm", "comm", "host"}


def test_parse_hlo_ops_garbage_degrades_to_empty():
    assert roofline.parse_hlo_ops("") == []
    assert roofline.parse_hlo_ops("not hlo at all\n{}\n") == []


def test_normalize_cost_analysis_sparse_and_absent():
    n = roofline._normalize_cost_analysis
    assert n(None) == {"flops": None, "bytes_accessed": None,
                       "transcendentals": None}
    assert n([]) == n(None)
    assert n([{}]) == n(None)          # CPU backends may omit every key
    got = n([{"flops": 7.0, "bytes accessed": 3.0}])
    assert got["flops"] == 7.0 and got["bytes_accessed"] == 3.0
    assert n({"flops": 1.0})["flops"] == 1.0  # dict form tolerated


def test_fit_roofline_counts_unmodeled_ops():
    spec = device_spec("TPU v5 lite")
    ops = [{"name": "a", "opcode": "dot", "klass": "mxu",
            "flops": 1e9, "bytes": 1e6, "integer": False},
           {"name": "b", "opcode": "mystery", "klass": "hbm",
            "flops": None, "bytes": None, "integer": False}]
    fit = roofline.fit_roofline(ops, spec)
    assert fit["ops_modeled"] == 1 and fit["ops_unmodeled"] == 1
    assert fit["mxu_s"] > 0


# -- gap budget -------------------------------------------------------------
_PHASES = {"data": 1.0, "compute": 7.0, "readback": 0.5, "collective": 1.5}


def test_gap_budget_sums_to_measured_unknown_device():
    spec = device_spec("Frobnicator 9000")
    blk = roofline.gap_budget(10.0, _PHASES, padding_frac=0.1, spec=spec)
    b = blk["buckets_ms"]
    assert abs(sum(b.values()) - 10.0) < 1e-6
    # unknown device: compute minus padding is explicitly unattributable
    assert b["mxu"] == 0.0 and b["memory_bound"] == 0.0
    assert b["unknown_device"] == pytest.approx(7.0 - 0.7)
    assert b["padding"] == pytest.approx(0.7)
    assert b["comm"] == pytest.approx(1.5)
    assert b["host"] == pytest.approx(1.5)
    assert blk["dominant_sink"] == "unknown_device"
    assert 0.0 <= blk["coverage"] <= 1.0
    assert not blk["device"]["known"]


def test_gap_budget_known_device_uses_fit():
    spec = device_spec("TPU v5 lite")
    analyses = {"step": {"name": "step", "error": None, "cost": {},
                         "fit": {"mxu_s": 0.004, "memory_s": 0.002,
                                 "comm_s": 0.0, "flops": 1e12,
                                 "bytes": 1e9, "comm_bytes": 0,
                                 "ops_modeled": 3, "ops_unmodeled": 0}}}
    blk = roofline.gap_budget(10.0, _PHASES, analyses=analyses,
                              calls={"step": 5}, spec=spec)
    b = blk["buckets_ms"]
    assert b["mxu"] == pytest.approx(4.0)
    assert b["memory_bound"] == pytest.approx(2.0)
    assert b["unknown_device"] == 0.0
    assert abs(sum(b.values()) - 10.0) < 1e-6
    assert blk["modeled_step_ms"] == pytest.approx(4.0 + 2.0 + 1.5 + 1.5)
    assert blk["programs"]["step"]["share"] == 1.0
    assert blk["ops"]["modeled"] == 3


def test_gap_budget_call_share_weighting():
    spec = device_spec("TPU v5 lite")
    fit_a = {"mxu_s": 0.004, "memory_s": 0.0, "comm_s": 0.0,
             "flops": 0, "bytes": 0, "comm_bytes": 0,
             "ops_modeled": 1, "ops_unmodeled": 0}
    fit_b = dict(fit_a, mxu_s=0.008)
    blk = roofline.gap_budget(
        10.0, _PHASES,
        analyses={"a": {"fit": fit_a}, "b": {"fit": fit_b}},
        calls={"a": 3, "b": 1}, spec=spec)
    # 3/4 * 4ms + 1/4 * 8ms = 5ms
    assert blk["buckets_ms"]["mxu"] == pytest.approx(5.0)


def test_inflation_drill_marks_injected(monkeypatch):
    monkeypatch.setenv(roofline.INFLATE_ENV, "memory_bound:0.6")
    blk = roofline.gap_budget(10.0, _PHASES,
                              spec=device_spec("Frobnicator"))
    b = blk["buckets_ms"]
    assert blk["injected"] == {"sink": "memory_bound", "frac": 0.6}
    assert b["memory_bound"] == pytest.approx(6.0)
    assert abs(sum(b.values()) - 10.0) < 1e-6
    assert blk["dominant_sink"] == "memory_bound"


def test_inflation_drill_bad_values_ignored(monkeypatch):
    for bad in ("nonsense", "memory_bound", "notasink:0.5", ":"):
        monkeypatch.setenv(roofline.INFLATE_ENV, bad)
        blk = roofline.gap_budget(10.0, _PHASES,
                                  spec=device_spec("Frobnicator"))
        assert blk["injected"] is None, bad


# -- schema v2 plumbing -----------------------------------------------------
def test_new_row_synthesizes_degraded_block():
    row = _mk_row()   # no roofline passed by the producer
    assert schema.validate_row(row) == []
    roof = row["roofline"]
    assert roof["degraded"]
    assert abs(sum(roof["buckets_ms"].values())
               - roof["measured_step_ms"]) < 1e-6


def test_validate_row_rejects_broken_roofline():
    row = _mk_row()
    bad = json.loads(json.dumps(row))
    bad["roofline"]["buckets_ms"]["host"] += 5.0
    assert any("sum" in e for e in schema.validate_row(bad))
    bad = json.loads(json.dumps(row))
    del bad["roofline"]["buckets_ms"]["comm"]
    assert any("comm" in e for e in schema.validate_row(bad))
    bad = json.loads(json.dumps(row))
    bad["roofline"]["dominant_sink"] = "gremlins"
    assert any("dominant_sink" in e for e in schema.validate_row(bad))
    bad = json.loads(json.dumps(row))
    bad["roofline"] = None
    assert any("roofline" in e for e in schema.validate_row(bad))


def test_v1_rows_stay_readable_and_gap_metrics_none():
    row = _mk_row()
    v1 = {k: v for k, v in row.items() if k != "roofline"}
    v1["schema_version"] = 1
    assert schema.validate_row(v1) == []    # old rows remain valid
    assert schema.metric_value(v1, "gap_host_ms") is None
    assert schema.metric_value(v1, "roofline_coverage") is None
    assert schema.metric_value(row, "gap_host_ms") is not None
    assert schema.metric_value(
        row, "roofline_coverage") == row["roofline"]["coverage"]


def test_gap_metrics_are_trendable_axes():
    assert "gap_host_ms" in schema.METRICS
    assert "roofline_coverage" in schema.METRICS
    assert "gap_mxu_ms" not in schema.METRICS  # mxu is work, not gap


# -- track_jit -> observatory -> block (e2e on CPU) -------------------------
def test_capture_window_end_to_end():
    def _step(a, b):
        return jnp.tanh(a @ b).sum()

    step = track_jit(jax.jit(_step), name="roof_step")

    a = jnp.ones((32, 64), jnp.float32)
    b = jnp.ones((64, 16), jnp.float32)
    with roofline.capture_window() as rw:
        for _ in range(3):
            step(a, b).block_until_ready()
    entries = roofline.get_observatory().entries()
    assert "roof_step" in entries
    blk = rw.build_block(10.0, _PHASES, padding_frac=0.0)
    assert blk["degraded"] is None
    prog = blk["programs"]["roof_step"]
    assert prog["error"] is None
    assert prog["flops"] and prog["flops"] >= 2 * 32 * 16 * 64
    assert abs(sum(blk["buckets_ms"].values()) - 10.0) < 1e-6
    # CPU is not in the device table → honest unknown_device routing
    assert not blk["device"]["known"]
    assert blk["buckets_ms"]["unknown_device"] > 0
    # outside the window nothing is captured
    assert not roofline.capture_active()


def test_capture_window_without_programs_degrades():
    with roofline.capture_window() as rw:
        pass
    blk = rw.build_block(10.0, _PHASES)
    assert blk["degraded"] == "no jitted step captured"
    assert abs(sum(blk["buckets_ms"].values()) - 10.0) < 1e-6


def test_harness_roofline_window_block():
    with harness.RooflineWindow() as rw:
        pass
    blk = rw.block([9.0, 10.0, 11.0], _PHASES, padding_frac=0.2)
    assert blk["measured_step_ms"] == pytest.approx(10.0)
    assert blk["padding_frac"] == pytest.approx(0.2)
    assert schema.validate_row(_mk_row(roofline_block=blk)) == []


# -- HLO dump knob ----------------------------------------------------------
def test_hlo_dump_and_gc(tmp_path, monkeypatch):
    monkeypatch.setenv(roofline.HLO_DUMP_ENV, str(tmp_path))
    monkeypatch.setenv(roofline.HLO_DUMP_KEEP_ENV, "2")

    @jax.jit
    def g(x):
        return x * 2.0

    obs = roofline.get_observatory()
    obs.enable()
    for i in range(4):
        arg = jax.ShapeDtypeStruct((8, 8 + i), jnp.float32)
        obs.record(f"fn{i}", g, (arg,), {}, sig_key=1000 + i, miss=True)
    names = sorted(os.listdir(str(tmp_path)))
    lowered = [n for n in names if n.endswith(".lowered.txt")]
    compiled = [n for n in names if n.endswith(".compiled.txt")]
    assert len(lowered) == 2 and len(compiled) == 2, names
    # sig-keyed filenames: the key is embedded as zero-padded hex
    assert any(f"{1003:016x}" in n for n in names)
    body = (tmp_path / compiled[-1]).read_text()
    assert body.strip(), "compiled dump is empty"


def test_capture_active_follows_dump_knob(monkeypatch):
    assert not roofline.capture_active()
    monkeypatch.setenv(roofline.HLO_DUMP_ENV, "/tmp/somewhere")
    assert roofline.capture_active()


# -- doctor verdict ---------------------------------------------------------
def _bench_rec(scenario="moe", dominant="comm", share=0.4, injected=False,
               measured=10.0, ts=1.0):
    buckets = {s: 0.0 for s in schema.GAP_SINKS}
    buckets[dominant] = share * measured
    buckets["mxu"] = measured - share * measured
    return {"kind": "bench.row", "scenario": scenario, "ts": ts,
            "mfu": 0.3,
            "roofline": {"buckets_ms": buckets,
                         "measured_step_ms": measured,
                         "dominant_sink": dominant, "coverage": 0.95,
                         "injected": injected}}


def test_check_mfu_gap_names_dominant_sink():
    (f,) = doctor.check_mfu_gap({0: [_bench_rec(dominant="comm")]})
    assert f["kind"] == "mfu_gap"
    assert f["data"]["dominant"] == "comm"
    assert "comm" in f["title"] and "moe" in f["title"]
    assert any("coverage" in e for e in f["evidence"])


def test_check_mfu_gap_threshold_and_mxu_quiet(monkeypatch):
    # below the default 25% share: no finding
    assert doctor.check_mfu_gap({0: [_bench_rec(share=0.1)]}) == []
    # mxu-dominant is the healthy case, never a finding
    rec = _bench_rec(share=0.4)
    rec["roofline"]["dominant_sink"] = "mxu"
    assert doctor.check_mfu_gap({0: [rec]}) == []
    # threshold is tunable
    monkeypatch.setenv("PTPU_MFU_GAP_FRAC", "0.05")
    assert doctor.check_mfu_gap({0: [_bench_rec(share=0.1)]})


def test_check_mfu_gap_unknown_device_wording_and_drill_flag():
    (f,) = doctor.check_mfu_gap(
        {0: [_bench_rec(dominant="unknown_device")]})
    assert "DEVICE_SPECS" in f["title"] or any(
        "DEVICE_SPECS" in e for e in f["evidence"])
    (f2,) = doctor.check_mfu_gap({0: [_bench_rec(injected=True)]})
    assert f2["data"]["injected"] is True
    assert any("PTPU_ROOFLINE_TEST_INFLATE" in e for e in f2["evidence"])


def test_check_mfu_gap_uses_newest_row_per_scenario():
    old = _bench_rec(dominant="comm", ts=1.0)
    new = _bench_rec(dominant="host", ts=2.0)
    (f,) = doctor.check_mfu_gap({0: [old, new]})
    assert f["data"]["dominant"] == "host"


def test_check_mfu_gap_ignores_rows_without_block():
    assert doctor.check_mfu_gap(
        {0: [{"kind": "bench.row", "scenario": "x"}]}) == []


# -- /statusz ---------------------------------------------------------------
def test_statusz_roofline_section_from_gauges():
    from paddle_tpu.observability.monitor import StatusServer
    from paddle_tpu.observability.registry import MetricsRegistry
    reg = MetricsRegistry()
    buckets = {"mxu": 2.0, "memory_bound": 5.0, "comm": 1.0, "host": 1.0,
               "padding": 0.5, "unknown_device": 0.0, "residual": 0.5}
    for sink, ms in buckets.items():
        reg.gauge(f"roofline.bucket_ms[scenario=moe,sink={sink}]").set(ms)
    reg.gauge("roofline.coverage[scenario=moe]").set(0.95)
    reg.gauge("roofline.modeled_step_ms[scenario=moe]").set(8.0)
    st = StatusServer(port=0, registry=reg).statusz()
    roof = st["roofline"]
    assert roof["scenarios"]["moe"]["buckets_ms"] == buckets
    assert roof["scenarios"]["moe"]["coverage"] == 0.95
    (verdict,) = roof["mfu_gap"]
    assert verdict["dominant"] == "memory_bound"
    # no roofline gauges at all -> section absent, statusz still renders
    st2 = StatusServer(port=0, registry=MetricsRegistry()).statusz()
    assert st2["roofline"] is None
