"""Run-doctor tests (ISSUE 4): compile/retrace tracking (storm detection
naming the offending argument), HBM watermark sampling + OOM postmortem,
cross-worker straggler attribution on synthetic skewed streams, schema-
version drop accounting, Prometheus label escaping, and the e2e
acceptance drill — a scripted degraded run (shape churn + an injected
slow worker) whose ``diagnosis.json`` names the retrace-causing argument
and the straggler worker index."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu.observability import aggregate as agg_mod
from paddle_tpu.observability import compilation, doctor
from paddle_tpu.observability import memory as mem_mod
from paddle_tpu.observability.registry import MetricsRegistry
from paddle_tpu.observability.sinks import PrometheusTextfile

pytestmark = pytest.mark.telemetry


class _ListSink:
    def __init__(self):
        self.records = []

    def write(self, record):
        self.records.append(record)

    def flush(self):
        pass

    def close(self):
        pass


def _tracked_registry():
    reg = MetricsRegistry()
    sink = _ListSink()
    reg.add_sink(sink)
    return reg, sink


# -- compile/retrace tracking ----------------------------------------------
class TestCompileTracking:
    def test_hit_miss_classification(self):
        reg, sink = _tracked_registry()
        tr = compilation.CompileTracker(registry=reg)
        f = compilation.track_jit(jax.jit(lambda x: x + 1), name="f",
                                  arg_names=("x",), tracker=tr)
        f(jnp.zeros((2, 4)))
        f(jnp.zeros((2, 4)))            # same signature → cache hit
        f(jnp.zeros((2, 5)))            # new shape → retrace
        stats = tr.stats("f")
        assert stats == {"calls": 3, "walks": 2, "traces": 2,
                         "retraces": 1, "storms": 0}
        compiles = [r for r in sink.records if r["kind"] == "compile"]
        assert len(compiles) == 2
        assert compiles[0]["retrace"] is False
        assert compiles[1]["retrace"] is True
        assert compiles[1]["changed"] == [
            {"arg": "x", "detail": "float32[2,4] -> float32[2,5]"}]
        assert compiles[1]["wall_ms"] > 0

    def test_retrace_storm_names_offending_argument(self):
        """Force shape churn on ONE argument and assert the storm record
        names it (the ISSUE 4 satellite contract)."""
        reg, sink = _tracked_registry()
        tr = compilation.CompileTracker(registry=reg, storm_threshold=3,
                                        storm_window=16)
        f = compilation.track_jit(
            jax.jit(lambda w, seq: (w * seq).sum()), name="step",
            arg_names=("weights", "seq"), tracker=tr)
        w = jnp.ones((4,))
        for n in (8, 9, 10, 11):        # seq churns, weights stable
            f(w, jnp.zeros((n, 4)))
        storms = [r for r in sink.records
                  if r["kind"] == "compile.retrace_storm"]
        assert len(storms) == 1
        assert storms[0]["culprit"] == "seq"
        assert storms[0]["function"] == "step"
        assert storms[0]["retraces"] >= 3
        assert reg.counter("compile.storms[fn=step]").value == 1

    def test_storm_rearms_after_firing(self):
        reg, sink = _tracked_registry()
        tr = compilation.CompileTracker(registry=reg, storm_threshold=2,
                                        storm_window=8)
        f = compilation.track_jit(jax.jit(lambda x: x), name="g",
                                  arg_names=("x",), tracker=tr)
        for n in range(1, 6):
            f(jnp.zeros((n,)))
        storms = [r for r in sink.records
                  if r["kind"] == "compile.retrace_storm"]
        assert len(storms) == 2         # 4 retraces, threshold 2, re-armed

    def test_structure_change_named(self):
        prev = [compilation.arg_signature({"a": 1})]
        cur = [compilation.arg_signature({"a": 1, "b": 2})]
        changed = compilation.diff_signatures(prev, cur, ["state"])
        assert changed == [{"arg": "state", "detail": "structure changed"}]

    def test_tracking_never_breaks_the_call(self):
        tr = compilation.CompileTracker(registry=MetricsRegistry())
        f = compilation.track_jit(lambda x: x * 2, name="plain",
                                  tracker=tr)
        assert f(21) == 42              # non-jitted callables work too

    def test_hapi_prepare_is_tracked(self):
        compilation.reset_tracker()
        net = pt.nn.Sequential(pt.nn.Linear(8, 4))
        model = pt.Model(net)
        model.prepare(optimizer=pt.optimizer.Adam(learning_rate=1e-3),
                      loss=pt.nn.CrossEntropyLoss())
        x = np.random.randn(4, 8).astype("float32")
        y = np.random.randint(0, 4, (4,)).astype("int64")
        model.train_batch([x], [y])
        assert compilation.get_tracker().stats(
            "hapi.train_step")["traces"] == 1
        model.train_batch([x], [y])     # same shapes → no new trace
        assert compilation.get_tracker().stats(
            "hapi.train_step")["traces"] == 1


# -- HBM watermarks ---------------------------------------------------------
class TestMemorySampler:
    @staticmethod
    def _stats_seq(rows):
        it = iter(rows)
        return lambda: next(it)

    def test_cadence_and_deltas(self):
        reg, sink = _tracked_registry()
        rows = [{"tpu:0": {"bytes_in_use": 100 * (i + 1),
                           "peak_bytes_in_use": 150 * (i + 1),
                           "largest_alloc_size": 64,
                           "bytes_limit": 1000}} for i in range(4)]
        ms = mem_mod.MemorySampler(every=2, stats_fn=self._stats_seq(rows),
                                   registry=reg)
        for step in range(8):
            ms.sample(step)
        recs = [r for r in sink.records if r["kind"] == "memory"]
        assert len(recs) == 4           # every=2 over 8 steps
        assert recs[0]["devices"]["tpu:0"]["in_use_delta"] == 0
        assert recs[1]["devices"]["tpu:0"]["in_use_delta"] == 100
        assert recs[1]["devices"]["tpu:0"]["largest_alloc_delta"] == 0
        assert recs[1]["devices"]["tpu:0"]["utilization"] == 0.2
        assert reg.gauge(
            "memory.bytes_in_use[device=tpu:0]").value == 400

    def test_cpu_backend_is_silent(self):
        reg, sink = _tracked_registry()
        ms = mem_mod.MemorySampler(every=1, registry=reg)
        assert ms.sample(0) is None     # CPU: no allocator stats
        assert sink.records == []

    def test_oom_postmortem_dumps_last_table(self):
        reg, sink = _tracked_registry()
        rows = [{"tpu:0": {"bytes_in_use": 900, "peak_bytes_in_use": 980,
                           "bytes_limit": 1000}}]
        ms = mem_mod.MemorySampler(every=1,
                                   stats_fn=self._stats_seq(rows),
                                   registry=reg)
        ms.sample(0)
        err = RuntimeError("RESOURCE_EXHAUSTED: out of memory while "
                           "allocating 512 bytes")
        assert mem_mod.is_oom_error(err)
        assert not mem_mod.is_oom_error(ValueError("shape mismatch"))
        rec = mem_mod.oom_postmortem(sampler=ms, error=err, step=7)
        assert rec["step"] == 7
        assert rec["devices"]["tpu:0"]["bytes_in_use"] == 900
        oom = [r for r in sink.records if r["kind"] == "memory.oom"]
        assert len(oom) == 1 and "RESOURCE_EXHAUSTED" in oom[0]["error"]
        assert reg.counter("memory.oom_count").value == 1

    def test_env_knob(self, monkeypatch):
        monkeypatch.setenv(mem_mod.MEM_SAMPLE_ENV, "5")
        assert mem_mod.default_sample_every() == 5
        assert mem_mod.MemorySampler().every == 5


# -- Prometheus label escaping ---------------------------------------------
class TestPrometheusLabels:
    def test_labeled_gauges_and_escaping(self, tmp_path):
        reg = MetricsRegistry()
        reg.gauge("memory.bytes_in_use[device=tpu:0]").set(42)
        reg.gauge('memory.bytes_in_use[device=we"ird\\dev]').set(7)
        reg.histogram("compile.wall_ms[fn=hapi.train_step]").observe(3.0)
        p = PrometheusTextfile(str(tmp_path / "m.prom"), interval=0)
        p.bind(reg)
        text = p.render()
        assert ('paddle_tpu_memory_bytes_in_use{device="tpu:0"} 42'
                in text)
        # label VALUES escaped, not name-sanitized
        assert ('device="we\\"ird\\\\dev"') in text
        assert ('paddle_tpu_compile_wall_ms_count{fn="hapi.train_step"}'
                in text)
        # one TYPE line per base metric even with multiple label sets
        assert text.count("# TYPE paddle_tpu_memory_bytes_in_use") == 1


# -- schema versioning ------------------------------------------------------
class TestSchemaVersion:
    def test_unknown_schema_dropped_with_accounting(self, tmp_path):
        path = tmp_path / "worker-0.jsonl"
        lines = [{"ts": 1.0, "kind": "step", "step": 0,
                  "step_time_ms": 5.0},
                 {"ts": 2.0, "kind": "step", "schema_version": 1,
                  "step": 1, "step_time_ms": 5.0},
                 {"ts": 3.0, "kind": "future-thing",
                  "schema_version": 99}]
        path.write_text("\n".join(json.dumps(l) for l in lines)
                        + "\n{torn")
        drops = {}
        recs = agg_mod.read_worker_stream(str(path), drops=drops)
        assert len(recs) == 2           # v-less (=v1) and v1 kept
        assert drops == {"torn_lines": 1, "unknown_schema": 1}

    def test_summary_stamped_and_drops_surface(self, tmp_path):
        mdir = tmp_path / "run" / "metrics"
        mdir.mkdir(parents=True)
        (mdir / "worker-0.jsonl").write_text(
            json.dumps({"ts": 1.0, "kind": "step", "step": 0,
                        "step_time_ms": 1.0}) + "\n"
            + json.dumps({"ts": 2.0, "kind": "x",
                          "schema_version": 42}) + "\n")
        summary = obs.aggregate_run(str(tmp_path / "run"))
        assert summary["schema_version"] == agg_mod.SCHEMA_VERSION
        assert summary["dropped"]["unknown_schema"] == 1


# -- straggler attribution on synthetic streams ----------------------------
def _synthetic_workers(n_steps=40, slow_worker=2, slow_ms=30.0,
                       base_ms=100.0):
    rng = np.random.RandomState(7)
    workers = {}
    for wid in range(3):
        recs = []
        for s in range(n_steps):
            t = base_ms + float(rng.rand()) * 2.0
            if wid == slow_worker:
                t += slow_ms
            recs.append({"ts": 1000.0 + s, "kind": "step", "step": s,
                         "step_time_ms": t, "data_ms": 1.0})
        workers[wid] = recs
    return workers


class TestStragglerStats:
    def test_attributes_slowest_worker(self):
        stats = agg_mod.straggler_stats(_synthetic_workers())
        assert stats["straggler"] == 2
        assert stats["straggler_fraction"] == 1.0
        assert stats["aligned_steps"] == 40
        assert stats["spread_ms"]["p50"] == pytest.approx(30.0, abs=5.0)
        assert stats["relative_spread"]["p99"] == pytest.approx(
            0.3, abs=0.1)
        assert stats["worker_mean_step_ms"]["2"] > \
            stats["worker_mean_step_ms"]["0"]

    def test_single_worker_returns_none(self):
        workers = {0: _synthetic_workers()[0]}
        assert agg_mod.straggler_stats(workers) is None

    def test_rollback_revisited_steps_keep_last(self):
        workers = _synthetic_workers(n_steps=10)
        # worker 0 rolled back and replayed step 3 fast
        workers[0].append({"ts": 2000.0, "kind": "step", "step": 3,
                           "step_time_ms": 50.0, "data_ms": 1.0})
        stats = agg_mod.straggler_stats(workers)
        assert stats["aligned_steps"] == 10


# -- the doctor -------------------------------------------------------------
def _write_stream(mdir, wid, records):
    os.makedirs(mdir, exist_ok=True)
    with open(os.path.join(mdir, f"worker-{wid}.jsonl"), "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")


def _degraded_run(tmp_path):
    run_dir = str(tmp_path / "run")
    mdir = os.path.join(run_dir, "metrics")
    workers = _synthetic_workers(n_steps=30, slow_worker=1)
    streams = {0: list(workers[0]), 1: list(workers[1])}
    streams[0] += [
        {"ts": 1000.5, "kind": "compile", "function": "hapi.train_step",
         "retrace": False, "changed": [], "wall_ms": 500.0, "nargs": 6},
        *[{"ts": 1001.0 + i, "kind": "compile",
           "function": "hapi.train_step", "retrace": True,
           "changed": [{"arg": "data[1]",
                        "detail": "int32[2,8] -> int32[2,12]"}],
           "wall_ms": 400.0, "nargs": 6} for i in range(4)],
        {"ts": 1006.0, "kind": "compile.retrace_storm",
         "function": "hapi.train_step", "retraces": 4, "window": 16,
         "culprits": ["data[1]"], "culprit": "data[1]"},
    ]
    for wid, recs in streams.items():
        _write_stream(mdir, wid, recs)
    return run_dir


class TestDoctor:
    def test_degraded_run_ranked_findings(self, tmp_path):
        run_dir = _degraded_run(tmp_path)
        diag = doctor.diagnose(run_dir)
        assert not diag["healthy"]
        kinds = [f["kind"] for f in diag["findings"]]
        assert "retrace_storm" in kinds and "straggler" in kinds
        storm = next(f for f in diag["findings"]
                     if f["kind"] == "retrace_storm")
        assert storm["data"]["argument"] == "data[1]"
        assert storm["data"]["function"] == "hapi.train_step"
        assert any("int32[2,8] -> int32[2,12]" in ev
                   for ev in storm["evidence"])
        strag = next(f for f in diag["findings"]
                     if f["kind"] == "straggler")
        assert strag["data"]["worker"] == 1
        # severities rank the list
        sevs = [f["severity"] for f in diag["findings"]]
        assert sevs == sorted(sevs, reverse=True)
        # diagnosis.json landed next to the metrics
        on_disk = json.load(open(os.path.join(run_dir,
                                              "diagnosis.json")))
        assert on_disk["findings"] == diag["findings"]

    def test_oom_outranks_everything(self, tmp_path):
        run_dir = _degraded_run(tmp_path)
        extra = [{"ts": 1030.0, "kind": "memory.oom", "step": 29,
                  "error": "RESOURCE_EXHAUSTED",
                  "devices": {"tpu:0": {"bytes_in_use": 990,
                                        "peak_bytes_in_use": 999,
                                        "bytes_limit": 1000,
                                        "utilization": 0.99}}}]
        _write_stream(os.path.join(run_dir, "metrics"), 2, extra)
        diag = doctor.diagnose(run_dir)
        assert diag["findings"][0]["kind"] == "oom"
        assert diag["findings"][0]["data"]["device"] == "tpu:0"

    def test_hbm_creep_detected(self, tmp_path):
        run_dir = str(tmp_path / "run")
        recs = [{"ts": 1000.0 + i, "kind": "step", "step": i,
                 "step_time_ms": 100.0, "data_ms": 1.0}
                for i in range(10)]
        recs += [{"ts": 1000.0 + i, "kind": "memory", "step": i,
                  "devices": {"tpu:0": {
                      "bytes_in_use": 500 + 40 * i,
                      "peak_bytes_in_use": 600 + 40 * i,
                      "bytes_limit": 10_000}}} for i in range(10)]
        _write_stream(os.path.join(run_dir, "metrics"), 0, recs)
        diag = doctor.diagnose(run_dir)
        creeps = [f for f in diag["findings"] if f["kind"] == "hbm_creep"]
        assert len(creeps) == 1
        assert creeps[0]["data"]["device"] == "tpu:0"
        assert creeps[0]["data"]["growth"] == pytest.approx(0.72)

    def test_data_starved_detected(self, tmp_path):
        run_dir = str(tmp_path / "run")
        recs = [{"ts": 1000.0 + i, "kind": "step", "step": i,
                 "step_time_ms": 100.0, "data_ms": 60.0}
                for i in range(10)]
        _write_stream(os.path.join(run_dir, "metrics"), 0, recs)
        diag = doctor.diagnose(run_dir)
        assert any(f["kind"] == "data_starved" for f in diag["findings"])

    def test_healthy_run(self, tmp_path):
        run_dir = str(tmp_path / "run")
        recs = [{"ts": 1000.0 + i, "kind": "step", "step": i,
                 "step_time_ms": 100.0 + (i % 3), "data_ms": 1.0}
                for i in range(10)]
        _write_stream(os.path.join(run_dir, "metrics"), 0, recs)
        diag = doctor.diagnose(run_dir)
        assert diag["healthy"] and diag["findings"] == []

    def test_no_telemetry_returns_none(self, tmp_path):
        assert doctor.diagnose(str(tmp_path / "empty")) is None

    def test_verdicts_mirrored_into_supervisor_report(self, tmp_path):
        from paddle_tpu.supervisor.report import SupervisorReport
        run_dir = _degraded_run(tmp_path)
        report = SupervisorReport(os.path.join(run_dir,
                                               "supervisor_report.json"))
        report.record("run_start", run_dir=run_dir)
        doctor.diagnose(run_dir)
        loaded = SupervisorReport.load(
            os.path.join(run_dir, "supervisor_report.json"))
        verdicts = loaded.of_kind("doctor.verdict")
        assert {v["verdict"] for v in verdicts} >= {"retrace_storm",
                                                    "straggler"}

    def test_cli_main(self, tmp_path, capsys):
        run_dir = _degraded_run(tmp_path)
        assert doctor.main([run_dir]) == 0
        out = capsys.readouterr().out
        assert "retrace_storm" in out and "straggler" in out
        assert doctor.main([str(tmp_path / "nothing")]) == 1
        assert doctor.main([]) == 2
        assert doctor.main(["--json", run_dir]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["findings"]


# -- the acceptance drill ---------------------------------------------------
class _RaggedLoader(pt.io.DataLoader):
    """Batches whose batch dimension churns — the classic leaky data
    pipeline that forces a retrace per distinct shape."""

    def __init__(self, sizes, n_feat=8):
        self.sizes = list(sizes)
        self.n_feat = n_feat

    def __iter__(self):
        rng = np.random.RandomState(3)
        for b in self.sizes:
            x = rng.randn(b, self.n_feat).astype("float32")
            y = rng.randint(0, 4, (b,)).astype("int64")
            yield [x, y]

    def __len__(self):
        return len(self.sizes)


class TestDoctorE2E:
    def test_degraded_fit_diagnosed(self, tmp_path):
        """ISSUE 4 acceptance: scripted degraded run — retraces injected
        via shape churn, a slow worker injected via
        ``testing/faults.slow_call`` — and the doctor's top findings
        name the retrace-causing argument and the straggler worker."""
        from paddle_tpu.supervisor import RunSupervisor
        from paddle_tpu.testing import faults
        compilation.reset_tracker()
        run_dir = str(tmp_path / "run")
        sizes = [4, 6, 8, 10, 4, 6, 8, 10]    # 4 distinct shapes →
        # 3 retraces inside the storm window
        for wid in (0, 1):
            net = pt.nn.Sequential(pt.nn.Linear(8, 16), pt.nn.ReLU(),
                                   pt.nn.Linear(16, 4))
            model = pt.Model(net)
            model.prepare(
                optimizer=pt.optimizer.Adam(learning_rate=1e-3),
                loss=pt.nn.CrossEntropyLoss())
            if wid == 1:                       # the straggler
                model._train_step = faults.slow_call(
                    model._train_step, 0.25)
            sup = RunSupervisor(run_dir, watchdog_secs=120.0,
                                worker_id=wid)
            model.fit(_RaggedLoader(sizes), epochs=1, verbose=0,
                      supervisor=sup)
        diag = doctor.diagnose(run_dir)
        assert diag is not None and not diag["healthy"]
        top_kinds = {f["kind"] for f in diag["findings"][:3]}
        assert "retrace_storm" in top_kinds
        assert "straggler" in top_kinds
        storm = next(f for f in diag["findings"]
                     if f["kind"] == "retrace_storm")
        assert storm["data"]["function"] == "hapi.train_step"
        assert str(storm["data"]["argument"]).startswith("data[")
        strag = next(f for f in diag["findings"]
                     if f["kind"] == "straggler")
        assert strag["data"]["worker"] == 1
        # the CLI renders the same verdicts
        assert doctor.main([run_dir]) == 0
