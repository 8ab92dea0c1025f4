"""Live run monitor tests (ISSUE 5): status server scraped during a real
supervised ``fit()``, flight-recorder dumps on a watchdog-killed hang and
on SIGTERM, the live aggregator naming a straggler from a *partial*
(still-growing) stream, and the doctor ingesting a flight bundle when the
worker JSONL tail was lost."""
import json
import os
import signal
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu.hapi.callbacks import Callback
from paddle_tpu.observability import aggregate as agg_mod
from paddle_tpu.observability import compilation, doctor, flight, monitor
from paddle_tpu.observability.registry import MetricsRegistry
from paddle_tpu.supervisor import RunSupervisor
from paddle_tpu.supervisor.rollback import RollbackBudgetExceeded
from paddle_tpu.testing import faults

pytestmark = pytest.mark.telemetry


def _get(url: str):
    """(status, body bytes) — 503s return instead of raising."""
    try:
        with urllib.request.urlopen(url, timeout=5) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _model(n_in=8, n_out=4):
    net = pt.nn.Sequential(pt.nn.Linear(n_in, n_out))
    model = pt.Model(net)
    model.prepare(optimizer=pt.optimizer.Adam(learning_rate=1e-3),
                  loss=pt.nn.CrossEntropyLoss())
    return model


def _data(n=32, n_in=8, n_cls=4):
    rng = np.random.RandomState(0)
    x = rng.randn(n, n_in).astype("float32")
    y = rng.randint(0, n_cls, (n,)).astype("int64")
    return list(zip(x, y))


class _RaggedLoader(pt.io.DataLoader):
    """Batch-dimension churn → one retrace per distinct shape."""

    def __init__(self, sizes, n_feat=8, slow_secs=0.0):
        self.sizes = list(sizes)
        self.n_feat = n_feat
        self.slow_secs = slow_secs

    def __iter__(self):
        rng = np.random.RandomState(3)
        for b in self.sizes:
            if self.slow_secs:
                faults.hang(self.slow_secs)
            x = rng.randn(b, self.n_feat).astype("float32")
            y = rng.randint(0, 4, (b,)).astype("int64")
            yield [x, y]

    def __len__(self):
        return len(self.sizes)


# -- the status server ------------------------------------------------------
class TestStatusServer:
    def test_scraped_during_supervised_fit(self, tmp_path, monkeypatch):
        """ISSUE 5 satellite: /metrics + /statusz answered mid-``fit()``
        — step counters, live MFU, heartbeat age, watchdog state and
        compile-cache stats all present while batches still run."""
        monkeypatch.setenv(monitor.MONITOR_PORT_ENV, "0")  # ephemeral
        scraped = {}

        class Scraper(Callback):
            def on_train_batch_end(self, step, logs=None):
                sup = self.model._supervisor
                if step == 2 and sup is not None:
                    base = f"http://127.0.0.1:{sup.status_server.port}"
                    scraped["healthz"] = _get(base + "/healthz")
                    scraped["metrics"] = _get(base + "/metrics")[1].decode()
                    scraped["statusz"] = json.loads(
                        _get(base + "/statusz")[1])
                    scraped["missing"] = _get(base + "/nope")[0]

        model = _model()
        sup = RunSupervisor(str(tmp_path / "run"), worker_id=0,
                            sigterm_handler=False)
        model.fit(_data(), batch_size=8, epochs=1, verbose=0,
                  supervisor=sup, callbacks=[Scraper()])
        assert scraped["healthz"][0] == 200
        assert json.loads(scraped["healthz"][1])["ok"] is True
        # a known instrument in Prometheus text format
        assert "paddle_tpu_step_time_ms_count" in scraped["metrics"]
        assert "# TYPE paddle_tpu_step_count counter" in scraped["metrics"]
        sz = scraped["statusz"]
        assert sz["step"] is not None and sz["step"] >= 2
        assert sz["step_time_ms"]["p50"] > 0
        assert sz["step_time_ms"]["p99"] >= sz["step_time_ms"]["p50"]
        assert sz["tokens_per_sec"] > 0
        assert "mfu" in sz          # None on a device with no known peak
        assert sz["heartbeat"]["beats"] >= 1
        assert sz["watchdog"]["timeouts"] == 0
        assert not sz["watchdog"]["closed"]
        assert sz["supervisor"]["running"] is True
        assert "hapi.train_step" in (sz["compile"] or {})
        assert sz["flight"]["capacity"] >= 16
        assert scraped["missing"] == 404
        # the server is torn down with the run
        assert sup.status_server is None

    def test_healthz_503_when_not_running(self):
        reg = MetricsRegistry()

        class _Sup:  # the duck the server reads
            _running = False
            pending_rollback = None
            monitor = type("M", (), {"_last_state": None})()

        with obs.StatusServer(port=0, registry=reg,
                              supervisor=_Sup()) as srv:
            code, body = _get(f"http://127.0.0.1:{srv.port}/healthz")
            assert code == 503
            assert json.loads(body)["state"] == "not-running"

    def test_port_offset_by_worker_rank(self, monkeypatch):
        srv0 = obs.StatusServer(port=0, registry=MetricsRegistry()).start()
        base = srv0.port  # a port we know is taken: rank 0 owns it
        monkeypatch.setenv(monitor.MONITOR_PORT_ENV, str(base))
        try:
            srv1 = monitor.maybe_start_server(worker_id=1)
            assert srv1 is not None and srv1.port == base + 1
            srv1.stop()
            # rank 0 would collide with the running server: bind fails
            # loudly→None, never takes the run down
            assert monitor.maybe_start_server(worker_id=0) is None
        finally:
            srv0.stop()

    def test_unset_port_means_no_server(self, monkeypatch):
        monkeypatch.delenv(monitor.MONITOR_PORT_ENV, raising=False)
        assert monitor.maybe_start_server(worker_id=0) is None


# -- stream tailing ---------------------------------------------------------
class TestStreamTail:
    def test_partial_tail_line_is_not_torn(self, tmp_path):
        p = str(tmp_path / "worker-0.jsonl")
        tail = agg_mod.StreamTail(p)
        with open(p, "a") as f:
            f.write(json.dumps({"ts": 1.0, "kind": "step", "step": 0})
                    + "\n")
            f.write('{"ts": 2.0, "kind": "st')     # writer mid-append
            f.flush()
            assert [r["step"] for r in tail.poll()] == [0]
            assert tail.drops["torn_lines"] == 0   # not torn, unfinished
            f.write('ep", "step": 1}\n')           # append completes
            f.flush()
            assert [r["step"] for r in tail.poll()] == [1]
        assert tail.poll() == []                    # nothing new

    def test_truncation_rereads(self, tmp_path):
        p = str(tmp_path / "worker-0.jsonl")
        tail = agg_mod.StreamTail(p)
        with open(p, "w") as f:
            f.write(json.dumps({"ts": 1.0, "kind": "step", "step": 0})
                    + "\n")
        assert len(tail.poll()) == 1
        with open(p, "w") as f:  # rotated under us: shorter file
            f.write(json.dumps({"ts": 9.0, "kind": "x"}) + "\n")
        assert tail.poll()[0]["kind"] == "x"


# -- the flight recorder ----------------------------------------------------
class TestFlightRecorder:
    def test_ring_is_bounded_and_dump_durable(self, tmp_path):
        fr = flight.FlightRecorder(str(tmp_path), worker_id=3, capacity=8)
        for i in range(50):
            fr.write({"ts": float(i), "kind": "step", "step": i})
        assert fr.seen == 50
        path = fr.dump("unit")
        bundle = flight.read_flight_bundles(str(tmp_path))[3]
        assert path.endswith("flight/worker-3.json")
        assert len(bundle["records"]) == 8          # only the newest ring
        assert bundle["records"][-1]["step"] == 49
        assert bundle["records_seen"] == 50
        assert bundle["reason"] == "unit"

    def test_env_knob(self, monkeypatch):
        monkeypatch.setenv(flight.FLIGHT_BUFFER_ENV, "64")
        assert flight.default_capacity() == 64

    def test_dump_on_hang_watchdog_kill(self, tmp_path):
        """ISSUE 5 satellite: injected ``faults.hang`` → watchdog
        StepTimeout on every step → rollback budget 0 → the run dies —
        and leaves a flight bundle the doctor can still rank."""
        run_dir = str(tmp_path / "run")
        model = _model()
        sup = RunSupervisor(run_dir, worker_id=0, watchdog_secs=0.2,
                            rollback_budget=0, sigterm_handler=False)
        sup.inject_loss(lambda step, loss: faults.hang(30.0) or loss)
        with pytest.raises(RollbackBudgetExceeded):
            model.fit(_data(), batch_size=8, epochs=1, verbose=0,
                      supervisor=sup)
        bundles = flight.read_flight_bundles(run_dir)
        assert 0 in bundles
        assert bundles[0]["reason"] == "end_run:failed"
        kinds = {r.get("kind") for r in bundles[0]["records"]}
        assert "supervisor.watchdog_timeout" in kinds
        # acceptance: kill the JSONL stream (the lost tail) — the doctor
        # diagnoses from the flight bundle alone, non-empty and ranked
        for name in os.listdir(obs.metrics_dir(run_dir)):
            os.remove(os.path.join(obs.metrics_dir(run_dir), name))
        diag = doctor.diagnose(run_dir)
        assert diag is not None and diag["findings"]
        assert diag["flight_workers"] == [0]
        sevs = [f["severity"] for f in diag["findings"]]
        assert sevs == sorted(sevs, reverse=True)
        assert any(f["kind"] == "unstable" for f in diag["findings"])
        # the CLI sees the same evidence
        assert doctor.main([run_dir]) == 0

    def test_dump_on_sigterm_chains_previous_handler(self, tmp_path):
        run_dir = str(tmp_path / "run")
        hits = []
        orig = signal.getsignal(signal.SIGTERM)
        signal.signal(signal.SIGTERM, lambda *a: hits.append(a))
        try:
            sup = RunSupervisor(run_dir, worker_id=0,
                                sigterm_handler=False)
            sup.begin_run()
            obs.get_registry().emit("step", step=1, step_time_ms=5.0)
            os.kill(os.getpid(), signal.SIGTERM)   # preemption notice
            bundles = flight.read_flight_bundles(run_dir)
            assert 0 in bundles
            assert bundles[0]["reason"] == f"signal-{signal.SIGTERM}"
            assert any(r.get("kind") == "step"
                       for r in bundles[0]["records"])
            assert hits, "previous SIGTERM handler was not chained"
            sup.end_run("completed")
            # clean end restores the chain and disarms atexit
            assert sup.flight is None
        finally:
            signal.signal(signal.SIGTERM, orig)

    def test_clean_run_leaves_no_bundle(self, tmp_path):
        run_dir = str(tmp_path / "run")
        model = _model()
        sup = RunSupervisor(run_dir, worker_id=0, sigterm_handler=False)
        model.fit(_data(n=16), batch_size=8, epochs=1, verbose=0,
                  supervisor=sup)
        assert flight.read_flight_bundles(run_dir) == {}


# -- the live aggregator ----------------------------------------------------
def _append_stream(mdir, wid, records):
    os.makedirs(mdir, exist_ok=True)
    with open(os.path.join(mdir, f"worker-{wid}.jsonl"), "a") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")


class TestLiveAggregator:
    def test_straggler_named_from_partial_stream(self, tmp_path):
        """The monitor's whole point: worker 1's stream is PARTIAL
        (still growing) and the straggler verdict already fires."""
        run_dir = str(tmp_path / "run")
        mdir = obs.metrics_dir(run_dir)
        steps = lambda wid, hi, ms: [  # noqa: E731
            {"ts": 1000.0 + s, "kind": "step", "step": s,
             "step_time_ms": ms, "data_ms": 1.0} for s in range(hi)]
        agg = obs.LiveAggregator(run_dir, interval=0)
        _append_stream(mdir, 0, steps(0, 20, 10.0))
        _append_stream(mdir, 1, steps(1, 4, 50.0))   # 4 of 20 so far
        status = agg.poll(force=True)
        strag = [f for f in status["findings"]
                 if f["kind"] == "straggler"]
        assert strag and strag[0]["data"]["worker"] == 1
        assert status["last_step"] == {"0": 19, "1": 3}
        assert len(status["alerts"]) == 1
        # stream grows; alert does NOT re-fire for the same verdict
        _append_stream(mdir, 1, steps(1, 20, 50.0)[4:])
        status = agg.poll(force=True)
        assert len(status["alerts"]) == 1
        assert status["last_step"]["1"] == 19

    def test_alert_lands_on_supervisor_timeline(self, tmp_path):
        from paddle_tpu.supervisor.report import SupervisorReport
        run_dir = str(tmp_path / "run")
        mdir = obs.metrics_dir(run_dir)
        _append_stream(mdir, 0, [
            {"ts": 1000.0 + i, "kind": "compile",
             "function": "hapi.train_step", "retrace": i > 0,
             "changed": [{"arg": "data[0]",
                          "detail": "f32[4,8] -> f32[5,8]"}],
             "wall_ms": 5.0} for i in range(5)])
        report = SupervisorReport(os.path.join(run_dir,
                                               "launcher_report.json"))
        agg = obs.LiveAggregator(run_dir, interval=0, report=report)
        agg.poll(force=True)
        alerts = report.of_kind("monitor.alert")
        assert alerts and alerts[0]["verdict"] == "retrace_storm"
        assert "data[0]" in alerts[0]["title"]

    def test_interval_throttling(self, tmp_path):
        agg = obs.LiveAggregator(str(tmp_path), interval=3600)
        assert agg.poll(force=True) is not None
        assert agg.poll() is None                   # throttled
        assert agg.poll(force=True) is not None

    def test_e2e_degraded_fit_alerts_before_run_ends(self, tmp_path,
                                                     monkeypatch):
        """ISSUE 5 acceptance: shape-churning loader + one worker slowed
        via ``faults.slow_call`` — ``live_status.json`` names a
        retrace/straggler alert asserted MID-RUN, before worker 1's fit
        returns."""
        monkeypatch.setenv("PTPU_METRICS_INTERVAL", "0.05")  # eager flush
        compilation.reset_tracker()
        run_dir = str(tmp_path / "run")
        sizes = [4, 6, 8, 10, 4, 6, 8, 10]

        def run_worker(wid, slow):
            model = _model()
            if slow:
                model._train_step = faults.slow_call(model._train_step,
                                                     0.25)
            sup = RunSupervisor(run_dir, worker_id=wid,
                                watchdog_secs=120.0,
                                sigterm_handler=False)
            model.fit(_RaggedLoader(sizes), epochs=1, verbose=0,
                      supervisor=sup)

        run_worker(0, slow=False)                   # fast worker: done
        done = threading.Event()

        def worker1():
            try:
                run_worker(1, slow=True)
            finally:
                done.set()

        t = threading.Thread(target=worker1, daemon=True)
        t.start()
        agg = obs.LiveAggregator(run_dir, interval=0)
        mid_run_alerts = None
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline and not done.is_set():
            status = agg.poll(force=True)
            kinds = {a["kind"] for a in status["alerts"]}
            if {"retrace_storm", "straggler"} <= kinds:
                assert not done.is_set(), "run ended before the alert"
                mid_run_alerts = json.load(
                    open(monitor.live_status_path(run_dir)))["alerts"]
                break
            time.sleep(0.05)
        t.join(timeout=60.0)
        assert mid_run_alerts is not None, \
            "no retrace+straggler alert before the run ended"
        by_kind = {a["kind"]: a for a in mid_run_alerts}
        assert "data[" in by_kind["retrace_storm"]["title"]
        assert "worker 1" in by_kind["straggler"]["title"]


# -- doctor × flight --------------------------------------------------------
class TestDoctorFlightIngestion:
    def test_truncated_stream_recovered_from_bundle(self, tmp_path):
        """Worker 1's JSONL lost its tail (buffered records died with the
        process); its flight bundle carries them — the doctor folds the
        bundle in and still attributes the straggler + the OOM."""
        run_dir = str(tmp_path / "run")
        mdir = obs.metrics_dir(run_dir)
        fast = [{"ts": 1000.0 + s, "kind": "step", "step": s,
                 "step_time_ms": 10.0, "data_ms": 1.0} for s in range(20)]
        slow = [{"ts": 1000.0 + s, "kind": "step", "step": s,
                 "step_time_ms": 40.0, "data_ms": 1.0} for s in range(20)]
        _append_stream(mdir, 0, fast)
        _append_stream(mdir, 1, slow[:3])           # the surviving head
        fr = flight.FlightRecorder(run_dir, worker_id=1, capacity=64)
        for r in slow:                              # the ring saw it all
            fr.write(r)
        fr.write({"ts": 1020.0, "kind": "memory.oom", "step": 19,
                  "error": "RESOURCE_EXHAUSTED",
                  "devices": {"tpu:1": {"bytes_in_use": 990,
                                        "peak_bytes_in_use": 999,
                                        "bytes_limit": 1000,
                                        "utilization": 0.99}}})
        fr.dump("sigkill-simulated")
        diag = doctor.diagnose(run_dir)
        assert diag["flight_workers"] == [1]
        kinds = [f["kind"] for f in diag["findings"]]
        assert kinds[0] == "oom"                    # only in the bundle
        strag = next(f for f in diag["findings"]
                     if f["kind"] == "straggler")
        assert strag["data"]["worker"] == 1
        # without the bundle the straggler is invisible (3 aligned steps
        # of a 20-step run barely registers) — prove the bundle mattered
        report = doctor.render_report(diag)
        assert "flight-recorder evidence" in report

    def test_garbled_bundle_is_skipped(self, tmp_path):
        run_dir = str(tmp_path / "run")
        os.makedirs(flight.flight_dir(run_dir))
        with open(os.path.join(flight.flight_dir(run_dir),
                               "worker-0.json"), "w") as f:
            f.write('{"worker": 0, "records": [')   # torn dump
        assert flight.read_flight_bundles(run_dir) == {}
        assert doctor.diagnose(run_dir) is None
