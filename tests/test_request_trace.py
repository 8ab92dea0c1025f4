"""Fleet request tracing (ISSUE 18): knob/sampling semantics, the
TraceAssembler (amortized decode, orphan detection, coverage, chrome
export), end-to-end continuity across failover / drain-migration /
router crash-recovery / preemption-recompute / quarantine (every
request yields exactly ONE assembled trace, no orphan spans), the
router's client-observed TTFT/TPOT histograms + slow-request table,
the autoscaler's PTPU_FLEET_SLO_SOURCE switch, and the doctor's
tail_latency verdict."""
import os
import re

import pytest

import jax.numpy as jnp
import numpy as np

import paddle_tpu as pt
from paddle_tpu.inference import ServingEngine
from paddle_tpu.inference.fleet import (FleetAutoscaler, FleetOverloaded,
                                        LocalReplica, Router, ServingSLO)
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import doctor, requesttrace
from paddle_tpu.observability.registry import MetricsRegistry
from paddle_tpu.testing import faults

pytestmark = pytest.mark.telemetry


def tiny_model(max_pos=64):
    pt.seed(7)
    cfg = GPTConfig(vocab_size=32, hidden_size=32, num_layers=2,
                    num_heads=2, ffn_hidden_size=64,
                    max_position_embeddings=max_pos, hidden_dropout=0.0,
                    attention_dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


class Capture:
    """List sink: every emitted record, in order."""

    def __init__(self):
        self.records = []

    def write(self, rec):
        self.records.append(rec)

    def flush(self):
        pass

    def close(self):
        pass


def capture_registry():
    reg = MetricsRegistry()
    cap = Capture()
    reg.add_sink(cap)
    return reg, cap


def local_fleet(n=2, registry=None, **engine_kw):
    reg = registry or MetricsRegistry()
    reps = [LocalReplica(ServingEngine(tiny_model(), registry=reg,
                                       replica_id=i, **engine_kw),
                         replica_id=i)
            for i in range(n)]
    return reps, reg


def assemble(records):
    return requesttrace.TraceAssembler().from_records(records)


def assert_one_complete_trace_per_request(result, rids):
    traces = result["traces"]
    assert len(traces) == len(rids), \
        f"{len(traces)} traces for {len(rids)} requests"
    assert {t["request_id"] for t in traces} == set(rids)
    assert result["complete"] == len(rids), result
    assert not result["orphan_spans"], result["orphan_spans"]
    return traces


# ---------------------------------------------------------------------------
# knobs & sampling
# ---------------------------------------------------------------------------
class TestKnobs:
    def test_default_on(self, monkeypatch):
        monkeypatch.delenv(requesttrace.TRACE_REQUESTS_ENV,
                           raising=False)
        monkeypatch.delenv(requesttrace.TRACE_SAMPLE_ENV, raising=False)
        assert requesttrace.tracing_enabled()
        assert requesttrace.mint_trace_id("r1") is not None

    def test_disabled_by_env(self, monkeypatch):
        for off in ("0", "false", "no", "off"):
            monkeypatch.setenv(requesttrace.TRACE_REQUESTS_ENV, off)
            assert not requesttrace.tracing_enabled()
            assert requesttrace.mint_trace_id("r1") is None

    def test_sampling_deterministic_per_request_id(self, monkeypatch):
        monkeypatch.setenv(requesttrace.TRACE_SAMPLE_ENV, "0.5")
        decisions = {f"req-{i}": requesttrace.sampled(f"req-{i}")
                     for i in range(64)}
        # deterministic: re-asking gives the same answer, no RNG state
        assert all(requesttrace.sampled(r) == d
                   for r, d in decisions.items())
        # a 50% sample actually splits the id space
        assert 0 < sum(decisions.values()) < len(decisions)
        monkeypatch.setenv(requesttrace.TRACE_SAMPLE_ENV, "0.0")
        assert not any(requesttrace.sampled(r) for r in decisions)
        monkeypatch.setenv(requesttrace.TRACE_SAMPLE_ENV, "1.0")
        assert all(requesttrace.sampled(r) for r in decisions)

    def test_component_buckets_fold_recompute_causes(self):
        bucket = requesttrace.component_bucket
        assert bucket("preempt") == "preempt_recompute"
        assert bucket("failover") == "failover_recompute"
        assert bucket("migration_recompute") == "migration"
        assert bucket("retry_backoff") == "retry_backoff"
        assert bucket("something_new") == "something_new"

    def test_untraced_engine_emits_no_spans(self, monkeypatch):
        monkeypatch.setenv(requesttrace.TRACE_REQUESTS_ENV, "0")
        reg, cap = capture_registry()
        eng = ServingEngine(tiny_model(), max_seqs=2, kv_block_size=4,
                            registry=reg)
        rid = eng.submit([1, 2, 3], max_new_tokens=4)
        eng.run(max_steps=50)
        assert eng.collect(rid)["tokens"]
        assert not [r for r in cap.records
                    if r["kind"].startswith("trace.")]


# ---------------------------------------------------------------------------
# assembler units
# ---------------------------------------------------------------------------
def span(tid, rid, name, comp, t0, t1, proc, **kw):
    return {"kind": "trace.span", "trace_id": tid, "request_id": rid,
            "name": name, "component": comp, "t0": t0, "t1": t1,
            "dur_ms": (t1 - t0) * 1e3, "proc": proc, **kw}


class TestAssembler:
    def test_amortized_decode_share(self):
        recs = [
            {"kind": "trace.request", "trace_id": "t1",
             "request_id": "r1", "t0": 0.0, "prompt_len": 3,
             "proc": "router"},
            {"kind": "trace.request", "trace_id": "t2",
             "request_id": "r2", "t0": 0.0, "prompt_len": 3,
             "proc": "router"},
            {"kind": "trace.span", "name": "decode_batch",
             "component": "decode", "t0": 0.0, "t1": 0.1,
             "dur_ms": 100.0, "proc": "replica-0", "residents": 4,
             "requests": [["r1", "t1"], ["r2", "t2"]]},
            {"kind": "trace.request_end", "trace_id": "t1",
             "request_id": "r1", "t1": 0.1, "reason": "length",
             "tokens": 4, "proc": "router"},
            {"kind": "trace.request_end", "trace_id": "t2",
             "request_id": "r2", "t1": 0.1, "reason": "length",
             "tokens": 4, "proc": "router"},
        ]
        result = assemble(recs)
        traces = assert_one_complete_trace_per_request(
            result, ["r1", "r2"])
        for t in traces:
            # 100ms batch over 4 residents -> 25ms amortized share
            assert t["components"]["decode"] == pytest.approx(25.0)
            assert t["coverage"] == pytest.approx(1.0)

    def test_orphan_span_detected(self):
        recs = [span("ghost", "rg", "prefill", "prefill",
                     0.0, 0.1, "replica-0")]
        result = assemble(recs)
        assert result["orphan_spans"] == ["ghost"]
        assert result["complete"] == 0

    def test_coverage_is_union_of_span_intervals(self):
        recs = [
            {"kind": "trace.request", "trace_id": "t1",
             "request_id": "r1", "t0": 0.0, "prompt_len": 1,
             "proc": "router"},
            # two overlapping spans covering [0, 0.5] of a 1s window
            span("t1", "r1", "prefill", "prefill", 0.0, 0.4,
                 "replica-0"),
            span("t1", "r1", "queue", "queue", 0.3, 0.5, "replica-0"),
            {"kind": "trace.request_end", "trace_id": "t1",
             "request_id": "r1", "t1": 1.0, "reason": "length",
             "tokens": 1, "proc": "router"},
        ]
        (trace,) = assemble(recs)["traces"]
        assert trace["coverage"] == pytest.approx(0.5)
        assert trace["latency_ms"] == pytest.approx(1000.0)

    def test_chrome_export_process_and_thread_metadata(self):
        recs = [
            {"kind": "trace.request", "trace_id": "t1",
             "request_id": "r1", "t0": 0.0, "prompt_len": 1,
             "proc": "router"},
            span("t1", "r1", "dispatch", "dispatch", 0.0, 0.01,
                 "router"),
            span("t1", "r1", "prefill", "prefill", 0.01, 0.1,
                 "replica-0"),
            {"kind": "trace.request_end", "trace_id": "t1",
             "request_id": "r1", "t1": 0.1, "reason": "length",
             "tokens": 1, "proc": "router"},
        ]
        events = requesttrace.chrome_trace_events(
            assemble(recs)["traces"])
        meta = [e for e in events if e["ph"] == "M"]
        names = {(e["name"], e["args"]["name"]) for e in meta}
        assert ("process_name", "router") in names
        assert ("process_name", "replica-0") in names
        assert any(e["name"] == "thread_name" and
                   e["args"]["name"] == "r1" for e in meta)
        xs = [e for e in events if e["ph"] == "X"]
        # spans land in their own process's track
        pid_by_proc = {e["args"]["name"]: e["pid"] for e in meta
                       if e["name"] == "process_name"}
        assert {e["pid"] for e in xs} == set(pid_by_proc.values())

    def test_aggregate_chrome_merge_disambiguates_workers(self, tmp_path):
        import json
        from paddle_tpu.observability.aggregate import export_chrome_trace
        from paddle_tpu.observability.sinks import metrics_dir
        mdir = metrics_dir(str(tmp_path))
        os.makedirs(mdir)
        with open(os.path.join(mdir, "worker-0.jsonl"), "w") as f:
            f.write(json.dumps(span("t1", "r1", "dispatch", "dispatch",
                                    0.0, 0.01, "router")) + "\n")
        with open(os.path.join(mdir, "worker-1.jsonl"), "w") as f:
            f.write(json.dumps(span("t1", "r1", "prefill", "prefill",
                                    0.01, 0.1, "replica-0")) + "\n")
            f.write(json.dumps({"kind": "step", "step": 1, "ts": 0.2,
                                "step_time_ms": 50.0}) + "\n")
        n = export_chrome_trace(str(tmp_path))
        assert n and n >= 5          # 2 proc meta + >=2 thread meta + X
        payload = json.loads(
            open(os.path.join(mdir, "trace.json")).read())
        events = payload["traceEvents"]
        procs = {e["args"]["name"]: e["pid"] for e in events
                 if e.get("ph") == "M" and e["name"] == "process_name"}
        # one pid per worker stream, labeled from the stream's own proc
        assert procs == {"router": 0, "replica-0": 1}
        xs = [e for e in events if e.get("ph") == "X"]
        assert {e["pid"] for e in xs} == {0, 1}
        assert any(e["cat"] == "step" for e in xs)


# ---------------------------------------------------------------------------
# end-to-end continuity: engine-owned traces
# ---------------------------------------------------------------------------
class TestEngineTraces:
    def test_direct_submission_yields_one_complete_trace(self):
        reg, cap = capture_registry()
        eng = ServingEngine(tiny_model(), max_seqs=2, kv_block_size=4,
                            registry=reg)
        rid = eng.submit([1, 2, 3], max_new_tokens=6)
        eng.run(max_steps=100)
        assert eng.collect(rid)["tokens"]
        result = assemble(cap.records)
        (trace,) = assert_one_complete_trace_per_request(result, [rid])
        assert trace["reason"] == "max_new_tokens"
        comps = trace["components"]
        assert comps.get("prefill", 0) > 0
        assert comps.get("decode", 0) > 0
        assert trace["procs"] == ["replica-0"]

    def test_preemption_recompute_traced(self):
        reg, cap = capture_registry()
        # pool far too small for 4 concurrent streams -> preemptions
        eng = ServingEngine(tiny_model(), max_seqs=4, kv_block_size=4,
                            num_kv_blocks=5, registry=reg)
        prompts = [[1, 2, 3, 4], [5, 6, 7], [8, 9],
                   [10, 11, 12, 13, 14]]
        rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        while eng.has_work():
            eng.step()
        for r in rids:
            assert eng.collect(r)["tokens"]
        assert eng.sched.preemptions > 0
        result = assemble(cap.records)
        traces = assert_one_complete_trace_per_request(result, rids)
        # the evicted stream's re-queue + re-prefill is attributed to
        # preempt_recompute, not generic queue/prefill
        assert any(t["components"].get("preempt_recompute", 0) > 0
                   for t in traces)

    def test_quarantine_traced_to_poisoned_end(self, tmp_path):
        reg, cap = capture_registry()
        injector = faults.poison_request(1, mode="raise",
                                         kinds=("decode",))
        eng = ServingEngine(tiny_model(), max_seqs=3, kv_block_size=4,
                            registry=reg, step_fault=injector,
                            run_dir=str(tmp_path))
        rids = [eng.submit([1 + i, 2, 3 + i], max_new_tokens=6)
                for i in range(3)]
        eng.run(max_steps=500)
        bad = eng._submit_order[1]
        assert list(eng.quarantined) == [bad]
        result = assemble(cap.records)
        traces = assert_one_complete_trace_per_request(result, rids)
        by_rid = {t["request_id"]: t for t in traces}
        assert by_rid[bad]["reason"] == "poisoned"
        assert by_rid[bad]["components"].get("quarantine", 0) > 0
        for r in rids:
            if r != bad:
                assert by_rid[r]["reason"] == "max_new_tokens"


# ---------------------------------------------------------------------------
# end-to-end continuity: router-owned traces across fleet chaos
# ---------------------------------------------------------------------------
class TestFleetTraces:
    def test_failover_stitches_one_trace_across_replicas(self):
        reps, _ = local_fleet(2, max_seqs=4, kv_block_size=4)
        reg, cap = capture_registry()
        # one registry for router + engines so the capture sees all
        for rep in reps:
            rep.engine._registry = reg
        router = Router(reps, registry=reg)
        rids = [router.submit([1, 2, 3 + i], max_new_tokens=10)
                for i in range(3)]
        while len(router.journals[rids[0]].tokens) < 3:
            router.pump()
        victim = router.journals[rids[0]].replica_id
        reps[victim].engine._state = "stopped"
        outs = [router.collect(r, timeout=60) for r in rids]
        assert all(len(o["tokens"]) == 10 for o in outs)
        assert router.failovers >= 1
        result = assemble(cap.records)
        traces = assert_one_complete_trace_per_request(result, rids)
        moved = [t for t in traces if len(
            [p for p in t["procs"] if p.startswith("replica-")]) == 2]
        assert moved, "no trace stitched across both replicas"
        for t in moved:
            assert t["components"].get("failover_recompute", 0) > 0

    def test_deliver_spans_coalesced_and_flushed_at_finish(self):
        reps, _ = local_fleet(1, max_seqs=2, kv_block_size=4)
        reg, cap = capture_registry()
        reps[0].engine._registry = reg
        router = Router(reps, registry=reg)
        rid = router.submit([1, 2, 3], max_new_tokens=6)
        router.collect(rid, timeout=60)
        journal = router.journals[rid]
        deliver = sorted(
            (r for r in cap.records if r["kind"] == "trace.span"
             and r.get("name") == "deliver"
             and r["request_id"] == rid),
            key=lambda r: r["t0"])
        # coalesced: far fewer spans than polls — at most one per
        # DELIVER_FLUSH_S stretch plus the finish flush
        wall = journal.end_wall - journal.submit_wall
        from paddle_tpu.inference.fleet.router import DELIVER_FLUSH_S
        assert 1 <= len(deliver) <= int(wall / DELIVER_FLUSH_S) + 2
        # contiguous chain from dispatch (the dispatch span covers
        # submit → dispatch) through finish: the residue bucket needs
        # the full client-observed window covered
        assert deliver[0]["t0"] >= journal.submit_wall - 1e-6
        assert deliver[0]["t0"] <= journal.first_token_wall + 1e-6
        assert abs(deliver[-1]["t1"] - journal.end_wall) < 1e-6
        for prev, nxt in zip(deliver, deliver[1:]):
            assert nxt["t0"] <= prev["t1"] + 1e-6

    def test_drain_migration_traced(self, tmp_path):
        reps, _ = local_fleet(2, max_seqs=4, kv_block_size=4,
                              run_dir=str(tmp_path))
        reg, cap = capture_registry()
        for rep in reps:
            rep.engine._registry = reg
        router = Router(reps, registry=reg)
        rids = [router.submit([1, 2, 3 + i], max_new_tokens=12)
                for i in range(4)]
        router.pump()
        moved = router.drain_replica(0, timeout=0.0)
        outs = [router.collect(r, timeout=60) for r in rids]
        assert all(len(o["tokens"]) == 12 for o in outs)
        result = assemble(cap.records)
        traces = assert_one_complete_trace_per_request(result, rids)
        if moved:
            assert any(t["components"].get("migration", 0) > 0
                       for t in traces)

    def test_router_crash_recovery_preserves_trace_id(self, tmp_path):
        reps, _ = local_fleet(2, max_seqs=4, kv_block_size=4)
        reg1, cap1 = capture_registry()
        for rep in reps:
            rep.engine._registry = reg1
        router = Router(reps, registry=reg1, run_dir=str(tmp_path))
        rids = [router.submit([1, 2, 3 + i], max_new_tokens=10)
                for i in range(3)]
        while any(len(j.tokens) < 2 for j in router.journals.values()):
            router.pump()
        want_tids = {r: router.journals[r].trace_id for r in rids}
        assert all(want_tids.values())
        # simulated router SIGKILL: no drain, no retire — a FRESH
        # router recovers from the journal directory alone
        del router
        reg2, cap2 = capture_registry()
        for rep in reps:
            rep.engine._registry = reg2
        recovered = Router(reps, registry=reg2, recover=str(tmp_path))
        for r in rids:
            assert recovered.journals[r].trace_id == want_tids[r], \
                "recovery minted a new trace_id"
        outs = [recovered.collect(r, timeout=60) for r in rids]
        assert all(len(o["tokens"]) == 10 for o in outs)
        # the two router incarnations' records merge into ONE trace
        # per request (same ids), nothing orphaned
        result = assemble(cap1.records + cap2.records)
        assert_one_complete_trace_per_request(result, rids)

    def test_shed_stream_is_a_complete_trace(self):
        from paddle_tpu.inference.fleet import DispatchExhausted

        class Unreachable:
            """Passes admission (idle stats) but every dispatch fails."""
            replica_id = 0

            def serving_stats(self):
                return {"queue_depth": 0, "waiting": 0, "running": 0}

            def healthz(self):
                return (200, "serving")

            def alive(self):
                return True

            def submit(self, record):
                raise ConnectionError("refused")

        reg, cap = capture_registry()
        router = Router([Unreachable()], registry=reg, retry_max=1,
                        sleep=lambda t: None)
        with pytest.raises((FleetOverloaded, DispatchExhausted)):
            router.submit([1, 2], max_new_tokens=4)
        result = assemble(cap.records)
        # the refusal still closed the lifecycle: one complete trace
        # with reason "shed", nothing orphaned
        assert result["complete"] == len(result["traces"]) == 1
        assert result["traces"][0]["reason"] == "shed"
        assert not result["orphan_spans"]

    def test_wal_cross_check_in_assemble_run(self, tmp_path):
        from paddle_tpu.observability.sinks import (MetricsWriter,
                                                    metrics_dir)
        reps, _ = local_fleet(1, max_seqs=2, kv_block_size=4)
        reg = MetricsRegistry()
        writer = reg.add_sink(MetricsWriter(metrics_dir(str(tmp_path)),
                                            worker_id=0, flush_every=1))
        reps[0].engine._registry = reg
        router = Router(reps, registry=reg, run_dir=str(tmp_path))
        rid = router.submit([1, 2, 3], max_new_tokens=6)
        router.collect(rid, timeout=60)
        reg.remove_sink(writer)
        result = requesttrace.assemble_run(str(tmp_path))
        assert_one_complete_trace_per_request(result, [rid])
        assert result["wal_streams"] == 1
        assert result["wal_matched"] == 1


# ---------------------------------------------------------------------------
# router SLO surfaces + autoscaler source switch
# ---------------------------------------------------------------------------
class TestRouterSLO:
    def _run_streams(self, n=3, max_new=8):
        reps, _ = local_fleet(2, max_seqs=4, kv_block_size=4)
        reg, cap = capture_registry()
        for rep in reps:
            rep.engine._registry = reg
        router = Router(reps, registry=reg)
        rids = [router.submit([1, 2, 3 + i], max_new_tokens=max_new)
                for i in range(n)]
        for r in rids:
            router.collect(r, timeout=60)
        return router, reg

    def test_ttft_tpot_histograms_and_slo_stats(self):
        router, reg = self._run_streams()
        snap = reg.snapshot()
        assert snap["fleet.ttft_ms"]["count"] == 3
        assert snap["fleet.ttft_ms"]["p50"] > 0
        assert snap["fleet.tpot_ms"]["count"] > 0
        slo = router.slo_stats()["slo"]
        assert slo["ttft_ms"]["samples"] == 3
        assert slo["ttft_ms"]["p99"] >= slo["ttft_ms"]["p50"] > 0
        assert slo["tpot_ms"]["samples"] > 0

    def test_slow_requests_table_in_stats(self):
        router, _ = self._run_streams()
        stats = router.stats()
        rows = stats["slow_requests"]
        assert rows and len(rows) <= 8
        top = rows[0]
        for field in ("request_id", "trace_id", "state", "latency_ms",
                      "ttft_ms", "tokens", "components"):
            assert field in top, field
        # sorted by latency, slowest first
        lats = [r["latency_ms"] for r in rows]
        assert lats == sorted(lats, reverse=True)
        assert stats["slo"]["ttft_ms"]["samples"] == 3

    def test_autoscaler_burns_on_router_tails(self):
        router, reg = self._run_streams()

        class Mgr:
            replicas = router.replicas

            def poll_states(self):
                return {0: "healthy", 1: "healthy"}

        scaler = FleetAutoscaler(
            Mgr(), router=router,
            slo=ServingSLO(queue_depth=None, ttft_p99_ms=0.0001),
            slo_source="router", registry=reg, clock=lambda: 0.0)
        sample = scaler.sample()
        assert sample["burning"]
        assert "router" in sample["violations"]
        assert any("ttft_p99" in v
                   for v in sample["violations"]["router"])
        assert scaler.stats()["slo_source"] == "router"

    def test_slo_source_env_default(self, monkeypatch):
        from paddle_tpu.inference.fleet.autoscaler import (
            SLO_SOURCE_ENV, default_slo_source)
        monkeypatch.delenv(SLO_SOURCE_ENV, raising=False)
        assert default_slo_source() == "engine"
        monkeypatch.setenv(SLO_SOURCE_ENV, "router")
        assert default_slo_source() == "router"
        monkeypatch.setenv(SLO_SOURCE_ENV, "bogus")
        with pytest.raises(Exception):
            default_slo_source()

    def test_router_slo_source_requires_router(self):
        class Mgr:
            replicas = []

            def poll_states(self):
                return {}

        with pytest.raises(Exception):
            FleetAutoscaler(Mgr(), slo_source="router",
                            registry=MetricsRegistry())


# ---------------------------------------------------------------------------
# doctor: tail_latency verdict
# ---------------------------------------------------------------------------
def _lifecycle(tid, rid, t0, t1, reason="length"):
    return [{"kind": "trace.request", "trace_id": tid,
             "request_id": rid, "t0": t0, "prompt_len": 3,
             "proc": "router"},
            {"kind": "trace.request_end", "trace_id": tid,
             "request_id": rid, "t1": t1, "reason": reason,
             "tokens": 8, "proc": "router"}]


class TestDoctorTailLatency:
    def _workers(self, slow_extra=2.0):
        recs = []
        for i in range(7):                 # healthy herd: 1s each
            tid, rid = f"t{i}", f"r{i}"
            recs += _lifecycle(tid, rid, 0.0, 1.0)
            recs.append(span(tid, rid, "decode_batch", "decode",
                             0.0, 1.0, "replica-0"))
        # one tail request: same decode, big failover recompute
        recs += _lifecycle("t9", "r9", 0.0, 1.0 + slow_extra)
        recs.append(span("t9", "r9", "decode_batch", "decode",
                         0.0, 1.0, "replica-0"))
        recs.append(span("t9", "r9", "prefill", "failover",
                         1.0, 1.0 + slow_extra, "replica-1"))
        return {0: recs}

    def test_names_dominant_tail_component(self):
        findings = doctor.check_tail_latency(self._workers())
        assert len(findings) == 1
        f = findings[0]
        assert f["kind"] == "tail_latency"
        assert f["data"]["dominant"] == "failover_recompute"
        assert f["data"]["p99_ms"] > f["data"]["median_ms"]
        assert any("failover_recompute" in line
                   for line in f["evidence"])

    def test_flat_tail_is_silent(self):
        findings = doctor.check_tail_latency(
            self._workers(slow_extra=0.05))
        assert findings == []

    def test_diagnose_includes_tail_latency(self, tmp_path):
        import json
        from paddle_tpu.observability.sinks import metrics_dir
        mdir = metrics_dir(str(tmp_path))
        os.makedirs(mdir)
        with open(os.path.join(mdir, "worker-0.jsonl"), "w") as f:
            for rec in self._workers()[0]:
                f.write(json.dumps(rec) + "\n")
        report = doctor.diagnose(str(tmp_path))
        kinds = [f["kind"] for f in report["findings"]]
        assert "tail_latency" in kinds

    def test_no_traces_no_finding(self):
        assert doctor.check_tail_latency({0: [
            {"kind": "step", "step": 1, "step_time_ms": 5.0}]}) == []
