"""Serving-fleet tests (ISSUE 16): router dispatch policy with fake
replicas, fleet admission + retry/backoff, token-exact failover via
journal replay in-process, drain migration, and the multi-process
SIGKILL drill (marked slow — ci.sh's fleet tier runs it).
"""
import json
import os
import time

import pytest
from serving_families import (dense_continuation, family,  # noqa: F401
                              tiny_model)

from paddle_tpu.inference import ServingEngine
from paddle_tpu.inference.fleet import (DispatchExhausted, FleetOverloaded,
                                        LocalReplica, ReplicaManager,
                                        Router)
from paddle_tpu.observability.registry import MetricsRegistry
from paddle_tpu.testing import faults

pytestmark = pytest.mark.serving


def local_fleet(n=2, registry=None, max_pos=64, **engine_kw):
    reg = registry or MetricsRegistry()
    reps = [LocalReplica(ServingEngine(tiny_model(max_pos), registry=reg,
                                       replica_id=i, **engine_kw),
                         replica_id=i)
            for i in range(n)]
    return reps, reg


# ---------------------------------------------------------------------------
# fake replicas: dispatch policy without a model
# ---------------------------------------------------------------------------
class FakeReplica:
    """Replica protocol stub with a scriptable load and liveness."""

    def __init__(self, replica_id, load=0.0, up=True):
        self.replica_id = replica_id
        self.load = float(load)
        self.up = up
        self.submitted = []

    def submit(self, record):
        if not self.up:
            raise ConnectionError(f"replica {self.replica_id} down")
        self.submitted.append(record)

    def poll(self, rid, start=0):
        if not self.up:
            raise ConnectionError(f"replica {self.replica_id} down")
        return {"tokens": [], "finished": False, "reason": None}

    def pump(self):
        return False

    def serving_stats(self):
        return {"queue_depth": self.load, "waiting": 0, "running": 0}

    def healthz(self):
        return (200, "serving") if self.up else (503, "dead")

    def alive(self):
        return self.up


class TestDispatchPolicy:
    def test_least_loaded_wins(self):
        reps = [FakeReplica(0, load=5), FakeReplica(1, load=1),
                FakeReplica(2, load=9)]
        router = Router(reps, registry=MetricsRegistry())
        router.submit([1, 2], max_new_tokens=4)
        assert len(reps[1].submitted) == 1
        assert not reps[0].submitted and not reps[2].submitted

    def test_session_affinity_beats_load(self):
        reps = [FakeReplica(0, load=5), FakeReplica(1, load=1)]
        router = Router(reps, registry=MetricsRegistry())
        router.submit([1], max_new_tokens=4, session="u1")
        first = 0 if reps[0].submitted else 1
        # second stream for the same session lands on the same replica
        # even though the other one is less loaded
        reps[first].load = 50
        router.submit([2], max_new_tokens=4, session="u1")
        assert len(reps[first].submitted) == 2

    def test_affinity_broken_when_replica_dies(self):
        reps = [FakeReplica(0, load=0), FakeReplica(1, load=5)]
        router = Router(reps, registry=MetricsRegistry())
        router.submit([1], max_new_tokens=4, session="u1")
        assert len(reps[0].submitted) == 1
        reps[0].up = False
        router.submit([2], max_new_tokens=4, session="u1")
        assert len(reps[1].submitted) == 1

    def test_fleet_admission_shed(self):
        reps = [FakeReplica(0, load=40), FakeReplica(1, load=30)]
        reg = MetricsRegistry()
        router = Router(reps, registry=reg, shed_queue_depth=64)
        with pytest.raises(FleetOverloaded, match="aggregate depth"):
            router.submit([1], max_new_tokens=4)
        snap = reg.snapshot()
        assert snap["fleet.shed"]["value"] == 1.0

    def test_no_healthy_replica_sheds(self):
        reps = [FakeReplica(0, up=False), FakeReplica(1, up=False)]
        router = Router(reps, registry=MetricsRegistry())
        with pytest.raises(FleetOverloaded, match="0 healthy"):
            router.submit([1], max_new_tokens=4)

    def test_retry_exhaustion_names_replica_set(self):
        reps = [FakeReplica(0), FakeReplica(1)]
        reg = MetricsRegistry()
        router = Router(reps, registry=reg, retry_max=2,
                        retry_backoff_ms=0.0, sleep=lambda _t: None)
        router.dispatch_fault = faults.drop_dispatch(count=10**6)
        with pytest.raises(DispatchExhausted) as ei:
            router.submit([1], max_new_tokens=4)
        msg = str(ei.value)
        assert "[0, 1]" in msg            # the replica set, by name
        assert "3 attempts" in msg
        assert reg.snapshot()["fleet.retries"]["value"] == 2.0

    def test_transient_drop_recovers_with_retry(self):
        reps = [FakeReplica(0), FakeReplica(1)]
        reg = MetricsRegistry()
        slept = []
        router = Router(reps, registry=reg, retry_max=3,
                        retry_backoff_ms=10.0, sleep=slept.append)
        fault = faults.drop_dispatch(count=3)
        router.dispatch_fault = fault
        rid = router.submit([1], max_new_tokens=4)
        assert rid in router.journals
        assert fault.fired == 3
        assert sum(len(r.submitted) for r in reps) == 1
        # one retry round (2 drops on attempt 0, 1 on attempt 1), so
        # exactly one backoff sleep at the base delay
        assert slept == [pytest.approx(0.010)]

    def test_drop_dispatch_scoped_to_replica(self):
        fault = faults.drop_dispatch(count=5, replica_id=1)
        fault(0, {"request_id": "a"})     # other replica: passes
        assert fault.fired == 0
        with pytest.raises(ConnectionError):
            fault(1, {"request_id": "a"})
        assert fault.fired == 1


# ---------------------------------------------------------------------------
# journal replay: token-exact failover, in-process
# ---------------------------------------------------------------------------
class TestFailoverInProcess:
    @pytest.mark.usefixtures("family")
    def test_failover_token_exact_vs_dense(self):
        model = tiny_model(64)
        want = {i: dense_continuation(model, [1, 2, 3 + i], 10)
                for i in range(3)}
        reps, reg = local_fleet(2, max_seqs=4, kv_block_size=4)
        router = Router(reps, registry=reg)
        rids = [router.submit([1, 2, 3 + i], max_new_tokens=10)
                for i in range(3)]
        # accept a few tokens, then hard-stop whichever replica serves
        # the first stream (simulated SIGKILL: no drain, no spill)
        while len(router.journals[rids[0]].tokens) < 3:
            router.pump()
        victim = router.journals[rids[0]].replica_id
        reps[victim].engine._state = "stopped"
        outs = [router.collect(r, timeout=60) for r in rids]
        for i, out in enumerate(outs):
            assert out["tokens"] == want[i], (i, out)
        assert router.failovers >= 1
        assert reg.snapshot()["fleet.failovers"]["value"] \
            == float(router.failovers)
        # survivors' allocators drained clean
        for i, rep in enumerate(reps):
            if i != victim:
                assert rep.engine.cache.leak_report()["leaked_blocks"] \
                    == 0

    @pytest.mark.usefixtures("family")
    def test_journal_record_is_spill_format(self):
        reps, reg = local_fleet(1, max_seqs=2, kv_block_size=4)
        router = Router(reps, registry=reg)
        rid = router.submit([1, 2, 3], max_new_tokens=8,
                            eos_token_id=9)
        while len(router.journals[rid].tokens) < 2:
            router.pump()
        rec = router.journals[rid].record()
        assert rec["prompt"] == [1, 2, 3]
        assert rec["output"] == router.journals[rid].tokens
        assert rec["max_new_tokens"] == 8
        assert rec["eos_token_id"] == 9
        # and it round-trips through a fresh engine's admit_record
        fresh = ServingEngine(tiny_model(64), max_seqs=2,
                              registry=MetricsRegistry())
        assert fresh.admit_record(rec) == rid

    @pytest.mark.usefixtures("family")
    def test_drain_migration_token_exact(self, tmp_path):
        model = tiny_model(64)
        want = {i: dense_continuation(model, [1, 2, 3 + i], 12)
                for i in range(4)}
        # both replicas share one run_dir — the ISSUE 16 namespacing
        # keeps their spill/quarantine artifacts from colliding
        reps, reg = local_fleet(2, max_seqs=4, kv_block_size=4,
                                run_dir=str(tmp_path))
        router = Router(reps, registry=reg)
        rids = [router.submit([1, 2, 3 + i], max_new_tokens=12)
                for i in range(4)]
        router.pump()
        moved = router.drain_replica(0, timeout=0.0)
        live_on_0 = [r for r in rids
                     if router.journals[r].replica_id == 0
                     and not router.journals[r].finished]
        assert not live_on_0                 # everything re-homed
        outs = [router.collect(r, timeout=60) for r in rids]
        for i, out in enumerate(outs):
            assert out["tokens"] == want[i], (i, out)
        assert router.migrations == moved
        if moved:
            assert reg.snapshot()["fleet.migrations"]["value"] \
                == float(moved)

    @pytest.mark.usefixtures("family")
    def test_statusz_fleet_section(self):
        from paddle_tpu.observability.monitor import StatusServer
        reps, reg = local_fleet(2, max_seqs=2, kv_block_size=4)
        router = Router(reps, registry=reg)
        rid = router.submit([1, 2, 3], max_new_tokens=4)
        router.collect(rid, timeout=60)
        page = StatusServer(registry=reg, router=router).statusz()
        fleet = page["fleet"]
        assert fleet["dispatch"] >= 1
        assert fleet["replicas"] == 2
        assert fleet["states"].get("healthy") == 2
        assert fleet["streams"]["finished"] == 1

    def test_doctor_fleet_failover_verdict(self):
        from paddle_tpu.observability.doctor import check_fleet
        recs = [{"kind": "fleet.failover", "request_id": "r1",
                 "from_replica": 0, "to_replica": 1,
                 "why": "replica died", "accepted_tokens": 5},
                {"kind": "fleet.replica_state", "replica": 0,
                 "prev": "healthy", "state": "dead"}]
        findings = check_fleet({0: recs})
        assert len(findings) == 1
        f = findings[0]
        assert f["kind"] == "fleet_failover"
        assert f["data"]["count"] == 1
        assert any("token-exact" in line for line in f["evidence"])
        assert not check_fleet({0: [recs[1]]})   # death alone: no verdict


# ---------------------------------------------------------------------------
# the multi-process drills (ci.sh fleet tier; slow)
# ---------------------------------------------------------------------------
def fleet_spec(max_pos=64):
    return {"seed": 7,
            "config": {"vocab_size": 32, "hidden_size": 32,
                       "num_layers": 2, "num_heads": 2,
                       "ffn_hidden_size": 64,
                       "max_position_embeddings": max_pos,
                       "hidden_dropout": 0.0, "attention_dropout": 0.0},
            "engine": {"max_seqs": 4}}


# workers inherit the parent's platform; a CPU drill says so
_CPU_ENV = {"JAX_PLATFORMS": "cpu"}


@pytest.mark.slow
class TestMultiProcessDrills:
    def test_sigkill_failover_drill(self, tmp_path):
        reg = MetricsRegistry()
        mgr = ReplicaManager(fleet_spec(), replicas=2, registry=reg,
                             run_dir=str(tmp_path), env=_CPU_ENV)
        mgr.start()
        try:
            router = Router(mgr.replicas, manager=mgr, registry=reg)
            rids = [router.submit([1, 2, 3 + i], max_new_tokens=40)
                    for i in range(6)]
            kill = faults.kill_replica(
                mgr, index=0,
                when=lambda: any(
                    len(j.tokens) >= 2 for j in router.journals.values()
                    if j.replica_id == 0 and not j.finished))
            deadline = time.monotonic() + 120
            while not kill.fired and time.monotonic() < deadline:
                router.pump()
                kill.maybe()
                time.sleep(0.01)
            assert kill.fired == 1
            assert mgr.poll_states()[0] == "dead"
            outs = [router.collect(r, timeout=120) for r in rids]
            assert router.failovers >= 1
            # token-exact vs an uninterrupted single-engine reference
            model = tiny_model(64)
            ref = ServingEngine(model, max_seqs=4,
                                registry=MetricsRegistry())
            ref_out = ref.generate([[1, 2, 3 + i] for i in range(6)],
                                   max_new_tokens=40)
            assert [o["tokens"] for o in outs] == ref_out
            # survivor leak report clean
            stats = router.replicas[1].serving_stats()
            assert stats["kv_blocks"]["leaked"] == 0
        finally:
            mgr.stop()

    def test_rolling_upgrade_zero_drops(self, tmp_path):
        reg = MetricsRegistry()
        mgr = ReplicaManager(fleet_spec(), replicas=2, registry=reg,
                             run_dir=str(tmp_path), env=_CPU_ENV)
        mgr.start()
        try:
            router = Router(mgr.replicas, manager=mgr, registry=reg)
            rids = [router.submit([1, 2, 3 + i], max_new_tokens=48)
                    for i in range(6)]
            router.pump()
            router.rolling_upgrade(timeout_per_replica=0.05)
            assert mgr.restarts == 2
            states = mgr.poll_states()
            assert all(s == "healthy" for s in states.values())
            outs = [router.collect(r, timeout=120) for r in rids]
            # zero dropped or truncated streams
            assert all(len(o["tokens"]) == 48 for o in outs)
            model = tiny_model(64)
            ref = ServingEngine(model, max_seqs=4,
                                registry=MetricsRegistry())
            assert [o["tokens"] for o in outs] == ref.generate(
                [[1, 2, 3 + i] for i in range(6)], max_new_tokens=48)
        finally:
            mgr.stop()

    def test_worker_spill_namespaced_per_replica(self, tmp_path):
        reg = MetricsRegistry()
        mgr = ReplicaManager(fleet_spec(), replicas=1, registry=reg,
                             run_dir=str(tmp_path), env=_CPU_ENV)
        mgr.start()
        try:
            router = Router(mgr.replicas, manager=mgr, registry=reg)
            router.submit([1, 2, 3], max_new_tokens=40)
            router.pump()
            report = router.replicas[0].drain(timeout=0.0)
            if report["spilled_records"]:
                spill = (tmp_path / "serve" / "replica-0"
                         / "spill.json")
                assert spill.exists()
                payload = json.loads(spill.read_text())
                assert payload["version"] == 1
        finally:
            mgr.stop()
