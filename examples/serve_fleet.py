"""Serving-fleet drills (ISSUE 16 + 17): a replica fleet behind the
router, killed, upgraded, crashed and autoscaled under load, with
token-exactness proved against an uninterrupted single-engine
reference.

    python examples/serve_fleet.py --sigkill_drill
        spawn 2 engine workers, push 6 concurrent streams, SIGKILL one
        replica after streams have accepted tokens, and assert: every
        client completes, every completion is token-identical to a
        single uninterrupted engine, `fleet.failovers` >= 1, and the
        surviving replica's KV allocator leak report is clean.

    python examples/serve_fleet.py --rolling_upgrade
        same fleet + load, then drain each replica in turn while the
        router migrates its spilled streams and the manager respawns
        it — zero dropped or truncated streams, and /statusz's fleet
        census shows every replica healthy again at the end.

    python examples/serve_fleet.py --router_crash_drill
        ISSUE 17 crash-safety acceptance: a child process runs a
        journaling router over 6 ragged streams, the parent SIGKILLs
        the *router* mid-stream (the workers survive as orphans), and
        a fresh ``Router(recover=run_dir)`` built from the journal
        directory alone must finish every stream token-identical to
        the reference — with zero replica restarts and no live
        journal files left behind.

    python examples/serve_fleet.py --autoscale_drill
        ISSUE 17 autoscaler acceptance, on fake time: a queue burst
        must scale the fleet up, continued burn at the ceiling must
        record ``blocked_at_max``, and a fully idle window must drain
        + retire back down — every transition a ``fleet.autoscale``
        record, and the burst's streams still token-exact.

    python examples/serve_fleet.py --trace_drill
        ISSUE 18 request-tracing acceptance: 8 ragged streams through
        a journaled 2-replica fleet, replica 0 SIGKILLed mid-stream.
        The assembler must produce exactly ONE waterfall per request
        (the victims stitched across both replicas), coverage >= 95%
        with zero orphan spans, and the tail-latency doctor must name
        failover recompute as the dominant p99 component.

All drills print one JSON line of evidence and exit nonzero on any
violated invariant, so ci.sh can run them as smokes.
"""
import argparse
import json
import os
import re
import signal
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as pt
from paddle_tpu.inference import ServingEngine
from paddle_tpu.inference.fleet import (FleetAutoscaler, HttpReplica,
                                        LocalReplicaManager, ReplicaManager,
                                        Router, ServingSLO)
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.observability.monitor import StatusServer
from paddle_tpu.observability.registry import MetricsRegistry
from paddle_tpu.testing import faults

SPEC = {"seed": 7,
        "config": {"vocab_size": 32, "hidden_size": 32, "num_layers": 2,
                   "num_heads": 2, "ffn_hidden_size": 64,
                   "max_position_embeddings": 64, "hidden_dropout": 0.0,
                   "attention_dropout": 0.0},
        "engine": {"max_seqs": 4}}
PROMPTS = [[1, 2, 3 + i] for i in range(6)]
# these drills run N worker subprocesses, which cannot share one chip
# (one process per chip; see ReplicaManager) — they are CPU drills
CPU_ENV = {"JAX_PLATFORMS": "cpu"}


def reference_outputs(max_new):
    """What an uninterrupted single engine produces for PROMPTS."""
    pt.seed(SPEC["seed"])
    model = GPTForCausalLM(GPTConfig(**SPEC["config"]))
    model.eval()
    ref = ServingEngine(model, max_seqs=4, registry=MetricsRegistry())
    return ref.generate(PROMPTS, max_new_tokens=max_new)


def start_fleet(run_dir, journal=False):
    reg = MetricsRegistry()
    mgr = ReplicaManager(SPEC, replicas=2, registry=reg, run_dir=run_dir,
                         env=CPU_ENV)
    mgr.start()
    router = Router(mgr.replicas, manager=mgr, registry=reg,
                    run_dir=run_dir if journal else None)
    return reg, mgr, router


def sigkill_drill(run_dir):
    max_new = 40
    reg, mgr, router = start_fleet(run_dir)
    try:
        rids = [router.submit(p, max_new_tokens=max_new)
                for p in PROMPTS]
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
        assert kill.fired == 1, "kill predicate never held"
        assert mgr.poll_states()[0] == "dead"
        outs = [router.collect(r, timeout=120) for r in rids]
        ref = reference_outputs(max_new)
        exact = sum(o["tokens"] == ref[i] for i, o in enumerate(outs))
        assert exact == len(PROMPTS), \
            f"only {exact}/{len(PROMPTS)} streams token-exact"
        assert router.failovers >= 1, "no failover observed"
        survivor = router.replicas[1].serving_stats()
        assert survivor["kv_blocks"]["leaked"] == 0, survivor
        page = StatusServer(registry=reg, router=router).statusz()
        assert page["fleet"]["states"].get("dead") == 1
        print(json.dumps({
            "drill": "sigkill", "streams": len(PROMPTS),
            "token_exact": exact, "failovers": router.failovers,
            "survivor_leaked_blocks":
                survivor["kv_blocks"]["leaked"]}))
    finally:
        mgr.stop()


def rolling_upgrade(run_dir):
    max_new = 48
    reg, mgr, router = start_fleet(run_dir)
    try:
        rids = [router.submit(p, max_new_tokens=max_new)
                for p in PROMPTS]
        router.pump()
        migrated = router.rolling_upgrade(timeout_per_replica=0.05)
        states = mgr.poll_states()
        assert all(s == "healthy" for s in states.values()), states
        outs = [router.collect(r, timeout=120) for r in rids]
        dropped = sum(len(o["tokens"]) != max_new for o in outs)
        assert dropped == 0, f"{dropped} truncated streams"
        ref = reference_outputs(max_new)
        exact = sum(o["tokens"] == ref[i] for i, o in enumerate(outs))
        assert exact == len(PROMPTS), \
            f"only {exact}/{len(PROMPTS)} streams token-exact"
        page = StatusServer(registry=reg, router=router).statusz()
        assert page["fleet"]["states"].get("healthy") == 2
        assert page["fleet"]["restarts"] == 2
        print(json.dumps({
            "drill": "rolling_upgrade", "streams": len(PROMPTS),
            "dropped": dropped, "token_exact": exact,
            "migrated": migrated, "restarts": mgr.restarts}))
    finally:
        mgr.stop()


_READY_FILE = "crash_child_ready.json"
_RAGGED_MAX_NEW = [40 + 4 * i for i in range(len(PROMPTS))]


def _crash_child(run_dir):
    """The victim: a journaling router that admits 6 ragged streams,
    pumps until every journal holds accepted tokens, then parks and
    waits for the parent's SIGKILL.  No cleanup — that is the point."""
    reg, mgr, router = start_fleet(run_dir, journal=True)
    rids = [router.submit(p, max_new_tokens=_RAGGED_MAX_NEW[i])
            for i, p in enumerate(PROMPTS)]
    deadline = time.monotonic() + 120
    while (any(len(j.tokens) < 2 for j in router.journals.values())
           and time.monotonic() < deadline):
        router.pump()
        time.sleep(0.01)
    assert all(len(j.tokens) >= 2 for j in router.journals.values()), \
        "streams never accepted tokens"
    ready = {"streams": [{"request_id": r, "max_new": _RAGGED_MAX_NEW[i]}
                         for i, r in enumerate(rids)],
             "workers": [{"replica": i, "port": rep.port,
                          "pid": rep.process.pid}
                         for i, rep in enumerate(mgr.replicas)]}
    path = os.path.join(run_dir, _READY_FILE)
    with open(path + ".tmp", "w") as f:
        json.dump(ready, f)
    os.replace(path + ".tmp", path)     # atomic: parent sees all or nothing
    while True:                          # hold streams mid-flight
        time.sleep(1)


def _reap_workers(workers):
    """Shut down the orphaned worker processes the drill left behind."""
    for w in workers:
        HttpReplica(w["replica"], w["port"]).stop()
    deadline = time.monotonic() + 15
    for w in workers:
        while time.monotonic() < deadline:
            try:
                os.kill(w["pid"], 0)
            except ProcessLookupError:
                break
            time.sleep(0.05)
        else:
            try:
                os.kill(w["pid"], signal.SIGKILL)
            except ProcessLookupError:
                pass


def router_crash_drill(run_dir):
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__),
         "--_crash_child", run_dir],
        stdout=subprocess.DEVNULL)
    ready_path = os.path.join(run_dir, _READY_FILE)
    info = None
    try:
        deadline = time.monotonic() + 300
        while not os.path.exists(ready_path):
            assert child.poll() is None, \
                f"router child died before ready (rc={child.returncode})"
            assert time.monotonic() < deadline, "router child never ready"
            time.sleep(0.02)
        with open(ready_path) as f:
            info = json.load(f)
        # SIGKILL the router — no atexit, no drain, no journal flush
        os.kill(child.pid, signal.SIGKILL)
        child.wait(timeout=10)
        for w in info["workers"]:        # workers must have survived
            os.kill(w["pid"], 0)
        reg = MetricsRegistry()
        replicas = [HttpReplica(w["replica"], w["port"])
                    for w in info["workers"]]
        router = Router(replicas, registry=reg, recover=run_dir)
        rec = dict(router.recovered)
        assert rec["streams"] == len(info["streams"]), rec
        assert rec["reattached"] + rec["redispatched"] >= 1, rec
        outs = [router.collect(s["request_id"], timeout=120)
                for s in info["streams"]]
        ref = reference_outputs(max(_RAGGED_MAX_NEW))
        exact = sum(o["tokens"] == ref[i][: s["max_new"]]
                    for i, (s, o) in enumerate(zip(info["streams"], outs)))
        assert exact == len(PROMPTS), \
            f"only {exact}/{len(PROMPTS)} recovered streams token-exact"
        leaked = 0
        for w, replica in zip(info["workers"], replicas):
            os.kill(w["pid"], 0)         # original pid: never restarted
            leaked += replica.serving_stats()["kv_blocks"]["leaked"]
        assert leaked == 0, f"{leaked} KV blocks leaked across the crash"
        assert router.store.live_count() == 0, \
            "live journal files left after every stream finished"
        print(json.dumps({
            "drill": "router_crash", "streams": len(PROMPTS),
            "token_exact": exact, "recovered": rec,
            "worker_restarts": 0, "leaked_blocks": leaked,
            "journal_live": router.store.live_count(),
            "journal_drops": dict(router.store.drops)}))
    finally:
        if child.poll() is None:
            child.kill()
            child.wait(timeout=10)
        if info is not None:
            _reap_workers(info["workers"])


def autoscale_drill(run_dir):
    max_new = 8
    reg = MetricsRegistry()
    records = []

    class _Capture:
        def write(self, r):
            records.append(r)

        def flush(self):
            pass

        def close(self):
            pass

    reg.add_sink(_Capture())

    def factory(i):
        pt.seed(SPEC["seed"])
        model = GPTForCausalLM(GPTConfig(**SPEC["config"]))
        model.eval()
        return ServingEngine(model, max_seqs=4, registry=reg)

    clk = {"t": 0.0}
    mgr = LocalReplicaManager(factory, replicas=1, registry=reg)
    router = Router(mgr.replicas, manager=mgr, registry=reg)
    scaler = FleetAutoscaler(
        mgr, router=router, slo=ServingSLO(queue_depth=2.0),
        min_replicas=1, max_replicas=2, window_secs=10.0,
        cooldown_secs=5.0, registry=reg, clock=lambda: clk["t"])

    def tick_until(action, limit=60):
        for _ in range(limit):
            clk["t"] += 1.0
            if scaler.step() == action:
                return
        raise AssertionError(f"autoscaler never chose {action!r}: "
                             f"{scaler.stats()}")

    # burst: 6 streams against 1 replica — queue SLO burns -> scale up
    rids = [router.submit(p, max_new_tokens=max_new) for p in PROMPTS]
    tick_until("up")
    assert len(scaler.active_ids()) == 2, mgr.poll_states()
    # still burning at the ceiling -> the page-worthy record, not a spawn
    tick_until("blocked_at_max")
    assert len(scaler.active_ids()) == 2, mgr.poll_states()
    # drain the burst; a fully idle window -> drain + retire back down
    router.run(timeout=120)
    tick_until("down")
    states = mgr.poll_states()
    assert sum(1 for s in states.values() if s == "retired") == 1, states
    assert len(scaler.active_ids()) == 1, states
    outs = [router.collect(r, timeout=10) for r in rids]
    ref = reference_outputs(max_new)
    exact = sum(o["tokens"] == ref[i] for i, o in enumerate(outs))
    assert exact == len(PROMPTS), \
        f"only {exact}/{len(PROMPTS)} streams token-exact across scaling"
    scale_records = [r for r in records if r["kind"] == "fleet.autoscale"]
    actions = [r["action"] for r in scale_records]
    for want in ("up", "blocked_at_max", "down"):
        assert want in actions, f"no fleet.autoscale {want!r}: {actions}"
    for r in scale_records:              # the timeline schema operators page on
        for field in ("action", "replicas", "target", "burn", "idle",
                      "why", "slo"):
            assert field in r, (field, r)
    print(json.dumps({
        "drill": "autoscale", "streams": len(PROMPTS),
        "token_exact": exact, "actions": actions,
        "active_end": len(scaler.active_ids()),
        "scaler": scaler.stats()["actions"]}))


_TRACE_PROMPTS = [[1, 2, 3 + i % 6, 4 + i % 3] for i in range(8)]
_TRACE_MAX_NEW = [24 + 2 * i for i in range(8)]     # ragged 24..38


def trace_drill(run_dir):
    """ISSUE 18 acceptance: per-request waterfalls survive a replica
    SIGKILL.  Every victim stream's trace must stitch across BOTH
    replicas, every request must assemble into exactly one trace with
    coverage >= 95% and zero orphan spans, and both the attribution
    helper and the doctor must name failover recompute as what the
    p99 tail pays extra for (migrants requeue + re-prefill behind the
    survivor's residents)."""
    from paddle_tpu.observability import doctor, requesttrace
    from paddle_tpu.observability.aggregate import read_worker_stream
    from paddle_tpu.observability.sinks import MetricsWriter, metrics_dir

    mdir = metrics_dir(run_dir)
    reg = MetricsRegistry()
    # router spans go to worker-0; each engine worker writes its own
    # stream (worker-i+1) via PTPU_METRICS_DIR, flushed per record so
    # the SIGKILL victim's spans survive
    writer = reg.add_sink(MetricsWriter(mdir, worker_id=0,
                                        flush_every=1))
    mgr = ReplicaManager(SPEC, replicas=2, registry=reg,
                         run_dir=run_dir,
                         env={**CPU_ENV, "PTPU_METRICS_DIR": mdir})
    mgr.start()
    router = Router(mgr.replicas, manager=mgr, registry=reg,
                    run_dir=run_dir)       # journaled: WAL cross-check
    rids = []
    try:
        # warm EVERY replica directly (least-loaded dispatch can pile
        # all warmup onto one replica, leaving the other to compile
        # mid-drill and serialize the whole fleet behind its worker
        # lock): the len-4 prefill bucket + the padded decode batch.
        # ``"trace_id": None`` is an explicit not-traced decision, so
        # warmup streams never enter the assembly
        for i, rep in enumerate(mgr.replicas):
            warm = [f"warm-{i}-{w}" for w in range(4)]
            for rid in warm:
                rep.submit({"request_id": rid, "prompt": [1, 2, 3, 4],
                            "output": [], "max_new_tokens": 4,
                            "eos_token_id": None, "preemptions": 0,
                            "trace_id": None})
            for rid in warm:
                deadline = time.monotonic() + 120
                while not rep.poll(rid, start=0)["finished"]:
                    assert time.monotonic() < deadline, \
                        f"warmup stream {rid} never finished"
                    time.sleep(0.01)
        rids = [router.submit(p, max_new_tokens=_TRACE_MAX_NEW[i])
                for i, p in enumerate(_TRACE_PROMPTS)]
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
        assert kill.fired == 1, "kill predicate never held"
        outs = [router.collect(r, timeout=120) for r in rids]
        truncated = sum(len(o["tokens"]) != _TRACE_MAX_NEW[i]
                        for i, o in enumerate(outs))
        assert truncated == 0, f"{truncated} truncated streams"
        assert router.failovers >= 1, "no failover observed"
    finally:
        mgr.stop()
    reg.remove_sink(writer)                # flush + close worker-0

    result = requesttrace.assemble_run(run_dir)
    traces = result["traces"]
    assert len(traces) == len(rids), \
        f"{len(traces)} traces for {len(rids)} requests"
    assert {t["request_id"] for t in traces} == set(rids), \
        "assembled request ids do not match the submitted set"
    assert result["complete"] == len(rids), result
    assert not result["orphan_spans"], result["orphan_spans"]
    stitched = [t for t in traces
                if {"replica-0", "replica-1"} <= set(t["procs"])]
    assert stitched, "no trace stitched across both replicas"
    min_cov = min(t["coverage"] for t in traces)
    assert min_cov >= 0.95, \
        f"trace coverage floor {min_cov:.1%} < 95%"
    attrib = requesttrace.tail_latency_attribution(traces)
    assert attrib is not None and \
        attrib["dominant"] == "failover_recompute", attrib

    workers = {}
    for name in sorted(os.listdir(mdir)):
        m = re.match(r"^worker-(\d+)\.jsonl$", name)
        if m:
            workers[int(m.group(1))] = read_worker_stream(
                os.path.join(mdir, name))
    findings = doctor.check_tail_latency(workers)
    assert findings, "doctor produced no tail_latency verdict"
    assert findings[0]["data"]["dominant"] == "failover_recompute", \
        findings[0]
    print(json.dumps({
        "drill": "trace", "streams": len(rids),
        "traces": len(traces), "complete": result["complete"],
        "stitched_across_replicas": len(stitched),
        "coverage_min": round(min_cov, 4),
        "orphan_spans": len(result["orphan_spans"]),
        "wal_matched": result["wal_matched"],
        "tail_dominant": attrib["dominant"],
        "tail_p99_ms": attrib["p99_ms"],
        "tail_median_ms": attrib["median_ms"],
        "doctor_severity": findings[0]["severity"]}))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sigkill_drill", action="store_true")
    ap.add_argument("--rolling_upgrade", action="store_true")
    ap.add_argument("--router_crash_drill", action="store_true")
    ap.add_argument("--autoscale_drill", action="store_true")
    ap.add_argument("--trace_drill", action="store_true")
    ap.add_argument("--_crash_child", metavar="RUN_DIR", default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args._crash_child:
        _crash_child(args._crash_child)
        return
    import tempfile
    with tempfile.TemporaryDirectory() as run_dir:
        if args.sigkill_drill:
            sigkill_drill(run_dir)
        elif args.rolling_upgrade:
            rolling_upgrade(run_dir)
        elif args.router_crash_drill:
            router_crash_drill(run_dir)
        elif args.autoscale_drill:
            autoscale_drill(run_dir)
        elif args.trace_drill:
            trace_drill(run_dir)
        else:
            ap.error("pick --sigkill_drill, --rolling_upgrade, "
                     "--router_crash_drill, --autoscale_drill or "
                     "--trace_drill")


if __name__ == "__main__":
    main()
