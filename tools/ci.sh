#!/usr/bin/env bash
# CI entry (component E10 — the analog of paddle_build.sh + parallel_UT_rule):
#   tools/ci.sh [shard_index shard_count]
#
# Shards the test files deterministically across workers (sorted list,
# round-robin) so a CI fleet can split the suite; no args = everything.
# API-compat guard + the smokes run in shard 0 only.
set -euo pipefail
cd "$(dirname "$0")/.."

SHARD=${1:-0}
SHARDS=${2:-1}

mapfile -t FILES < <(ls tests/test_*.py | sort)
SELECTED=()
for i in "${!FILES[@]}"; do
    if (( i % SHARDS == SHARD )); then
        SELECTED+=("${FILES[$i]}")
    fi
done

echo "shard ${SHARD}/${SHARDS}: ${#SELECTED[@]} files"
if (( ${#SELECTED[@]} )); then
    python -m pytest "${SELECTED[@]}" -q
else
    echo "shard ${SHARD} has no files — nothing to run"
fi

if (( SHARD == 0 )); then
    python tools/print_signatures.py --check
    # static analysis (ISSUE 12): one engine, one AST parse — the three
    # legacy lints plus trace-safety / lock-discipline / knob inventory,
    # gated by tools/ptlint/baseline.json
    python -m tools.ptlint --all
    python -m pytest -q tests/test_ptlint.py
    # resilience tier: the fault-injection suite must stay green even when
    # sharding happens to place its files elsewhere
    python -m pytest -q -m faults tests/test_fault_tolerance.py \
        tests/test_supervisor.py
    # telemetry tier (ISSUE 3/4/5/18): registry/tracing/sinks/aggregation +
    # compile/memory/doctor diagnosis + live monitor/flight recorder +
    # the e2e records contracts + request-trace continuity (failover,
    # migration, router crash-recovery, preemption, quarantine)
    python -m pytest -q -m telemetry tests/test_observability.py \
        tests/test_doctor.py tests/test_monitor.py \
        tests/test_request_trace.py
    # request-trace chaos drill (ISSUE 18 acceptance): 8 ragged streams
    # through a 2-replica fleet, one replica SIGKILLed mid-stream —
    # every request must assemble into exactly ONE waterfall (the
    # victims stitched across both replicas), coverage >= 95%, and the
    # tail-latency doctor must name failover recompute as the dominant
    # p99 component
    JAX_PLATFORMS=cpu python examples/serve_fleet.py --trace_drill
    # live-monitor smoke (ISSUE 5): a supervised run with the status
    # server on an ephemeral port; scrape /healthz + /metrics mid-fit
    # and assert a known instrument is exposed
    MONITOR_TMP=$(mktemp -d)
    PTPU_MONITOR_PORT=0 JAX_PLATFORMS=cpu python - "$MONITOR_TMP" <<'PYEOF'
import json, sys, urllib.request
import numpy as np
import paddle_tpu as pt
from paddle_tpu.hapi.callbacks import Callback
from paddle_tpu.supervisor import RunSupervisor

scraped = {}

class Scraper(Callback):
    def on_train_batch_end(self, step, logs=None):
        sup = self.model._supervisor
        if step == 2 and sup is not None and not scraped:
            base = f"http://127.0.0.1:{sup.status_server.port}"
            scraped["healthz"] = json.loads(
                urllib.request.urlopen(base + "/healthz", timeout=5).read())
            scraped["metrics"] = urllib.request.urlopen(
                base + "/metrics", timeout=5).read().decode()

net = pt.nn.Sequential(pt.nn.Linear(8, 4))
model = pt.Model(net)
model.prepare(optimizer=pt.optimizer.Adam(learning_rate=1e-3),
              loss=pt.nn.CrossEntropyLoss())
rng = np.random.RandomState(0)
data = list(zip(rng.randn(32, 8).astype("float32"),
                rng.randint(0, 4, (32,)).astype("int64")))
sup = RunSupervisor(sys.argv[1] + "/run", worker_id=0,
                    sigterm_handler=False)
model.fit(data, batch_size=8, epochs=1, verbose=0, supervisor=sup,
          callbacks=[Scraper()])
assert scraped["healthz"]["ok"] is True, scraped["healthz"]
assert "paddle_tpu_step_time_ms_count" in scraped["metrics"], \
    "monitor smoke: step.time_ms instrument missing from /metrics"
print("monitor smoke: /healthz ok, /metrics exposes step.time_ms")
PYEOF
    rm -rf "$MONITOR_TMP"
    # run-doctor smoke (ISSUE 4): diagnose the checked-in degraded
    # fixture run; fail on nonzero exit or an empty diagnosis
    DOCTOR_TMP=$(mktemp -d)
    cp -r tests/fixtures/doctor_run "$DOCTOR_TMP/run"
    python -m paddle_tpu.observability.doctor "$DOCTOR_TMP/run"
    python - "$DOCTOR_TMP/run/diagnosis.json" <<'PYEOF'
import json, sys
diag = json.load(open(sys.argv[1]))
assert diag["findings"], "doctor smoke: empty diagnosis on degraded fixture"
kinds = {f["kind"] for f in diag["findings"]}
assert {"retrace_storm", "straggler"} <= kinds, f"doctor smoke: {kinds}"
PYEOF
    rm -rf "$DOCTOR_TMP"
    # serving tier (ISSUE 6 + 15): paged-KV cache invariants, scheduler
    # policy, ragged-vs-dense numerics, compile contract, facade routing,
    # and the resilience layer (deadlines/cancel, quarantine, drain)
    python -m pytest -q -m serving tests/test_serving.py \
        tests/test_serving_resilience.py
    # serve smoke: engine + status server on an ephemeral port, 8
    # concurrent synthetic streams; /statusz must report nonzero TTFT
    # percentiles and KV occupancy mid-flight
    JAX_PLATFORMS=cpu python - <<'PYEOF'
import json, urllib.request
import paddle_tpu as pt
from paddle_tpu.inference import ServingEngine
from paddle_tpu.models import GPTConfig, GPTForCausalLM

pt.seed(0)
cfg = GPTConfig(vocab_size=32, hidden_size=32, num_layers=2, num_heads=2,
                ffn_hidden_size=64, max_position_embeddings=32,
                hidden_dropout=0.0, attention_dropout=0.0)
model = GPTForCausalLM(cfg)
engine = ServingEngine(model, max_seqs=8, kv_block_size=4)
srv = engine.start_status_server(port=0, host="127.0.0.1")
for i in range(8):
    engine.submit([1 + i % 4] * (2 + i % 5), max_new_tokens=6)
# step until every stream produced its first token, then scrape mid-run
while any(s.first_token_time is None
          for s in engine.sched.running + list(engine.sched.waiting)):
    engine.step()
base = f"http://127.0.0.1:{srv.port}"
sz = json.loads(urllib.request.urlopen(base + "/statusz", timeout=5).read())
serving = sz["serving"]
assert serving["ttft_ms"]["count"] >= 8, serving["ttft_ms"]
assert serving["ttft_ms"]["p50"] > 0 and serving["ttft_ms"]["p99"] > 0
assert serving["kv_occupancy"] > 0, serving
hz = json.loads(urllib.request.urlopen(base + "/healthz", timeout=5).read())
assert hz["ok"] is True, hz
engine.run(max_steps=500)
engine.stop()
print("serve smoke: 8 streams, /statusz TTFT p50/p99 + KV occupancy ok")
PYEOF
    # serving chaos drill (ISSUE 15): poison one of 8 ragged streams →
    # exactly that request quarantined with a durable record, peers
    # token-exact, allocator back to baseline; then drain under load →
    # spill → fresh-engine resume to completion
    JAX_PLATFORMS=cpu python examples/gpt_generate.py --chaos_serve
    # drain-state smoke: /healthz must flip to 503 draining the moment
    # admission closes, then report a clean stop
    JAX_PLATFORMS=cpu python - <<'PYEOF'
import json, urllib.request
import paddle_tpu as pt
from paddle_tpu.inference import ServingEngine
from paddle_tpu.models import GPTConfig, GPTForCausalLM

pt.seed(0)
cfg = GPTConfig(vocab_size=32, hidden_size=32, num_layers=2, num_heads=2,
                ffn_hidden_size=64, max_position_embeddings=32,
                hidden_dropout=0.0, attention_dropout=0.0)
engine = ServingEngine(GPTForCausalLM(cfg), max_seqs=4, kv_block_size=4)
srv = engine.start_status_server(port=0, host="127.0.0.1")
for i in range(4):
    engine.submit([1 + i] * 3, max_new_tokens=4)
base = f"http://127.0.0.1:{srv.port}"
for _ in range(4):
    engine.step()
engine.begin_drain()
try:
    urllib.request.urlopen(base + "/healthz", timeout=5)
    raise AssertionError("healthz should be 503 while draining")
except urllib.error.HTTPError as e:
    assert e.code == 503, e.code
    hz = json.loads(e.read())
    assert hz["state"] == "draining", hz
report = engine.drain(timeout=60.0)
assert not report["timed_out"] and report["spilled"] == 0, report
sz = json.loads(urllib.request.urlopen(base + "/statusz", timeout=5).read())
assert sz["serving"]["resilience"]["state"] == "stopped", sz["serving"]
engine.stop()
print("drain smoke: healthz 503 draining -> clean stop, 4 streams finished")
PYEOF
    # fleet tier (ISSUE 16): router dispatch/affinity/admission units,
    # journal-replay failover token-exactness, and the multi-process
    # drills — one replica SIGKILLed mid-stream (every client must
    # complete token-exact vs an uninterrupted single-engine reference,
    # fleet.failovers >= 1, survivor allocators clean) and a rolling
    # upgrade (drain each replica in turn under load, zero drops)
    python -m pytest -q -m serving tests/test_serve_fleet.py \
        tests/test_fleet_autonomy.py
    JAX_PLATFORMS=cpu python examples/serve_fleet.py --sigkill_drill
    JAX_PLATFORMS=cpu python examples/serve_fleet.py --rolling_upgrade
    # fleet autonomy drills (ISSUE 17): SIGKILL the *router* mid-stream
    # (the workers survive) — Router(recover=run_dir) must finish every
    # stream token-exact from the journal directory alone with zero
    # replica restarts; then the SLO autoscaler on fake time — burst ->
    # up, ceiling -> blocked_at_max, idle window -> drain + retire down
    JAX_PLATFORMS=cpu python examples/serve_fleet.py --router_crash_drill
    JAX_PLATFORMS=cpu python examples/serve_fleet.py --autoscale_drill
    # serve_fleet smoke row; request tracing (ISSUE 18): the row carries
    # the untraced-vs-traced p50s and the assembled coverage
    JAX_PLATFORMS=cpu python -m paddle_tpu.bench \
        --scenario serve_fleet --smoke | python -c '
import json, sys
ex = json.loads(sys.stdin.readline())["extra"]
assert ex["traces_assembled"] >= 4, ex
assert ex["traces_complete"] == ex["traces_assembled"], ex
assert ex["trace_orphan_spans"] == 0, ex
print("%d traces complete, coverage p50 %.0f%%"
      % (ex["traces_complete"], 100 * ex["trace_coverage_p50"]))
'
    # kernels tier (ISSUE 7): Pallas/fused-op parity — flash attention,
    # fused block (both routes), fused CE, rope cache
    python -m pytest -q -m kernels tests/test_ops.py tests/test_fused_block.py
    # comm tier (ISSUE 8): blockwise quantization bounds, compressed
    # collectives, error-feedback sync, ZeRO-1 ShardedOptimizer parity
    # (uneven shapes / scalar leaves / mixed dtypes), fleet wiring,
    # doctor comm_bound
    python -m pytest -q -m comm tests/test_comm.py
    # MULTICHIP-style 8-device virtual-mesh drill (ISSUE 8 acceptance):
    # ZeRO-1 must match replicated Adam params to dtype tolerance
    JAX_PLATFORMS=cpu python - <<'PYEOF'
from paddle_tpu.framework.vmesh import force_virtual_cpu_mesh
force_virtual_cpu_mesh(8)
import numpy as np
import jax, jax.numpy as jnp
import paddle_tpu as pt
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.comm.config import set_default_comm_config

# param-level parity drill: ZeRO-1 through the fleet one-config-line
# switch vs replicated AdamW, 3 jitted steps on the dp=8 mesh
strategy = fleet.DistributedStrategy()
strategy.hybrid_configs = {"dp_degree": 8, "mp_degree": 1}
strategy.sharding = True
strategy.sharding_configs = {"stage": 1, "shard_weight_update": True}
fleet.init(is_collective=True, strategy=strategy)
opt = fleet.distributed_optimizer(
    pt.optimizer.AdamW(learning_rate=1e-3, weight_decay=0.01), strategy)
ref = pt.optimizer.AdamW(learning_rate=1e-3, weight_decay=0.01)
rng = np.random.RandomState(0)
params = {"w": jnp.asarray(rng.randn(37, 19), jnp.float32),
          "b": jnp.asarray(rng.randn(11), jnp.float32)}
st, rst = opt.init(params), ref.init(params)
step = jax.jit(opt.apply_gradients)
p_z, p_r = params, params
for i in range(3):
    grads = {k: jnp.asarray(np.random.RandomState(i).randn(*v.shape),
                            jnp.float32) for k, v in params.items()}
    p_z, st = step(grads, p_z, st)
    p_r, rst = ref.apply_gradients(grads, p_r, rst)
for k in params:
    d = float(jnp.abs(p_z[k] - p_r[k]).max())
    assert d < 3e-6, f"ZeRO-1 {k} diverged from replicated AdamW: {d}"
set_default_comm_config(None)
print("comm smoke: ZeRO-1 == replicated AdamW (8-device drill)")
PYEOF
    # elastic tier (ISSUE 9): world descriptor/fencing/relayout units +
    # the SIGKILL fault drills (marker `faults`; the subprocess drills
    # are `slow`, so tier-1 skips them — this is where they run)
    python -m pytest -q -m faults tests/test_elastic_fleet.py
    # launcher reconciliation smoke: SIGKILL worker 1 mid-run under
    # `launch --elastic 1:2` on the virtual-CPU mesh — the run must
    # complete (rc 0) with BOTH transitions (shrink + re-expand) and a
    # worker rewind to last_good_step in the reports
    ELASTIC_TMP=$(mktemp -d)
    JAX_PLATFORMS=cpu PTPU_HEARTBEAT_SECS=0.5 \
        PTPU_ELASTIC_RESPAWN_SECS=1.5 PTPU_TEST_SIGKILL_STEP=10 \
        PTPU_TEST_SIGKILL_RANK=1 \
        python -m paddle_tpu.distributed.launch --nnodes 2 \
        --elastic 1:2 --run_dir "$ELASTIC_TMP" \
        examples/train_elastic.py -- --steps 30 --save-interval 8 \
        --step-time 0.08
    python - "$ELASTIC_TMP" <<'PYEOF'
import json, sys
run = sys.argv[1]
report = json.load(open(run + "/launcher_report.json"))
dirs = [e["direction"] for e in report["events"]
        if e["kind"] == "elastic.resize"]
assert "shrink" in dirs and "grow" in dirs, dirs
(done,) = [e for e in report["events"] if e["kind"] == "elastic.done"]
assert done["returncode"] == 0, done
r0 = json.load(open(run + "/result-worker-0.json"))
assert r0["rewinds"] >= 1 and len(r0["losses"]) == 30, r0["rewinds"]
world = json.load(open(run + "/world.json"))
assert world["generation"] >= 2 and world["members"] == [0, 1], world
print("elastic smoke: SIGKILL drill — shrink + re-expand recorded, "
      f"worker rewound {r0['rewinds']}x, run completed at gen "
      f"{world['generation']}")
PYEOF
    rm -rf "$ELASTIC_TMP"
    # integrity tier (ISSUE 11): fingerprint/guard/heal units + the
    # cross-width relayout invariance drill in the elastic suite
    python -m pytest -q -m integrity tests/test_integrity.py \
        tests/test_elastic_fleet.py
    # integrity smoke (ISSUE 11 acceptance): 3 lockstep replicas, one
    # injected bitflip — detected within one interval, attributed to the
    # right worker by majority vote, classified hardware-SDC by the
    # replay audit, healed by resync, and the healed run's final state
    # must be bit-identical to an un-faulted reference
    INTEG_TMP=$(mktemp -d)
    JAX_PLATFORMS=cpu python - "$INTEG_TMP" <<'PYEOF'
import os, sys
import numpy as np
import paddle_tpu as pt
from paddle_tpu import nn
from paddle_tpu.distributed.fingerprint import digest_tree_host
from paddle_tpu.hapi import Model
from paddle_tpu.supervisor import RunSupervisor
from paddle_tpu.supervisor.integrity import IntegrityGuard
from paddle_tpu.testing.faults import bitflip

run_dir = sys.argv[1]

def worker(i, n):
    pt.seed(7)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    m = Model(net)
    m.prepare(optimizer=pt.optimizer.SGD(learning_rate=0.1,
                                         parameters=net.parameters()),
              loss=nn.CrossEntropyLoss())
    guard = IntegrityGuard(run_dir, worker_id=i, every=2, expected=n,
                           action="resync", resync_timeout=5.0)
    sup = RunSupervisor(
        run_dir, worker_id=i, expected_workers=n, sigterm_handler=False,
        integrity=guard, report_path=os.path.join(
            run_dir, "supervisor_report.json" if i == 0
            else f"supervisor_report-{i}.json"))
    sup.attach(m)
    return m, sup

N, STEPS, FLIP = 3, 8, 4
workers = [worker(i, N) for i in range(N)]
fault = bitflip("params/0.weight", bit=13, step=FLIP, worker=2)
rng = np.random.RandomState(0)
batches = [(rng.randn(8, 8).astype("float32"),
            (np.arange(8) % 4).astype("int64")) for _ in range(STEPS)]
losses = {i: [] for i in range(N)}
for step0, (xs, ys) in enumerate(batches):
    for i, (m, sup) in enumerate(workers):
        losses[i].append(m.train_batch(xs, ys)[0])
        m._load_supervised_state(
            fault(step0 + 1, m._supervised_state(), worker=i))
        sup.note_step_ok(m._supervised_state())
    for m, sup in workers:
        sup.recheck_integrity()
    suspects = set()
    for m, sup in workers:
        if sup.pending_integrity is not None:
            suspects.update(sup.pending_integrity["suspects"])
    for i, (m, sup) in enumerate(workers):
        if sup.pending_integrity is not None and i not in suspects:
            m._supervised_integrity_heal(sup)
    for i, (m, sup) in enumerate(workers):
        if sup.pending_integrity is not None:
            m._supervised_integrity_heal(sup)
assert fault.fired == FLIP, "bitflip never fired"
desyncs = workers[0][1].report.of_kind("integrity.desync")
assert desyncs and desyncs[0]["step"] == FLIP, desyncs  # one interval
assert desyncs[0]["suspects"] == [2], desyncs[0]        # right worker
heals = workers[2][1].report.of_kind("integrity.heal")
resyncs = [h for h in heals if h.get("action") == "resync"]
assert resyncs and resyncs[0]["audit"]["verdict"] == "sdc_suspect", heals
finals = {digest_tree_host(m._supervised_state()).hex()
          for m, _ in workers}
assert len(finals) == 1, finals
pt.seed(7)
ref_net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
ref = Model(ref_net)
ref.prepare(optimizer=pt.optimizer.SGD(learning_rate=0.1,
                                       parameters=ref_net.parameters()),
            loss=nn.CrossEntropyLoss())
ref_losses = [ref.train_batch(xs, ys)[0] for xs, ys in batches]
assert digest_tree_host(ref._supervised_state()).hex() in finals, \
    "healed fleet diverged from the un-faulted reference"
assert losses[0][-1] == ref_losses[-1]
print(f"integrity smoke: bitflip at step {FLIP} detected same interval, "
      "attributed to worker 2 (sdc_suspect), resync-healed, final state "
      "bit-equal to un-faulted reference")
PYEOF
    rm -rf "$INTEG_TMP"
    BENCH_CPU=1 python examples/gpt_generate.py --bench_serve > /dev/null
    # roofline drill: inject a synthetic memory_bound gap and assert the
    # doctor names exactly that sink — the alarm must fire for the right
    # reason, not merely fire
    JAX_PLATFORMS=cpu PTPU_ROOFLINE_TEST_INFLATE=memory_bound:0.6 \
        python - <<'PYEOF'
from paddle_tpu.bench import runner
from paddle_tpu.observability import doctor
row = runner.run_scenario("mnist", mode="smoke")
roof = row["roofline"]
assert roof["injected"], "inflation knob did not mark the block"
assert roof["dominant_sink"] == "memory_bound", roof["dominant_sink"]
total = sum(roof["buckets_ms"].values())
tol = max(0.01, 0.005 * roof["measured_step_ms"])
assert abs(total - roof["measured_step_ms"]) <= tol, (
    total, roof["measured_step_ms"])
rec = {"kind": "bench.row", "scenario": row["scenario"], "ts": 0.0,
       "mfu": row["mfu"], "roofline": roof}
(finding,) = doctor.check_mfu_gap({0: [rec]})
assert finding["data"]["dominant"] == "memory_bound", finding
assert finding["data"]["injected"] is True, finding
print("roofline drill: injected memory_bound gap -> doctor verdict:",
      finding["title"])
PYEOF
    # comm-inflation drill: inflate the comm bucket AND inject a named
    # (op, axis) into the sub-budget, then assert the doctor names
    # exactly that collective on exactly that axis — the alarm must fire
    # for the right reason, not merely fire
    JAX_PLATFORMS=cpu PTPU_ROOFLINE_TEST_INFLATE=comm:0.5 \
        PTPU_INTERCONNECT_TEST_INFLATE=all_to_all:ep:0.8 \
        python - <<'PYEOF'
from paddle_tpu.bench import runner
from paddle_tpu.observability import doctor
row = runner.run_scenario("mnist", mode="smoke")
ic = row["interconnect"]
assert ic["injected"] == {"op": "all_to_all", "axis": "ep",
                          "frac": 0.8}, ic["injected"]
entries = ic["entries"]
dom = max((e for e in entries if e["op"] != "(unattributed)"),
          key=lambda e: e["measured_ms"])
assert (dom["op"], dom["axis"]) == ("all_to_all", "ep"), dom
total = sum(e["measured_ms"] for e in entries)
tol = max(0.01, 0.005 * abs(ic["comm_bucket_ms"]))
assert abs(total - ic["comm_bucket_ms"]) <= tol, (
    total, ic["comm_bucket_ms"])
assert abs(ic["comm_bucket_ms"]
           - row["roofline"]["buckets_ms"]["comm"]) <= tol
rec = {"kind": "bench.row", "scenario": row["scenario"], "ts": 0.0,
       "roofline": {"measured_step_ms":
                    row["roofline"]["measured_step_ms"]},
       "interconnect": ic}
(finding,) = doctor.check_comm_budget({0: [rec]})
assert finding["data"]["op"] == "all_to_all", finding
assert finding["data"]["axis"] == "ep", finding
print("interconnect drill: injected all_to_all[axis=ep] -> doctor "
      "verdict:", finding["title"])
PYEOF
    # warm-start drill (ROADMAP 5a): the persistent-compile-cache test is
    # `slow` (two fresh jax processes), so tier-1 skips it — run it here
    python -m pytest -q -m slow tests/test_compile_cache.py
    echo "api-guard + ptlint + faults tier + telemetry tier + trace" \
         "drill + doctor smoke + monitor smoke + serving tier + serve" \
         "smoke + serve chaos drill + drain smoke + fleet tier + fleet" \
         "drills + trace overhead + kernels tier + comm tier + comm" \
         "smoke + elastic tier + elastic smoke + integrity tier +" \
         "integrity smoke + serve bench smoke + roofline drill +" \
         "interconnect drill + warm-start ok"
fi
echo "shard ${SHARD} green"
