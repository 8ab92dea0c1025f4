"""Distributed launcher (component D13).

Reference: ``python -m paddle.distributed.launch`` —
launch/controllers/collective.py spawns one process per device and wires
PADDLE_TRAINER_ENDPOINTS / PADDLE_CURRENT_ENDPOINT / PADDLE_TRAINER_ID /
PADDLE_TRAINERS_NUM (:89-92); multi-node rendezvous via
launch/controllers/master.py (etcd/http).

TPU-native model: ONE process per HOST (single-controller SPMD), not one
per device — the per-device process zoo is NCCL's requirement, not XLA's.
Responsibilities that remain real:

- ``init_from_env()``: called in the training process; wires
  ``jax.distributed.initialize`` (the TCPStore-analog rendezvous — on TPU
  pods the runtime discovers the topology itself and all arguments are
  optional) from the reference's PADDLE_* env names or JAX's own.
- ``python -m paddle_tpu.distributed.launch --nnodes N --master host:port
  train.py ...``: spawns N local host-processes with the env wired (the
  localhost simulation of a pod, ≙ the reference's test doctrine), or with
  ``--nnodes 1`` just execs the script.
"""
from __future__ import annotations

import os
import runpy
import subprocess
import sys
from typing import List, Optional

import jax

from ...framework.log import vlog

__all__ = ["init_from_env", "launch"]


def _env(name: str, *alts: str, default: Optional[str] = None
         ) -> Optional[str]:
    for n in (name,) + alts:
        v = os.environ.get(n)
        if v:
            return v
    return default


def init_from_env() -> None:
    """Bring up multi-host JAX from launcher env vars.

    Env (reference names first, JAX names accepted):
      PADDLE_MASTER / JAX_COORDINATOR_ADDRESS — host:port of process 0
      PADDLE_TRAINERS_NUM / JAX_NUM_PROCESSES — process count
      PADDLE_TRAINER_ID / JAX_PROCESS_ID — this process's id
    With none set on a TPU pod, jax.distributed.initialize() lets the
    runtime discover everything (the TPU-native path).
    """
    if jax.distributed.is_initialized():
        return  # idempotent: the launcher already initialized this process
    coord = _env("PADDLE_MASTER", "JAX_COORDINATOR_ADDRESS")
    nproc = _env("PADDLE_TRAINERS_NUM", "JAX_NUM_PROCESSES")
    pid = _env("PADDLE_TRAINER_ID", "JAX_PROCESS_ID")
    kwargs = {}
    if coord:
        kwargs["coordinator_address"] = coord
    if nproc:
        kwargs["num_processes"] = int(nproc)
    if pid:
        kwargs["process_id"] = int(pid)
    vlog(1, "launch.init_from_env: %s", kwargs or "(TPU pod auto-discovery)")
    jax.distributed.initialize(**kwargs)


def launch(argv: Optional[List[str]] = None) -> int:
    """Entry of ``python -m paddle_tpu.distributed.launch``."""
    import argparse
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="Launch a (multi-host) training script.")
    p.add_argument("--nnodes", type=int,
                   default=int(os.environ.get("PADDLE_TRAINERS_NUM", "1")),
                   help="number of host processes (local simulation when "
                        "they all run here)")
    p.add_argument("--master", default=os.environ.get(
        "PADDLE_MASTER", "127.0.0.1:37777"),
        help="host:port of the coordinator (process 0)")
    p.add_argument("--node_rank", type=int, default=None,
                   help="run ONLY this rank (real multi-host: one launcher "
                        "per host); default spawns all ranks locally")
    p.add_argument("--run_dir", default=os.environ.get("PTPU_RUN_DIR"),
                   help="supervised run directory: the launcher monitors "
                        "<run_dir>/heartbeats and logs/records run-state "
                        "transitions (healthy/degraded/lost-worker)")
    p.add_argument("--elastic", default=os.environ.get("PTPU_ELASTIC"),
                   metavar="MIN:MAX",
                   help="elastic fleet mode (ISSUE 9): reconcile the "
                        "worker set between MIN and MAX instead of dying "
                        "with the first lost worker — publishes a "
                        "generation-stamped <run_dir>/world.json, shrinks "
                        "the world when a worker dies, respawns it after "
                        "PTPU_ELASTIC_RESPAWN_SECS and re-expands; every "
                        "transition is an elastic.resize event in "
                        "launcher_report.json (requires --run_dir)")
    p.add_argument("script")
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)

    def env_for(rank: int) -> dict:
        env = dict(os.environ)
        env["PADDLE_MASTER"] = args.master
        env["PADDLE_TRAINERS_NUM"] = str(args.nnodes)
        env["PADDLE_TRAINER_ID"] = str(rank)
        return env

    if args.elastic:
        return _reconcile_elastic(args)

    if args.nnodes <= 1:
        sys.argv = [args.script] + list(args.script_args)
        stop_live = (_live_aggregate(args.run_dir) if args.run_dir
                     else None)
        try:
            runpy.run_path(args.script, run_name="__main__")
        finally:
            if stop_live is not None:
                stop_live()
        if args.run_dir:
            _aggregate_metrics(args.run_dir)
        return 0

    if args.node_rank is not None:
        os.environ.update(env_for(args.node_rank))
        init_from_env()
        sys.argv = [args.script] + list(args.script_args)
        runpy.run_path(args.script, run_name="__main__")
        return 0

    # local simulation: spawn every rank here (≙ the reference's
    # localhost-multiprocess test doctrine, test_dist_base.py:782)
    procs = []
    for rank in range(args.nnodes):
        cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
               "--nnodes", str(args.nnodes), "--master", args.master,
               "--node_rank", str(rank), args.script] + list(args.script_args)
        procs.append(subprocess.Popen(cmd, env=env_for(rank)))
    stop_monitor = stop_live = None
    if args.run_dir:
        # one launcher report shared by the heartbeat monitor and the
        # live aggregator — both record onto the same event log
        from ...supervisor.report import SupervisorReport
        report = SupervisorReport(
            os.path.join(args.run_dir, "launcher_report.json"))
        stop_monitor = _monitor_heartbeats(args.run_dir, args.nnodes,
                                           report)
        stop_live = _live_aggregate(args.run_dir, report)
    rc = 0
    for rank, proc in enumerate(procs):
        code = proc.wait()
        vlog(1, "rank %d exited with %d", rank, code)
        rc = rc or code
    if stop_live is not None:
        stop_live()
    if stop_monitor is not None:
        stop_monitor()
    if args.run_dir:
        _aggregate_metrics(args.run_dir)
    return rc


def _parse_elastic(spec: str, nnodes: int):
    """``MIN:MAX`` (or ``MIN``) → (min, max); the launch width must sit
    inside the range."""
    lo, _, hi = str(spec).partition(":")
    min_n = int(lo)
    max_n = int(hi) if hi else max(nnodes, min_n)
    if not (1 <= min_n <= nnodes <= max_n):
        raise SystemExit(
            f"--elastic {spec!r}: need 1 <= MIN <= --nnodes <= MAX "
            f"(got min={min_n} nnodes={nnodes} max={max_n})")
    return min_n, max_n


def _reconcile_elastic(args) -> int:
    """The elastic fleet's control loop (ISSUE 9) — the launcher-side
    half of the reference ElasticManager's watch cycle.

    The launcher is the single writer of ``<run_dir>/world.json``.  Every
    membership change bumps the world generation, which (a) tells the
    surviving workers to rewind to ``last_good_step()`` and re-form at
    the new width, and (b) fences the departed worker: if its process is
    somehow still alive (zombie, GC pause), its checkpoint commits are
    refused against the newer generation.

    Workers are spawned as plain script processes (NOT through the
    ``--node_rank`` re-exec, which would initialize a fixed-size
    ``jax.distributed`` world — on a real TPU pod the runtime re-forms
    the SPMD world per relaunch; membership is the launcher's job).

    Env knobs: ``PTPU_ELASTIC_RESPAWN_SECS`` (delay before a lost rank
    is retried, default 5), ``PTPU_ELASTIC_MAX_RESPAWNS`` (retries per
    rank, default 2).
    """
    import time

    from ...supervisor.heartbeat import HeartbeatMonitor, default_interval
    from ...supervisor.report import SupervisorReport
    from ..elastic import write_world

    if not args.run_dir:
        raise SystemExit("--elastic requires --run_dir (the world "
                         "descriptor and heartbeats live there)")
    min_n, max_n = _parse_elastic(args.elastic, args.nnodes)
    respawn_secs = float(os.environ.get("PTPU_ELASTIC_RESPAWN_SECS", "5"))
    max_respawns = int(os.environ.get("PTPU_ELASTIC_MAX_RESPAWNS", "2"))
    run_dir = args.run_dir
    report = SupervisorReport(os.path.join(run_dir, "launcher_report.json"))

    generation = 0
    members = set(range(args.nnodes))
    write_world(run_dir, generation=generation, members=members,
                min_size=min_n, max_size=max_n, reason="launch")
    report.record("elastic.world", generation=generation,
                  members=sorted(members), min=min_n, max=max_n)

    # workers run the script directly (sys.path[0] becomes the script's
    # dir, not ours) — make sure they can import this very package
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))

    def spawn(rank: int) -> subprocess.Popen:
        env = dict(os.environ)
        env["PADDLE_TRAINER_ID"] = str(rank)
        env["PADDLE_TRAINERS_NUM"] = str(len(members))
        env["PTPU_RUN_DIR"] = run_dir
        env["PTPU_ELASTIC"] = args.elastic
        env["PYTHONPATH"] = pkg_root + os.pathsep + env.get(
            "PYTHONPATH", "")
        cmd = [sys.executable, args.script] + [
            a for a in args.script_args if a != "--"]
        vlog(1, "elastic: spawning rank %d: %s", rank, cmd)
        return subprocess.Popen(cmd, env=env)

    def publish(reason: str, direction: str, changed):
        nonlocal generation
        generation += 1
        write_world(run_dir, generation=generation, members=members,
                    min_size=min_n, max_size=max_n, reason=reason)
        monitor.set_expected(set(members))
        report.record("elastic.resize", generation=generation,
                      world_size=len(members), members=sorted(members),
                      direction=direction, changed=sorted(changed),
                      reason=reason)
        try:
            from ...observability import get_registry
            reg = get_registry()
            reg.counter("elastic.resizes").inc()
            reg.gauge("elastic.generation").set(generation)
            reg.gauge("elastic.world_size").set(len(members))
        except Exception as e:
            vlog(1, "elastic: resize metrics failed: %r", e)
        vlog(0, "elastic: world generation %d — %s %s (%d member(s): %s)",
             generation, direction, sorted(changed), len(members),
             sorted(members))

    monitor = HeartbeatMonitor(run_dir, expected=set(members),
                               report=report)
    stop_live = _live_aggregate(run_dir, report)
    procs = {rank: spawn(rank) for rank in sorted(members)}
    respawn_at: dict = {}      # rank -> monotonic deadline
    respawns: dict = {}        # rank -> attempts used
    finished_clean = set()
    failed = False
    poll_every = min(0.2, default_interval() / 2.0)
    last_hb_poll = 0.0
    try:
        while procs or respawn_at:
            now = time.monotonic()
            if now - last_hb_poll >= default_interval() / 2.0:
                last_hb_poll = now
                monitor.poll()
            for rank, proc in list(procs.items()):
                rc = proc.poll()
                if rc is None:
                    continue
                del procs[rank]
                if rc == 0:
                    finished_clean.add(rank)
                    vlog(1, "elastic: rank %d finished clean", rank)
                    continue
                if rank not in members:
                    vlog(1, "elastic: retired rank %d exited %d", rank, rc)
                    continue
                # lost worker — shrink the world (or fail below MIN)
                members.discard(rank)
                report.record("elastic.worker_lost", rank=rank,
                              returncode=rc)
                if len(members) < min_n:
                    report.record("elastic.failed", reason="below-min",
                                  world_size=len(members), min=min_n)
                    vlog(0, "elastic: %d member(s) left < min %d — "
                         "failing the run", len(members), min_n)
                    failed = True
                    for p in procs.values():
                        p.terminate()
                    return 1
                publish(f"lost-worker:{rank}", "shrink", {rank})
                if respawns.get(rank, 0) < max_respawns \
                        and len(members) < max_n:
                    respawn_at[rank] = now + respawn_secs
            # a finished world means the run is over: members that are
            # neither running nor scheduled for respawn all exited clean
            live_members = [r for r in members
                            if r in procs or r in respawn_at]
            if not live_members and members <= finished_clean:
                respawn_at.clear()
                break
            for rank, deadline in list(respawn_at.items()):
                if time.monotonic() < deadline:
                    continue
                del respawn_at[rank]
                respawns[rank] = respawns.get(rank, 0) + 1
                members.add(rank)
                publish(f"respawn:{rank}", "grow", {rank})
                procs[rank] = spawn(rank)
            time.sleep(poll_every)
    finally:
        for rank, proc in procs.items():
            if proc.poll() is None:   # retired stragglers: the run is over
                vlog(1, "elastic: terminating leftover rank %d", rank)
                proc.terminate()
        if stop_live is not None:
            stop_live()
        monitor.poll()
        rc_final = 1 if failed or not (members <= finished_clean) else 0
        report.record("elastic.done", returncode=rc_final,
                      generation=generation, members=sorted(members),
                      finished=sorted(finished_clean),
                      respawns=dict(respawns))
        _aggregate_metrics(run_dir)
    return rc_final


def _aggregate_metrics(run_dir: str) -> None:
    """Merge the workers' ``<run_dir>/metrics/worker-*.jsonl`` telemetry
    streams into ``metrics/summary.json`` (ISSUE 3) — the launcher is the
    one process guaranteed to outlive every worker, so cross-worker
    aggregation happens here."""
    from ...observability import aggregate_run
    try:
        summary = aggregate_run(run_dir)
    except OSError as e:
        vlog(0, "launch: metrics aggregation under %s failed: %s",
             run_dir, e)
        return
    if summary is not None:
        vlog(0, "launch: merged %d worker metric streams (%d records) → "
             "%s/metrics/summary.json", len(summary["workers"]),
             summary["records"], run_dir)
        _run_doctor(run_dir)


def _run_doctor(run_dir: str) -> None:
    """Post-run diagnosis (ISSUE 4): rank retrace storms / HBM pressure /
    stragglers / data starvation into ``<run_dir>/diagnosis.json`` and
    log the verdicts — the launcher outlives every worker, so this is
    where the whole-run view exists."""
    from ...observability import doctor as doctor_mod
    try:
        diagnosis = doctor_mod.diagnose(run_dir)
    except Exception as e:  # diagnosis is best-effort, the run is done
        vlog(0, "launch: run doctor failed under %s: %r", run_dir, e)
        return
    if diagnosis is None:
        return
    if diagnosis["healthy"]:
        vlog(0, "launch: run doctor — no findings (healthy run)")
        return
    top = diagnosis["findings"][0]
    vlog(0, "launch: run doctor — %d finding(s) → %s/diagnosis.json; "
         "top: [%d] %s: %s", len(diagnosis["findings"]), run_dir,
         top["severity"], top["kind"], top["title"])


def _live_aggregate(run_dir: str, report=None):
    """In-flight cross-worker aggregation (ISSUE 5): a background
    :class:`~paddle_tpu.observability.monitor.LiveAggregator` tail-reads
    the workers' still-growing JSONL streams every
    ``PTPU_MONITOR_INTERVAL`` seconds, re-runs the doctor's rules on the
    window, keeps ``<run_dir>/live_status.json`` rolling, and records
    ``monitor.alert`` events in ``launcher_report.json`` the moment a
    verdict first fires — the launcher names a retrace storm or a
    straggler while the run still burns chips, not at teardown.
    Returns a callable that stops the thread (with one final poll)."""
    from ...observability.monitor import LiveAggregator

    if report is None:
        from ...supervisor.report import SupervisorReport
        report = SupervisorReport(os.path.join(run_dir,
                                               "launcher_report.json"))
    aggregator = LiveAggregator(run_dir, report=report).start()

    def stop_fn():
        aggregator.stop()
        if aggregator.alerts:
            vlog(0, "launch: live monitor raised %d alert(s); first: %s",
                 len(aggregator.alerts), aggregator.alerts[0]["title"])

    return stop_fn


def _monitor_heartbeats(run_dir: str, nnodes: int, report=None):
    """Launcher-side health view (ISSUE 2): poll the workers' heartbeat
    files and record every healthy/degraded/lost-worker transition in
    ``<run_dir>/launcher_report.json`` — the acting end of the heartbeat
    subsystem (the relaunch decision itself belongs to the cluster
    scheduler, ≙ the reference ElasticManager's watch loop).  Returns a
    callable that stops the monitor and does one final poll."""
    import threading

    from ...supervisor.heartbeat import HeartbeatMonitor, default_interval
    from ...supervisor.report import SupervisorReport

    if report is None:
        report = SupervisorReport(os.path.join(run_dir,
                                               "launcher_report.json"))
    monitor = HeartbeatMonitor(run_dir, expected=nnodes, report=report)
    stop = threading.Event()

    def poll_loop():
        while not stop.wait(default_interval()):
            monitor.poll()

    t = threading.Thread(target=poll_loop, name="ptpu-launch-monitor",
                         daemon=True)
    t.start()

    def stop_fn():
        stop.set()
        t.join(timeout=2.0)
        monitor.poll()

    return stop_fn
