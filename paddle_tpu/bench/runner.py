"""Scenario runner (ISSUE 13): registry entry → one validated row.

``run_scenario`` is the assembly point — it brackets the scenario with
the compile window and bytes-on-wire baselines and stamps device
provenance; ``run_scenarios`` validates each row.  The runner never
touches model code.
"""
from __future__ import annotations

import os
import sys
import traceback
from typing import Any, Dict, List, Optional

from . import harness, scenarios, schema

__all__ = ["run_scenario", "run_scenarios", "ensure_devices"]


def _emit_diag(msg: str) -> None:
    sys.stderr.write(msg + "\n")
    sys.stderr.flush()


def ensure_devices() -> str:
    """Decide what the matrix runs on; returns the platform.

    ``BENCH_CPU=1`` or ``JAX_PLATFORMS=cpu`` asks for the virtual CPU
    mesh (8-wide so the meshed scenarios — long_context's dp×sp axes —
    have devices to shard over); otherwise
    the matrix runs on the TPU this process finds, and finding anything
    else is an error: a run that was not told to use a CPU never
    continues on one.
    """
    import jax

    if (os.environ.get("BENCH_CPU") == "1"
            or os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"):
        from ..framework.vmesh import force_virtual_cpu_mesh
        force_virtual_cpu_mesh(int(os.environ.get("BENCH_CPU_DEVICES", "8")))
        return "cpu"
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"[bench] found {platform!r} devices, not a TPU; nothing was "
            f"measured (BENCH_CPU=1 runs the CPU smoke on purpose)")
    return "tpu"


def run_scenario(name: str, mode: str = "smoke",
                 registry=None) -> Dict[str, Any]:
    """Run one registered scenario and assemble its schema row."""
    from ..observability import get_registry
    from ..observability.compilecache import enable_persistent_cache

    registry = registry or get_registry()
    enable_persistent_cache()
    fn = scenarios.get(name)
    wire = harness.bytes_on_wire(registry)
    with harness.CompileWindow(registry) as cw, \
            harness.RooflineWindow() as rw:
        payload = fn(mode)
    phases = payload.get("phases_ms") or {}
    padding_frac = float(
        (payload.get("extra") or {}).get("padding_frac") or 0.0)
    roof = rw.block(payload["step_times_ms"], phases,
                    padding_frac=padding_frac)
    from ..observability import interconnect as ic_mod
    comm_bucket = float(((roof or {}).get("buckets_ms") or {})
                        .get("comm") or 0.0)
    try:
        import jax
        default_n = jax.device_count()
    except Exception:
        default_n = None
    per_op = payload.get("collective_by_op")
    if per_op is None:
        ic = ic_mod.degraded_block(
            comm_bucket, reason="scenario reports no per-collective "
                                "deltas")
    else:
        ic = ic_mod.build_block(comm_bucket, per_op,
                                hlo_comm=roof.get("comm_ops"),
                                default_participants=default_n)
    row = schema.new_row(
        name, mode,
        step_times_ms=payload["step_times_ms"],
        phases_ms=phases,
        config=payload.get("config"),
        tokens_per_sec=payload.get("tokens_per_sec"),
        mfu=payload.get("mfu"),
        compile_stats=cw.stats(),
        bytes_on_wire=wire.delta(),
        peak_hbm_bytes=payload.get("peak_hbm_bytes"),
        roofline=roof,
        interconnect=ic,
        extra=payload.get("extra"),
    )
    # mirror the headline figures into the live registry so /statusz and
    # the doctor see the freshest matrix
    p50 = row["step_time_ms"]["p50"]
    if p50 is not None:
        registry.gauge(f"perf.step_time_ms[scenario={name}]").set(p50)
    if row["tokens_per_sec"] is not None:
        registry.gauge(
            f"perf.tokens_per_sec[scenario={name}]").set(
                row["tokens_per_sec"])
    for phase, ms in row["phases_ms"].items():
        registry.gauge(
            f"perf.phase_ms[scenario={name},phase={phase}]").set(ms)
    rl = row.get("roofline") or {}
    for sink, ms in (rl.get("buckets_ms") or {}).items():
        registry.gauge(
            f"roofline.bucket_ms[scenario={name},sink={sink}]").set(ms)
    if isinstance(rl.get("coverage"), (int, float)):
        registry.gauge(
            f"roofline.coverage[scenario={name}]").set(rl["coverage"])
    if isinstance(rl.get("modeled_step_ms"), (int, float)):
        registry.gauge(
            f"roofline.modeled_step_ms[scenario={name}]").set(
                rl["modeled_step_ms"])
    ic_blk = row.get("interconnect") or {}
    registry.gauge(
        f"interconnect.comm_bucket_ms[scenario={name}]").set(
            float(ic_blk.get("comm_bucket_ms") or 0.0))
    if isinstance(ic_blk.get("overlapped_ms"), (int, float)):
        registry.gauge(
            f"interconnect.overlapped_ms[scenario={name}]").set(
                ic_blk["overlapped_ms"])
    for e in (ic_blk.get("entries") or []):
        if e.get("op") == ic_mod.UNATTRIBUTED:
            registry.gauge(
                f"interconnect.unattributed_ms[scenario={name}]").set(
                    float(e.get("measured_ms") or 0.0))
            continue
        axis = e.get("axis") or "none"
        registry.gauge(
            f"interconnect.entry_ms[scenario={name},op={e['op']},"
            f"axis={axis}]").set(float(e.get("measured_ms") or 0.0))
        if isinstance(e.get("efficiency"), (int, float)):
            registry.gauge(
                f"interconnect.efficiency[scenario={name},op={e['op']},"
                f"axis={axis}]").set(e["efficiency"])
    registry.emit("bench.row", scenario=name, mode=mode,
                  step_time_p50_ms=p50, phases_ms=row["phases_ms"],
                  compile_wall_ms=row["compile"].get("wall_ms"),
                  device_kind=row["device_kind"],
                  mfu=row["mfu"],
                  roofline={
                      "dominant_sink": rl.get("dominant_sink"),
                      "coverage": rl.get("coverage"),
                      "measured_step_ms": rl.get("measured_step_ms"),
                      "modeled_step_ms": rl.get("modeled_step_ms"),
                      "buckets_ms": rl.get("buckets_ms"),
                      "injected": bool(rl.get("injected")),
                      "device_known": (rl.get("device") or {}).get("known"),
                  },
                  interconnect={
                      "comm_bucket_ms": ic_blk.get("comm_bucket_ms"),
                      "unattributed_ms": ic_blk.get("unattributed_ms"),
                      "overlapped_ms": ic_blk.get("overlapped_ms"),
                      "entries": [
                          {"op": e.get("op"), "axis": e.get("axis"),
                           "participants": e.get("participants"),
                           "measured_ms": e.get("measured_ms"),
                           "modeled_ms": e.get("modeled_ms"),
                           "efficiency": e.get("efficiency")}
                          for e in (ic_blk.get("entries") or [])],
                      "injected": ic_blk.get("injected"),
                      "degraded": bool(ic_blk.get("degraded")),
                  })
    return row


def run_scenarios(names: Optional[List[str]] = None,
                  mode: str = "smoke") -> List[Dict[str, Any]]:
    """Run the matrix; each scenario's row is validated as it lands.
    Scenario failures (an invalid row is one) are reported and skipped,
    not fatal — the matrix must degrade scenario-by-scenario, like the
    doctor's checks.
    """
    ensure_devices()
    rows: List[Dict[str, Any]] = []
    for name in (names or scenarios.names()):
        _emit_diag(f"[bench] {name} ({mode}) ...")
        try:
            row = run_scenario(name, mode)
            errors = schema.validate_row(row)
            if errors:
                raise ValueError(f"invalid row for scenario {name!r}: "
                                 + "; ".join(errors))
        except Exception:
            _emit_diag(f"[bench] scenario {name!r} failed:\n"
                       + traceback.format_exc())
            continue
        rows.append(row)
        _emit_diag(f"[bench] {name}: p50={row['step_time_ms']['p50']:.2f}ms"
                   f" compile={row['compile'].get('wall_ms', 0):.0f}ms"
                   f" device={row['device_kind']}")
    return rows
