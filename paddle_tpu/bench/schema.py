"""Ledger row schema (ISSUE 13).

Every scenario the observatory runs emits exactly ONE row shaped like
this, so rows from different scenarios, machines, and months are
comparable by construction:

- ``schema_version`` — bumped on any incompatible shape change; the
  reader drops foreign versions with accounting instead of mis-parsing
  them (the same doctrine as ``observability/aggregate.py``);
- ``fingerprint`` + ``git_sha`` — where the number came from: device
  kind/count, jax/python versions, the commit that produced it;
- ``device_kind`` — the row is self-describing about *what hardware
  actually ran*;
- ``step_time_ms`` p50/p99 plus the ``phases_ms`` breakdown
  (data / compute / readback / collective) — the axes perfdiff
  attributes a regression to;
- ``compile`` — wall + trace counts from the PR 4 tracker and
  persistent-cache hit/miss from ``observability/compilecache``;
- ``tokens_per_sec`` / ``mfu`` — through the shared
  ``observability/mfu`` definitions (never a per-scenario formula);
- ``bytes_on_wire`` — the comm package's trace-time accounting (PR 8);
- ``extra`` — scenario-specific figures (img/s, TTFT/TPOT, ...) that
  must not leak into the comparable core.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

__all__ = ["SCHEMA_VERSION", "KNOWN_SCHEMA_VERSIONS", "PHASES", "METRICS",
           "CORE_METRICS", "GAP_SINKS", "GAP_METRICS", "COMM_METRICS",
           "fingerprint", "fingerprint_key", "metric_value", "new_row",
           "validate_row"]

# v2 (ISSUE 19): every row carries a ``roofline`` MFU-gap budget block
# whose buckets (with residual) sum to the measured step p50; v1 rows
# remain readable — gap axes are simply None on them.
# v3 (ISSUE 20): every row additionally carries an ``interconnect``
# per-collective sub-budget whose entries (with the signed
# "(unattributed)" remainder) sum to the roofline ``comm`` bucket
# exactly; v1/v2 rows remain readable — comm axes are None on them.
SCHEMA_VERSION = 3
KNOWN_SCHEMA_VERSIONS = (1, 2, 3)

# the step-time decomposition perfdiff attributes regressions to; every
# row carries all four (0.0 when a scenario has no such phase)
PHASES = ("data", "compute", "readback", "collective")

# the MFU-gap sink taxonomy (ISSUE 19) — a literal mirror of
# ``observability.roofline.SINKS`` (pinned equal by a test) so this
# module never imports the roofline at module scope
GAP_SINKS = ("mxu", "memory_bound", "comm", "host", "padding",
             "unknown_device", "residual")

# the original five metric axes — what the report's sparkline table
# shows; the gap axes below join them in the full trendable set
CORE_METRICS = ("step_p50", "mfu", "compile_wall_ms", "bytes_on_wire",
                "peak_hbm_bytes")

# per-sink gap axes (mxu excluded — it is the useful part, not a gap)
# plus the attribution-honesty coverage gauge
GAP_METRICS = tuple("gap_%s_ms" % s for s in GAP_SINKS if s != "mxu") \
    + ("roofline_coverage",)

# per-collective comm axes (ISSUE 20): the modeled wire time of the
# attributed entries, the XLA-overlap estimate, and the honesty gauge —
# how much of the comm bucket no (op, axis) claims
COMM_METRICS = ("comm_modeled_ms", "comm_overlapped_ms",
                "comm_unattributed_ms")

# the metric axes the trend engine models as per-scenario series
# (ISSUE 14); each maps to one numeric field of the row via
# :func:`metric_value`
METRICS = CORE_METRICS + GAP_METRICS + COMM_METRICS

_MODES = ("smoke", "full")


def metric_value(row: Dict[str, Any], metric: str) -> Optional[float]:
    """One :data:`METRICS` axis out of a row (None when the row doesn't
    carry it — e.g. ``mfu`` on a vision scenario)."""
    if metric == "step_p50":
        v = (row.get("step_time_ms") or {}).get("p50")
    elif metric == "mfu":
        v = row.get("mfu")
    elif metric == "compile_wall_ms":
        v = (row.get("compile") or {}).get("wall_ms")
    elif metric == "bytes_on_wire":
        v = row.get("bytes_on_wire")
    elif metric == "peak_hbm_bytes":
        v = row.get("peak_hbm_bytes")
    elif metric == "roofline_coverage":
        v = (row.get("roofline") or {}).get("coverage")
    elif metric == "comm_modeled_ms":
        v = (row.get("interconnect") or {}).get("modeled_ms_total")
    elif metric == "comm_overlapped_ms":
        v = (row.get("interconnect") or {}).get("overlapped_ms")
    elif metric == "comm_unattributed_ms":
        v = (row.get("interconnect") or {}).get("unattributed_ms")
    elif metric.startswith("gap_") and metric.endswith("_ms"):
        sink = metric[len("gap_"):-len("_ms")]
        if sink not in GAP_SINKS:
            raise KeyError(f"unknown metric {metric!r}; have {METRICS}")
        v = ((row.get("roofline") or {}).get("buckets_ms") or {}).get(sink)
    else:
        raise KeyError(f"unknown metric {metric!r}; have {METRICS}")
    return float(v) if isinstance(v, (int, float)) else None


def fingerprint_key(row: Dict[str, Any]) -> str:
    """The series-partition key (ISSUE 14): rows from different hardware
    or device counts never mix into one trend series — a CPU-smoke point
    in a TPU series would read as a catastrophic changepoint."""
    fp = row.get("fingerprint") or {}
    return "%s/%s/x%s" % (fp.get("platform", "?"),
                          fp.get("device_kind", row.get("device_kind", "?")),
                          fp.get("device_count", "?"))


def _git_sha() -> Optional[str]:
    """Commit of the tree that produced the row (None outside a repo)."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10)
    except (OSError, subprocess.TimeoutExpired):
        return None
    sha = out.stdout.strip()
    return sha if out.returncode == 0 and sha else None


def fingerprint() -> Dict[str, Any]:
    """Device / software environment stamp for one row."""
    import jax
    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", str(dev)),
        "device_count": jax.device_count(),
        "jax": jax.__version__,
        "python": "%d.%d.%d" % sys.version_info[:3],
    }


def new_row(scenario: str, mode: str, *,
            step_times_ms: List[float],
            phases_ms: Dict[str, float],
            config: Optional[Dict[str, Any]] = None,
            tokens_per_sec: Optional[float] = None,
            mfu: Optional[float] = None,
            compile_stats: Optional[Dict[str, Any]] = None,
            bytes_on_wire: int = 0,
            peak_hbm_bytes: Optional[int] = None,
            roofline: Optional[Dict[str, Any]] = None,
            interconnect: Optional[Dict[str, Any]] = None,
            extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Assemble one schema-v3 row from a scenario's measurements.

    ``step_times_ms`` is the raw per-step series (percentiles are
    computed here so every scenario uses the same definition);
    ``phases_ms`` maps each :data:`PHASES` entry to its per-step p50.
    ``roofline`` is the MFU-gap budget block from a capture window; when
    omitted, a degraded phase-only block is synthesized so every row
    still carries buckets that sum to the measured step time.
    ``interconnect`` is the per-collective sub-budget of the roofline's
    ``comm`` bucket; when omitted, a degraded all-unattributed block is
    synthesized so the v3 sum invariant holds for every producer.
    """
    times = sorted(float(t) for t in step_times_ms)

    def pct(p: float) -> Optional[float]:
        if not times:
            return None
        idx = min(len(times) - 1,
                  max(0, int(round(p / 100.0 * (len(times) - 1)))))
        return times[idx]

    fp = fingerprint()
    if roofline is None:
        from ..observability.roofline import degraded_block
        roofline = degraded_block(
            pct(50) or 0.0,
            {p: float(phases_ms.get(p, 0.0) or 0.0) for p in PHASES},
            padding_frac=float((extra or {}).get("padding_frac") or 0.0),
            reason="producer passed no roofline block")
    if interconnect is None:
        from ..observability import interconnect as ic
        interconnect = ic.degraded_block(
            float(((roofline or {}).get("buckets_ms") or {}).get("comm")
                  or 0.0),
            reason="producer passed no interconnect block")
    row: Dict[str, Any] = {
        "schema_version": SCHEMA_VERSION,
        "scenario": str(scenario),
        "mode": str(mode),
        "ts": time.time(),
        "git_sha": _git_sha(),
        "device_kind": fp["device_kind"],
        "fingerprint": fp,
        "config": dict(config or {}),
        "steps": len(times),
        "step_time_ms": {"p50": pct(50), "p99": pct(99),
                         "mean": (sum(times) / len(times)) if times
                         else None,
                         "min": (times[0] if times else None)},
        "phases_ms": {p: float(phases_ms.get(p, 0.0) or 0.0)
                      for p in PHASES},
        "tokens_per_sec": tokens_per_sec,
        "mfu": mfu,
        "compile": dict(compile_stats or {}),
        "bytes_on_wire": int(bytes_on_wire),
        "peak_hbm_bytes": (None if peak_hbm_bytes is None
                           else int(peak_hbm_bytes)),
        "roofline": roofline,
        "interconnect": interconnect,
        "extra": dict(extra or {}),
    }
    return row


def validate_row(row: Any) -> List[str]:
    """Schema check; returns the list of violations (empty = valid).

    Mirrors the reader-side doctrine: a row that fails here must never
    reach the ledger, so every row IN the ledger is loadable by tooling
    of the same schema generation.
    """
    errors: List[str] = []
    if not isinstance(row, dict):
        return ["row is not an object"]
    if row.get("schema_version") not in KNOWN_SCHEMA_VERSIONS:
        errors.append(f"unknown schema_version "
                      f"{row.get('schema_version')!r}")
    if not row.get("scenario") or not isinstance(row.get("scenario"), str):
        errors.append("missing/invalid scenario")
    if row.get("mode") not in _MODES:
        errors.append(f"mode must be one of {_MODES}, "
                      f"got {row.get('mode')!r}")
    if not isinstance(row.get("ts"), (int, float)):
        errors.append("missing/invalid ts")
    if not isinstance(row.get("device_kind"), str):
        errors.append("missing/invalid device_kind")
    fp = row.get("fingerprint")
    if not isinstance(fp, dict):
        errors.append("missing fingerprint")
    else:
        for k in ("platform", "device_count", "jax"):
            if k not in fp:
                errors.append(f"fingerprint missing {k!r}")
    st = row.get("step_time_ms")
    if not isinstance(st, dict) or not isinstance(
            st.get("p50"), (int, float)):
        errors.append("step_time_ms.p50 missing (no timed steps?)")
    elif not isinstance(st.get("p99"), (int, float)):
        errors.append("step_time_ms.p99 missing")
    ph = row.get("phases_ms")
    if not isinstance(ph, dict):
        errors.append("missing phases_ms")
    else:
        for p in PHASES:
            if not isinstance(ph.get(p), (int, float)):
                errors.append(f"phases_ms.{p} missing/invalid")
    comp = row.get("compile")
    if not isinstance(comp, dict):
        errors.append("missing compile stats")
    if not isinstance(row.get("bytes_on_wire"), int):
        errors.append("bytes_on_wire must be an int")
    for opt_num in ("tokens_per_sec", "mfu"):
        v = row.get(opt_num)
        if v is not None and not isinstance(v, (int, float)):
            errors.append(f"{opt_num} must be null or a number")
    if not isinstance(row.get("extra", {}), dict):
        errors.append("extra must be an object")
    if row.get("schema_version") in (2, 3):
        errors.extend(_validate_roofline(row))
    if row.get("schema_version") == 3:
        errors.extend(_validate_interconnect(row))
    return errors


def _validate_roofline(row: Dict[str, Any]) -> List[str]:
    """The v2 contract: a complete gap-bucket set whose values (with
    residual) sum to the block's measured step time — a roofline block
    that doesn't reconcile with itself must never reach the ledger."""
    errors: List[str] = []
    rl = row.get("roofline")
    if not isinstance(rl, dict):
        return ["schema v2 row missing roofline block"]
    measured = rl.get("measured_step_ms")
    if not isinstance(measured, (int, float)):
        errors.append("roofline.measured_step_ms missing/invalid")
        measured = None
    buckets = rl.get("buckets_ms")
    if not isinstance(buckets, dict):
        errors.append("roofline.buckets_ms missing")
    else:
        total = 0.0
        complete = True
        for s in GAP_SINKS:
            v = buckets.get(s)
            if not isinstance(v, (int, float)):
                errors.append(f"roofline.buckets_ms.{s} missing/invalid")
                complete = False
            else:
                total += float(v)
        if complete and measured is not None:
            tol = max(0.01, 0.005 * abs(float(measured)))
            if abs(total - float(measured)) > tol:
                errors.append(
                    "roofline buckets sum %.4fms != measured %.4fms"
                    % (total, float(measured)))
    cov = rl.get("coverage")
    if not isinstance(cov, (int, float)) or not (0.0 <= cov <= 1.0):
        errors.append("roofline.coverage must be in [0, 1]")
    if rl.get("dominant_sink") not in GAP_SINKS:
        errors.append("roofline.dominant_sink must be one of GAP_SINKS")
    dev = rl.get("device")
    if not isinstance(dev, dict) or not isinstance(
            dev.get("known"), bool):
        errors.append("roofline.device.known missing/invalid")
    return errors


def _validate_interconnect(row: Dict[str, Any]) -> List[str]:
    """The v3 contract: a per-collective entry list (with the signed
    ``"(unattributed)"`` remainder) that sums to the block's comm
    bucket, which in turn equals the roofline ``comm`` bucket — a
    sub-budget that doesn't reconcile with its parent must never reach
    the ledger."""
    errors: List[str] = []
    ic = row.get("interconnect")
    if not isinstance(ic, dict):
        return ["schema v3 row missing interconnect block"]
    bucket = ic.get("comm_bucket_ms")
    if not isinstance(bucket, (int, float)):
        errors.append("interconnect.comm_bucket_ms missing/invalid")
        bucket = None
    entries = ic.get("entries")
    if not isinstance(entries, list) or not entries:
        errors.append("interconnect.entries missing/empty")
    else:
        total = 0.0
        complete = True
        for i, e in enumerate(entries):
            if not isinstance(e, dict) or not isinstance(
                    e.get("measured_ms"), (int, float)):
                errors.append(
                    f"interconnect.entries[{i}].measured_ms "
                    f"missing/invalid")
                complete = False
                continue
            total += float(e["measured_ms"])
        if complete and bucket is not None:
            tol = max(0.01, 0.005 * abs(float(bucket)))
            if abs(total - float(bucket)) > tol:
                errors.append(
                    "interconnect entries sum %.4fms != comm bucket "
                    "%.4fms" % (total, float(bucket)))
    rl_comm = ((row.get("roofline") or {}).get("buckets_ms")
               or {}).get("comm")
    if (bucket is not None and isinstance(rl_comm, (int, float))
            and abs(float(bucket) - float(rl_comm))
            > max(0.01, 0.005 * abs(float(rl_comm)))):
        errors.append(
            "interconnect.comm_bucket_ms %.4fms != roofline comm "
            "bucket %.4fms" % (float(bucket), float(rl_comm)))
    dev = ic.get("device")
    if not isinstance(dev, dict) or not isinstance(
            dev.get("known"), bool):
        errors.append("interconnect.device.known missing/invalid")
    return errors
