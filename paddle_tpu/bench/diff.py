"""perfdiff (ISSUE 13): compare two ledger rows and say *why* they
differ, not just that they do.

A step-time regression is only actionable once it is attributed to the
phase that moved — compile wall (one-time, its own axis), data wait,
compute, collective, or readback.  ``attribute`` computes per-phase
deltas from the rows' ``phases_ms`` breakdown and ranks the movers;
``render`` prints the doctor-style report the CI gate shows on failure.

CLI::

    python -m paddle_tpu.bench.diff ROW_A.json ROW_B.json
    python -m paddle_tpu.bench.diff --golden [--scenario gpt_pretrain_fused]
    python -m paddle_tpu.bench.diff --baseline median:8   # vs trailing median

``--baseline median:N`` (ISSUE 14) compares each scenario's newest
ledger row against the **median pseudo-row of its trailing N prior
rows** instead of a single (possibly noisy) golden or prior row — the
same baseline the noise-aware gate enforces against.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from typing import Any, Dict, List, Optional

from ..utils import fsio
from . import ledger
from .schema import GAP_SINKS, PHASES

__all__ = ["attribute", "diff_rows", "render", "main"]

# human phrasing per phase for the report's remedy line
_PHASE_HINTS = {
    "data": "host input pipeline (batch production) slowed — check "
            "tokenizer/augment work and PTPU_DATA_* staging",
    "compute": "on-device step math slowed — check fusion flags, dtype, "
               "and recent kernel changes",
    "readback": "device→host sync slowed — check what the step returns",
    "collective": "cross-device traffic slowed — check compression tier "
                  "and topology (comm package)",
}


def _p50(row: Dict[str, Any]) -> Optional[float]:
    st = row.get("step_time_ms") or {}
    v = st.get("p50")
    return float(v) if isinstance(v, (int, float)) else None


def attribute(base: Dict[str, Any],
              cur: Dict[str, Any]) -> Dict[str, Any]:
    """Per-phase movement between two rows of the same scenario.

    Returns ``movers`` ranked by signed per-step delta (worst first),
    the ``dominant`` phase (largest positive delta, None when nothing
    grew), the ``unattributed`` remainder of the p50 delta the phase
    breakdown doesn't explain, and the compile-wall delta on its own
    axis (one-time cost, never part of the steady-state step).
    """
    base_ph = base.get("phases_ms") or {}
    cur_ph = cur.get("phases_ms") or {}
    movers: List[Dict[str, Any]] = []
    for p in PHASES:
        b = float(base_ph.get(p, 0.0) or 0.0)
        c = float(cur_ph.get(p, 0.0) or 0.0)
        movers.append({"phase": p, "base_ms": b, "cur_ms": c,
                       "delta_ms": c - b,
                       "ratio": (c / b) if b > 0 else None})
    movers.sort(key=lambda m: -m["delta_ms"])
    dominant = (movers[0]["phase"]
                if movers and movers[0]["delta_ms"] > 0 else None)
    b50, c50 = _p50(base), _p50(cur)
    total_delta = ((c50 - b50) if (b50 is not None and c50 is not None)
                   else None)
    explained = sum(m["delta_ms"] for m in movers)
    comp_b = float((base.get("compile") or {}).get("wall_ms", 0.0) or 0.0)
    comp_c = float((cur.get("compile") or {}).get("wall_ms", 0.0) or 0.0)
    out = {
        "movers": movers,
        "dominant": dominant,
        "step_p50_delta_ms": total_delta,
        "unattributed_ms": (None if total_delta is None
                            else total_delta - explained),
        "compile_wall_delta_ms": comp_c - comp_b,
    }
    # MFU-gap movers (ISSUE 19): only when *both* rows carry a roofline
    # block — doctor's regression check builds row-alikes without one,
    # and v1 rows predate the block entirely.
    base_gb = (base.get("roofline") or {}).get("buckets_ms")
    cur_gb = (cur.get("roofline") or {}).get("buckets_ms")
    if isinstance(base_gb, dict) and isinstance(cur_gb, dict):
        gap_movers: List[Dict[str, Any]] = []
        for s in GAP_SINKS:
            if s == "mxu":   # useful-work bucket, not a gap sink
                continue
            b = float(base_gb.get(s, 0.0) or 0.0)
            c = float(cur_gb.get(s, 0.0) or 0.0)
            gap_movers.append({"sink": s, "base_ms": b, "cur_ms": c,
                               "delta_ms": c - b,
                               "ratio": (c / b) if b > 0 else None})
        gap_movers.sort(key=lambda m: -m["delta_ms"])
        out["gap_movers"] = gap_movers
        out["gap_dominant"] = (gap_movers[0]["sink"]
                               if gap_movers and gap_movers[0]["delta_ms"] > 0
                               else None)
    # comm movers (ISSUE 20): per-(op, axis) exposed-comm deltas — only
    # when *both* rows carry interconnect entries (v3 rows); row-alikes
    # and v1/v2 rows skip the axis entirely.
    base_ic = (base.get("interconnect") or {}).get("entries")
    cur_ic = (cur.get("interconnect") or {}).get("entries")
    if isinstance(base_ic, list) and isinstance(cur_ic, list):
        def by_key(entries):
            keyed: Dict[tuple, float] = {}
            for e in entries:
                if isinstance(e, dict) and e.get("op"):
                    k = (str(e["op"]), e.get("axis"))
                    keyed[k] = keyed.get(k, 0.0) + float(
                        e.get("measured_ms") or 0.0)
            return keyed
        b_keyed, c_keyed = by_key(base_ic), by_key(cur_ic)
        comm_movers: List[Dict[str, Any]] = []
        for k in sorted(set(b_keyed) | set(c_keyed),
                        key=lambda k: (k[0], k[1] or "")):
            b, c = b_keyed.get(k, 0.0), c_keyed.get(k, 0.0)
            comm_movers.append({"op": k[0], "axis": k[1],
                                "base_ms": b, "cur_ms": c,
                                "delta_ms": c - b,
                                "ratio": (c / b) if b > 0 else None})
        comm_movers.sort(key=lambda m: -m["delta_ms"])
        out["comm_movers"] = comm_movers
        out["comm_dominant"] = (
            {"op": comm_movers[0]["op"], "axis": comm_movers[0]["axis"]}
            if comm_movers and comm_movers[0]["delta_ms"] > 0 else None)
    return out


def diff_rows(base: Dict[str, Any], cur: Dict[str, Any],
              threshold_frac: float = None) -> Dict[str, Any]:
    """Full comparison of two rows; ``regression`` is True when the
    current p50 is *strictly* above ``(1 + threshold) × base`` (exactly
    at the threshold passes — the gate's edge-case contract)."""
    if threshold_frac is None:
        threshold_frac = ledger.DEFAULT_THRESHOLDS[
            "step_time_regression_frac"]
    b50, c50 = _p50(base), _p50(cur)
    ratio = (c50 / b50) if (b50 and c50 is not None) else None
    regression = (b50 is not None and c50 is not None
                  and c50 > (1.0 + threshold_frac) * b50)
    return {
        "scenario": cur.get("scenario") or base.get("scenario"),
        "mode": cur.get("mode"),
        "base_p50_ms": b50,
        "cur_p50_ms": c50,
        "ratio": ratio,
        "threshold_frac": threshold_frac,
        "regression": regression,
        "attribution": attribute(base, cur),
        "base_sha": base.get("git_sha"),
        "cur_sha": cur.get("git_sha"),
        "base_device": base.get("device_kind"),
        "cur_device": cur.get("device_kind"),
    }


def _fmt_ms(v: Optional[float]) -> str:
    return "—" if v is None else f"{v:.2f}ms"


def render(report: Dict[str, Any]) -> str:
    """Doctor-style text: verdict line, ranked movers, remedy hint."""
    att = report["attribution"]
    lines: List[str] = []
    verdict = ("REGRESSION" if report["regression"] else "ok")
    ratio = report.get("ratio")
    lines.append(
        f"[{verdict}] {report['scenario']}: step p50 "
        f"{_fmt_ms(report['base_p50_ms'])} -> "
        f"{_fmt_ms(report['cur_p50_ms'])}"
        + (f"  ({ratio:.2f}x, threshold "
           f"{1.0 + report['threshold_frac']:.2f}x)"
           if ratio is not None else ""))
    if (report.get("base_device") and report.get("cur_device")
            and report["base_device"] != report["cur_device"]):
        lines.append(f"  ! devices differ: {report['base_device']} vs "
                     f"{report['cur_device']} — not comparable")
    lines.append("  movers (per-step phase delta, worst first):")
    for m in att["movers"]:
        mark = " <-- dominant" if m["phase"] == att["dominant"] else ""
        lines.append(
            f"    {m['phase']:<10} {_fmt_ms(m['base_ms'])} -> "
            f"{_fmt_ms(m['cur_ms'])}  ({m['delta_ms']:+.2f}ms){mark}")
    ua = att.get("unattributed_ms")
    if ua is not None:
        lines.append(f"    {'unattributed':<10} {ua:+.2f}ms "
                     "(p50 delta not explained by phases)")
    if att.get("gap_movers"):
        lines.append("  MFU-gap sinks (roofline bucket delta, worst "
                     "first):")
        for m in att["gap_movers"]:
            mark = (" <-- dominant"
                    if m["sink"] == att.get("gap_dominant") else "")
            lines.append(
                f"    {m['sink']:<14} {_fmt_ms(m['base_ms'])} -> "
                f"{_fmt_ms(m['cur_ms'])}  ({m['delta_ms']:+.2f}ms){mark}")
    if att.get("comm_movers"):
        lines.append("  exposed-comm collectives (per-(op, axis) delta, "
                     "worst first):")
        dom = att.get("comm_dominant") or {}
        for m in att["comm_movers"]:
            label = m["op"] + (f"[axis={m['axis']}]" if m["axis"] else "")
            mark = (" <-- dominant"
                    if (m["op"] == dom.get("op")
                        and m["axis"] == dom.get("axis")) else "")
            lines.append(
                f"    {label:<24} {_fmt_ms(m['base_ms'])} -> "
                f"{_fmt_ms(m['cur_ms'])}  ({m['delta_ms']:+.2f}ms){mark}")
    cw = att.get("compile_wall_delta_ms") or 0.0
    if abs(cw) > 1.0:
        lines.append(f"  compile wall moved {cw:+.0f}ms (one-time cost, "
                     "outside the step budget)")
    if report["regression"] and att["dominant"]:
        lines.append(f"  likely cause: "
                     f"{_PHASE_HINTS.get(att['dominant'], att['dominant'])}")
    if report.get("base_sha") or report.get("cur_sha"):
        lines.append(f"  base sha {report.get('base_sha') or '?'}  "
                     f"cur sha {report.get('cur_sha') or '?'}")
    return "\n".join(lines)


def _load_row_file(path: str) -> Dict[str, Any]:
    payload = json.loads(fsio.read_bytes(path))
    if not isinstance(payload, dict):
        raise ValueError(f"{path}: not a row object")
    return payload


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.bench.diff",
        description="perfdiff: attribute the difference between two "
                    "ledger rows (or latest ledger vs golden)")
    ap.add_argument("rows", nargs="*",
                    help="two row JSON files (base, then current)")
    ap.add_argument("--golden", action="store_true",
                    help="compare the newest ledger row per scenario "
                         "against benchmarks/golden.json")
    ap.add_argument("--ledger", default=None, help="ledger path override")
    ap.add_argument("--golden-path", default=None,
                    help="golden path override")
    ap.add_argument("--scenario", default=None,
                    help="restrict --golden/--baseline mode to one "
                         "scenario")
    ap.add_argument("--baseline", default=None, metavar="median:N",
                    help="compare each newest ledger row against the "
                         "median pseudo-row of its trailing N prior "
                         "rows instead of the golden")
    ap.add_argument("--json", action="store_true",
                    help="emit the report(s) as JSON")
    args = ap.parse_args(argv)

    reports: List[Dict[str, Any]] = []
    if args.baseline is not None:
        from . import trends
        m = re.fullmatch(r"median:(\d+)", args.baseline)
        if not m or int(m.group(1)) < 1:
            ap.error("--baseline must look like median:N with N >= 1")
        n = int(m.group(1))
        rows = ledger.read_ledger(args.ledger)
        latest = ledger.latest_rows(rows)
        names = ([args.scenario] if args.scenario else sorted(latest))
        thr = ledger.threshold(ledger.load_golden(args.golden_path),
                               "step_time_regression_frac")
        for name in names:
            cur = latest.get(name)
            if cur is None:
                sys.stderr.write(f"perfdiff: {name}: not in ledger, "
                                 "skipped\n")
                continue
            pts = ledger.read_series(name, str(cur.get("mode")),
                                     rows=rows, dedupe_sha=False)
            if len(pts) < 2:
                sys.stderr.write(f"perfdiff: {name}: fewer than 2 rows "
                                 "— no trailing median to compare "
                                 "against, skipped\n")
                continue
            base = trends.median_row([p["row"] for p in pts[:-1][-n:]])
            reports.append(diff_rows(base, cur, thr))
    elif args.golden or not args.rows:
        golden = ledger.load_golden(args.golden_path)
        if golden is None:
            sys.stderr.write("perfdiff: no golden baseline "
                             "(run the gate with --write-golden)\n")
            return 2
        thr = ledger.threshold(golden, "step_time_regression_frac")
        latest = ledger.latest_rows(ledger.read_ledger(args.ledger))
        names = ([args.scenario] if args.scenario
                 else sorted(set(latest) & set(golden["scenarios"])))
        for name in names:
            if name not in latest or name not in golden["scenarios"]:
                sys.stderr.write(f"perfdiff: {name}: missing from "
                                 "ledger or golden, skipped\n")
                continue
            reports.append(diff_rows(golden["scenarios"][name],
                                     latest[name], thr))
    elif len(args.rows) == 2:
        reports.append(diff_rows(_load_row_file(args.rows[0]),
                                 _load_row_file(args.rows[1])))
    else:
        ap.error("pass exactly two row files, or --golden")

    if args.json:
        print(json.dumps(reports, indent=1))  # noqa: print
    else:
        for rep in reports:
            print(render(rep))  # noqa: print
    return 1 if any(r["regression"] for r in reports) else 0


if __name__ == "__main__":
    raise SystemExit(main())
