"""Interconnect microscope (ISSUE 20) — per-collective wire-time
attribution of the roofline's ``comm`` sink.

PR 19's MFU microscope reconciles the achieved-vs-peak gap but folds
every collective into one ``comm`` lump.  This module is the comm-side
sibling: a per-``device_kind`` ICI spec table (aggregate link Gbps,
link count, torus topology) plus an algorithm-aware cost model per
collective that turns each observed collective's payload bytes,
participant count, and mesh axis into a modeled wire time, then
reconciles modeled vs measured per (op, axis) into an efficiency table
and a **per-collective sub-budget** of the roofline's ``comm`` bucket.

Cost model (ring schedules on a torus; ``n`` = participants):

==================  =====================================================
collective          wire bytes shipped per device / payload
==================  =====================================================
``all_reduce``      ``2(n-1)/n``  (reduce-scatter + all-gather ring)
``reduce_scatter``  ``(n-1)/n``
``all_gather``      ``(n-1)/n``
``broadcast``       ``(n-1)/n``   (masked-psum lowering)
``all_to_all``      ``(n-1)/n × max(1, n/4)``  (bisection penalty — a
                    2D torus bisects at ~n/4 links, so large fan-outs
                    serialize on the cut)
``ppermute``/p2p    ``1``         (every byte crosses once)
``split``/barrier   ``0``         (no payload on the wire)
==================  =====================================================

Modeled wire time = payload × factor / ring bandwidth, where ring
bandwidth is two links' worth (a bidirectional ring uses both
neighbors) at ``ici_gbps / links`` per link.

Sub-budget doctrine (mirrors the roofline's ``residual``): entries
carry the RAW measured per-step milliseconds from the
``collective.<op>.ms[axis=..]`` histogram deltas, and an explicit
``"(unattributed)"`` entry equals ``comm_bucket − Σ attributed`` —
signed, so nested collectives (``reduce`` calls ``all_reduce``) or
trace-time-only observations never silently break the invariant that
**entries sum to the roofline comm bucket exactly, by construction**.
Unknown device kinds degrade honestly: measured attribution still
happens, but ``modeled_ms``/``efficiency`` come back None rather than
pretending nominal ICI figures describe the hardware.

Exposed vs overlapped: the roofline's compiled-HLO op table (split by
collective opcode, with ``replica_groups`` participant counts) gives an
HLO-side modeled comm time; the measured collective phase is the
*exposed* part, and ``max(0, hlo_modeled − exposed)`` estimates what
XLA's schedule overlapped behind compute.

Knobs: ``PTPU_INTERCONNECT_TEST_INFLATE=<op>:<axis>:<frac>`` — the
synthetic drill (per-collective sibling of
``PTPU_ROOFLINE_TEST_INFLATE``): claim ``frac`` of the comm bucket for
the named (op, axis), rescale the other attributed entries, and mark
the block ``injected``; CI uses it to prove the doctor names exactly
the injected collective op + axis.
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

__all__ = ["ICI_SPECS", "INFLATE_ENV", "ici_spec", "wire_factor",
           "modeled_wire_time_ms", "build_block", "degraded_block",
           "attributed_total_ms", "unattributed_ms"]

# Per-chip ICI specs keyed by ``device_kind`` (the same keys as
# ``observability.mfu.DEVICE_SPECS``; Google Cloud TPU documentation):
# aggregate inter-chip interconnect bandwidth in Gbps across all links,
# the link count, and the torus the links form.  Per-link GB/s falls
# out as ici_gbps / links / 8.
ICI_SPECS = {
    "TPU v2":      {"gen": "v2",  "ici_gbps": 496.0,  "links": 4,
                    "topology": "2d_torus"},
    "TPU v3":      {"gen": "v3",  "ici_gbps": 656.0,  "links": 4,
                    "topology": "2d_torus"},
    "TPU v4":      {"gen": "v4",  "ici_gbps": 2400.0, "links": 6,
                    "topology": "3d_torus"},
    "TPU v5 lite": {"gen": "v5e", "ici_gbps": 1600.0, "links": 4,
                    "topology": "2d_torus"},
    "TPU v5p":     {"gen": "v5p", "ici_gbps": 4800.0, "links": 6,
                    "topology": "3d_torus"},
    "TPU v6 lite": {"gen": "v6e", "ici_gbps": 3584.0, "links": 4,
                    "topology": "2d_torus"},
}

INFLATE_ENV = "PTPU_INTERCONNECT_TEST_INFLATE"

# the explicit remainder entry's op name (never a real collective)
UNATTRIBUTED = "(unattributed)"

# HLO collective opcode → the python-surface op name the cost model
# keys on (ragged all-to-all shares all_to_all's bisection penalty)
HLO_OPCODE_OPS = {
    "all-reduce": "all_reduce",
    "all-gather": "all_gather",
    "reduce-scatter": "reduce_scatter",
    "all-to-all": "all_to_all",
    "ragged-all-to-all": "all_to_all",
    "collective-permute": "send_recv_permute",
    "collective-broadcast": "broadcast",
}


def ici_spec(device_kind: Optional[str] = None) -> Dict[str, Any]:
    """Resolve a device kind to its ICI spec — the comm-side mirror of
    :func:`~paddle_tpu.observability.mfu.device_spec`, same lookup: the
    exact ``device_kind`` jax reports, and ``known=False`` with no
    figures at all for anything else (CPU dev boxes, future
    generations)."""
    if device_kind is None:
        import jax
        device_kind = getattr(jax.devices()[0], "device_kind", "")
    spec = ICI_SPECS.get(device_kind)
    if spec is None:
        return {"device_kind": device_kind, "gen": None, "known": False}
    return {"device_kind": device_kind, "known": True, **spec}


def wire_factor(op: str, participants: Any) -> float:
    """Wire bytes shipped per device as a multiple of the payload size
    for one ``op`` over ``participants`` ranks (the module-docstring
    table).  Single-rank groups (or unknown sizes) ship nothing."""
    try:
        n = int(participants or 0)
    except (TypeError, ValueError):
        n = 0
    if n <= 1:
        return 0.0
    base = str(op).replace("-", "_")
    if base in ("all_reduce", "sync_gradients"):
        return 2.0 * (n - 1) / n
    if base in ("all_gather", "reduce_scatter", "broadcast", "reduce",
                "scatter", "collective_broadcast"):
        return (n - 1) / n
    if base in ("all_to_all", "ragged_all_to_all"):
        return ((n - 1) / n) * max(1.0, n / 4.0)
    if base in ("send_recv_permute", "p2p_push", "collective_permute",
                "ppermute"):
        return 1.0
    if base in ("split", "barrier"):
        return 0.0
    # unknown collective: assume every payload byte crosses once rather
    # than silently modeling it free
    return 1.0


def modeled_wire_time_ms(op: str, payload_bytes: Any, participants: Any,
                         spec: Dict[str, Any]) -> float:
    """Best-case wire time (ms) for one collective call: wire bytes at
    the bidirectional-ring bandwidth (two links at ``ici_gbps/links``
    per link).  An unknown kind carries no figures and models 0.0 —
    callers gate on ``spec["known"]`` and report ``modeled_ms=None``."""
    factor = wire_factor(op, participants)
    try:
        payload = float(payload_bytes or 0.0)
    except (TypeError, ValueError):
        payload = 0.0
    if factor <= 0.0 or payload <= 0.0:
        return 0.0
    links = max(1, int(spec.get("links") or 1))
    link_bytes_per_s = float(spec.get("ici_gbps") or 0.0) / links / 8.0 * 1e9
    ring_bytes_per_s = 2.0 * link_bytes_per_s
    if ring_bytes_per_s <= 0.0:
        return 0.0
    return payload * factor / ring_bytes_per_s * 1e3


# --------------------------------------------------------------------------
# sub-budget assembly
# --------------------------------------------------------------------------

def _apply_inflation(entries: List[Dict[str, Any]],
                     comm_bucket_ms: float) -> Optional[Dict[str, Any]]:
    """The synthetic drill (``PTPU_INTERCONNECT_TEST_INFLATE=
    <op>:<axis>:<frac>``): claim ``frac`` of the comm bucket for the
    named (op, axis) — creating the entry when no real observation
    exists — and rescale the other attributed entries so the remainder
    math stays consistent.  Returns the ``injected`` marker; a drilled
    block is labeled, never passed off as a real attribution."""
    raw = os.environ.get(INFLATE_ENV, "").strip()
    if not raw or comm_bucket_ms <= 0:
        return None
    parts = raw.split(":")
    if len(parts) != 3:
        return None
    op, axis = parts[0].strip(), parts[1].strip()
    try:
        frac = float(parts[2])
    except ValueError:
        return None
    if not op or not axis:
        return None
    frac = min(max(frac, 0.0), 1.0)
    target = frac * comm_bucket_ms
    named = None
    for e in entries:
        if e["op"] == op and e["axis"] == axis:
            named = e
            break
    if named is None:
        named = {"op": op, "axis": axis, "participants": None,
                 "calls": 0.0, "payload_bytes": 0.0, "wire_bytes": 0.0,
                 "measured_ms": 0.0, "modeled_ms": None,
                 "efficiency": None}
        entries.append(named)
    others = sum(e["measured_ms"] for e in entries if e is not named)
    scale = (max(0.0, (comm_bucket_ms - target) / others)
             if others > 1e-12 else 0.0)
    for e in entries:
        if e is not named:
            e["measured_ms"] *= scale
    named["measured_ms"] = target
    return {"op": op, "axis": axis, "frac": frac}


def build_block(comm_bucket_ms: float,
                per_op: Optional[List[Dict[str, Any]]] = None, *,
                hlo_comm: Optional[Dict[str, Dict[str, Any]]] = None,
                spec: Optional[Dict[str, Any]] = None,
                default_participants: Optional[int] = None,
                degraded: Optional[str] = None) -> Dict[str, Any]:
    """Assemble the per-collective sub-budget of the roofline ``comm``
    bucket for one scenario.

    ``per_op`` carries the harness's per-(op, axis) deltas over the
    timed window, already normalized per step: ``{"op", "axis",
    "participants", "calls", "ms", "payload_bytes"}``.  ``hlo_comm`` is
    the roofline fit's per-opcode comm table (``gap_budget``'s
    ``comm_ops``) for the exposed-vs-overlapped estimate;
    ``default_participants`` backfills HLO ops whose ``replica_groups``
    didn't name a group size.  Entries (with the signed
    ``"(unattributed)"`` remainder) sum to ``comm_bucket_ms`` exactly.
    """
    spec = spec or ici_spec()
    known = bool(spec.get("known"))
    bucket = float(comm_bucket_ms or 0.0)

    entries: List[Dict[str, Any]] = []
    for rec in per_op or []:
        op = str(rec.get("op") or "")
        if not op or op == UNATTRIBUTED:
            continue
        n = rec.get("participants")
        payload = float(rec.get("payload_bytes") or 0.0)
        measured = float(rec.get("ms") or 0.0)
        factor = wire_factor(op, n)
        modeled = (modeled_wire_time_ms(op, payload, n, spec)
                   if known else None)
        eff = None
        if modeled is not None and measured > 0 and modeled > 0:
            eff = modeled / measured
        entries.append({
            "op": op,
            "axis": rec.get("axis"),
            "participants": (int(n) if isinstance(n, (int, float)) and n
                             else None),
            "calls": float(rec.get("calls") or 0.0),
            "payload_bytes": payload,
            "wire_bytes": payload * factor,
            "measured_ms": measured,
            "modeled_ms": modeled,
            "efficiency": eff,
        })
    entries.sort(key=lambda e: e["measured_ms"], reverse=True)

    injected = _apply_inflation(entries, bucket)

    attributed = sum(e["measured_ms"] for e in entries)
    unatt = bucket - attributed
    modeled_total = sum(e["modeled_ms"] for e in entries
                        if isinstance(e["modeled_ms"], (int, float)))
    entries.append({
        "op": UNATTRIBUTED, "axis": None, "participants": None,
        "calls": 0.0, "payload_bytes": 0.0, "wire_bytes": 0.0,
        "measured_ms": unatt, "modeled_ms": None, "efficiency": None,
    })

    hlo_ops: Dict[str, Dict[str, Any]] = {}
    hlo_modeled: Optional[float] = 0.0 if known else None
    for opcode in sorted(hlo_comm or {}):
        rec = (hlo_comm or {})[opcode]
        n = rec.get("participants") or default_participants or 0
        b = float(rec.get("bytes") or 0.0)
        opname = HLO_OPCODE_OPS.get(opcode, opcode)
        t = (modeled_wire_time_ms(opname, b, n, spec) if known else None)
        hlo_ops[opcode] = {"count": int(rec.get("count") or 0),
                           "bytes": b,
                           "participants": int(n) if n else None,
                           "modeled_ms": (round(t, 6)
                                          if t is not None else None)}
        if t is not None and hlo_modeled is not None:
            hlo_modeled += t

    exposed = bucket
    overlapped = (max(0.0, hlo_modeled - exposed)
                  if hlo_modeled is not None else None)

    def _r(v):
        return round(v, 6) if isinstance(v, (int, float)) else v

    for e in entries:
        for k in ("calls", "payload_bytes", "wire_bytes", "measured_ms",
                  "modeled_ms", "efficiency"):
            e[k] = _r(e[k])
    return {
        "device": {k: spec.get(k) for k in
                   ("device_kind", "gen", "known", "ici_gbps", "links",
                    "topology")},
        "comm_bucket_ms": _r(bucket),
        "entries": entries,
        "modeled_ms_total": _r(modeled_total if known else None),
        "unattributed_ms": _r(unatt),
        "exposed_ms": _r(exposed),
        "hlo_modeled_ms": _r(hlo_modeled),
        "overlapped_ms": _r(overlapped),
        "hlo_ops": hlo_ops,
        "injected": injected,
        "degraded": degraded,
    }


def degraded_block(comm_bucket_ms: float, *,
                   reason: str = "no per-collective deltas captured",
                   spec: Optional[Dict[str, Any]] = None
                   ) -> Dict[str, Any]:
    """A schema-valid sub-budget with no per-op attribution — the whole
    bucket lands in ``"(unattributed)"``.  ``schema.new_row`` synthesizes
    this when a producer passes no interconnect block, so every v3
    row's entries still sum to the comm bucket."""
    return build_block(comm_bucket_ms, None, spec=spec, degraded=reason)


def attributed_total_ms(block: Dict[str, Any]) -> float:
    """Σ measured over the real (non-remainder) entries."""
    return sum(float(e.get("measured_ms") or 0.0)
               for e in (block.get("entries") or [])
               if e.get("op") != UNATTRIBUTED)


def unattributed_ms(block: Dict[str, Any]) -> float:
    """The signed remainder entry's measured milliseconds."""
    for e in (block.get("entries") or []):
        if e.get("op") == UNATTRIBUTED:
            return float(e.get("measured_ms") or 0.0)
    return 0.0
