"""MFU microscope (ISSUE 19) — roofline attribution of the gap between
achieved and peak FLOP throughput.

The bench matrix has always reported *achieved* MFU; nothing could say
where the missing fraction went.  This module is the instrument: for
every jitted step the PR 4 compile tracker already sees, it captures the
compiled artifact (``lowered.compile().cost_analysis()`` plus the
optimized-HLO text), classifies each op, fits a per-``device_kind``
roofline (Williams et al.: per-op time = max(flops/peak_flops,
bytes/peak_bw)) and decomposes the measured step time into an **MFU-gap
budget** of named sinks:

==================  ====================================================
sink                meaning
==================  ====================================================
``mxu``             modeled matrix-unit time — the useful part
``memory_bound``    per-op excess of ``bytes/bw`` over ``flops/peak``
``comm``            exposed collectives (the measured collective phase)
``host``            input pipeline + readback (measured data+readback)
``padding``         wasted flops: pow2 prefill buckets and batch pad
                    rows (``padding_frac`` × compute phase)
``unknown_device``  device kind absent from the roofline table — the
                    whole compute phase lands here *explicitly* rather
                    than being silently skipped (CPU dev boxes included)
``residual``        unattributed remainder — the honesty gauge,
                    mirroring request-trace ``coverage``
==================  ====================================================

Buckets (with residual) sum to the measured step p50 by construction;
``coverage`` = 1 − |residual|/measured.

Capture path: :func:`~paddle_tpu.observability.compilation.track_jit`
records each wrapped function's *abstract* argument shapes (taken
before the call — donated buffers are gone after) into the process
:class:`RooflineObservatory` whenever a :class:`capture_window` is open.
The bench runner opens one around each scenario and asks the window for
the row's ``roofline`` block at the end; capture is lazy (one
``lower().compile()`` per distinct function, at window close, never in
the timed region).

Portability: ``cost_analysis()`` on this jax returns aggregate totals
(a list of one dict on CPU) and may be sparse or missing entirely on
some backends — the per-op model therefore comes from parsing the
compiled HLO text, with the cost totals kept as a cross-check, and any
op whose shapes/flops can't be recovered is counted ``unmodeled``
instead of silently dropped.

Knobs: ``PTPU_HLO_DUMP_DIR`` (dump lowered + compiled text per jit
entry, filenames keyed by the PR 4 signature-cache key, newest
``PTPU_HLO_DUMP_KEEP`` entries kept), ``PTPU_ROOFLINE_TEST_INFLATE``
(``<sink>:<frac>`` synthetic drill — claims that fraction of the
measured step for the named sink and marks the block ``injected``; CI
uses it to prove the doctor names the right dominant sink).
"""
from __future__ import annotations

import os
import re
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

# `from . import mfu` would resolve to the package's re-exported
# mfu() *function* (it shadows the submodule attr); import by
# module path instead
from .mfu import device_spec as _device_spec

__all__ = ["SINKS", "RooflineObservatory", "get_observatory",
           "reset_observatory", "capture_window", "abstractify",
           "parse_hlo_ops", "fit_roofline", "analyze_program",
           "gap_budget", "degraded_block", "hlo_dump_dir",
           "hlo_dump_keep", "dump_hlo",
           "HLO_DUMP_ENV", "HLO_DUMP_KEEP_ENV", "INFLATE_ENV"]

# the gap-bucket taxonomy; bench.schema mirrors this literally (a test
# pins the two tuples equal) so the row schema never imports this module
# at module scope
SINKS = ("mxu", "memory_bound", "comm", "host", "padding",
         "unknown_device", "residual")

HLO_DUMP_ENV = "PTPU_HLO_DUMP_DIR"
HLO_DUMP_KEEP_ENV = "PTPU_HLO_DUMP_KEEP"
INFLATE_ENV = "PTPU_ROOFLINE_TEST_INFLATE"
DEFAULT_HLO_DUMP_KEEP = 16


# --------------------------------------------------------------------------
# HLO text parsing
# --------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1,
    "f8e5m2fnuz": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}
_INT_DTYPES = frozenset(d for d in _DTYPE_BYTES
                        if d[0] in "su" and d != "u4" and d != "s4")

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
# `%dot.4 = f32[64,32]{1,0} dot(f32[64,128]{1,0} %Arg_0.1, ...)` — the
# optimized-HLO def line shape this jax's compiled.as_text() emits;
# tuple-shaped results (fusions, ROOT) match the paren alternative
_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*"
    r"(\([^=]*?\)|[a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"([\w\-]+)\(")

_COMM_OPS = frozenset({
    "all-reduce", "all-gather", "all-to-all", "reduce-scatter",
    "collective-permute", "collective-broadcast", "ragged-all-to-all"})
_HOST_OPS = frozenset({"infeed", "outfeed", "send", "recv"})
# ops that move no bytes of their own (views, metadata)
_FREE_OPS = frozenset({
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "partition-id", "replica-id", "opt-barrier"})
_MXU_CUSTOM_RE = re.compile(r"gemm|matmul|dot|conv|einsum", re.IGNORECASE)
# `replica_groups={{0,1,2,3},{4,5,6,7}}` — the first group's size is the
# collective's participant count (groups are uniform by construction)
_REPLICA_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


def _strip_async(opcode: str) -> str:
    """Base collective opcode: ``all-reduce-start`` → ``all-reduce``."""
    for suf in ("-start", "-done", "-update"):
        if opcode.endswith(suf):
            return opcode[:-len(suf)]
    return opcode


def _shape_stats(shape_str: str) -> Tuple[Optional[int], int, Optional[str]]:
    """(total bytes, total elements, first dtype) of a shape string —
    handles tuples by summing components; bytes is None when any dtype
    is outside the table (token, opaque)."""
    total_b: Optional[int] = 0
    elems = 0
    first_dtype = None
    saw = False
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        saw = True
        if first_dtype is None:
            first_dtype = dtype
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        elems += n
        sz = _DTYPE_BYTES.get(dtype)
        if sz is None or total_b is None:
            total_b = None
        else:
            total_b += n * sz
    if not saw:
        return None, 0, None
    return total_b, elems, first_dtype


def _dims_of(shape_str: str) -> Optional[List[int]]:
    """Dims of a single (non-tuple) shape string, else None."""
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return None
    return [int(d) for d in m.group(2).split(",") if d]


def _operand_region(rest: str) -> str:
    """The text inside the op's call parens (``rest`` starts right after
    the opening paren); trailing attributes are excluded by depth scan."""
    depth = 1
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return rest[:i]
    return rest


def _classify(opcode: str, rest: str) -> Optional[str]:
    base = opcode
    for suf in ("-start", "-done", "-update"):
        if base.endswith(suf):
            base = base[:-len(suf)]
    if base in _FREE_OPS:
        return None
    if base in _COMM_OPS:
        return "comm"
    if base in _HOST_OPS:
        return "host"
    if base in ("dot", "convolution"):
        return "mxu"
    if base == "custom-call":
        m = re.search(r'custom_call_target="([^"]*)"', rest)
        if m and _MXU_CUSTOM_RE.search(m.group(1)):
            return "mxu"
        return "hbm"
    return "hbm"


def _dot_flops(rest: str, operands: str, out_elems: int,
               symtab: Dict[str, str]) -> Optional[float]:
    """Exact dot flops = 2 · out_elems · K, K from the lhs contracting
    dims (``lhs_contracting_dims={1}`` + the lhs shape)."""
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", rest)
    if not m:
        return None
    contracting = [int(d) for d in m.group(1).split(",") if d]
    lhs_dims = None
    sm = _SHAPE_RE.search(operands)
    if sm:
        lhs_dims = [int(d) for d in sm.group(2).split(",") if d]
    else:
        rm = re.search(r"%([\w.\-]+)", operands)
        if rm and rm.group(1) in symtab:
            lhs_dims = _dims_of(symtab[rm.group(1)])
    if lhs_dims is None:
        return None
    k = 1.0
    for i in contracting:
        if i >= len(lhs_dims):
            return None
        k *= lhs_dims[i]
    return 2.0 * out_elems * k


def _conv_flops(rest: str, operands: str, out_elems: int,
                symtab: Dict[str, str]) -> Optional[float]:
    """Conv flops = 2 · out_elems · (kernel spatial × in-features) —
    the rhs element count divided by its output-feature dim, located via
    ``dim_labels=b01f_01io->b01f``."""
    m = re.search(r"dim_labels=[0-9a-z]+_([0-9a-z]+)->", rest)
    if not m or "o" not in m.group(1):
        return None
    o_pos = m.group(1).index("o")
    shapes = _SHAPE_RE.findall(operands)
    rhs_dims = None
    if len(shapes) >= 2:
        rhs_dims = [int(d) for d in shapes[1][1].split(",") if d]
    else:
        refs = re.findall(r"%([\w.\-]+)", operands)
        if len(refs) >= 2 and refs[1] in symtab:
            rhs_dims = _dims_of(symtab[refs[1]])
    if rhs_dims is None or o_pos >= len(rhs_dims):
        return None
    k = 1.0
    for i, d in enumerate(rhs_dims):
        if i != o_pos:
            k *= d
    return 2.0 * out_elems * k


def _entry_span(lines: List[str]) -> Tuple[int, int]:
    """(start, end) line indices of the ENTRY computation body; the
    whole text when no ENTRY header is found (already a single block)."""
    start = None
    for i, ln in enumerate(lines):
        if ln.lstrip().startswith("ENTRY ") and "{" in ln:
            start = i
            break
    if start is None:
        return 0, len(lines)
    depth = 0
    for i in range(start, len(lines)):
        depth += lines[i].count("{") - lines[i].count("}")
        if depth <= 0 and i > start:
            return start, i + 1
    return start, len(lines)


def parse_hlo_ops(text: str) -> List[Dict[str, Any]]:
    """Parse optimized-HLO text into per-op records:
    ``{"name", "opcode", "klass", "bytes", "flops", "integer"}``.

    Only the ENTRY computation is walked (fused computations would
    double-count against their fusion op) — except dot/convolution defs,
    which are collected wherever they live so matmuls folded into
    fusions still contribute MXU flops.  ``bytes``/``flops`` are None
    when the line can't be modeled; the fit counts those as
    ``unmodeled`` rather than dropping them silently.
    """
    if not text:
        return []
    lines = text.splitlines()
    matches: List[Tuple[int, Any]] = []
    symtab: Dict[str, str] = {}
    for i, ln in enumerate(lines):
        m = _DEF_RE.match(ln)
        if not m:
            continue
        matches.append((i, m))
        symtab.setdefault(m.group(1), m.group(2))
    lo, hi = _entry_span(lines)
    ops: List[Dict[str, Any]] = []
    seen = set()
    for i, m in matches:
        name, shape_str, opcode = m.group(1), m.group(2), m.group(3)
        in_entry = lo <= i < hi
        if not in_entry and opcode not in ("dot", "convolution"):
            continue
        rest = lines[i][m.end():]
        klass = _classify(opcode, rest)
        if klass is None or name in seen:
            continue
        seen.add(name)
        out_bytes, out_elems, dtype = _shape_stats(shape_str)
        operands = _operand_region(rest)
        op_bytes: Optional[float] = None
        opn_b, _opn_e, _ = _shape_stats(operands)
        if opn_b is None:
            # untyped operands — resolve %refs through the symbol table
            opn_b = 0
            for ref in re.findall(r"%([\w.\-]+)", operands):
                rb, _re_, _rd = _shape_stats(symtab.get(ref, ""))
                if rb is None:
                    opn_b = None
                    break
                opn_b += rb
        if out_bytes is not None and opn_b is not None:
            op_bytes = float(out_bytes + opn_b)
        flops: Optional[float] = None
        if opcode == "dot":
            flops = _dot_flops(rest, operands, out_elems, symtab)
        elif opcode == "convolution":
            flops = _conv_flops(rest, operands, out_elems, symtab)
        participants = None
        if klass == "comm":
            gm = _REPLICA_GROUPS_RE.search(rest)
            if gm:
                ids = [t for t in gm.group(1).replace(" ", "").split(",")
                       if t]
                participants = len(ids) or None
        ops.append({"name": name, "opcode": opcode, "klass": klass,
                    "bytes": op_bytes, "flops": flops,
                    "integer": dtype in _INT_DTYPES,
                    "participants": participants})
    return ops


# --------------------------------------------------------------------------
# roofline fit
# --------------------------------------------------------------------------

def _zero_fit() -> Dict[str, Any]:
    return {"mxu_s": 0.0, "memory_s": 0.0, "flops": 0.0, "bytes": 0.0,
            "comm_bytes": 0.0, "comm_ops": {}, "ops_modeled": 0,
            "ops_unmodeled": 0, "ops_total": 0}


def fit_roofline(ops: List[Dict[str, Any]],
                 spec: Dict[str, Any]) -> Dict[str, Any]:
    """Per-op roofline over a parsed op list: MXU ops contribute
    ``flops/peak`` (int8 peak for integer dots) with any ``bytes/bw``
    excess booked as memory-bound; HBM ops contribute ``bytes/bw``.
    Comm/host op *time* belongs to the measured phase split — only
    their bytes are tallied.  Ops missing shapes/flops are counted
    ``unmodeled``; they never silently vanish.  A ``spec`` with
    ``known=False`` has no peaks: flops and bytes are still tallied but
    every modeled time stays 0.0."""
    known = bool(spec.get("known"))
    peak_bf16 = float(spec["bf16_tflops"]) * 1e12 if known else None
    peak_int8 = float(spec["int8_tops"]) * 1e12 if known else None
    bw = float(spec["hbm_gbps"]) * 1e9 if known else None
    fit = _zero_fit()
    fit["ops_total"] = len(ops)
    for op in ops:
        klass = op["klass"]
        if klass == "comm":
            fit["comm_bytes"] += op["bytes"] or 0.0
            # per-opcode comm table (ISSUE 20): the interconnect
            # microscope models each collective opcode separately
            base = _strip_async(op["opcode"])
            rec = fit["comm_ops"].setdefault(
                base, {"count": 0, "bytes": 0.0, "participants": None})
            rec["count"] += 1
            rec["bytes"] += op["bytes"] or 0.0
            if op.get("participants"):
                rec["participants"] = max(rec["participants"] or 0,
                                          int(op["participants"]))
            fit["ops_modeled"] += 1
            continue
        if klass == "host":
            fit["ops_modeled"] += 1
            continue
        b, f = op["bytes"], op["flops"]
        if klass == "mxu":
            if f is None or b is None:
                fit["ops_unmodeled"] += 1
                continue
            if known:
                peak = peak_int8 if op.get("integer") else peak_bf16
                t_flops = f / peak
                t_bytes = b / bw
                fit["mxu_s"] += t_flops
                if t_bytes > t_flops:
                    fit["memory_s"] += t_bytes - t_flops
            fit["flops"] += f
            fit["bytes"] += b
            fit["ops_modeled"] += 1
        else:  # hbm
            if b is None:
                fit["ops_unmodeled"] += 1
                continue
            if known:
                fit["memory_s"] += b / bw
            fit["bytes"] += b
            fit["ops_modeled"] += 1
    return fit


def _normalize_cost_analysis(raw: Any) -> Dict[str, Optional[float]]:
    """Flatten the backend's ``cost_analysis()`` return — a dict, a
    list of one dict (CPU on this jax), or None/garbage — into the three
    totals the roofline cross-checks, with None for missing keys (the
    sparse-key portability contract the tests pin)."""
    if isinstance(raw, (list, tuple)):
        raw = raw[0] if raw else {}
    if not isinstance(raw, dict):
        raw = {}

    def _num(key: str) -> Optional[float]:
        v = raw.get(key)
        return float(v) if isinstance(v, (int, float)) else None

    return {"flops": _num("flops"),
            "bytes_accessed": _num("bytes accessed"),
            "transcendentals": _num("transcendentals")}


def analyze_program(fn: Any, abstract_args: tuple,
                    abstract_kwargs: Dict[str, Any], *,
                    name: str, spec: Dict[str, Any]) -> Dict[str, Any]:
    """Lower + compile one jitted function at its recorded abstract
    signature and fit the roofline; never raises — failures come back as
    ``error`` with a zero fit (degrade, don't crash the bench)."""
    res: Dict[str, Any] = {"name": name, "error": None, "cost": {},
                           "fit": _zero_fit()}
    inner = getattr(fn, "__wrapped_fn__", fn)
    if not hasattr(inner, "lower"):
        res["error"] = "not lowerable (no .lower)"
        return res
    try:
        compiled = inner.lower(*abstract_args, **abstract_kwargs).compile()
    except Exception as e:  # noqa: BLE001 — degrade per-program
        res["error"] = repr(e)
        return res
    try:
        res["cost"] = _normalize_cost_analysis(compiled.cost_analysis())
    except Exception:  # noqa: BLE001 — cost_analysis is optional
        res["cost"] = _normalize_cost_analysis(None)
    try:
        text = compiled.as_text()
    except Exception:  # noqa: BLE001 — text is optional too
        text = ""
    res["fit"] = fit_roofline(parse_hlo_ops(text), spec)
    return res


# --------------------------------------------------------------------------
# gap budget
# --------------------------------------------------------------------------

def _apply_inflation(buckets: Dict[str, float],
                     measured: float) -> Optional[Dict[str, Any]]:
    """The synthetic drill (``PTPU_ROOFLINE_TEST_INFLATE=<sink>:<frac>``):
    claim ``frac`` of the measured step for the named sink and rescale
    the others so the budget still sums to measured.  Returns the
    ``injected`` marker (honesty: a drilled block is labeled, never
    passed off as a real attribution)."""
    raw = os.environ.get(INFLATE_ENV, "").strip()
    if not raw or measured <= 0:
        return None
    try:
        sink, frac_s = raw.split(":", 1)
        frac = float(frac_s)
    except ValueError:
        return None
    if sink not in buckets:
        return None
    frac = min(max(frac, 0.0), 1.0)
    target = frac * measured
    others = sum(v for k, v in buckets.items() if k != sink)
    scale = max(0.0, (measured - target) / others) if others > 1e-12 else 0.0
    for k in list(buckets):
        if k != sink:
            buckets[k] *= scale
    buckets[sink] = target
    return {"sink": sink, "frac": frac}


def gap_budget(step_p50_ms: float, phases_ms: Dict[str, float], *,
               analyses: Optional[Dict[str, Dict[str, Any]]] = None,
               calls: Optional[Dict[str, int]] = None,
               padding_frac: float = 0.0,
               spec: Optional[Dict[str, Any]] = None,
               degraded: Optional[str] = None) -> Dict[str, Any]:
    """Assemble the MFU-gap budget block for one scenario.

    ``analyses`` maps function name → :func:`analyze_program` result;
    ``calls`` weights multi-program scenarios (serve's prefill buckets +
    decode) by tracker call share, assuming one tracked call per bench
    step.  On an unknown ``device_kind`` the fit is not trusted: the
    compute phase lands in the explicit ``unknown_device`` sink and the
    raw model is still reported under ``programs`` for reference.
    """
    spec = spec or _device_spec()
    measured = float(step_p50_ms or 0.0)
    ph = {p: float((phases_ms or {}).get(p, 0.0) or 0.0)
          for p in ("data", "compute", "readback", "collective")}
    comm_ms = ph["collective"]
    host_ms = ph["data"] + ph["readback"]
    compute_ms = ph["compute"]
    padding_frac = min(max(float(padding_frac or 0.0), 0.0), 1.0)
    padding_ms = padding_frac * compute_ms

    programs: Dict[str, Any] = {}
    comm_ops: Dict[str, Dict[str, Any]] = {}
    model_mxu_s = model_mem_s = 0.0
    ops_modeled = ops_unmodeled = 0
    analyses = analyses or {}
    total_calls = sum(max(0, int((calls or {}).get(n, 0)))
                      for n in analyses)
    for name in sorted(analyses):
        a = analyses[name]
        c = max(0, int((calls or {}).get(name, 0)))
        share = (c / total_calls) if total_calls else 1.0 / len(analyses)
        fit = a.get("fit") or _zero_fit()
        model_mxu_s += share * fit["mxu_s"]
        model_mem_s += share * fit["memory_s"]
        ops_modeled += fit["ops_modeled"]
        ops_unmodeled += fit["ops_unmodeled"]
        # call-share-weighted per-opcode comm table (ISSUE 20): bytes a
        # step ships per HLO collective opcode, for the interconnect
        # microscope's exposed-vs-overlapped estimate
        for opcode, rec in (fit.get("comm_ops") or {}).items():
            agg = comm_ops.setdefault(
                opcode, {"count": 0, "bytes": 0.0, "participants": None})
            agg["count"] += int(rec.get("count") or 0)
            agg["bytes"] += share * float(rec.get("bytes") or 0.0)
            if rec.get("participants"):
                agg["participants"] = max(agg["participants"] or 0,
                                          int(rec["participants"]))
        cost = a.get("cost") or {}
        programs[name] = {
            "calls": c, "share": round(share, 4),
            "flops": fit["flops"], "bytes": fit["bytes"],
            "mxu_ms": round(fit["mxu_s"] * 1e3, 6),
            "memory_ms": round(fit["memory_s"] * 1e3, 6),
            "ops_modeled": fit["ops_modeled"],
            "ops_unmodeled": fit["ops_unmodeled"],
            "cost_flops": cost.get("flops"),
            "cost_bytes": cost.get("bytes_accessed"),
            "error": a.get("error"),
        }

    model_mxu_ms = model_mxu_s * 1e3
    model_mem_ms = model_mem_s * 1e3
    if spec.get("known"):
        buckets = {"mxu": model_mxu_ms, "memory_bound": model_mem_ms,
                   "comm": comm_ms, "host": host_ms,
                   "padding": padding_ms, "unknown_device": 0.0}
    else:
        buckets = {"mxu": 0.0, "memory_bound": 0.0,
                   "comm": comm_ms, "host": host_ms,
                   "padding": padding_ms,
                   "unknown_device": max(0.0, compute_ms - padding_ms)}
    injected = _apply_inflation(buckets, measured)
    residual = measured - sum(buckets.values())
    buckets["residual"] = residual
    coverage = (1.0 - min(1.0, abs(residual) / measured)
                if measured > 0 else 0.0)
    candidates = {k: v for k, v in buckets.items() if k != "mxu"}
    dominant = (max(candidates, key=lambda k: candidates[k])
                if candidates and max(candidates.values()) > 0
                else "residual")
    block = {
        "device": {k: spec.get(k) for k in
                   ("device_kind", "gen", "known", "bf16_tflops",
                    "int8_tops", "hbm_gbps")},
        "measured_step_ms": round(measured, 6),
        # the roofline prediction: modeled compute + the measured
        # comm/host phases; a device without peaks has no prediction
        "modeled_step_ms": (round(
            model_mxu_ms + model_mem_ms + comm_ms + host_ms, 6)
            if spec.get("known") else None),
        "buckets_ms": {k: round(v, 6) for k, v in buckets.items()},
        "coverage": round(coverage, 6),
        "dominant_sink": dominant,
        "padding_frac": round(padding_frac, 6),
        "ops": {"modeled": ops_modeled, "unmodeled": ops_unmodeled},
        "comm_ops": comm_ops,
        "programs": programs,
        "injected": injected,
        "degraded": degraded,
    }
    return block


def degraded_block(step_p50_ms: float, phases_ms: Dict[str, float], *,
                   padding_frac: float = 0.0,
                   reason: str = "no compiled-program capture",
                   spec: Optional[Dict[str, Any]] = None
                   ) -> Dict[str, Any]:
    """A schema-valid gap budget with no compiled-program model — the
    phase split carries all attribution.  ``schema.new_row`` synthesizes
    this when a caller passes no roofline block, so every v2 row sums to
    measured even from producers that never opened a capture window."""
    return gap_budget(step_p50_ms, phases_ms, analyses=None, calls=None,
                      padding_frac=padding_frac, spec=spec,
                      degraded=reason)


# --------------------------------------------------------------------------
# the observatory (track_jit hook target)
# --------------------------------------------------------------------------

def abstractify(args: tuple, kwargs: Dict[str, Any]) -> Tuple[tuple, dict]:
    """Shape-and-dtype skeleton of a call's arguments — taken *before*
    the call (donated buffers are unreadable after), cheap (no device
    sync), and sufficient for a later ``fn.lower()``."""
    import jax

    def to_abstract(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            try:
                return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
            except Exception:  # noqa: BLE001 — keep the odd leaf as-is
                return x
        return x

    return (jax.tree_util.tree_map(to_abstract, tuple(args)),
            jax.tree_util.tree_map(to_abstract, dict(kwargs)))


class RooflineObservatory:
    """Bounded registry of (function, abstract signature) pairs seen by
    ``track_jit`` while a capture window is open.  Nothing is lowered or
    compiled at record time — :meth:`analyses` does that lazily, outside
    any timed region."""

    def __init__(self, limit: int = 32):
        self._lock = threading.Lock()
        self._limit = int(limit)
        self._entries: "OrderedDict[str, Dict[str, Any]]" = OrderedDict()
        self._enabled = False

    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self) -> None:
        self._enabled = True

    def disable(self) -> None:
        self._enabled = False

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()

    def record(self, name: str, fn: Any, abstract_args: tuple,
               abstract_kwargs: Dict[str, Any], *,
               sig_key: int = 0, miss: bool = False) -> None:
        """One tracked call: remember the newest abstract signature per
        function name; on a compile miss, honor ``PTPU_HLO_DUMP_DIR``."""
        with self._lock:
            self._entries[name] = {
                "fn": fn, "args": abstract_args, "kwargs": abstract_kwargs,
                "sig_key": int(sig_key), "ts": time.time()}
            self._entries.move_to_end(name)
            while len(self._entries) > self._limit:
                self._entries.popitem(last=False)
        if miss:
            d = hlo_dump_dir()
            if d:
                try:
                    dump_hlo(d, name, fn, abstract_args, abstract_kwargs,
                             sig_key)
                except Exception as e:  # noqa: BLE001 — dump is best-effort
                    from ..framework.log import vlog
                    vlog(1, "observability: hlo dump failed for %s: %r",
                         name, e)

    def entries(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            return {k: dict(v) for k, v in self._entries.items()}

    def analyses(self, spec: Optional[Dict[str, Any]] = None
                 ) -> Dict[str, Dict[str, Any]]:
        """Lower + compile every recorded program and fit the roofline;
        one entry per function name, errors included (never raises)."""
        spec = spec or _device_spec()
        out: Dict[str, Dict[str, Any]] = {}
        for name, e in self.entries().items():
            out[name] = analyze_program(e["fn"], e["args"], e["kwargs"],
                                        name=name, spec=spec)
        return out


_obs_lock = threading.Lock()
_observatory: Optional[RooflineObservatory] = None


def get_observatory() -> RooflineObservatory:
    """The process-global observatory (mirrors ``get_tracker``)."""
    global _observatory
    with _obs_lock:
        if _observatory is None:
            _observatory = RooflineObservatory()
        return _observatory


def reset_observatory() -> None:
    """Disable and clear all captured state (tests)."""
    obs = get_observatory()
    obs.disable()
    obs.reset()


def capture_active() -> bool:
    """Cheap per-call gate for the ``track_jit`` hook: abstract shapes
    are only captured while a window is open or HLO dumping is on."""
    return bool((_observatory is not None and _observatory.enabled)
                or hlo_dump_dir())


class capture_window:
    """Scoped observatory enablement — the bench runner brackets each
    scenario with one and asks it for the row's ``roofline`` block:

    >>> with capture_window() as rw:
    ...     payload = scenario(mode)
    >>> block = rw.build_block(p50_ms, phases_ms, padding_frac=0.0)
    """

    def __init__(self, spec: Optional[Dict[str, Any]] = None):
        self._spec = spec

    def __enter__(self) -> "capture_window":
        obs = get_observatory()
        obs.reset()
        obs.enable()
        return self

    def __exit__(self, *exc) -> None:
        get_observatory().disable()

    def build_block(self, step_p50_ms: float,
                    phases_ms: Dict[str, float], *,
                    padding_frac: float = 0.0,
                    calls: Optional[Dict[str, int]] = None
                    ) -> Dict[str, Any]:
        spec = self._spec or _device_spec()
        obs = get_observatory()
        analyses = obs.analyses(spec)
        if not analyses:
            return degraded_block(step_p50_ms, phases_ms,
                                  padding_frac=padding_frac,
                                  reason="no jitted step captured",
                                  spec=spec)
        if calls is None:
            from .compilation import get_tracker
            tr = get_tracker()
            calls = {n: tr.stats(n)["calls"] for n in analyses}
        return gap_budget(step_p50_ms, phases_ms, analyses=analyses,
                          calls=calls, padding_frac=padding_frac,
                          spec=spec)


# --------------------------------------------------------------------------
# HLO dumping (satellite: PTPU_HLO_DUMP_DIR)
# --------------------------------------------------------------------------

def hlo_dump_dir() -> Optional[str]:
    d = os.environ.get(HLO_DUMP_ENV, "").strip()
    return d or None


def hlo_dump_keep() -> int:
    """Newest-N bound on dumped jit entries (pairs of files), mirroring
    the fleet journal's ``PTPU_FLEET_JOURNAL_KEEP`` doctrine."""
    try:
        return max(1, int(os.environ.get(HLO_DUMP_KEEP_ENV,
                                         str(DEFAULT_HLO_DUMP_KEEP))))
    except ValueError:
        return DEFAULT_HLO_DUMP_KEEP


def dump_hlo(dump_dir: str, name: str, fn: Any, abstract_args: tuple,
             abstract_kwargs: Dict[str, Any],
             sig_key: int) -> Optional[str]:
    """Write ``<name>-<sigkey>.lowered.txt`` + ``.compiled.txt`` for one
    jit entry — the filename key is the PR 4 signature-cache key
    (``hash(tuple(sigs))``), so one file pair per distinct trace.
    Returns the stem, or None when ``fn`` isn't lowerable."""
    inner = getattr(fn, "__wrapped_fn__", fn)
    if not hasattr(inner, "lower"):
        return None
    os.makedirs(dump_dir, exist_ok=True)
    safe = re.sub(r"[^\w.\-]+", "_", str(name)) or "fn"
    stem = "%s-%016x" % (safe, sig_key & 0xFFFFFFFFFFFFFFFF)
    from ..utils import fsio
    lowered = inner.lower(*abstract_args, **abstract_kwargs)
    fsio.atomic_write_bytes(os.path.join(dump_dir, stem + ".lowered.txt"),
                            lowered.as_text().encode("utf-8"))
    fsio.atomic_write_bytes(os.path.join(dump_dir, stem + ".compiled.txt"),
                            lowered.compile().as_text().encode("utf-8"))
    _gc_dumps(dump_dir, hlo_dump_keep())
    return stem


def _gc_dumps(dump_dir: str, keep: int) -> None:
    """Drop all but the newest ``keep`` dumped entries (by mtime of the
    newest file in each pair)."""
    try:
        names = os.listdir(dump_dir)
    except OSError:
        return
    stems: Dict[str, List[Any]] = {}
    for n in names:
        for suf in (".lowered.txt", ".compiled.txt"):
            if n.endswith(suf):
                stem = n[:-len(suf)]
                p = os.path.join(dump_dir, n)
                try:
                    mt = os.path.getmtime(p)
                except OSError:
                    continue
                cur = stems.setdefault(stem, [0.0, []])
                cur[0] = max(cur[0], mt)
                cur[1].append(p)
    if len(stems) <= keep:
        return
    ordered = sorted(stems.items(), key=lambda kv: kv[1][0], reverse=True)
    for _stem, (_mt, paths) in ordered[keep:]:
        for p in paths:
            try:
                os.remove(p)
            except OSError:
                pass
