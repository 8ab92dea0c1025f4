"""ServingEngine (ISSUE 6): the continuous-batching serving loop.

The training side of this codebase drives a model with one jitted step
over a fixed batch; serving traffic is the opposite shape — requests
arrive at random times, with ragged prompts, and leave when *they* are
done.  The engine turns that traffic into fixed-shape device work:

    engine = ServingEngine(model, max_seqs=8, kv_block_size=16)
    rid = engine.submit([1, 5, 9], max_new_tokens=32)
    while engine.has_work():
        engine.step()               # one prefill OR one decode batch lands
    out = engine.collect(rid)       # {"tokens": [...], "ttft_ms": ...}

A unit of work is a LAUNCH and a LANDING, and the engine launches unit n+1
before it lands unit n (ISSUE 36): the only thing a decode row needs of
the unit before it is the token that unit sampled, and that is on the
device already, so the step program takes the previous program's tokens
(``prev``) and a word a row that says which of them is the row's id
(``src``).  The host's part of a step (schedule, tables, the put, the
call, the copy, the accept) then runs while the chip works.  See
:meth:`ServingEngine.step`.

Pieces (all under ``paddle_tpu/inference/``):

- ``kv_cache.PagedKVCache`` — block-pooled KV with per-sequence tables;
- ``scheduler.ContinuousBatchingScheduler`` — admission by block
  budget, newest-first preemption, prefill/decode interleaving;
- ``paged_attention`` — the ragged decode kernel (lax fallback on CPU);
- this module — the jitted step functions, sampling, SLO metrics, and
  the submit/step/collect surface.

Step shapes come from a closed set — decode is always
``(max_seqs, 1)``; prefill is padded to power-of-two buckets — and each
shape's jitted function is wrapped in the PR 4 compile tracker under its
own name (``serve_decode``, ``serve_prefill_b<bucket>``), so a full
serve run compiles **once per bucket** and any retrace is attributable.

SLO telemetry rides the PR 3 registry: gauges ``serve.queue_depth`` /
``serve.running`` / ``serve.waiting`` / ``serve.kv_occupancy``,
histograms ``serve.ttft_ms`` / ``serve.tpot_ms``, counters
``serve.tokens`` / ``serve.requests`` / ``serve.finished`` /
``serve.preemptions`` / ``serve.units_launched`` / ``serve.units_ahead``
(launched while another was unread) / ``serve.ahead_rows_discarded`` (rows
computed for a request that had ended a unit earlier) /
``serve.ahead_units_dropped`` (launched after a unit whose landing raised)
/ ``serve.ahead_breaks.<idle|preempt|fault|drain>`` (why a call launched
nothing ahead) / ``serve.h2d_bytes`` / ``serve.d2h_bytes`` (what
a step puts on the device and copies back: its int32 inputs in one
buffer; the next tokens, a finite flag a row and the model's counts) /
``serve.logits_fetch_steps`` (the steps whose ``[rows, vocab]`` float32
logits crossed too: only a captured request or the fault seam asks for
them) / ``serve.pool_rebuilds`` /
``serve.paged_blocks_live`` / ``serve.paged_blocks_table`` (the pages a
decode step's rows hold, and rows launched x table width); over a cache
with a page pool a kind of layer (``kv_cache.py``: window layers beside
full ones) also ``serve.kv_<kind>_blocks_live`` (each pool's blocks under
a decode step's rows) and ``serve.kv_window_blocks_freed`` (blocks let go
behind the window),
gauge ``serve.kv_pool_bytes`` (the live page handles: one pool).  Every
``step()`` is an ``engine.step`` span of :mod:`observability.tracing`
whose children name its phases (see :meth:`ServingEngine.step`);
``stats()["phases"]`` sums them; a request's wait in the queue is one
``engine.request/queue`` span, recorded when its first prefill is
launched; the unit ledger's sums (what the device starved for, how long
the host waited for it) are ``stats()["units"]`` and nowhere else.
``start_status_server()`` exposes them on the
PR 5 monitor (``/statusz`` serving section; ``/healthz`` goes 503 when
the admission queue exceeds ``PTPU_SHED_QUEUE_DEPTH`` — load shedding).

Token callbacks (``submit(..., on_token=fn)``) are dispatched from a
separate drain thread: a slow consumer (``testing/faults.slow_call``)
delays its own stream, never the batch.  Consumer exceptions are
counted (``serve.callback_errors``) and timelined, never fatal.

The request-lifecycle guard (ISSUE 15) wraps all of the above in the
same robustness treatment the training path earned:

- **deadlines & cancellation** — ``submit(deadline_ms=,
  ttft_deadline_ms=)`` and ``cancel(rid)``; a between-steps reaper
  evicts expired/cancelled sequences with every KV block returned and a
  terminal reason (``deadline`` / ``cancelled``) through ``collect()``
  and the callback path;
- **poisoned-request quarantine** — the jitted step runs inside a fault
  boundary; a step exception bisects the batch (a nonfinite logits row
  under ``PTPU_SERVE_NAN_GUARD`` names its request itself, from a flag
  a row that the step program computes), evicts the culprit(s)
  with ``reason="poisoned"`` plus a durable record under
  ``<run_dir>/serve_quarantine/``, and replays the step so every other
  request completes token-exact (decode rows are independent).  The
  boundary is a unit's LANDING: the unit launched after it is dropped
  unread and its launch-time marks are taken back, so bisect and replay
  run with nothing in flight.  The
  step program updates the KV pool in place (the pages are donated), so
  a faulted, probed or dropped unit leaves its page writes behind —
  harmless, they land where the replay writes the same values — and a
  culprit's blocks are zeroed before they are freed; a call that dies after
  consuming the pool gets a zeroed pool and the running set goes back
  through recompute-prefill (``serve.pool_rebuilds``).  The
  boundary only covers a step program that has run to completion at
  least once: an error from a program's first run (a lowering or
  compile failure, a missing device) is the engine's fault, not a
  request's, and propagates out of ``step()``;
- **supervision + graceful drain** — ``step()`` arms the PR 2 watchdog
  (a hung step gets a stack dump; the engine rebuilds its jitted fns
  and re-admits the running set via recompute-prefill), and
  ``drain(timeout=)`` stops admission (``/healthz`` → 503 ``draining``),
  finishes what it can, spills the rest to a JSON file a fresh engine
  ``resume()``s from, then stops the callback thread.

Durable artifacts are namespaced per replica (ISSUE 16): quarantine
records land under ``<run_dir>/serve/replica-<i>/quarantine/`` and the
drain spill at ``<run_dir>/serve/replica-<i>/spill.json`` (``<i>`` is
``replica_id``, 0 when unset), so N engines sharing one run_dir — the
fleet layout — never collide.  ``resume()`` without an explicit path
reads the namespaced location and falls back to the legacy
``<run_dir>/serve_spill.json``.

Env knobs: ``PTPU_MAX_SEQS``, ``PTPU_KV_BLOCK_SIZE``,
``PTPU_SHED_QUEUE_DEPTH``, ``PTPU_SERVE_NAN_GUARD``,
``PTPU_SERVE_DEADLINE_MS``, ``PTPU_SERVE_DRAIN_SECS``.  Single-host by
design: the page scatter and the Pallas kernel are opaque to GSPMD (the
engine enforces no mesh).
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import os
import queue
import re
import threading
import time
from collections import deque
from typing import (Any, Callable, Deque, Dict, List, Mapping, Optional,
                    Sequence, Union)

import numpy as np

import jax
import jax.numpy as jnp

from ..framework.errors import enforce
from ..observability import requesttrace
from ..observability.compilation import track_jit
from ..observability.tracing import record as record_span
from ..observability.tracing import span, span_tree_totals
from ..supervisor.watchdog import StepTimeout, Watchdog, guarded
from ..utils import fsio
from .kv_cache import (PagedKVCache, PagedLayerCache,
                       default_kv_block_size, layout_kinds,
                       window_table_width)
from .scheduler import (RUNNING, ContinuousBatchingScheduler,
                        SequenceState)

__all__ = ["MAX_SEQS_ENV", "SHED_QUEUE_DEPTH_ENV", "NAN_GUARD_ENV",
           "DEADLINE_MS_ENV", "DRAIN_SECS_ENV", "default_max_seqs",
           "default_shed_queue_depth", "default_nan_guard",
           "default_deadline_ms", "default_drain_secs", "CollectTimeout",
           "ServingEngine", "pack_step_inputs", "unpack_step_inputs"]

MAX_SEQS_ENV = "PTPU_MAX_SEQS"
SHED_QUEUE_DEPTH_ENV = "PTPU_SHED_QUEUE_DEPTH"
NAN_GUARD_ENV = "PTPU_SERVE_NAN_GUARD"
DEADLINE_MS_ENV = "PTPU_SERVE_DEADLINE_MS"
DRAIN_SECS_ENV = "PTPU_SERVE_DRAIN_SECS"

_PAD_SEQ = "__pad__"          # never a real request id
# a ``device_wait`` no longer than this found the device done already (the
# unit is ``late``: the HOST was the slower of the two).  The span and
# ``block_until_ready`` of arrays that ARE ready cost 2.5 us back to back
# on a v5e's host (p99 5.5-9.8) and, after 5 ms of sleep, 18-23 us (p90
# 29-62, p99 51-100; ``perfbench/tools/ledger_cost.py``, PERF.md PR 37)
LATE_EPS_S = 100e-6
# why the device had nothing of this engine's to run before a unit that was
# launched with nothing in flight: ``ahead_breaks``' words, and the first
# unit of the engine's life
_STARVED_WHY = ("start", "idle", "preempt", "fault", "drain")


def _unit_sums() -> Dict[str, Any]:
    """What the ledger sums over landed units of one kind (or bucket)."""
    return {"units": 0, "rows": 0, "device_s": 0.0, "units_bound": 0,
            "device_s_bound": 0.0, "wait_s": 0.0}
_CB_STOP = object()           # callback-thread shutdown sentinel

# recompute cause → trace-span component (ISSUE 18): the re-prefill (and
# the re-queue wait before it) is attributed to whatever evicted the KV
_RESUME_COMPONENT = {"preempt": "preempt_recompute",
                     "failover": "failover_recompute",
                     "migration": "migration_recompute"}


def _pctl(values, p: float) -> Optional[float]:
    """Nearest-rank percentile over a small sample; None when empty."""
    if not values:
        return None
    ordered = sorted(values)
    idx = min(len(ordered) - 1, int(len(ordered) * p / 100.0))
    return float(ordered[idx])


def default_max_seqs() -> int:
    return int(os.environ.get(MAX_SEQS_ENV, "8"))


def default_shed_queue_depth() -> int:
    return int(os.environ.get(SHED_QUEUE_DEPTH_ENV, "64"))


def default_nan_guard() -> bool:
    return os.environ.get(NAN_GUARD_ENV, "0").lower() in ("1", "true",
                                                          "yes", "on")


def default_deadline_ms() -> Optional[float]:
    raw = os.environ.get(DEADLINE_MS_ENV)
    return None if raw is None else float(raw)


def default_drain_secs() -> float:
    return float(os.environ.get(DRAIN_SECS_ENV, "30"))


class CollectTimeout(TimeoutError):
    """``collect(timeout=)`` expired before the request finished; the
    message names the request's current scheduler state."""


def pack_step_inputs(ids, positions, last_index, block_tables, seq_lens,
                     slot_mapping, step: int = 0, src=None) -> np.ndarray:
    """A step's int32 inputs end to end in one host buffer, so that they
    cross to the device in one put: token ids ``(rows, chunk)``,
    positions ``(rows,)``, the index of the position sampled, block
    tables ``(rows, width)``, sequence lengths ``(rows,)``, write slots
    ``(rows, chunk)``, ``src`` ``(rows,)`` (where a row's first id comes
    from: the row of the previous program's tokens that holds it, or -1,
    the default, for "the id in this buffer"), and the step's number
    (what a sampling program folds into the engine's key; a greedy one
    reads past it).  Over a cache with a pool a kind of layer
    ``block_tables`` and ``slot_mapping`` are lists, an array a kind: each
    list goes in where its one array would."""
    if src is None:
        src = np.full(np.shape(positions), -1, np.int32)
    flat = []
    for a in (ids, positions, last_index, block_tables, seq_lens,
              slot_mapping, src, step):
        flat += a if isinstance(a, (list, tuple)) else [a]
    return np.concatenate([np.asarray(a, np.int32).reshape(-1)
                           for a in flat])


def unpack_step_inputs(packed, rows: int, chunk: int,
                       widths: Optional[Sequence[int]] = None):
    """Cut :func:`pack_step_inputs`'s buffer apart again (inside a trace:
    static slices).  The table width is whatever is left of the length;
    ``widths`` are the kinds' table widths where the tables and the slots
    went in as lists, and they come out as lists."""
    kinds = 1 if widths is None else len(widths)
    width, rest = divmod(
        packed.shape[0] - rows * ((kinds + 1) * chunk + 3) - 2, rows)
    enforce(width > 0 and rest == 0
            and (widths is None or width == sum(widths)),
            f"{packed.shape[0]} packed step inputs do not hold {rows} rows "
            f"of {chunk}" + ("" if widths is None
                             else f" with tables of {tuple(widths)}"))
    at = 0

    def cut(shape):
        nonlocal at
        n = int(np.prod(shape, dtype=np.int64))
        at += n
        return packed[at - n:at].reshape(shape)

    ids, positions, last_index = cut((rows, chunk)), cut((rows,)), cut(())
    tables = [cut((rows, w)) for w in (widths or (width,))]
    lens = cut((rows,))
    slots = [cut((rows, chunk)) for _ in range(kinds)]
    if widths is None:
        tables, slots = tables[0], slots[0]
    return [ids, positions, last_index, tables, lens, slots, cut((rows,)),
            cut(())]


class _NonfiniteLogits(RuntimeError):
    """NaN-guard verdict: the named rows came back nonfinite — unlike a
    raised step error this carries the culprits, no bisection needed."""

    def __init__(self, request_ids: List[str]):
        super().__init__(f"nonfinite logits for {request_ids}")
        self.request_ids = list(request_ids)


@dataclasses.dataclass
class _Unit:
    """One step program that was launched and that the host has not read
    yet (a prefill or a decode batch): what its landing needs."""
    kind: str                         # "prefill" | "decode"
    seqs: List[SequenceState]
    bucket: int                       # a prefill's pad length, else 0
    number: int                       # what a sampling program folds in
    t0: float                         # the engine's clock at the launch
    # per row, whether the program samples the sequence's NEXT token (a
    # recompute-prefill does not: that token was streamed before)
    emits: List[bool] = dataclasses.field(default_factory=list)
    fetch_logits: bool = False
    # device handles: what the host will read ([next tokens, finite flags,
    # the model's counts] and the logits where asked for), what stays
    # (the rest of aux), and the tokens as the next program takes them
    out: Optional[list] = None
    logits: Any = None
    aux: Dict[str, Any] = dataclasses.field(default_factory=dict)
    carry: Any = None
    counts: Any = None                # the model's counts, once landed
    error: Optional[BaseException] = None   # the launch raised
    marks: List[tuple] = dataclasses.field(default_factory=list)
    # booked at the landing: root-span attributes and block counts
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    blocks: Dict[str, int] = dataclasses.field(default_factory=dict)
    stall: str = "stall"              # what the residents it leaves out wait for
    # the ledger's stamps (ISSUE 37), both on ``time.perf_counter()`` and
    # both the ends of spans the engine opens anyway: of the unit's
    # ``dispatch`` and of its ``device_wait`` (when the HOST saw the device
    # done)
    enqueued: Optional[float] = None
    done: Optional[float] = None
    # derived at ``done``: the wait itself, whether it found the device done
    # already, the unit's time on the device as the host can see it, and
    # whether that is exact (neither this unit nor the one before was late)
    wait_s: float = 0.0
    late: bool = False
    device_s: float = 0.0
    exact: bool = True

    @property
    def program(self):
        """The step program's name among those that have run to an end."""
        return "decode" if self.kind == "decode" else ("prefill",
                                                        self.bucket)


class ServingEngine:
    """Paged-KV continuous-batching serving engine over a decoder model.

    ``model`` must expose the serving surface of ``GPTForCausalLM`` and
    ``DeepseekV2ForCausalLM``: ``.config`` (max_position_embeddings /
    dtype), ``.state_dict()``, ``.eval()``, ``kv_cache_layout()`` (per
    layer, the per-token shape of each page array: the pool is built from
    it) and an ``apply(..., method="serving_step")`` entry point
    returning ``(logits, new_caches)`` over ``PagedLayerCache`` lists, or
    ``(logits, new_caches, aux)``.  ``aux`` rides out with the step's
    outputs and its meaning is the model's: ``aux["counts"]`` (small
    arrays, copied to the host with the next tokens) goes to the model's
    ``serving_counts(counts, kind)``, which names the counters to add to
    and the gauges to set; ``aux["per_token"]`` (arrays of ``(rows, chunk,
    ...)``) stays on the device unless a request captures logits, and is
    then handed out by ``collect()`` a token at a time; ``aux["per_logit"]``
    (arrays of ``(rows, ...)``: what belongs to the ONE position a row
    whose logits the step returns, too large to keep for a chunk's every
    token) likewise, one entry a captured logits row.

    ``temperature`` is engine-level (it is baked into the jitted step;
    per-request temperatures would multiply the compile set).
    ``capture_logits=True`` keeps every sampled position's logits row on
    the host per request — the numerics-equality hook for tests.  It is
    read at ``submit()``, so it can be set for some requests only.  A
    step moves over PCIe what the host reads: the next tokens, one
    finite flag a row and the model's counts.  The ``[rows, vocab]``
    float32 logits stay on the device unless a row of the step belongs
    to a capturing request or ``step_fault`` is set
    (``serve.logits_fetch_steps`` counts those steps).

    Resilience knobs (ISSUE 15): ``nan_guard`` enables the per-step
    nonfinite-logits check (env ``PTPU_SERVE_NAN_GUARD``);
    ``step_timeout`` arms a watchdog around every launch and every landing
    of a step (or pass a shared ``watchdog``) — set it above the worst-case COLD compile of your
    shape set (the watchdog cannot tell XLA compiling from a wedged
    device), or warm the shapes first; ``run_dir`` is where quarantine
    records and the drain spill file land; ``step_fault`` is the test
    seam the ``testing/faults.poison_request`` injector plugs into — it
    is called as ``fault(engine, kind, request_ids, logits)`` on every
    executed step, bisection probes included; while it is set the
    engine fetches every step's logits for it, and the NaN guard checks
    on the host what the hook hands back.  Without it the guard reads
    the flags and no logits cross for its sake.
    """

    def __init__(self, model, *, max_seqs: Optional[int] = None,
                 kv_block_size: Optional[int] = None,
                 num_kv_blocks: Union[None, int, Mapping[str, int]] = None,
                 max_model_len: Optional[int] = None,
                 temperature: float = 0.0,
                 capture_logits: bool = False,
                 shed_queue_depth: Optional[int] = None,
                 registry=None, seed: int = 0,
                 clock: Callable[[], float] = time.time,
                 nan_guard: Optional[bool] = None,
                 step_timeout: Optional[float] = None,
                 watchdog: Optional[Watchdog] = None,
                 run_dir: Optional[str] = None,
                 replica_id: Optional[int] = None,
                 step_fault: Optional[Callable] = None):
        from ..distributed.topology import get_mesh
        from ..observability.compilecache import enable_persistent_cache
        enforce(get_mesh() is None,
                "ServingEngine is single-host (the paged path is opaque "
                "to GSPMD) — run it outside fleet meshes")
        enable_persistent_cache()
        cfg = model.config
        self.model = model
        model.eval()
        self._params = model.state_dict()
        self.max_seqs = int(max_seqs if max_seqs is not None
                            else default_max_seqs())
        self.max_model_len = int(max_model_len if max_model_len is not None
                                 else cfg.max_position_embeddings)
        enforce(self.max_model_len <= cfg.max_position_embeddings,
                f"max_model_len {self.max_model_len} exceeds the model's "
                f"{cfg.max_position_embeddings} positions")
        block_size = (default_kv_block_size() if kv_block_size is None
                      else int(kv_block_size))
        blocks_per_seq = -(-self.max_model_len // block_size)
        layout = model.kv_cache_layout()
        kinds = layout_kinds(layout)
        # roomy default: every batch slot can hold a full-length
        # sequence (tests pass tight pools to exercise preemption)
        roomy = {kind: self.max_seqs * (
            blocks_per_seq if window is None
            else min(blocks_per_seq, window_table_width(window, block_size)))
            for kind, window in kinds.items()}
        if num_kv_blocks is None:
            num_kv_blocks = roomy
        elif isinstance(num_kv_blocks, Mapping):
            num_kv_blocks = {**roomy, **num_kv_blocks}
        if len(kinds) == 1 and isinstance(num_kv_blocks, Mapping):
            num_kv_blocks = num_kv_blocks[next(iter(kinds))]
        dtype = (jnp.dtype(cfg.dtype) if cfg.dtype != "float32"
                 else jnp.float32)
        self.cache = PagedKVCache(layout, num_kv_blocks,
                                  block_size=block_size, dtype=dtype)
        self.sched = ContinuousBatchingScheduler(
            self.cache, self.max_seqs, self.max_model_len, clock=clock)
        self.temperature = float(temperature)
        self.capture_logits = bool(capture_logits)
        self.shed_queue_depth = int(
            shed_queue_depth if shed_queue_depth is not None
            else default_shed_queue_depth())
        self._registry = registry
        # what the model wants shown beside the engine's own gauges (a
        # latent cache's bytes a token, say): constants, set once
        self._model_gauges: Dict[str, float] = dict(
            getattr(model, "serving_gauges", dict)())
        for name, value in self._model_gauges.items():
            self._reg().gauge(name).set(value)
        self.clock = clock
        # the one key: a sampling step program folds its step number in
        self._key = jax.random.PRNGKey(seed)
        self._ids = itertools.count()
        self.steps = 0
        self.status_server = None
        self._jit_step = None
        self._decode_tracked = None
        self._prefill_tracked: Dict[int, Callable] = {}
        # step programs ("decode", ("prefill", bucket)) that have run to
        # completion once — only those can poison a request
        self._proven: set = set()
        self._cb_queue: Optional[queue.Queue] = None
        self._cb_thread: Optional[threading.Thread] = None
        # request-lifecycle guard (ISSUE 15)
        self.nan_guard = (default_nan_guard() if nan_guard is None
                          else bool(nan_guard))
        self.run_dir = run_dir
        self.replica_id = None if replica_id is None else int(replica_id)
        self.step_fault = step_fault      # fault seam for the drills
        self.step_timeout = step_timeout
        self._owns_watchdog = watchdog is None and step_timeout is not None
        self._watchdog = (Watchdog(timeout=step_timeout)
                          if self._owns_watchdog else watchdog)
        self._state = "serving"           # serving | draining | stopped
        self._submit_order: List[str] = []
        self.quarantined: Dict[str, Dict[str, Any]] = {}
        self.watchdog_restarts = 0
        self.pool_rebuilds = 0
        self.lifecycle_counts = {"deadline": 0, "cancelled": 0,
                                 "poisoned": 0, "spilled": 0}
        self._cb_dispatched = 0
        self._cb_errors = 0
        self._last_callback_error: Optional[str] = None
        # engine-local latency tails for the stats() "slo" section —
        # per-replica, unlike the (possibly fleet-shared) registry
        # histograms, so the autoscaler sees THIS engine's p99
        self._ttft_ms: Deque[float] = deque(maxlen=512)
        self._tpot_ms: Deque[float] = deque(maxlen=512)
        # request tracing (ISSUE 18): the process tag every span this
        # engine emits carries, and the set of request ids whose trace
        # lifecycle THIS engine owns (direct submissions — fleet
        # streams are owned by the router, which emits the
        # trace.request / trace.request_end records itself)
        self._proc = f"replica-{self.replica_id or 0}"
        self._trace_owned: set = set()
        # padding-waste accounting (ISSUE 19): pow2 prefill buckets and
        # fixed-shape decode both process padded slots; real-vs-padded
        # counts feed serve.padding_frac (and the bench row's roofline
        # padding sink) so padded rows stop inflating tokens/s and MFU
        self._pad_real_tokens = 0
        self._pad_slot_tokens = 0
        # the unit launched and not read yet, if any (ISSUE 36); the
        # number the next unit folds into the key; events of a unit that
        # landed outside step(); when the last unit landed; what a program
        # launched with nothing unread reads as the previous tokens; and
        # how often running ahead engaged and why not
        self._in_flight: Optional[_Unit] = None
        self._unit_no = 0
        self._early: List[Dict[str, Any]] = []
        self._landed_at = 0.0
        self._no_prev = jnp.zeros((self.max_seqs,), jnp.int32)
        self._ahead: Dict[str, Any] = {
            "units_launched": 0, "units_ahead": 0,
            "ahead_rows_discarded": 0, "ahead_units_dropped": 0,
            "ahead_breaks": {"idle": 0, "preempt": 0, "fault": 0,
                             "drain": 0}}
        # the step's root span, and what the model booked so far
        self._step_root: Optional[span] = None
        self._model_counts: Dict[str, Dict[str, Any]] = {
            "counters": {}, "gauges": {}}
        self._paged_blocks = {"live": 0, "table": 0}
        # a kind's blocks under the rows of the decode steps so far
        self._kv_live: Dict[str, int] = {}
        self._logits_fetch_steps = 0
        # the unit ledger (ISSUE 37): sums over landed units by kind and, a
        # prefill's, by bucket; what the device starved for, by reason; the
        # units launched ahead of a late landing; seconds inside step().
        # Beside them, what the next unit's sums need of the last one
        # (when the host saw the device done, whether it was late), why
        # nothing is in flight, and when this engine could first have run
        self._units: Dict[str, Any] = {
            "by_kind": {"prefill": _unit_sums(), "decode": _unit_sums()},
            "prefill_by_bucket": {},
            "starved": {why: [0, 0.0] for why in _STARVED_WHY},
            "host_late": 0, "step_s": 0.0}
        self._last_done: Optional[float] = None
        self._last_late = False
        self._starve_why = "idle"
        self._born = time.perf_counter()

    # -- plumbing ----------------------------------------------------------
    def serve_dir(self) -> Optional[str]:
        """Per-replica durable-artifact namespace (ISSUE 16):
        ``<run_dir>/serve/replica-<i>`` — quarantine records and the
        drain spill live here so N engines sharing one ``run_dir``
        never collide.  None without a ``run_dir``."""
        if self.run_dir is None:
            return None
        return os.path.join(self.run_dir, "serve",
                            f"replica-{self.replica_id or 0}")

    def _reg(self):
        if self._registry is not None:
            return self._registry
        from ..observability.registry import get_registry
        return get_registry()

    # -- jitted step functions --------------------------------------------
    _STEP_ARGS = ("params", "packed", "pages", "key", "prev")

    def _build_step_fn(self):
        """The step program, ``fn(params, packed, pages, key, prev, *,
        rows, chunk)``.  ``packed`` is the step's int32 inputs in one
        buffer (:func:`pack_step_inputs`), cut apart here at offsets that
        ``rows`` and ``chunk`` fix for each program (decode; each prefill
        bucket).  ``pages`` (per layer its page arrays, as the model
        declared them) is donated and nothing else is: with token-major
        pages XLA scatters the new tokens into the pool in place and
        every returned page array aliases its input, so no step copies
        the pool.  Each layer's view is built here, inside the trace.

        ``prev`` (int32, ``(max_seqs,)`` whatever launched before) is the
        tokens the program launched immediately before this one sampled,
        which the host may not have read yet: a row whose ``src`` word is
        not negative takes its first id from ``prev[src]``, the others the
        id in the buffer.  Where an id comes from is data: one decode
        program, one a prefill bucket, whatever the order of units.

        Returns ``(next tokens, finite, logits, pages, aux, carry)``: the
        float32 logits stay on the device unless the host asks for them,
        so the program says itself which rows of them are finite, and
        ``carry`` is the next tokens at ``prev``'s shape, for the program
        launched next.  ``key`` is the engine's one key, the same array
        every step: a sampling program folds the step's number (the last
        packed word) into it, a greedy one reads neither."""
        model, temperature = self.model, self.temperature
        block_size, max_seqs = self.cache.block_size, self.max_seqs
        # a cache of one kind of layer: one table, one slot matrix, every
        # layer's view over them.  Of several: one of each a kind
        kind_of = [list(self.cache.pools).index(k)
                   for k in self.cache.layer_kinds]
        widths = (None if len(self.cache.pools) == 1 else
                  self.cache.table_widths(self.sched.max_blocks_per_seq))

        def fn(params, packed, pages, key, prev, *, rows, chunk):
            (ids, positions, last_index, block_tables, seq_lens,
             slot_mapping, src, step) = unpack_step_inputs(
                 packed, rows, chunk, widths)
            ids = ids.at[:, 0].set(jnp.where(
                src >= 0, prev[jnp.maximum(src, 0)], ids[:, 0]))
            if widths is None:
                block_tables, slot_mapping = [block_tables], [slot_mapping]
            caches = [PagedLayerCache(layer, block_tables[k], seq_lens,
                                      slot_mapping[k], block_size=block_size)
                      for layer, k in zip(pages, kind_of)]
            logits, new_caches, *aux = model.apply(
                params, ids, caches, positions, last_index,
                method="serving_step")
            logits = logits.astype(jnp.float32)
            if temperature <= 0.0:
                nxt = jnp.argmax(logits, axis=-1)
            else:
                nxt = jax.random.categorical(
                    jax.random.fold_in(key, step), logits / temperature,
                    axis=-1)
            nxt = nxt.astype(jnp.int32)
            carry = (nxt if rows == max_seqs else
                     jnp.zeros((max_seqs,), jnp.int32).at[:rows].set(nxt))
            return (nxt, jnp.isfinite(logits).all(-1), logits,
                    [c.pages for c in new_caches], aux[0] if aux else {},
                    carry)

        return jax.jit(fn, donate_argnames=("pages",),
                       static_argnames=("rows", "chunk"))

    def _tracked_step(self, name: str):
        # one underlying jitted callable (jax caches per shape); a tracker
        # name per program makes "one compile per bucket" directly
        # observable and keeps retrace counts at zero
        if self._jit_step is None:
            self._jit_step = self._build_step_fn()
        return track_jit(self._jit_step, name=name,
                         arg_names=self._STEP_ARGS)

    def _decode_fn(self):
        if self._decode_tracked is None:
            self._decode_tracked = self._tracked_step("serve_decode")
        return self._decode_tracked

    def _prefill_fn(self, bucket: int):
        fn = self._prefill_tracked.get(bucket)
        if fn is None:
            fn = self._tracked_step(f"serve_prefill_b{bucket}")
            self._prefill_tracked[bucket] = fn
        return fn

    # -- intake ------------------------------------------------------------
    def submit(self, prompt_ids: Sequence[int], max_new_tokens: int = 32,
               request_id: Optional[str] = None,
               eos_token_id: Optional[int] = None,
               on_token: Optional[Callable] = None,
               deadline_ms: Optional[float] = None,
               ttft_deadline_ms: Optional[float] = None,
               trace_id: Optional[str] = None) -> str:
        """Queue one request; returns its id.  ``on_token(request_id,
        token, finished)`` — when given — is invoked from the callback
        drain thread, decoupled from the step loop.

        ``deadline_ms`` bounds the whole request (default from
        ``PTPU_SERVE_DEADLINE_MS``; None = no deadline);
        ``ttft_deadline_ms`` bounds the wait for the FIRST token only —
        both relative to now, enforced by the between-steps reaper with
        terminal ``reason="deadline"``."""
        enforce(self._state == "serving",
                f"engine is {self._state} — not accepting new requests")
        rid = request_id or f"req-{next(self._ids)}"
        prompt = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        now = float(self.clock())
        if deadline_ms is None:
            deadline_ms = default_deadline_ms()
        seq = SequenceState(request_id=rid, prompt=prompt,
                            max_new_tokens=int(max_new_tokens),
                            eos_token_id=eos_token_id,
                            arrival=now, queued=time.perf_counter(),
                            on_token=on_token,
                            capture_logits=self.capture_logits,
                            deadline=(None if deadline_ms is None
                                      else now + float(deadline_ms) / 1e3),
                            ttft_deadline=(
                                None if ttft_deadline_ms is None
                                else now + float(ttft_deadline_ms) / 1e3))
        # trace context (ISSUE 18): a fleet router passes its minted
        # ``trace_id``; direct submissions mint (and own) their own, so
        # standalone engines get waterfalls too
        if trace_id is None:
            trace_id = requesttrace.mint_trace_id(rid)
            if trace_id is not None:
                self._trace_owned.add(rid)
        seq.trace_id = trace_id
        self.sched.submit(seq)
        self._submit_order.append(rid)
        reg = self._reg()
        reg.counter("serve.requests").inc()
        reg.emit("serve.request", request_id=rid, prompt_len=len(prompt),
                 max_new_tokens=seq.max_new_tokens, trace_id=trace_id)
        if rid in self._trace_owned:
            reg.emit("trace.request", trace_id=trace_id, request_id=rid,
                     t0=now, prompt_len=len(prompt), proc=self._proc)
        self._update_gauges()
        return rid

    def cancel(self, request_id: str) -> bool:
        """Flag a live request for eviction at the next step boundary
        (terminal ``reason="cancelled"``, KV blocks returned).  False
        when the request already finished or was never submitted."""
        for seq in list(self.sched.running) + list(self.sched.waiting):
            if seq.request_id == request_id:
                seq.cancelled = True
                return True
        return False

    def should_shed(self) -> bool:
        """Load-shed signal: the admission queue is past the knob —
        ``/healthz`` turns 503 so the balancer drains elsewhere."""
        return self.sched.queue_depth > self.shed_queue_depth

    # -- the step ----------------------------------------------------------
    def _trace_end(self, seq: SequenceState, reason: str) -> None:
        """Close an engine-owned trace at a terminal transition.  Fleet
        streams are closed by the router (it observes the finish through
        its own poll, which is the client-observed end)."""
        if seq.trace_id is None or seq.request_id not in self._trace_owned:
            return
        self._trace_owned.discard(seq.request_id)
        self._reg().emit("trace.request_end", trace_id=seq.trace_id,
                         request_id=seq.request_id,
                         t1=float(self.clock()), reason=reason,
                         tokens=len(seq.output), proc=self._proc)

    def _evict(self, seq: SequenceState, reason: str) -> Dict[str, Any]:
        """Terminal eviction with reason ``deadline`` / ``cancelled``:
        free blocks, bump counters, emit the timeline record, and deliver
        the terminal event down the callback path."""
        self.sched.evict(seq, reason)
        self.lifecycle_counts[reason] += 1
        reg = self._reg()
        if reason == "cancelled":
            reg.counter("serve.cancelled").inc()
            reg.emit("serve.cancel", request_id=seq.request_id,
                     generated=len(seq.output), trace_id=seq.trace_id)
        else:
            reg.counter("serve.deadline_misses").inc()
            reg.emit("serve.deadline_miss", request_id=seq.request_id,
                     generated=len(seq.output), trace_id=seq.trace_id,
                     miss=("ttft" if seq.first_token_time is None
                           and seq.ttft_deadline is not None else "total"))
        self._trace_end(seq, reason)
        event = {"request_id": seq.request_id, "token": None,
                 "finished": True, "reason": reason}
        if seq.on_token is not None:
            self._dispatch_callback(seq.on_token, event, seq)
        return event

    def _reap(self) -> List[Dict[str, Any]]:
        """Between-steps lifecycle sweep: evict cancelled and
        deadline-expired sequences (running or waiting) before the
        scheduler plans this step — their blocks fund the admissions."""
        now = float(self.clock())
        events = []
        for seq in list(self.sched.running) + list(self.sched.waiting):
            if seq.cancelled:
                events.append(self._evict(seq, "cancelled"))
            elif seq.deadline is not None and now >= seq.deadline:
                events.append(self._evict(seq, "deadline"))
            elif (seq.ttft_deadline is not None
                    and seq.first_token_time is None
                    and now >= seq.ttft_deadline):
                events.append(self._evict(seq, "deadline"))
        return events

    def _step_guard(self):
        if self._watchdog is not None:
            return self._watchdog.armed("serve_step",
                                        timeout=self.step_timeout)
        return guarded("serve_step")

    def step(self) -> List[Dict[str, Any]]:
        """Land one unit of work (one prefill or one decode batch) and
        return its token events, inside the lifecycle guard: reap
        expired/cancelled requests first, arm the watchdog around the
        device work, recover from a hung step by rebuilding the jitted fns
        and re-admitting the running set (recompute-prefill).  Empty when
        idle AND no queued work remains.

        A unit is a LAUNCH (``tables``, ``h2d``, ``dispatch``: the step
        program is called and the copies of what the host will read are
        asked for) and, later, a LANDING (``device_wait``, ``logits_copy``,
        ``guard``, ``accept``).  The engine runs one unit ahead: before it
        lands the unit in flight it plans and launches the next one,
        whenever that plan can be made without the tokens in flight (a
        decode row takes its id from the previous program's output on the
        device), so the host's part of a step hides behind the device's.
        Each call still returns the events of exactly ONE unit, in the
        order the units were launched; the first call of a busy stretch
        launches two.  It does NOT run ahead when there is nothing to
        plan (``idle``), when growing a table would have to preempt
        (``preempt``: victims are chosen from landed state), after a
        landing raised (``fault``: the unit launched after it is dropped
        unread, its marks taken back, and quarantine / bisect / replay run
        with nothing in flight) and while the engine is not ``serving``
        (``drain``); ``stats()["ahead"]`` counts each.  Counters, token
        events and request-trace spans of a unit are booked once, at its
        landing.

        The call is one ``engine.step`` span (attributes ``step``, and of
        the LANDED unit ``kind``, ``rows``, ``bucket``, ``logits_fetched``,
        the model's counts; a decode unit also ``kv_blocks_live``,
        ``kv_blocks_table``; ``ahead_kind``: the kind launched ahead in
        this call, or None) whose children name where its host time goes —
        ``reap``, ``schedule``, ``tables``, ``h2d``, ``dispatch``,
        ``device_wait``, ``logits_copy``, ``guard``, ``accept``,
        ``gauges``, and the rare ``quarantine`` / ``recover``;
        ``stats()["phases"]`` sums them.

        Every unit has a number, and the spans say whose they are: a child
        that works for a unit carries ``unit`` beside ``step`` (the launch
        children the launched unit's, the landing children the landed
        unit's; ``reap`` and ``gauges`` work for none), the root ``unit``
        (landed) and ``ahead_unit``.  Two of those spans' ends are the
        unit's stamps (``_Unit.enqueued``, the end of its ``dispatch``, and
        ``done``, the end of its ``device_wait``) and the ledger sums what
        follows from them, a few float
        operations a landing (``stats()["units"]``): the host's wait for
        the unit; the unit's time on the device, ``done(n) -
        max(done(n-1), enqueued(n))``, exact unless the host came ``late``
        to this landing or the last (a wait of ``LATE_EPS_S`` or less
        found the device done already); and for a unit launched with
        nothing in flight how long the device had nothing of this engine's
        to run, ``enqueued(n) - done(n-1)``, by the reason nothing was
        launched ahead (``ahead_breaks``' words, and ``start``).  The call
        whose launch ended such an interval says so on its root:
        ``starved_t0``, ``starved_t1``, ``starved_why``."""
        with self._phase("engine.step") as root:
            self._step_root = root
            with self._phase("reap"):
                # what a landing outside step() produced (defrag, drain)
                events, self._early = self._early + self._reap(), []
            try:
                events += self._step_inner(root)
            except StepTimeout:
                with self._phase("recover"):
                    events += self._recover_from_hang()
            with self._phase("gauges"):
                self.steps += 1
                self._update_gauges()
        self._units["step_s"] += root.elapsed
        return events

    def _phase(self, name: str, unit: Optional[int] = None) -> span:
        """A span of this step: children share the root's ``step``, and
        one that works for a unit says which."""
        if unit is None:
            return span(name, step=self.steps)
        return span(name, step=self.steps, unit=unit)

    def _step_inner(self, root: span) -> List[Dict[str, Any]]:
        """Launch the next unit, land the one in flight.  The watchdog is
        armed around each launch and around the landing, so its deadline
        bounds one program's call (its trace and compile, when cold) or
        one program's wait, however many a call holds."""
        unit, self._in_flight = self._in_flight, None
        if unit is None:                  # a busy stretch begins
            with self._step_guard():
                unit = self._launch_next(None)
            if unit is None:
                root.set(kind="other", rows=0, bucket=0, ahead_kind=None)
                return []
        with self._step_guard():
            self._in_flight = ahead = self._launch_next(unit)
        root.set(ahead_kind=None if ahead is None else ahead.kind,
                 ahead_unit=None if ahead is None else ahead.number)
        with self._step_guard():
            return self._land(unit)

    def _count_ahead(self, name: str, n: int = 1) -> None:
        """One of ``stats()["ahead"]``'s sums and its ``serve.`` counter."""
        self._ahead[name] += n
        self._reg().counter(f"serve.{name}").inc(n)

    def _note_break(self, why: str) -> None:
        self._ahead["ahead_breaks"][why] += 1
        self._reg().counter(f"serve.ahead_breaks.{why}").inc()
        self._starve_why = why

    def _launch_next(self, prev: Optional[_Unit]) -> Optional[_Unit]:
        """Plan the next unit and launch it, ``prev`` (launched, unread)
        or nothing in flight.  None where there is nothing to launch, or
        nothing that may be launched ahead of ``prev``'s landing."""
        reg = self._reg()
        if prev is not None and (self._state != "serving"
                                 or prev.error is not None):
            self._note_break("drain" if prev.error is None else "fault")
            return None
        number = self._unit_no
        with self._phase("schedule", number) as planning:
            plan = self.sched.schedule(ahead=prev is not None)
            for victim in plan.preempted:
                reg.counter("serve.preemptions").inc()
                reg.emit("serve.preempt", request_id=victim.request_id,
                         generated=len(victim.output),
                         trace_id=victim.trace_id)
                now = float(self.clock())
                requesttrace.emit_span(reg, victim.trace_id,
                                       victim.request_id, "preempt",
                                       "preempt", now, now, self._proc)
            if plan.kind not in ("prefill", "decode"):
                planning.set(unit=None)           # it planned no unit
                if prev is not None:
                    self._note_break("preempt" if plan.kind == "wait"
                                     else "idle")
                else:
                    self._starve_why = "idle"
                return None
            # head-of-line stall: residents live on this engine but not
            # in this unit's batch wait the full unit out.  When the
            # served unit is induced work (a recompute prefill), their
            # stall is that cause's cost — the survivor decodes late
            # *because of* the failover, not by scheduler bad luck.
            stall = "stall"
            if plan.kind == "prefill" and plan.seqs[0].resume_why:
                stall = _RESUME_COMPONENT.get(plan.seqs[0].resume_why,
                                              "stall")
        if plan.kind == "prefill" and plan.seqs[0].queued is not None:
            # the request's wait, once: from submit() to the plan that
            # took it
            seq = plan.seqs[0]
            record_span("engine.request/queue", seq.queued, planning.start,
                        request_id=seq.request_id, unit=number)
            seq.queued = None
        unit = self._start(plan.kind, plan.seqs, plan.bucket, prev, number)
        unit.stall = stall
        self._unit_no += 1
        self._count_ahead("units_launched")
        if prev is not None:
            self._count_ahead("units_ahead")
        return unit

    def _start(self, kind: str, seqs: List[SequenceState], bucket: int,
               prev: Optional[_Unit], number: int) -> _Unit:
        """Launch a unit and move the scheduler's launch-time marks."""
        unit = self._launch(kind, seqs, bucket, prev, number)
        unit.marks = self.sched.mark_launched(kind, unit.seqs, unit.emits)
        if prev is None and unit.enqueued is not None:
            self._book_starved(unit)
        return unit

    def _book_starved(self, unit: _Unit) -> None:
        """A unit launched with nothing in flight ends an interval in which
        the device had nothing of this engine's to run: the ledger books it
        under the reason nothing was launched ahead, and the root of the
        call that is open says where it lay."""
        since, why = self._idle_since()
        gap = max(0.0, unit.enqueued - since)
        row = self._units["starved"][why]
        row[0] += 1
        row[1] += gap
        if self._step_root is not None:
            self._step_root.set(starved_t0=since, starved_t1=unit.enqueued,
                                starved_why=why)

    def _settle(self) -> List[Dict[str, Any]]:
        """Land what is in flight, with nothing launched after it: what
        ``begin_drain`` / ``defrag`` / ``stop`` do first, so that they act
        on landed state.  Returns the unit's events."""
        unit, self._in_flight = self._in_flight, None
        if unit is None:
            return []
        self._step_root = None            # no step() is open
        self._note_break("drain")
        return self._land(unit)

    def _recover_from_hang(self) -> List[Dict[str, Any]]:
        """Hung-step recovery: the watchdog already dumped every thread's
        stack.  Device work in flight is abandoned, every unit of it,
        read or not.  The pool is not protected any more — the step
        program consumes the page handles, and a step cut between the
        call and ``update_pages`` leaves them dead — so a lost pool is
        replaced by a zeroed one.  Rebuild the jitted fns and preempt the
        running set back to the queue: a preempted sequence starts over
        from the tokens that landed, so recompute-prefill rewrites their
        KV and replays them token-exact, whichever pool they land in."""
        self._jit_step = None
        self._decode_tracked = None
        self._prefill_tracked = {}
        self._proven.clear()
        self._in_flight = None
        self._starve_why, self._last_late = "fault", False
        victims = self.sched.preempt_all()
        self.cache.restore_held()     # their sequences are gone: freed
        self._rebuild_lost_pool()
        self.watchdog_restarts += 1
        reg = self._reg()
        reg.counter("serve.watchdog_restarts").inc()
        reg.emit("serve.watchdog_restart", step=self.steps,
                 victims=[s.request_id for s in victims])
        return []

    def has_work(self) -> bool:
        """Queued or running requests, a unit in flight, or events of a
        unit that landed outside ``step()`` still to hand out."""
        return (self._in_flight is not None or self.sched.has_work()
                or bool(self._early))

    def run(self, max_steps: Optional[int] = None) -> int:
        """Drive :meth:`step` until every submitted request finishes;
        returns the number of steps taken."""
        taken = 0
        while self.has_work():
            self.step()
            taken += 1
            if max_steps is not None and taken > max_steps:
                stuck = ([s.request_id for s in self.sched.running]
                         + [s.request_id for s in self.sched.waiting])
                raise RuntimeError(
                    f"engine did not drain in {max_steps} steps; stuck "
                    f"requests: {', '.join(stuck) or 'none'}")
        return taken

    # -- a unit: its launch and its landing --------------------------------
    # The step program consumes the page arrays (they are donated), so the
    # cache takes the new ones the moment the call returns: a failed,
    # probed or dropped unit DOES leave its page writes behind.  That is
    # safe because a unit writes only its own rows' slots at positions
    # that no row reads until a later unit is launched over them, and a
    # replay or a bisection probe of the same rows writes the same values
    # to the same slots.  So pages are adopted always; the scheduler's
    # marks move at the launch and are taken back when a unit is dropped
    # or faults.  What a culprit wrote is scrubbed when it is quarantined;
    # a pool that a failed call consumed is rebuilt (_rebuild_lost_pool).

    def _wants_logits(self, seqs: List[SequenceState]) -> bool:
        """Whether this unit's logits have a reader on the host: the
        fault seam, or a row whose request captures them."""
        return (self.step_fault is not None
                or any(s.capture_logits for s in seqs))

    def _apply_fault(self, unit: _Unit, finite: np.ndarray,
                     logits_np: Optional[np.ndarray]):
        """Fault seam + NaN guard, applied to every landed unit
        (bisection probes included — injected faults must re-fire on the
        subset that still contains the target).  The guard reads the
        flags the step program computed, a row each; where the seam is
        set it reads what the hook handed back instead.  A row whose
        request left while the unit was in flight has nobody to name."""
        kind, seqs = unit.kind, unit.seqs
        with self._phase("guard", unit.number):
            if self.step_fault is not None:
                out = self.step_fault(self, kind,
                                      [s.request_id for s in seqs],
                                      logits_np)
                if out is not None:
                    logits_np = np.asarray(out)
            if self.nan_guard:
                if self.step_fault is not None:
                    finite = np.isfinite(logits_np[:len(seqs)]).all(axis=-1)
                bad = [s.request_id for s, ok in zip(seqs, finite)
                       if not ok and s.state == RUNNING]
                if bad:
                    raise _NonfiniteLogits(bad)
        return logits_np

    def _launch(self, kind: str, seqs: List[SequenceState], bucket: int,
                prev: Optional[_Unit], number: int) -> _Unit:
        """The first half of a unit, one span a leg: ``tables`` (the
        rows' ids, positions, block tables and write slots; a decode row
        that ``prev`` samples for takes its id from ``prev``'s output on
        the device, by the row's ``src`` word), ``h2d`` (the inputs
        packed into one buffer with the unit's number and put once) and
        ``dispatch`` (the jitted call until it returns, the cache taking
        the new page handles — the old ones are dead by then — and the
        copies to the host asked for, so that they follow the program
        out).  Nothing here waits for the device, and no mark moves.  A
        launch that raises is kept in the unit and raised at its landing,
        where every fault is met.  ``enqueued`` is the end of
        ``dispatch``."""
        unit = _Unit(kind, list(seqs), bucket, number, float(self.clock()))
        reg = self._reg()
        try:
            with self._phase("tables", number):
                if kind == "prefill":
                    seq, rows, chunk = seqs[0], 1, bucket
                    unit.emits = [seq.pending is None]
                    ctx = seq.context()
                    L = len(ctx)
                    ids = np.zeros((1, bucket), np.int32)
                    ids[0, :L] = ctx
                    self._note_padding(L, bucket)
                    tables, slots = self._tables_and_slots(
                        [seq.request_id], [0], bucket)
                    inputs = (ids, np.zeros((1,), np.int32), L - 1, tables,
                              np.asarray([L], np.int32), slots)
                    src = None            # a prefill takes no id from it
                    fn = self._prefill_fn(bucket)
                else:
                    rows, chunk = self.max_seqs, 1
                    *inputs, src = self._decode_inputs(unit, prev)
                    fn = self._decode_fn()
                unit.fetch_logits = self._wants_logits(seqs)
            with self._phase("h2d", number):
                # a replay or a probe of a faulted unit carries the
                # unit's own number, and so draws the same
                packed = pack_step_inputs(*inputs, step=number % 2 ** 31,
                                          src=src)
                packed_d = jax.device_put(packed)
                reg.counter("serve.h2d_bytes").inc(packed.nbytes)
            with self._phase("dispatch", number) as sp:
                nxt, finite, logits, pages, aux, unit.carry = fn(
                    self._params, packed_d, self.cache.pages, self._key,
                    self._no_prev if prev is None else prev.carry,
                    rows=rows, chunk=chunk)
                self.cache.update_pages(pages)
                # the counts come with the outputs; the rest of aux stays.
                # What the host reads sets out as soon as the program is
                # done
                unit.out = ([nxt, finite, aux.get("counts", {})]
                            + ([logits] if unit.fetch_logits else []))
                unit.aux, unit.logits = aux, logits
                for a in jax.tree_util.tree_leaves(unit.out):
                    a.copy_to_host_async()
            unit.enqueued = sp.end
        except StepTimeout:
            raise
        except Exception as e:
            unit.error = e
        return unit

    def _decode_inputs(self, unit: _Unit, prev: Optional[_Unit]):
        seqs, B = unit.seqs, self.max_seqs
        enforce(len(seqs) <= B, f"{len(seqs)} decode rows > max_seqs {B}")
        unit.emits = [True] * len(seqs)
        self._note_padding(len(seqs), B)
        sids = [s.request_id for s in seqs] + [_PAD_SEQ] * (B - len(seqs))
        ids = np.zeros((B, 1), np.int32)
        positions = np.zeros((B,), np.int32)
        lens = np.zeros((B,), np.int32)
        src = np.full((B,), -1, np.int32)
        starts = [-1] * B
        # the rows of the unread unit that hold a sequence's newest token
        unread = ({} if prev is None else
                  {id(s): i for i, s in enumerate(prev.seqs)
                   if prev.emits[i]})
        for i, s in enumerate(seqs):
            row = unread.get(id(s), -1)
            if row >= 0:
                src[i] = row
            else:
                enforce(s.pending is not None,
                        f"{s.request_id}: decode row without a pending "
                        "token")
                ids[i, 0] = s.pending
            positions[i] = s.computed_len
            lens[i] = s.computed_len + 1      # includes the written token
            starts[i] = s.computed_len
        tables, slots = self._tables_and_slots(sids, starts, 1)
        self._count_paged_blocks(unit, lens, tables)
        return ids, positions, 0, tables, lens, slots, src

    def _arrive(self, unit: _Unit):
        """The landing up to the guard: host ``(next tokens, logits or
        None)`` of a launched unit, one span a leg: ``device_wait`` (until
        the device is done — the copy below would wait for the same),
        ``logits_copy`` (what is left of the device-to-host copy by then:
        the next tokens, a flag a row, the model's counts — and the
        ``[rows, vocab]`` float32 logits only where the launch asked for
        them; otherwise they stay where they are and go with the unit)
        and ``guard``.  Raises what the launch, the device, the fault
        seam or the NaN guard raised."""
        if unit.error is not None:
            raise unit.error
        reg = self._reg()
        with self._phase("device_wait", unit.number) as waited:
            try:
                jax.block_until_ready((unit.out[0], unit.logits))
            except StepTimeout:
                raise
            except Exception:
                # the program failed on the device: its page outputs, and
                # those of whatever was launched over them, are as dead
                # as its logits
                self.cache.drop_pages()
                raise
        self._note_done(unit, waited)
        with self._phase("logits_copy", unit.number):
            out = jax.device_get(unit.out)
            nxt_np, finite_np, unit.counts, *fetched = out
            reg.counter("serve.d2h_bytes").inc(sum(
                v.nbytes for v in jax.tree_util.tree_leaves(out)))
            if unit.fetch_logits:
                reg.counter("serve.logits_fetch_steps").inc()
                self._logits_fetch_steps += 1
        if self._step_root is not None:
            self._step_root.set(logits_fetched=unit.fetch_logits)
        self._proven.add(unit.program)
        return nxt_np, self._apply_fault(unit, finite_np,
                                         fetched[0] if fetched else None)

    def _idle_since(self):
        """``(since, why)``: when the host last saw the device finish a
        unit of this engine's, and why nothing was launched ahead of that
        landing; before the first unit, when the engine was built and
        ``"start"``."""
        if self._last_done is None:
            return self._born, "start"
        return self._last_done, self._starve_why

    def _note_done(self, unit: _Unit, waited: span) -> None:
        """The host has seen the device finish ``unit``: its ``done``
        stamp and what follows from it.  The unit's time on the device is
        counted from when the device could start it: when it finished the
        unit before, or when this one was handed over if that was later
        (it was launched with nothing in flight).  Where the host came
        late to this landing or to the last, one of the two ends is later
        than the device's own and the figure is no longer exact (too long
        for this unit, too short for the next: their sum still holds).  A
        unit launched ahead of a late landing may have found the device
        idle, for how long the host cannot say: it is counted."""
        unit.done, unit.wait_s = waited.end, waited.elapsed
        unit.late = waited.elapsed <= LATE_EPS_S
        unit.device_s = unit.done - max(self._idle_since()[0], unit.enqueued)
        unit.exact = not (unit.late or self._last_late)
        self._last_done, self._last_late = unit.done, unit.late
        if unit.late and self._in_flight is not None:
            self._units["host_late"] += 1

    def _land(self, unit: _Unit) -> List[Dict[str, Any]]:
        """The second half of a unit: wait for it, read it, guard it,
        accept its tokens.  A landing that raises is the fault boundary:
        see :meth:`_fault`."""
        try:
            nxt_np, logits_np = self._arrive(unit)
        except StepTimeout:
            raise                      # the watchdog owns this one
        except Exception as e:
            return self._fault(unit, e)
        # no replay of this unit any more: the window blocks that the
        # plan made over it let go of are free
        self.cache.release_held()
        return self._accept(unit, nxt_np, logits_np)

    def _fault(self, unit: _Unit, error: Exception) -> List[Dict[str, Any]]:
        """A landing raised (the launch, the device, the fault seam or
        the NaN guard).  The unit launched after it is dropped unread and
        both units' launch-time marks are taken back, so what follows
        runs with nothing in flight, on landed state, as the serial
        engine's fault path did: a lost pool is rebuilt (every row goes
        back through recompute-prefill); a program that never ran to its
        end is the engine's fault and the error propagates; otherwise the
        culprits are quarantined and a decode unit's survivors replayed,
        launch and landing back to back, under the unit's own number."""
        ahead, self._in_flight = self._in_flight, None
        self._starve_why = "fault"
        if ahead is not None:
            self.sched.unmark(ahead.marks)
            if ahead.kind == "prefill":
                self.sched.unadmit(ahead.seqs[0])
            self._unit_no = ahead.number       # its number is free again
            self._count_ahead("ahead_units_dropped")
            self._note_break("fault")
        self.cache.restore_held()
        self.sched.unmark(unit.marks)
        rebuilt = self._rebuild_lost_pool()
        if unit.program not in self._proven:
            raise error                # never ran: not a request's fault
        live = [s for s in unit.seqs if s.state == RUNNING]
        if rebuilt or not live:        # the engine's loss, not a row's
            return []
        survivors = self._quarantine_step(unit.kind, live, error,
                                          unit.number)
        if unit.kind == "prefill" or not survivors:
            return []
        # replay: the culprit rows are gone, every surviving row is
        # re-run with the same pending tokens — per-row paged attention
        # makes the survivors' logits identical to the un-faulted unit's,
        # and their tokens where they kept their rows (sampling draws a
        # row's noise by its place in the batch)
        replay = self._start("decode", survivors, 0, None, unit.number)
        replay.t0, replay.stall = unit.t0, unit.stall
        return self._land(replay)

    def _accept(self, unit: _Unit, nxt_np: np.ndarray,
                logits_np: Optional[np.ndarray]) -> List[Dict[str, Any]]:
        """Book a landed unit, once: its counters, its root-span
        attributes, the model's counts, its request-trace spans and its
        tokens.  A row whose request ended while the unit was in flight
        (an end-of-sequence token a unit earlier, a cancel, a deadline)
        is discarded: no token, no event; its blocks went when it ended."""
        reg, seqs, events = self._reg(), unit.seqs, []
        # on a request's waterfall a unit starts where the one before it
        # ended, if it was launched before that
        t0 = max(unit.t0, self._landed_at)
        with self._phase("accept", unit.number):
            if self._step_root is not None:
                self._step_root.set(kind=unit.kind, rows=len(seqs),
                                    bucket=unit.bucket, unit=unit.number,
                                    **unit.attrs)
            self._book_paged_blocks(unit)
            live = [s.state == RUNNING for s in seqs]
            self._note_aux(unit, live)
            for s, ok, emits in zip(seqs, live, unit.emits):
                if ok and emits:
                    s.in_flight -= 1
            if not all(live):
                self._count_ahead("ahead_rows_discarded",
                                  len(seqs) - sum(live))
            if unit.kind == "prefill":
                reg.counter("serve.prefills").inc()
                if live[0]:
                    events = self._accept_prefill(unit, t0, nxt_np,
                                                  logits_np)
            else:
                reg.counter("serve.decode_steps").inc()
                for i, s in enumerate(seqs):
                    if live[i]:
                        events.append(self._accept_token(
                            s, int(nxt_np[i]),
                            logits_np[i] if s.capture_logits else None,
                            first=False))
                # one batch-level decode span; the assembler amortizes the
                # unit across its residents to produce per-request decode
                # time
                requesttrace.emit_decode_span(
                    reg, [(s.request_id, s.trace_id)
                          for s, ok in zip(seqs, live) if ok], sum(live),
                    t0, float(self.clock()), self._proc)
        with self._phase("accept", unit.number):
            served = {s.request_id for s in seqs}
            if self._in_flight is not None \
                    and self._in_flight.kind == "prefill":
                # admitted after this unit was launched: it is no resident
                served.add(self._in_flight.seqs[0].request_id)
            stalled = [(s.request_id, s.trace_id)
                       for s in self.sched.running
                       if s.request_id not in served
                       and s.trace_id is not None]
            self._landed_at = float(self.clock())
            if stalled:
                requesttrace.emit_stall_span(reg, stalled, t0,
                                             self._landed_at, self._proc,
                                             component=unit.stall,
                                             cause=unit.kind)
        self._book_landing(unit)
        return events

    def _book_landing(self, unit: _Unit) -> None:
        """A landed unit into the ledger's sums, once: under its kind and,
        a prefill, under its bucket."""
        rows = [self._units["by_kind"][unit.kind]]
        if unit.kind == "prefill":
            rows.append(self._units["prefill_by_bucket"].setdefault(
                unit.bucket, _unit_sums()))
        for row in rows:
            row["units"] += 1
            row["rows"] += len(unit.seqs)
            row["wait_s"] += unit.wait_s
            if unit.exact:
                row["device_s"] += unit.device_s
            else:
                row["units_bound"] += 1
                row["device_s_bound"] += unit.device_s

    def _accept_prefill(self, unit: _Unit, t0: float, nxt_np, logits_np):
        seq, reg = unit.seqs[0], self._reg()
        if seq.trace_id is not None:
            # the (re-)prefill plus the queue wait before it; a
            # recompute's wait is attributed to its cause, not "queue"
            comp = _RESUME_COMPONENT.get(seq.resume_why, "prefill")
            t_q0 = seq.trace_enqueued
            if t_q0 is None:
                t_q0 = seq.arrival
            if t0 > t_q0:
                requesttrace.emit_span(
                    reg, seq.trace_id, seq.request_id, "queue",
                    "queue" if seq.resume_why is None else comp,
                    t_q0, t0, self._proc)
            requesttrace.emit_span(reg, seq.trace_id, seq.request_id,
                                   "prefill", comp, t0,
                                   float(self.clock()), self._proc,
                                   bucket=unit.bucket)
        seq.resume_why = None
        seq.trace_enqueued = None
        if not unit.emits[0]:
            # recompute prefill after preemption: the next token was
            # already sampled (and streamed) before eviction — only
            # the KV was rebuilt; nothing new to emit
            return []
        return [self._accept_token(
            seq, int(nxt_np[0]),
            logits_np[0] if seq.capture_logits else None, first=True)]

    def _rebuild_lost_pool(self) -> bool:
        """If a step program consumed the pool and handed none back (the
        call raised after its inputs were donated, or a hung step was cut
        inside it), start over with a zeroed pool and send the running
        set back through recompute-prefill.  True when it did."""
        if not self.cache.pages_lost():
            return False
        self.cache.reset_pages()
        victims = self.sched.preempt_all()
        self.pool_rebuilds += 1
        reg = self._reg()
        reg.counter("serve.pool_rebuilds").inc()
        reg.emit("serve.pool_rebuild", step=self.steps,
                 victims=[s.request_id for s in victims])
        return True

    def _tables_and_slots(self, sids, starts, chunk: int):
        """A step's block tables and write slots, an array of each a kind
        of layer (one, for every model whose layers are of one kind)."""
        return (self.cache.step_tables(sids, self.sched.max_blocks_per_seq),
                self.cache.step_slots(sids, starts, chunk))

    def _count_paged_blocks(self, unit: _Unit, lens: np.ndarray,
                            tables: List[np.ndarray]) -> None:
        """How much of a decode unit's block tables is live: the pages
        its rows hold against rows launched x table width, which is what
        a kernel that walked the whole table would visit.  Over a cache
        with a pool a kind of layer that is the first kind's, and each
        kind's blocks under the unit's rows are counted beside it.
        Counted at the launch, where the tables are; booked at the
        landing (:meth:`_book_paged_blocks`)."""
        if len(self.cache.pools) > 1:
            for kind, pool in self.cache.pools.items():
                unit.blocks[kind] = sum(
                    len(pool.tables.get(s.request_id, ()))
                    for s in unit.seqs)
        unit.attrs.update(
            kv_blocks_live=int(np.sum(-(-lens // self.cache.block_size))),
            kv_blocks_table=tables[0].size)

    def _book_paged_blocks(self, unit: _Unit) -> None:
        """``serve.paged_blocks_live`` / ``serve.paged_blocks_table`` and,
        a kind, ``serve.kv_<kind>_blocks_live`` /
        ``serve.kv_<kind>_blocks_freed`` of a landed decode unit."""
        if not unit.attrs:
            return
        reg = self._reg()
        for kind, held in unit.blocks.items():
            reg.counter(f"serve.kv_{kind}_blocks_live").inc(held)
            self._kv_live[kind] = self._kv_live.get(kind, 0) + held
            pool = self.cache.pools[kind]
            if pool.window is not None:
                freed = reg.counter(f"serve.kv_{kind}_blocks_freed")
                freed.inc(pool.freed_behind - freed.value)
        live, table = (unit.attrs["kv_blocks_live"],
                       unit.attrs["kv_blocks_table"])
        reg.counter("serve.paged_blocks_live").inc(live)
        reg.counter("serve.paged_blocks_table").inc(table)
        self._paged_blocks["live"] += live
        self._paged_blocks["table"] += table

    def _note_aux(self, unit: _Unit, live: List[bool]) -> None:
        """Book what a landed unit handed out beside its logits (nothing
        for a model without ``aux``).  The model's ``serving_counts``
        says which counters the counts add to and which gauges they set;
        the engine books them in the registry, sums them for
        ``stats()["model_counts"]`` and sets the counters on the step's
        span under the name after their last dot.  A captured request
        keeps its rows of the per-token arrays."""
        aux, kind = unit.aux, unit.kind
        if not aux:
            return
        if unit.counts:
            booked = self.model.serving_counts(unit.counts, kind)
            reg, mine = self._reg(), self._model_counts
            for name, n in booked.get("counters", {}).items():
                reg.counter(name).inc(n)
                mine["counters"][name] = mine["counters"].get(name, 0) + n
            for name, v in booked.get("gauges", {}).items():
                reg.gauge(name).set(v)
                g = mine["gauges"].setdefault(
                    name, {"last": None, "sum": 0.0, "steps": 0})
                g.update(last=v, sum=g["sum"] + v, steps=g["steps"] + 1)
            if self._step_root is not None:
                self._step_root.set(**{
                    name.rsplit(".", 1)[-1]: n
                    for name, n in booked.get("counters", {}).items()})
        captured = [(i, s) for i, s in enumerate(unit.seqs)
                    if s.capture_logits and live[i]]
        if aux.get("per_token") and captured:
            host = {k: np.asarray(v) for k, v in aux["per_token"].items()}
            for i, s in captured:
                if kind == "prefill":      # a re-prefill covers it all again
                    n = len(s.context())
                    s.per_token = [{k: v[i, :n] for k, v in host.items()}]
                else:
                    s.per_token.append({k: v[i, :1]
                                        for k, v in host.items()})
        # a re-prefill after preemption emits no logits row (the token
        # was sampled before eviction): nothing of its own to keep
        emits = [(i, s) for i, s in captured if unit.emits[i]]
        if aux.get("per_logit") and emits:
            host = {k: np.asarray(v) for k, v in aux["per_logit"].items()}
            for i, s in emits:
                s.per_logit.append({k: v[i] for k, v in host.items()})

    # -- poisoned-request quarantine ---------------------------------------
    def _probe(self, seqs: List[SequenceState], number: int) -> bool:
        """Re-run the decode unit on a subset, launch and landing back to
        back; True when it faults.  A probe rewrites its rows' pending
        slots with the values already there and moves no mark, so probing
        is free to repeat."""
        try:
            self._arrive(self._launch("decode", seqs, 0, None, number))
        except StepTimeout:
            raise
        except Exception:
            self._rebuild_lost_pool()
            return True
        return False

    def _bisect(self, seqs: List[SequenceState],
                number: int) -> List[SequenceState]:
        """Find the faulting sequence(s) by halving.  A passing half is
        exonerated (faults here are deterministic per-row).  When the
        whole group faults but neither half does, the fault is an
        interaction — quarantine the whole group rather than loop."""
        if len(seqs) == 1:
            return seqs
        mid = len(seqs) // 2
        left, right = seqs[:mid], seqs[mid:]
        culprits: List[SequenceState] = []
        if self._probe(left, number):
            culprits += self._bisect(left, number)
        if self._probe(right, number):
            culprits += self._bisect(right, number)
        return culprits or seqs

    def _quarantine_step(self, kind: str, seqs: List[SequenceState],
                         error: Exception,
                         number: int) -> List[SequenceState]:
        """Fault-boundary handler: identify the culprit rows (probes run
        under the faulted unit's ``number``), evict each with
        ``reason="poisoned"`` and a durable record, return the surviving
        sequences for replay."""
        with self._phase("quarantine", number):
            t0 = float(self.clock())
            if isinstance(error, _NonfiniteLogits):
                bad = set(error.request_ids)
                culprits = [s for s in seqs if s.request_id in bad]
            elif kind == "prefill" or len(seqs) == 1:
                culprits = list(seqs)
            else:
                rebuilds = self.pool_rebuilds
                culprits = self._bisect(seqs, number)
                if self.pool_rebuilds != rebuilds:
                    # a probe lost the pool: every row is queued for
                    # recompute, and the fault will show again there
                    return []
            for seq in culprits:
                self._quarantine(seq, error, kind)
            # the bisect stalls every row in the faulted batch — attribute
            # that time to quarantine for culprits and survivors alike
            t1 = float(self.clock())
            reg = self._reg()
            for seq in seqs:
                requesttrace.emit_span(reg, seq.trace_id, seq.request_id,
                                       "quarantine_bisect", "quarantine",
                                       t0, t1, self._proc)
            return [s for s in seqs if s not in culprits]

    def _quarantine(self, seq: SequenceState, error: Exception,
                    kind: str) -> None:
        # before the blocks go back to the allocator: whatever the culprit
        # wrote (non-finite K/V, maybe) must not meet their next owner
        self.cache.scrub_seq(seq.request_id)
        self.sched.evict(seq, "poisoned")
        self.lifecycle_counts["poisoned"] += 1
        record = {"request_id": seq.request_id, "reason": "poisoned",
                  "step_kind": kind, "error": repr(error),
                  "engine_step": self.steps,
                  "prompt_len": len(seq.prompt),
                  "generated": len(seq.output),
                  "output": list(seq.output),
                  "trace_id": seq.trace_id,
                  "time": float(self.clock())}
        self.quarantined[seq.request_id] = record
        reg = self._reg()
        reg.counter("serve.poisoned").inc()
        reg.emit("serve.quarantine", **record)
        self._trace_end(seq, "poisoned")
        if self.run_dir is not None:
            qdir = os.path.join(self.serve_dir(), "quarantine")
            os.makedirs(qdir, exist_ok=True)
            fname = re.sub(r"[^\w.-]", "_", seq.request_id) + ".json"
            fsio.atomic_write_bytes(
                os.path.join(qdir, fname),
                json.dumps(record, indent=1).encode())
        event = {"request_id": seq.request_id, "token": None,
                 "finished": True, "reason": "poisoned"}
        if seq.on_token is not None:
            self._dispatch_callback(seq.on_token, event, seq)

    def _accept_token(self, seq: SequenceState, token: int, logits_row,
                      first: bool) -> Dict[str, Any]:
        now = float(self.clock())
        seq.output.append(token)
        seq.pending = token
        reg = self._reg()
        if first:
            seq.first_token_time = now
            ttft = (now - seq.arrival) * 1e3
            reg.histogram("serve.ttft_ms").observe(ttft)
            self._ttft_ms.append(ttft)
        elif seq.last_token_time is not None:
            tpot = (now - seq.last_token_time) * 1e3
            reg.histogram("serve.tpot_ms").observe(tpot)
            self._tpot_ms.append(tpot)
        seq.last_token_time = now
        reg.counter("serve.tokens").inc()
        if logits_row is not None:
            # a copy: a view would keep the whole batch's array alive
            seq.logits.append(np.array(logits_row))
        reason = seq.should_finish()
        if reason is not None:
            self.sched.complete(seq, reason)
            reg.counter("serve.finished").inc()
            reg.emit("serve.finish", request_id=seq.request_id,
                     reason=reason, generated=len(seq.output),
                     preemptions=seq.preemptions, trace_id=seq.trace_id)
            self._trace_end(seq, reason)
        event = {"request_id": seq.request_id, "token": token,
                 "finished": reason is not None, "reason": reason}
        if seq.on_token is not None:
            self._dispatch_callback(seq.on_token, event, seq)
        return event

    # -- decoupled token callbacks ----------------------------------------
    def _dispatch_callback(self, cb: Callable, event: Dict[str, Any],
                           seq: Optional[SequenceState] = None) -> None:
        if self._cb_queue is None:
            self._cb_queue = queue.Queue()
            self._cb_thread = threading.Thread(
                target=self._cb_worker, name="ptpu-serve-callbacks",
                daemon=True)
            self._cb_thread.start()
        self._cb_dispatched += 1
        self._cb_queue.put((cb, event,
                            None if seq is None else seq.trace_id))

    def _cb_worker(self) -> None:
        while True:
            item = self._cb_queue.get()
            try:
                if item is _CB_STOP:
                    return
                cb, event, trace_id = item
                cb_t0 = float(self.clock())
                try:
                    cb(event["request_id"], event["token"],
                       event["finished"])
                except Exception as e:  # consumer bug must not kill serving
                    self._cb_errors += 1
                    self._last_callback_error = \
                        f"{event['request_id']}: {e!r}"
                    reg = self._reg()
                    reg.counter("serve.callback_errors").inc()
                    reg.emit("serve.callback_error",
                             request_id=event["request_id"], error=repr(e))
                    from ..framework.log import vlog
                    vlog(0, "serving: on_token callback failed for %s: %r",
                         event["request_id"], e)
                requesttrace.emit_span(self._reg(), trace_id,
                                       event["request_id"], "callback",
                                       "callback", cb_t0,
                                       float(self.clock()), self._proc)
            finally:
                self._cb_queue.task_done()

    def _stop_callbacks(self, timeout: Optional[float] = None) -> bool:
        """Stop the callback thread after it drains the queue; True when
        it exited within the timeout (or was never started)."""
        if self._cb_thread is None:
            return True
        self._cb_queue.put(_CB_STOP)
        self._cb_thread.join(timeout=timeout)
        alive = self._cb_thread.is_alive()
        if not alive:
            self._cb_thread = None
            self._cb_queue = None
        return not alive

    def drain_callbacks(self, timeout: Optional[float] = None) -> bool:
        """Block until every queued on_token callback ran (tests); True
        when drained."""
        if self._cb_queue is None:
            return True
        deadline = None if timeout is None else time.monotonic() + timeout
        while self._cb_queue.unfinished_tasks:
            if deadline is not None and time.monotonic() > deadline:
                return False
            time.sleep(0.005)
        return True

    # -- results ------------------------------------------------------------
    def _request_state(self, request_id: str) -> str:
        """Human-readable scheduler state for timeout/stuck messages."""
        for seq in self.sched.running:
            if seq.request_id == request_id:
                return (f"state=running, generated={len(seq.output)}/"
                        f"{seq.max_new_tokens}, "
                        f"computed_len={seq.computed_len}")
        for pos, seq in enumerate(self.sched.waiting):
            if seq.request_id == request_id:
                return (f"state={seq.state}, queue_position={pos}, "
                        f"queue_depth={len(self.sched.waiting)}")
        return "state=unknown (never submitted?)"

    def collect(self, request_id: str,
                max_steps: Optional[int] = None,
                timeout: Optional[float] = None) -> Dict[str, Any]:
        """Drive the engine until ``request_id`` finishes; return its
        result record.  ``timeout`` (seconds, wall clock) bounds the
        wait — on expiry raises :class:`CollectTimeout` naming the
        request's current scheduler state instead of spinning forever."""
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        while request_id not in self.sched.finished:
            enforce(self.has_work(),
                    f"{request_id}: unknown request (never submitted?)")
            if deadline is not None and time.monotonic() >= deadline:
                raise CollectTimeout(
                    f"{request_id}: not finished after {timeout}s "
                    f"({self._request_state(request_id)})")
            self.step()
            if max_steps is not None:
                max_steps -= 1
                enforce(max_steps >= 0, f"{request_id}: step budget spent")
        seq = self.sched.finished[request_id]
        n = len(seq.output)
        tpot = None
        if (n > 1 and seq.first_token_time is not None
                and seq.last_token_time is not None):
            tpot = (seq.last_token_time - seq.first_token_time) / (n - 1)
        out = {"request_id": request_id, "tokens": list(seq.output),
               "finish_reason": seq.finish_reason,
               "preemptions": seq.preemptions,
               "ttft_ms": (None if seq.first_token_time is None else
                           (seq.first_token_time - seq.arrival) * 1e3),
               "tpot_ms": None if tpot is None else tpot * 1e3}
        if seq.capture_logits:
            out["logits"] = list(seq.logits)
            if seq.per_token:
                # what the model hands out a token, (cached tokens, ...)
                out["per_token"] = {
                    k: np.concatenate([c[k] for c in seq.per_token])
                    for k in seq.per_token[0]}
            if seq.per_logit:
                # what the model hands out a logits row, (rows kept, ...)
                out["per_logit"] = {
                    k: np.stack([c[k] for c in seq.per_logit])
                    for k in seq.per_logit[0]}
        return out

    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: int = 32,
                 eos_token_id: Optional[int] = None) -> List[List[int]]:
        """Batch convenience: submit every prompt, drain, return the
        generated token lists in submit order."""
        rids = [self.submit(p, max_new_tokens=max_new_tokens,
                            eos_token_id=eos_token_id) for p in prompts]
        self.run()
        return [self.collect(r)["tokens"] for r in rids]

    # -- graceful drain / resume -------------------------------------------
    @property
    def state(self) -> str:
        """``serving`` | ``draining`` | ``stopped`` — mirrored on
        ``/healthz`` (503 once not ``serving``)."""
        return self._state

    def begin_drain(self) -> None:
        """Stop admission without blocking: new ``submit()`` calls are
        refused, ``/healthz`` goes 503 ``draining``, but already-admitted
        work keeps stepping.  The unit in flight lands first (its events
        come back with the next ``step()``'s), and an engine that is not
        ``serving`` launches nothing ahead of a landing.  Idempotent;
        ``drain()`` calls it first."""
        if self._state != "serving":
            return
        self._early += self._settle()
        self._state = "draining"
        self.sched.admission_open = False
        c = self.sched.counts()
        self._reg().emit("serve.drain_begin", running=c["running"],
                         waiting=c["waiting"])

    def drain(self, timeout: Optional[float] = None,
              spill_path: Optional[str] = None) -> Dict[str, Any]:
        """Graceful shutdown: stop admission, finish what fits inside
        ``timeout`` (default ``PTPU_SERVE_DRAIN_SECS``), spill the rest
        to ``spill_path`` (default
        ``<run_dir>/serve/replica-<i>/spill.json``) as a JSON file a
        fresh engine can :meth:`resume` from, stop the callback thread,
        and mark the engine ``stopped``.  The report carries the spill
        records inline (``"spilled_records"``) so a fleet router can
        migrate them without re-reading the file."""
        if timeout is None:
            timeout = default_drain_secs()
        self.begin_drain()
        hard = time.monotonic() + float(timeout)
        timed_out = False
        finished = 0
        while (self.sched.running
               or any(s.output for s in self.sched.waiting)):
            if time.monotonic() >= hard:
                timed_out = True
                break
            before = len(self.sched.finished)
            self.step()
            finished += len(self.sched.finished) - before
        # spill whatever is still live — running sequences that ran out
        # of time spill too (their generated tokens ride along, resume
        # recomputes their KV and continues decoding)
        leftovers = list(self.sched.running) + list(self.sched.waiting)
        spilled = []
        for seq in leftovers:
            spilled.append({"request_id": seq.request_id,
                            "prompt": list(seq.prompt),
                            "output": list(seq.output),
                            "max_new_tokens": seq.max_new_tokens,
                            "eos_token_id": seq.eos_token_id,
                            "preemptions": seq.preemptions,
                            # trace context survives the spill; ownership
                            # transfers to whichever engine resumes it
                            "trace_id": seq.trace_id,
                            "trace_owner": seq.request_id in
                            self._trace_owned,
                            "resume_why": "migration"})
            self._trace_owned.discard(seq.request_id)
            self.sched.evict(seq, "spilled")
            self.lifecycle_counts["spilled"] += 1
            self._reg().counter("serve.spilled").inc()
        if spilled:
            if spill_path is None and self.run_dir is not None:
                os.makedirs(self.serve_dir(), exist_ok=True)
                spill_path = os.path.join(self.serve_dir(), "spill.json")
            enforce(spill_path is not None,
                    "drain spilled requests but no spill_path was given "
                    "and the engine has no run_dir")
            fsio.atomic_write_bytes(
                spill_path,
                json.dumps({"version": 1, "spilled": spilled},
                           indent=1).encode())
        callbacks_stopped = self._stop_callbacks(timeout=5.0)
        self._state = "stopped"
        self._reg().emit("serve.drain_end", finished=finished,
                         spilled=len(spilled), timed_out=timed_out)
        self._update_gauges()
        return {"finished": finished, "spilled": len(spilled),
                "spill_path": spill_path if spilled else None,
                "spilled_records": spilled,
                "timed_out": timed_out,
                "callbacks_stopped": callbacks_stopped}

    def admit_record(self, record: Dict[str, Any]) -> str:
        """Admit one spill-format record (``request_id`` / ``prompt`` /
        ``output`` / ``max_new_tokens`` / ``eos_token_id``) into this
        serving engine.  The generated ``output`` tail is preserved and
        its newest token becomes ``pending``, so the recompute-prefill
        path rebuilds the KV and decoding continues **token-exact** —
        the seam both :meth:`resume` and the fleet router's failover
        re-submission go through.  Returns the request id.

        Idempotent on ``request_id``: a record the engine already holds
        (running, waiting or finished) is NOT re-admitted — the router's
        crash recovery may race a re-dispatch against a replica that
        still owns the stream, and a duplicate sequence would double-
        schedule it."""
        enforce(self._state == "serving",
                f"admit_record() needs a serving engine "
                f"(state={self._state})")
        rid = record["request_id"]
        if rid in self.sched.finished or any(
                s.request_id == rid for s in
                list(self.sched.running) + list(self.sched.waiting)):
            self._reg().counter("serve.readmit_dupes").inc()
            return rid
        seq = SequenceState(
            request_id=record["request_id"],
            prompt=[int(t) for t in record["prompt"]],
            max_new_tokens=int(record["max_new_tokens"]),
            eos_token_id=record.get("eos_token_id"),
            arrival=float(self.clock()), queued=time.perf_counter(),
            capture_logits=self.capture_logits)
        seq.output = [int(t) for t in record.get("output", [])]
        seq.pending = seq.output[-1] if seq.output else None
        seq.preemptions = int(record.get("preemptions", 0))
        # trace context (ISSUE 18): keep the record's trace_id so the
        # assembled waterfall stitches across engines.  An explicit
        # ``"trace_id": None`` is a deliberate decision (disabled or
        # sampled out at the router) and must survive the process
        # boundary; only a record WITHOUT the key (pre-tracing spill,
        # direct admit) gets an engine-owned trace minted here
        if "trace_id" in record:
            seq.trace_id = record["trace_id"]
            if seq.trace_id is not None and record.get("trace_owner"):
                self._trace_owned.add(rid)
        else:
            seq.trace_id = requesttrace.mint_trace_id(rid)
            if seq.trace_id is not None:
                self._trace_owned.add(rid)
                self._reg().emit("trace.request", trace_id=seq.trace_id,
                                 request_id=rid, t0=seq.arrival,
                                 prompt_len=len(seq.prompt),
                                 proc=self._proc)
        if seq.output:
            seq.resume_why = record.get("resume_why") or "failover"
        self.sched.submit(seq)
        self._submit_order.append(seq.request_id)
        self._update_gauges()
        return seq.request_id

    def resume(self, spill_path: Optional[str] = None) -> List[str]:
        """Re-admit a drain spill file into THIS (fresh, serving)
        engine.  Sequences resume exactly where they left off: generated
        output is preserved and the newest token becomes ``pending``, so
        the recompute-prefill path rebuilds the KV and decoding
        continues token-exact.  Returns the resumed request ids.

        Without ``spill_path`` the engine reads its namespaced
        ``<run_dir>/serve/replica-<i>/spill.json``, falling back to the
        pre-ISSUE-16 ``<run_dir>/serve_spill.json`` so old run dirs
        stay resumable."""
        enforce(self._state == "serving",
                f"resume() needs a serving engine (state={self._state})")
        if spill_path is None:
            enforce(self.run_dir is not None,
                    "resume() without a spill_path needs a run_dir")
            spill_path = os.path.join(self.serve_dir(), "spill.json")
            if not os.path.exists(spill_path):
                legacy = os.path.join(self.run_dir, "serve_spill.json")
                enforce(os.path.exists(legacy),
                        f"no spill file at {spill_path} or {legacy}")
                spill_path = legacy
        payload = json.loads(fsio.read_bytes(spill_path).decode())
        enforce(payload.get("version") == 1,
                f"unknown spill-file version {payload.get('version')!r}")
        return [self.admit_record(rec) for rec in payload["spilled"]]

    # -- observability ------------------------------------------------------
    def _note_padding(self, real: int, total: int) -> None:
        """One padded launch (prefill bucket or fixed decode batch):
        ``real`` of ``total`` token slots carried actual work.  Keeps
        the cumulative ``serve.padding_frac`` gauge current."""
        real = max(0, int(real))
        total = max(real, int(total))
        self._pad_real_tokens += real
        self._pad_slot_tokens += total
        reg = self._reg()
        reg.counter("serve.tokens_real").inc(real)
        reg.counter("serve.tokens_padded").inc(total - real)
        if self._pad_slot_tokens:
            reg.gauge("serve.padding_frac").set(
                1.0 - self._pad_real_tokens / self._pad_slot_tokens)

    def padding_frac(self) -> float:
        """Cumulative fraction of launched token slots that were pad
        (0.0 before any launch)."""
        if not self._pad_slot_tokens:
            return 0.0
        return 1.0 - self._pad_real_tokens / self._pad_slot_tokens

    def _update_gauges(self) -> None:
        reg = self._reg()
        c = self.sched.counts()
        reg.gauge("serve.queue_depth").set(float(self.sched.queue_depth))
        reg.gauge("serve.waiting").set(float(c["waiting"]))
        reg.gauge("serve.running").set(float(c["running"]))
        reg.gauge("serve.kv_occupancy").set(self.cache.occupancy())
        reg.gauge("serve.kv_blocks_used").set(
            float(self.cache.blocks_used()))
        reg.gauge("serve.kv_pool_bytes").set(float(self.cache.pool_bytes()))

    def _ledger(self) -> Dict[str, Any]:
        """``stats()["units"]``: the ledger's sums as they stand, on
        ``time.perf_counter()``.  ``by_kind`` / ``prefill_by_bucket``:
        landed units, their rows, the host's wait for them (``wait_s``)
        and their time on the device (``device_s`` over the exact ones,
        ``device_s_bound`` over the ``units_bound`` others); ``starved``:
        ``{why: [intervals, seconds]}`` before units launched with nothing
        in flight; ``host_late``: units launched ahead of a landing the
        host came late to (the gap before such a unit is not bounded from
        here: while this stays 0 the host is not what the device waits
        for); ``step_s``: seconds inside ``step()``.  ``now_s`` stamps the
        snapshot and ``last_done_s`` is up to where the sums account: what
        lies between the two is the unit in flight, which is booked when
        it lands, or, with nothing in flight, a starved interval that is
        still open, booked when the next unit is launched: ``starving``
        is its reason then, else None."""
        u = self._units
        since, why = self._idle_since()
        return {
            "by_kind": {k: dict(v) for k, v in u["by_kind"].items()},
            "prefill_by_bucket": {k: dict(v) for k, v
                                  in u["prefill_by_bucket"].items()},
            "starved": {k: list(v) for k, v in u["starved"].items()},
            "host_late": u["host_late"], "step_s": u["step_s"],
            "eps_s": LATE_EPS_S, "now_s": time.perf_counter(),
            "last_done_s": since,
            "starving": None if self._in_flight is not None else why}

    def stats(self) -> Dict[str, Any]:
        """Engine-state snapshot for ``/statusz`` (counts the registry
        cannot derive: pool geometry, scheduler queues, shed state, the
        resilience section)."""
        c = self.sched.counts()
        leak = self.cache.leak_report()
        # where engine.step's host time went, from the span tree (which is
        # the process's: engines that share a process share these sums)
        phases = {path.split("/")[1]: row
                  for path, row in span_tree_totals().items()
                  if path.startswith("engine.step/")
                  and path.count("/") == 1}
        return {
            "steps": self.steps,
            "phases": phases,
            # units launched, how many of them while another was unread,
            # what running ahead cost and why it did not engage
            "ahead": dict(self._ahead,
                          ahead_breaks=dict(self._ahead["ahead_breaks"])),
            "units": self._ledger(),
            "logits_fetch_steps": self._logits_fetch_steps,
            "replica_id": self.replica_id,
            "queue_depth": self.sched.queue_depth,
            "waiting": c["waiting"],
            "running": c["running"],
            "finished": c["finished"],
            "preemptions": c["preemptions"],
            "max_seqs": self.max_seqs,
            "max_model_len": self.max_model_len,
            "kv_block_size": self.cache.block_size,
            "kv_bytes_per_token": self.cache.bytes_per_token(),
            "model_gauges": dict(self._model_gauges),
            "model_counts": {
                "counters": dict(self._model_counts["counters"]),
                "gauges": {k: dict(v) for k, v
                           in self._model_counts["gauges"].items()}},
            "paged_blocks": dict(self._paged_blocks),
            "kv_blocks": {"total": leak["num_blocks"],
                          "used": leak["num_used"],
                          "occupancy": self.cache.occupancy(),
                          "high_water": leak["high_water"],
                          "leaked": leak["leaked_blocks"],
                          "balanced": leak["balanced"]},
            "kv_pools": {
                kind: {"total": pool.num_blocks,
                       "used": pool.allocator.num_used,
                       "high_water": pool.allocator.high_water,
                       "block_bytes": self.cache.block_bytes(kind),
                       "freed_behind": pool.freed_behind,
                       "blocks_live": self._kv_live.get(kind, 0)}
                for kind, pool in self.cache.pools.items()},
            "load_shed": {"active": self.should_shed(),
                          "queue_threshold": self.shed_queue_depth},
            "padding": {"real_tokens": self._pad_real_tokens,
                        "padded_slots": self._pad_slot_tokens,
                        "frac": self.padding_frac()},
            "slo": {"ttft_ms": {"p50": _pctl(self._ttft_ms, 50),
                                "p99": _pctl(self._ttft_ms, 99),
                                "samples": len(self._ttft_ms)},
                    "tpot_ms": {"p50": _pctl(self._tpot_ms, 50),
                                "p99": _pctl(self._tpot_ms, 99),
                                "samples": len(self._tpot_ms)}},
            "resilience": {
                "state": self._state,
                "deadline_misses": self.lifecycle_counts["deadline"],
                "cancelled": self.lifecycle_counts["cancelled"],
                "poisoned": self.lifecycle_counts["poisoned"],
                "spilled": self.lifecycle_counts["spilled"],
                "watchdog_restarts": self.watchdog_restarts,
                "pool_rebuilds": self.pool_rebuilds,
                "quarantined": sorted(self.quarantined),
                "callbacks": {"dispatched": self._cb_dispatched,
                              "errors": self._cb_errors,
                              "last_error": self._last_callback_error},
            },
        }

    def defrag(self) -> bool:
        """Compact the KV pool (see ``PagedKVCache.defrag``), once the
        unit in flight has landed (its events come back with the next
        ``step()``'s): the tables renumbered here are the landed ones."""
        self._early += self._settle()
        return self.cache.defrag()

    def start_status_server(self, port: int = 0, host: str = "0.0.0.0"):
        """Expose serving SLOs on the PR 5 monitor; returns the server
        (``.port`` holds the bound port)."""
        from ..observability.monitor import StatusServer
        self.status_server = StatusServer(port=port, host=host,
                                          registry=self._registry,
                                          engine=self).start()
        return self.status_server

    def stop(self) -> None:
        """Land what is in flight (its tokens reach the callbacks and
        ``collect()``), then stop the callback thread, the watchdog and
        the status server."""
        if self._state != "stopped":
            self._early += self._settle()
        self._stop_callbacks(timeout=1.0)
        if self._owns_watchdog and self._watchdog is not None:
            self._watchdog.close()
            self._watchdog = None
        if self.status_server is not None:
            self.status_server.stop()
            self.status_server = None
        self._state = "stopped"
