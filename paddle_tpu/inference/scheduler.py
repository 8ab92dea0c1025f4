"""Continuous-batching scheduler (ISSUE 6).

Static batching pads every request to the batch's slowest member; a
serving engine under ragged traffic wastes most of its step time on
finished or not-yet-started rows.  Continuous batching re-forms the
batch **every step**: finished sequences leave immediately, waiting
sequences are admitted the moment KV blocks free up, and the decode
batch only ever contains live rows (PAPERS.md: *ClusterFusion++*'s
per-step decode unit; the vLLM-style admit/evict loop on top).

This module is the pure-host half: no jax, no device traffic — just
sequence state machines and block accounting against
``kv_cache.BlockAllocator``.  That makes every policy decision unit
testable with a fake clock and a tiny pool (``tests/test_serving.py``).

Sequence lifecycle::

    WAITING --admit(prefill)--> RUNNING --eos/max_tokens--> FINISHED
       ^                          |                            ^
       +------- PREEMPTED <-- OOM on next-token block          |
       |                                                       |
       +--- cancel / deadline / poisoned / spilled (evict) ----+

Terminal reasons beyond ``eos`` / ``max_new_tokens`` (ISSUE 15):
``cancelled`` and ``deadline`` land through the engine's between-steps
reaper, ``poisoned`` through the fault-boundary quarantine, ``spilled``
through graceful drain.  All of them go through :meth:`evict`, which
frees the sequence's blocks from *any* live state — waiting sequences
hold no blocks, but removing them from the queue here keeps the
lifecycle single-exit.

- **Admission** is by KV-block budget: a sequence is admitted only when
  the allocator can hold its whole prefill context *now* (all-or-nothing
  — partial holds deadlock a full pool).  A cache with a pool a kind of
  layer (``kv_cache.py``) admits, grows and preempts on all of them: a
  sequence takes its blocks in every pool or in none, and gives all back.  Preempted sequences re-admit
  ahead of new arrivals (front of queue) so preemption cannot starve a
  request forever.
- **Preemption** frees the victim's entire table (recompute-style: its
  tokens so far become the new, longer prefill prompt).  Victims are
  picked newest-admitted-first, so the oldest running sequence always
  survives and finishes — the loop cannot livelock.
- **Prefill/decode interleaving**: each ``schedule()`` returns either
  ONE prefill (padded to a power-of-two bucket) or one decode batch over
  all running sequences (fixed ``max_seqs`` × 1 shape).  Step shapes
  therefore come from a small closed set, and the PR 4 compile tracker
  sees exactly one compilation per bucket — no retrace storms from
  ragged traffic.
- **One unit ahead of the accept** (ISSUE 36): the engine plans and
  launches unit n+1 before it reads unit n, so what the NEXT plan needs
  moves at launch (:meth:`mark_launched`: ``computed_len`` of the launched
  rows, ``in_flight`` of the rows whose token the unit samples) and what
  depends on the token at landing (``output``, ``pending``, finishing,
  the freeing of blocks).  ``schedule(ahead=True)`` plans with a unit
  unread: a row whose output reaches ``max_new_tokens`` with what is in
  flight is left out of the decode batch, blocks let go behind a window
  are held back until that unit has landed (``kv_cache._Pool.settle``),
  and a plan that would have to preempt is not made (``StepPlan("wait")``:
  the engine lands first, so that victims are chosen from real state).
  :meth:`unmark` and :meth:`unadmit` take a launch back.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Sequence

from ..framework.errors import enforce
from .kv_cache import PagedKVCache

__all__ = ["WAITING", "RUNNING", "PREEMPTED", "FINISHED", "SequenceState",
           "StepPlan", "ContinuousBatchingScheduler", "prefill_bucket"]

WAITING = "waiting"
RUNNING = "running"
PREEMPTED = "preempted"
FINISHED = "finished"

_MIN_BUCKET = 8


def prefill_bucket(length: int, cap: int) -> int:
    """Smallest power-of-two >= ``length`` (floor ``_MIN_BUCKET``),
    capped at ``cap`` — the closed set of prefill step shapes."""
    enforce(0 < length <= cap, f"prefill length {length} outside (0, {cap}]")
    b = _MIN_BUCKET
    while b < length:
        b *= 2
    return min(b, cap)


@dataclasses.dataclass
class SequenceState:
    """One request's scheduling state.  Token bookkeeping:

    - ``prompt``: the submitted prompt ids (never mutated);
    - ``output``: every token generated so far (streamed to the caller);
    - ``context()``: the tokens whose KV must be cached before the next
      decode step — prompt + generated output *except* ``pending`` (the
      newest sampled token, whose KV is written by the step that feeds
      it back in);
    - ``computed_len``: cache entries on device for this sequence once
      every unit launched so far has run (it moves at launch; 0 after
      preemption — recompute rebuilds them);
    - ``in_flight``: tokens that launched units sample for this sequence
      and the host has not read yet.  While it is not 0, ``pending`` is
      the newest token that LANDED (None before the first): the unit in
      flight already feeds it, or takes the id it has to feed from the
      previous program's output on the device, and ``output`` grows only
      as units land.
    """
    request_id: str
    prompt: List[int]
    max_new_tokens: int
    eos_token_id: Optional[int] = None
    arrival: float = 0.0
    on_token: Optional[Callable] = None
    capture_logits: bool = False

    # request-lifecycle guard (ISSUE 15): absolute clock() times — the
    # engine computes them from submit()'s relative deadline_ms knobs
    deadline: Optional[float] = None
    ttft_deadline: Optional[float] = None
    cancelled: bool = False

    state: str = WAITING
    output: List[int] = dataclasses.field(default_factory=list)
    pending: Optional[int] = None       # sampled, KV not yet cached
    computed_len: int = 0
    in_flight: int = 0                  # sampled by launched units, unread
    logits: List = dataclasses.field(default_factory=list)
    # under capture_logits, what the model hands out a token beside the
    # logits (an expert layer's choices, say), a step at a time
    per_token: List = dataclasses.field(default_factory=list)
    # and what it hands out a logits row (the positions the one query
    # behind the row attended, say), an entry a row of `logits`
    per_logit: List = dataclasses.field(default_factory=list)
    first_token_time: Optional[float] = None
    last_token_time: Optional[float] = None
    finish_reason: Optional[str] = None
    preemptions: int = 0

    # request tracing (ISSUE 18): the fleet-wide trace context.
    # ``trace_id`` is minted by the router (or the engine for direct
    # submissions) and rides every ``trace.span`` this sequence emits;
    # ``resume_why`` marks a recompute's cause ("preempt" / "failover" /
    # "migration") so the next prefill span is attributed to it;
    # ``trace_enqueued`` is the wall time the sequence (re-)entered the
    # waiting queue — the start of its next queue span.
    trace_id: Optional[str] = None
    resume_why: Optional[str] = None
    trace_enqueued: Optional[float] = None
    # when it was submitted, on ``time.perf_counter()``, the clock of the
    # engine's spans (ISSUE 37): the engine records the wait as a span when
    # it launches the request's first prefill, once, and clears this
    queued: Optional[float] = None

    def context(self) -> List[int]:
        """Tokens needing cached KV before the next decode step.
        ``pending`` (invariantly ``output[-1]`` when set) is excluded:
        its KV is written by the decode step that consumes it."""
        toks = list(self.prompt) + list(self.output)
        return toks[:-1] if self.pending is not None else toks

    @property
    def total_len(self) -> int:
        return len(self.prompt) + len(self.output)

    def fills_up_in_flight(self) -> bool:
        """Whether the tokens in flight bring the output to
        ``max_new_tokens``: the one finish that is known at launch."""
        return len(self.output) + self.in_flight >= self.max_new_tokens

    def should_finish(self) -> Optional[str]:
        if (self.eos_token_id is not None and self.output
                and self.output[-1] == self.eos_token_id):
            return "eos"
        if len(self.output) >= self.max_new_tokens:
            return "max_new_tokens"
        return None


@dataclasses.dataclass
class StepPlan:
    """What the engine should run this step."""
    # "prefill" | "decode" | "idle" | "wait" (planned ahead of a landing:
    # growing a table would have to preempt, so land first)
    kind: str
    seqs: List[SequenceState]
    bucket: int = 0                         # prefill pad length
    preempted: List[SequenceState] = dataclasses.field(default_factory=list)


class ContinuousBatchingScheduler:
    """Admission / preemption / interleaving policy over a
    :class:`PagedKVCache`'s allocator.

    The engine loop is ``plan = schedule(); launch(plan);
    mark_launched(plan)`` and, a unit later, the landing: ``complete`` for
    what finished, or ``unmark`` / ``unadmit`` for a unit that is dropped
    unread.  The scheduler owns the queues and the block accounting; it
    never touches device arrays.
    """

    def __init__(self, cache: PagedKVCache, max_seqs: int,
                 max_model_len: int, clock: Callable[[], float] = time.time):
        enforce(max_seqs >= 1, "max_seqs must be >= 1")
        self.cache = cache
        self.max_seqs = int(max_seqs)
        self.max_model_len = int(max_model_len)
        self.max_blocks_per_seq = cache.allocator.blocks_for_tokens(
            self.max_model_len)
        self.clock = clock
        self.waiting: Deque[SequenceState] = deque()
        self.running: List[SequenceState] = []
        self.finished: Dict[str, SequenceState] = {}
        self.preemptions = 0
        # drain gate (ISSUE 15): closed admission still lets preempted
        # sequences (anything that already produced output) re-admit —
        # drain must finish started work, only fresh arrivals wait out
        self.admission_open = True

    # -- intake ------------------------------------------------------------
    def submit(self, seq: SequenceState) -> None:
        worst = len(seq.prompt) + seq.max_new_tokens
        enforce(worst <= self.max_model_len,
                f"{seq.request_id}: prompt {len(seq.prompt)} + "
                f"max_new {seq.max_new_tokens} exceeds max_model_len "
                f"{self.max_model_len}")
        enforce(self.cache.holds(worst),
                f"{seq.request_id}: needs more KV blocks than the whole "
                f"pool holds ({self.cache.num_blocks})")
        enforce(len(seq.prompt) >= 1, f"{seq.request_id}: empty prompt")
        seq.state = WAITING
        seq.arrival = seq.arrival or float(self.clock())
        if seq.trace_enqueued is None:
            seq.trace_enqueued = seq.arrival
        self.waiting.append(seq)

    @property
    def queue_depth(self) -> int:
        return len(self.waiting)

    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    # -- the per-step decision ---------------------------------------------
    def schedule(self, ahead: bool = False) -> StepPlan:
        """Pick this step's work: one prefill when a waiting sequence
        fits the block budget and a batch slot, else one decode batch
        over the running set (preempting on next-token OOM), else idle.
        Prefill-first keeps TTFT low under load; decode throughput costs
        at most one interleaved step per admission.

        ``ahead``: a launched unit has not landed.  Rows that it fills up
        to ``max_new_tokens`` are left out, window blocks behind a row are
        held back for its replay, and where a table cannot grow without a
        victim the plan is ``"wait"``."""
        plan_preempted: List[SequenceState] = []

        if (self.waiting and len(self.running) < self.max_seqs
                and (self.admission_open or self.waiting[0].output)):
            seq = self.waiting[0]
            ctx = len(seq.context())
            need = self.cache.allocator.blocks_for_tokens(ctx)
            if (need <= self.max_blocks_per_seq
                    and self.cache.ensure_capacity(seq.request_id, ctx)):
                self.waiting.popleft()
                seq.state = RUNNING
                self.running.append(seq)
                bucket = prefill_bucket(ctx, self.max_model_len)
                return StepPlan("prefill", [seq], bucket=bucket)

        if self.running:
            survivors: List[SequenceState] = []
            for seq in list(self.running):
                if seq.state != RUNNING:
                    continue      # already preempted as a victim above
                if seq.fills_up_in_flight():
                    continue      # its last token is on its way
                # a decode step writes the pending token's KV at position
                # computed_len — grow the table to cover it, preempting
                # newest-admitted sequences on OOM
                while not self.cache.ensure_capacity(
                        seq.request_id, seq.computed_len + 1, hold=ahead):
                    if ahead:
                        return StepPlan("wait", [])
                    victim = self.running[-1]
                    self._preempt(victim)
                    plan_preempted.append(victim)
                    if victim is seq:
                        break
                else:
                    survivors.append(seq)
            if survivors:
                return StepPlan("decode", survivors,
                                preempted=plan_preempted)
        return StepPlan("idle", [], preempted=plan_preempted)

    def preempt(self, seq: SequenceState) -> None:
        """Send one running sequence back to the head of the queue for
        recompute (its landed tokens stay; nothing of it is in flight)."""
        self._preempt(seq)
        seq.in_flight = 0

    def _preempt(self, seq: SequenceState) -> None:
        self.running.remove(seq)
        self.cache.free_seq(seq.request_id)
        seq.computed_len = 0
        seq.state = PREEMPTED
        seq.preemptions += 1
        self.preemptions += 1
        # trace attribution (ISSUE 18): the wait + re-prefill this
        # preemption causes belongs to the preemption, not to "queue"
        seq.resume_why = "preempt"
        seq.trace_enqueued = float(self.clock())
        # head of the queue: preempted work re-admits before new arrivals
        self.waiting.appendleft(seq)

    def preempt_all(self) -> List[SequenceState]:
        """Evict every running sequence back to the queue (recompute) —
        the engine's hang-recovery path.  Device-side work in flight is
        abandoned, read or not; host state stays consistent because a
        preempted sequence starts over from its landed tokens
        (``computed_len`` 0, nothing in flight).  Newest-first so
        re-admission replays in the original admission order."""
        victims = list(reversed(self.running))
        for seq in victims:
            self._preempt(seq)
            seq.in_flight = 0
        return victims

    # -- engine feedback ---------------------------------------------------
    def mark_prefilled(self, seq: SequenceState) -> None:
        seq.computed_len = len(seq.context())

    def mark_decoded(self, seq: SequenceState) -> None:
        seq.computed_len += 1

    def mark_launched(self, kind: str, seqs: Sequence[SequenceState],
                      emits: Sequence[bool]) -> List[tuple]:
        """What the next plan needs of a unit, moved as the unit is
        launched: the rows' ``computed_len``, and ``in_flight`` of the rows
        whose next token it samples (``emits``: every decode row; a
        prefill's unless it recomputes a sequence whose next token was
        sampled before).  Returns what :meth:`unmark` needs to take it
        back."""
        marks = []
        for seq, e in zip(seqs, emits):
            marks.append((seq, seq.computed_len, bool(e)))
            if kind == "prefill":
                self.mark_prefilled(seq)
            else:
                self.mark_decoded(seq)
            seq.in_flight += bool(e)
        return marks

    def unmark(self, marks: List[tuple]) -> None:
        """Take back :meth:`mark_launched` of a unit that will not be
        accepted (dropped unread, or faulted and about to be replayed).
        Units are taken back newest first."""
        for seq, computed_len, emits in marks:
            if seq.state == RUNNING:
                seq.computed_len = computed_len
                seq.in_flight = max(0, seq.in_flight - emits)

    def unadmit(self, seq: SequenceState) -> None:
        """Send a sequence whose prefill is dropped unread back to the
        head of the queue with its blocks returned: it was never
        prefilled, so this is no preemption."""
        if seq.state != RUNNING:
            return
        self.running.remove(seq)
        self.cache.free_seq(seq.request_id)
        seq.computed_len = 0
        seq.in_flight = 0
        seq.state = PREEMPTED if seq.preemptions else WAITING
        self.waiting.appendleft(seq)

    def complete(self, seq: SequenceState, reason: str,
                 hold: bool = False) -> None:
        """Evict a finished sequence: free its blocks immediately so the
        next schedule() can admit into the reclaimed space."""
        self.evict(seq, reason, hold)

    def evict(self, seq: SequenceState, reason: str,
              hold: bool = False) -> None:
        """Terminal eviction from ANY live state — finish, cancel,
        deadline, quarantine and drain-spill all exit through here:
        remove the sequence from whichever queue holds it, free its
        blocks, record the reason, file it under ``finished``.  ``hold``:
        a unit in flight may still step its state slot
        (:meth:`PagedKVCache.free_seq`)."""
        if seq in self.running:
            self.running.remove(seq)
        else:
            try:
                self.waiting.remove(seq)
            except ValueError:
                pass
        self.cache.free_seq(seq.request_id, hold)
        seq.state = FINISHED
        seq.finish_reason = reason
        self.finished[seq.request_id] = seq

    # -- introspection ------------------------------------------------------
    def counts(self) -> Dict[str, int]:
        return {"waiting": len(self.waiting),
                "running": len(self.running),
                "finished": len(self.finished),
                "preemptions": self.preemptions}
