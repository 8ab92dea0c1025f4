"""The serving job: one ``ServingEngine`` stepped by the harness under a
traffic generator (``kinds/<kind>.py``).

Set-up: weights from ``--seed`` in the type they are served in; the engine
and its pool; one request per prefill bucket the cell's lengths can reach
(the decode program with them); four seeded sequences checked against the
plain reference's logits; then a ramp of the cell's own traffic, so that the
window opens at steady occupancy.  The window is ``[ramp_s, ramp_s +
seconds)`` on the run's clock.  No drain: the run ends when every request
that fell due inside the window has its first token (a backlog owes nothing:
its queue is there by construction).  A traced run goes on
for ``trace_s`` seconds after the window with the profiler on.

Times are the harness's own ``time.perf_counter()``, stamped as ``step()``
returns; a request's clock starts when it was DUE, not when it was handed
over.  The engine's own ``ttft_ms`` (from ``submit``) is not read.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

GRACE_S = 20.0        # after the window, for the first tokens still owed


def warm_up(engine, traffic, vocab, rng) -> None:
    """Compile (or load) the decode program and every prefill bucket named
    in the traffic file, by one short request each: the engine has no call
    that warms named buckets ahead of traffic (PERF.md section 7)."""
    cap = engine.max_model_len
    for bucket in traffic["warm_buckets"]:
        # the longest prompt of this bucket that leaves room for two tokens
        n = min(int(bucket), cap - 2)
        engine.submit(rng.integers(0, vocab, n).tolist(), max_new_tokens=2)
        engine.run()


def _reference_check(engine, system, traffic, vocab, rng) -> dict:
    """Prefill, then paged decode, against the reference's full forward
    pass: logits, not tokens (with random weights the largest logit changes
    on rounding).  The reference is fed the engine's own tokens."""
    import jax.numpy as jnp
    chk = traffic["check"]
    new = int(chk["new_tokens"])
    prompts = [rng.integers(0, vocab, n).tolist()
               for n in chk["prompt_lens"]]
    engine.capture_logits = True          # for these sequences only
    try:
        rids = [engine.submit(p, max_new_tokens=new) for p in prompts]
    finally:
        engine.capture_logits = False
    engine.run()
    results = [engine.collect(r) for r in rids]
    width = -(-(max(len(p) for p in prompts) + new) // 64) * 64
    ids = np.zeros((len(prompts), width), np.int32)
    pos = np.zeros((len(prompts), new), np.int32)
    for i, (p, r) in enumerate(zip(prompts, results)):
        seq = p + r["tokens"][:new - 1]
        ids[i, :len(seq)] = seq
        pos[i] = np.arange(len(p) - 1, len(p) - 1 + new)
    ref = np.asarray(system.reference_logits_fn()(
        system.reference_params(engine._params), jnp.asarray(ids),
        jnp.asarray(pos)))
    got = np.stack([np.stack(r["logits"][:new]) for r in results])
    finite = bool(np.isfinite(got).all())
    worst = float(np.max(np.abs(got - ref)))
    complete = all(len(r["tokens"]) == new for r in results)
    return {"logits_max_abs_diff": worst,
            "logits_tolerance": float(chk["logits_tolerance"]),
            "positions_compared": int(got.shape[0] * got.shape[1]),
            "logits_finite": finite, "check_complete": complete,
            "ok": finite and complete
            and worst <= float(chk["logits_tolerance"])}


def run(ctx) -> dict:
    import jax

    t = ctx.traffic
    system, parts, spans = ctx.system, ctx.setup_parts, ctx.spans
    clock = time.perf_counter
    vocab = system.shape["vocab"]
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(ctx.seed), 0x7365]))

    t0 = clock()
    engine = system.build_for_serving(t["engine"])
    jax.block_until_ready(engine._params)
    parts["weights_and_pool_s"] = clock() - t0
    reg = system.registry
    prefills = reg.counter("serve.prefills")
    decodes = reg.counter("serve.decode_steps")

    t0 = clock()
    warm_up(engine, t, vocab, rng)
    parts["warmup_s"] = clock() - t0
    t0 = clock()
    checks = _reference_check(engine, system, t, vocab, rng)
    parts["reference_s"] = clock() - t0

    ramp_s = float(t["ramp_s"])
    w0, w1 = ramp_s, ramp_s + ctx.seconds
    t_stop = w1 + (ctx.trace_s if ctx.trace else 0.0)
    source = ctx.manifest.load_module("kinds", t["kind"]).make(
        t, ctx.seed, vocab, t_stop + 2.0)

    reqs = {}          # request id -> record
    steps = []         # (t0, t1, kind, rows, live_kv_tokens)
    refused = 0
    opened = closed = False
    stack = contextlib.ExitStack()
    at_open = None
    t_start = clock()

    def now():
        return clock() - t_start

    def attempted(r):
        """Open loop: the request fell due inside the window.  A backlog
        hands requests over long before their turn and always leaves a
        queue behind, so there it is the requests STARTED in the window
        (first token inside it); nothing is owed at the end."""
        if source.closed:
            return r["first"] is not None and w0 <= r["first"] < w1
        return w0 <= r["due"] < w1

    with stack:
        while True:
            tn = now()
            if not opened and tn >= w0:
                ctx.open_window()
                at_open = engine.stats()
                opened = True
            if opened and not closed and tn >= w1:
                ctx.close_window()
                closed = True
                if ctx.trace:
                    stack.enter_context(ctx.tracing())
            if closed and tn >= t_stop:
                owed = [r for r in reqs.values() if attempted(r)
                        and r["first"] is None and not r["failed"]]
                if not owed or tn >= t_stop + GRACE_S:
                    break
            if tn < t_stop + 2.0:
                with spans.span("submit"):
                    for r in source.poll(tn, engine.sched.queue_depth):
                        try:
                            rid = engine.submit(r.prompt,
                                                max_new_tokens=r.out_len)
                        except Exception:     # refused: counts as failed
                            refused += int(w0 <= r.due < w1)
                            continue
                        reqs[rid] = {"due": r.due, "submitted": now(),
                                     "prompt_len": len(r.prompt),
                                     "out_len": r.out_len, "first": None,
                                     "times": [], "failed": False}
            if engine.has_work():
                p0, d0 = prefills.value, decodes.value
                ts = now()
                with spans.span("engine.step"):
                    events = engine.step()
                te = now()
                kind = ("prefill" if prefills.value > p0 else
                        "decode" if decodes.value > d0 else "other")
                live = 0
                for ev in events:
                    r = reqs.get(ev["request_id"])
                    if r is None:
                        continue
                    if kind == "decode":
                        live += r["prompt_len"] + len(r["times"])
                    if r["first"] is None:
                        r["first"] = te
                    r["times"].append(te)
                    if ev["finished"] and ev["reason"] != "max_new_tokens":
                        r["failed"] = True
                steps.append((ts, te, kind, len(events), live))
            else:
                nxt = source.next_due()
                with spans.span("idle_wait"):
                    time.sleep(0.005 if nxt is None
                               else min(0.005, max(0.0, nxt - tn)))

    stats = engine.stats()
    engine.stop()
    in_window = [r for r in reqs.values() if attempted(r)]
    failed = refused + sum(1 for r in in_window
                           if r["failed"] or r["first"] is None)
    res = stats["resilience"]
    healthy = (res["poisoned"] == 0 and res["watchdog_restarts"] == 0
               and stats["kv_blocks"]["balanced"])
    checks.update(poisoned=res["poisoned"],
                  watchdog_restarts=res["watchdog_restarts"],
                  kv_blocks_balanced=bool(stats["kv_blocks"]["balanced"]))

    def mean_rows(a, b):
        rows = [n for (_, te, kind, n, _) in steps
                if kind == "decode" and a <= te < b]
        return sum(rows) / len(rows) if rows else 0.0

    # the ramp is long enough when the window opens at steady occupancy:
    # the first tenth of the window at 90% or more of the window's mean
    whole = mean_rows(w0, w1)
    ramp_check = {"rows_first_tenth": mean_rows(w0, w0 + 0.1 * (w1 - w0)),
                  "rows_window_mean": whole}
    return {
        "job": "serve", "correct": bool(checks["ok"] and healthy),
        "checks": checks,
        "attempted": len(in_window) + refused, "failed": failed,
        "window": {"t0": w0, "t1": w1, "seconds": w1 - w0},
        "serve": {"requests": list(reqs.values()), "steps": steps,
                  "max_seqs": engine.max_seqs,
                  "stats_at_open": at_open, "stats_at_end": stats,
                  "traced": (w1, t_stop) if ctx.trace else None},
        "planned_bytes": None,
        "samples": {"requests_attempted": len(in_window),
                    "steps": len(steps), **ramp_check},
    }
