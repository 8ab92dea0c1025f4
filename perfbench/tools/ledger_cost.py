#!/usr/bin/env python3
"""What the serving engine's unit ledger costs, and what its one constant
should be, on the machine it runs on.

    python3 perfbench/tools/ledger_cost.py [--n 20000]

Prints one JSON line:

- ``ready_wait_us``: a ``device_wait`` span around ``block_until_ready`` of
  a unit's outputs that ARE ready (next tokens and a ``[rows, vocab]``
  float32 logits array), back to back and each after 5 ms of sleep (an
  engine between a chat cell's arrivals): p50 / p90 / p99 / max.  The
  engine calls a landing ``late`` when its wait was no longer than
  ``inference.engine.LATE_EPS_S``; that constant is set from these.
- ``landing_us``: the ledger's own work a unit (``_note_done`` +
  ``_book_landing``) and ``launch_us``: the booking of a starved interval
  with the three attributes on the call's root, from a loop over a tiny
  engine and hand-made units.
- ``ring``: the span ring filled with what the engine writes (a call is a
  root with its attributes and ten children with ``step`` and ``unit``):
  records, resident bytes (``tracemalloc``), bytes a record.

Runs no step program; touches the device only for the two arrays.  Not part
of a run (PERF.md section 6, PR 37).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import tracemalloc

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


from perfbench.harness.arith import percentile                  # noqa: E402


def _summary(us):
    return {"n": len(us), "p50": percentile(us, 50),
            "p90": percentile(us, 90), "p99": percentile(us, 99),
            "max": max(us)}


def ready_waits(n: int, rows: int = 128, vocab: int = 50304):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.observability import tracing
    out = (jnp.zeros((rows,), jnp.int32),
           jnp.zeros((rows, vocab), jnp.float32))
    jax.block_until_ready(out)

    def one(i):
        with tracing.span("device_wait", step=i, unit=i) as sp:
            jax.block_until_ready(out)
        return sp.elapsed * 1e6

    with tracing.span("engine.step", step=0):
        for i in range(n // 10):
            one(i)
        hot = [one(i) for i in range(n)]
        cold = []
        for i in range(min(n, 400)):
            time.sleep(0.005)
            cold.append(one(i))
    return {"back_to_back": _summary(hot), "after_5ms_idle": _summary(cold),
            "device": jax.devices()[0].device_kind}


def bookkeeping(n: int):
    import paddle_tpu as pt
    from paddle_tpu.inference import ServingEngine
    from paddle_tpu.inference.engine import _Unit
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.observability import tracing
    from paddle_tpu.observability.registry import MetricsRegistry
    if not hasattr(ServingEngine, "_book_landing"):     # an older commit
        return {"landing_us": None, "launch_us": None}
    pt.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=32, hidden_size=32, num_layers=1, num_heads=2,
        ffn_hidden_size=64, max_position_embeddings=32))
    eng = ServingEngine(model, max_seqs=4, kv_block_size=4,
                        registry=MetricsRegistry())
    clock = time.perf_counter

    class Waited:
        start, end, elapsed = 0.0, 1.0, 1.0

    unit = _Unit("decode", [None] * 4, 0, 0, 0.0)
    unit.enqueued = 0.5
    eng._in_flight = unit
    for _ in range(n // 10):
        eng._note_done(unit, Waited)
        eng._book_landing(unit)
    t0 = clock()
    for _ in range(n):
        eng._note_done(unit, Waited)
        eng._book_landing(unit)
    landing = (clock() - t0) / n * 1e6
    eng._in_flight = None
    eng._launch = lambda *a, **kw: unit
    eng.sched.mark_launched = lambda *a: []
    eng._step_root = tracing.span("engine.step", step=0)
    t0 = clock()
    for _ in range(n):
        eng._start("decode", unit.seqs, 0, None, 0)
    launch = (clock() - t0) / n * 1e6
    return {"landing_us": landing, "launch_us": launch}


def ring_bytes():
    from paddle_tpu.observability import tracing
    tracing.reset_tracing()
    children = ("reap", "schedule", "tables", "h2d", "dispatch",
                "device_wait", "logits_copy", "guard", "accept", "accept")
    calls = tracing.BUFFER_SPANS // (len(children) + 1) + 1
    tracemalloc.start()
    before = tracemalloc.get_traced_memory()[0]
    for call in range(calls):
        with tracing.span("engine.step", step=call) as root:
            for name in children:
                with tracing.span(name, step=call, unit=call):
                    pass
            root.set(ahead_kind="decode", ahead_unit=call + 1,
                     logits_fetched=False, kind="decode", rows=128,
                     bucket=0, unit=call, kv_blocks_live=4096,
                     kv_blocks_table=8192)
    held = tracemalloc.get_traced_memory()[0] - before
    tracemalloc.stop()
    records = len(tracing.spans_between(0.0, float("inf")))
    tracing.reset_tracing()
    return {"records": records, "resident_bytes": held,
            "bytes_a_record": held / records}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=20000)
    n = ap.parse_args(argv).n
    out = {"ready_wait_us": ready_waits(n)}
    out.update(bookkeeping(n))
    out["ring"] = ring_bytes()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
