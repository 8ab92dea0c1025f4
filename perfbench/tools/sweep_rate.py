#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell, once, on the chip.

    python3 perfbench/tools/sweep_rate.py --workload gpt3-xl.chat --rates 2,3,4,5,6

One process, one engine.  For each rate the cell's own traffic is offered
for ``--seconds`` (30); the waiting queue is read at a third of the way and
at the end, the running set at two thirds and at the end; the engine then
drains before the next rate.  A rate is sustained when the queue at the end
is no deeper than at a third (within one request), the running set has
stopped growing (the end within 15% + 2 of two thirds: the scheduler
prefills before it decodes, so overload shows first as a growing running
set, and in the queue only once the pool is full) and nothing was
preempted.  The knee is the highest sustained rate.  The cell's ``rate_rps`` is then written into its
traffic file by hand, at about four fifths of the knee: the benchmark itself
never searches for a rate.  Not part of a run; prints one line per rate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import numpy as np                                              # noqa: E402

from perfbench.harness.manifest import Manifest                 # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="2,3,4,5,6")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--manifest", default=None)
    ap.add_argument("--root", action="append", default=[])
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    manifest = Manifest(args.manifest, args.root)
    cell = manifest.cell(args.workload)
    config = manifest.load_config(cell["config"])
    traffic = manifest.load_traffic(cell["traffic"])
    import jax
    if not args.rehearsal and jax.devices()[0].platform != "tpu":
        raise SystemExit("sweep_rate: no TPU")
    from perfbench.jobs import serve
    system = manifest.load_entry(config["entry"])(config, args.seed)
    engine = system.build_for_serving(traffic["engine"])
    vocab = system.shape["vocab"]
    rng = np.random.default_rng(args.seed)
    serve.warm_up(engine, traffic, vocab, rng)
    kind = manifest.load_module("kinds", traffic["kind"])
    knee, pre = None, engine.sched.preemptions
    for rate in [float(r) for r in args.rates.split(",")]:
        source = kind.make({**traffic, "rate_rps": rate}, args.seed, vocab,
                           args.seconds)
        depth, t_start, n_sub, tokens = [], time.perf_counter(), 0, 0
        while True:
            tn = time.perf_counter() - t_start
            if tn >= args.seconds:
                break
            for r in source.poll(tn, engine.sched.queue_depth):
                engine.submit(r.prompt, max_new_tokens=r.out_len)
                n_sub += 1
            if engine.has_work():
                tokens += len(engine.step())
            else:
                time.sleep(0.002)
            depth.append((tn, engine.sched.queue_depth,
                          len(engine.sched.running)))

        def mean_depth(a, b, col):
            xs = [d[col] for d in depth if a <= d[0] < b]
            return sum(xs) / max(1, len(xs))

        third = args.seconds / 3.0
        pre0, pre = pre, engine.sched.preemptions
        row = {"rate_rps": rate, "submitted": n_sub,
               "running_at_two_thirds": mean_depth(2 * third - 1,
                                                   2 * third + 1, 2),
               "preemptions": pre - pre0,
               "waiting_at_third": mean_depth(third - 1, third + 1, 1),
               "waiting_at_end": mean_depth(args.seconds - 2,
                                            args.seconds, 1),
               "running_at_end": mean_depth(args.seconds - 2,
                                            args.seconds, 2),
               "tokens_per_s": tokens / args.seconds}
        row["sustained"] = (
            row["waiting_at_end"] <= row["waiting_at_third"] + 1.0
            and row["running_at_end"]
            <= 1.15 * row["running_at_two_thirds"] + 2.0
            and row["preemptions"] == 0)
        if row["sustained"]:
            knee = rate
        t0 = time.perf_counter()
        engine.run()                      # drain before the next rate
        row["drain_s"] = round(time.perf_counter() - t0, 1)
        print("sweep: " + json.dumps(row), flush=True)
    print("sweep: " + json.dumps({"knee_rps": knee}), flush=True)
    engine.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
