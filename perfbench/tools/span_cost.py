#!/usr/bin/env python3
"""What one span of the program's costs, on the host it runs on.

    python3 perfbench/tools/span_cost.py [--spans 10000]

Times ``--spans`` empty ``paddle_tpu.observability.tracing.span``s in a
loop, bare and as the engine opens them (an attribute, under an open root),
with no profiler running, and prints one JSON line of microseconds per span.
Times spans per step (``engine_phases`` of a run gives the counts) over a
step's duration, that is the share of a step the spans themselves take
(PERF.md section 6, PR 26).  Touches no device; not part of a run.
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

from paddle_tpu.observability import tracing                    # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--spans", type=int, default=10000)
    n = ap.parse_args(argv).spans
    clock = time.perf_counter

    def per_span(body) -> float:
        body(n // 10)                                   # warm
        t0 = clock()
        body(n)
        return (clock() - t0) / n * 1e6

    def bare(k):
        for _ in range(k):
            with tracing.span("x"):
                pass

    def as_the_engine(k):
        with tracing.span("root"):
            for i in range(k):
                with tracing.span("phase", step=i):
                    pass

    print(json.dumps({"spans": n, "us_per_span": per_span(bare),
                      "us_per_span_child_with_attr": per_span(as_the_engine)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
