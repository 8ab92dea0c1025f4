"""perfbench — the benchmark of paddle_tpu (BENCHMARK.json at the repo root).

Everything that decides a number lives here, where a PR that claims a gain
cannot edit it: traffic generation, the reduction from traces and spans to
metrics, the table of peaks, the operation and byte counts of the kernels,
the plain float32 reference and the comparison that decides ``correct``.
From the program it takes only the system under test and its counters and
kernel names.  ``perfbench/README.txt`` says how a later PR adds a cell, a
traffic mix, a configuration or a metric with new files alone.
"""
