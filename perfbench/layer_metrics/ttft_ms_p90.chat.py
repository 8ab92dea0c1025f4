"""Due time to first token, requests due in the window, 90th percentile.
Recorded, not judged: about a hundred requests a window put a standard error
of 3% on it (PERF.md section 6, PR 22's lesson)."""
from perfbench.harness.reads import ttft_ms


def read(run):
    return ttft_ms(run, 90.0, "ttft_ms_p90.chat")
