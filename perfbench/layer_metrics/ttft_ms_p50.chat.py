"""Due time to first token, requests due in the window, median."""
from perfbench.harness.reads import ttft_ms


def read(run):
    return ttft_ms(run, 50.0, "ttft_ms_p50.chat")
