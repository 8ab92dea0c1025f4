"""Per request, the mean gap between its output tokens emitted inside the
window, over requests with at least 8 such gaps; the 90th percentile across
requests, interpolated (``harness/arith.py``)."""
from perfbench.harness.reads import tpot_ms


def read(run):
    return tpot_ms(run, 90.0, "tpot_ms_p90")
