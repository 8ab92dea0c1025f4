"""How late the generator handed requests over: submit time - due time, 90th
percentile over the requests due in the window.  A starved generator must
not read as a fast server."""
from perfbench.harness.reads import gen_late_ms


def read(run):
    return gen_late_ms(run, 90.0, "gen_late_ms_p90")
