"""Persistent-cache hits over lookups during set-up, in percent: 100 when
every program of the cell was found in the cache (any run after the first)."""


def read(run):
    c = run["compile"]
    if not c.get("setup_requests"):
        return None
    return 100.0 * c["setup_hits"] / c["setup_requests"]
