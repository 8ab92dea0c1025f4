"""Mean running sequences over ``max_seqs``, decode steps in the window."""
from perfbench.harness.reads import decode_occupancy as read  # noqa: F401
