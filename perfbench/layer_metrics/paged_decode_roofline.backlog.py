"""``paged_decode``: bytes of live KV over the HBM peak over its device time."""
from perfbench.harness.reads import paged_decode_roofline as read  # noqa: F401
