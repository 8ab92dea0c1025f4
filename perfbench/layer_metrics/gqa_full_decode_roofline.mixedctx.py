"""``gqa_full_decode``: the larger of the attended tokens' key/value bytes / HBM
peak and attention FLOPs / bf16 peak, over its device time in the traced
decode steps."""
from perfbench.harness.gqa_reads import (  # noqa: F401
    gqa_full_decode_roofline as read)
