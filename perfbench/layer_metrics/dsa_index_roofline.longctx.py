"""``dsa_index_scores``: the larger of live index-key bytes / HBM peak and index
FLOPs / bf16 peak, over its device time in the traced decode steps."""
from perfbench.harness.sparse_reads import (  # noqa: F401
    dsa_index_roofline as read)
