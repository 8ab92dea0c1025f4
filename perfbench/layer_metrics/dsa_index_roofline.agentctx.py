"""``dsa_index_scores`` in dots3's two full layers: the larger of live
index-key bytes / HBM peak and index FLOPs / bf16 peak, over its device time
in the traced decode steps (the sliding layers score nothing)."""
from perfbench.harness.sparse_reads import (  # noqa: F401
    dsa_index_roofline as read)
