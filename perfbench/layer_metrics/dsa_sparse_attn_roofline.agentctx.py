"""``dsa_sparse_attn`` in dots3's full layers (128 heads): the larger of
selected latent rows' bytes / HBM peak and absorbed-form FLOPs / bf16 peak,
over its device time."""
from perfbench.harness.sparse_reads import (  # noqa: F401
    dsa_sparse_attn_roofline as read)
