"""``mla_latent_attn``: the larger of latent bytes / HBM peak and absorbed-form
FLOPs / bf16 peak, over its device time."""
from perfbench.harness.expert_reads import (  # noqa: F401
    mla_decode_roofline as read)
