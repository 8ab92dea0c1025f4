"""``moe_grouped_*``: bytes of the experts touched plus tokens in and out over the
HBM peak (or the FLOPs over the bf16 peak) over their device time."""
from perfbench.harness.expert_reads import (  # noqa: F401
    moe_expert_roofline as read)
