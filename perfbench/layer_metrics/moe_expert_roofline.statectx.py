"""``moe_grouped_*`` in Nemotron-3-Super's five expert layers, whose experts
read and write the 1,024-wide latent: bytes of the experts touched plus rows
in and out over the HBM peak (or the FLOPs over the bf16 peak) over their
device time."""
from perfbench.harness.ssm_reads import (  # noqa: F401
    latent_expert_roofline as read)
