"""A decode step's busiest held expert over the mean, since the window
opened."""
from perfbench.harness.expert_reads import (  # noqa: F401
    moe_load_max_over_mean as read)
