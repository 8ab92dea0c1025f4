"""A decode step's busiest held expert over the mean, since the window
opened; and, said once beside it, the run's ``engine_counts`` line."""
from perfbench.harness import expert_reads


def read(run):
    expert_reads.say_engine_counts(run)
    return expert_reads.moe_load_max_over_mean(run)
