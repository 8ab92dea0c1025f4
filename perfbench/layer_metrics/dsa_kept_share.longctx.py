"""Cache entries attended over entries scored by the window's decode steps,
percent; 100 where the traffic bypasses the selection.  Says the run's
``engine_counts`` line once beside it."""
from perfbench.harness import expert_reads, sparse_reads


def read(run):
    expert_reads.say_engine_counts(run)
    return sparse_reads.dsa_kept_share(run)
