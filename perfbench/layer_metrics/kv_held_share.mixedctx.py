"""Bytes both page pools held under the decode steps' rows since the window
opened over what one table for all layers would have held, percent.  Says the
run's ``engine_counts`` and ``kv_pools`` lines once beside it."""
from perfbench.harness import expert_reads, gqa_reads


def read(run):
    expert_reads.say_engine_counts(run)
    gqa_reads.say_kv_pools(run)
    return gqa_reads.kv_held_share(run)
