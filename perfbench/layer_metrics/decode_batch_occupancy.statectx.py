"""Mean running sequences over ``max_seqs``, decode steps in the window;
beside it, the lines ``engine_counts`` (the model's counters, preemptions
since the window opened) and ``kv_pools`` (the page pool and the state
slots)."""
from perfbench.harness import expert_reads, gqa_reads
from perfbench.harness.reads import decode_occupancy


def read(run):
    expert_reads.say_engine_counts(run)
    gqa_reads.say_kv_pools(run)
    return decode_occupancy(run)
