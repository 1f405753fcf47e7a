"""fastsync.head_wait span (the head block's batched prefetch + resolve), per
decision."""

from benchmark.harness import spans


def read(run):
    return spans.ms_per_decision(run, "fastsync.head_wait")
