"""A drain's own work per vote it holds: consensus.vote_drain less the
flush_wait and vote_apply of the flush before it (its children): height
filter, index and address check, sign bytes, cache lookup, add, dispatch."""

from benchmark.harness import drain


def read(run):
    return drain.drain_build_us_per_vote(run)
