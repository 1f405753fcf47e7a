"""fastsync.dispatch span (one height's speculative commit-verify dispatch),
per decision."""

from benchmark.harness import spans


def read(run):
    return spans.ms_per_decision(run, "fastsync.dispatch")
