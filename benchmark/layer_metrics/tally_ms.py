"""commit.tally span (the serial accept/reject replay over the bitmap), per
decision."""

from benchmark.harness import spans


def read(run):
    return spans.ms_per_decision(run, "commit.tally")
