"""Real signatures over launched lanes, %, where decisions_per_s is the
metric."""

from benchmark.harness import spans


def read(run):
    return spans.lane_fill(run)
