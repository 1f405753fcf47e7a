"""Real signatures over launched lanes, %: the sigs and lanes tags of every
prep.launch of the window."""

from benchmark.harness import spans


def read(run):
    return spans.lane_fill(run)
