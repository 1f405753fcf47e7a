"""consensus.flush_wait per decision: the consensus thread blocked on a vote
flush's bitmap."""

from benchmark.harness import spans


def read(run):
    return spans.ms_per_decision(run, "consensus.flush_wait")
