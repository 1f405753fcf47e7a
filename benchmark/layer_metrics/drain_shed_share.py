"""Messages of the live height the peer queue shed over the deliveries made
in the window (ConsensusState.shed_counts), %: 0 when healthy."""

from benchmark.harness import drain


def read(run):
    return drain.shed_share(run)
