"""Signatures one vote drain hands the verifier (the `queued` tag of
consensus.vote_drain), mean over the drains that dispatch: a 4,096-lane chunk
costs what it costs whatever its fill."""

from benchmark.harness import drain


def read(run):
    return drain.votes_per_flush(run)
