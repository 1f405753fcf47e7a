"""commit.assemble span (the decision's root: structural check, sign bytes and
add per signature, verifier.dispatch), per decision."""

from benchmark.harness import spans


def read(run):
    return spans.ms_per_decision(run, "commit.assemble")
