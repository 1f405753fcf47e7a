"""consensus.finalize_commit per decision: validate (the LastCommit's
verification included) + save + WAL end-height + apply of the decided block."""

from benchmark.harness import spans


def read(run):
    return spans.ms_per_decision(run, "consensus.finalize_commit")
