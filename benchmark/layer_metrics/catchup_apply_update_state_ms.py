"""apply.update_state span (one of the four phases of BlockExecutor.apply_block),
per decision."""

from benchmark.harness import spans


def read(run):
    return spans.ms_per_decision(run, "apply.update_state")
