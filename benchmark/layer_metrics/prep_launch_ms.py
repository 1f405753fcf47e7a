"""prep.launch spans (host time to enqueue each device program), per
decision."""

from benchmark.harness import spans


def read(run):
    return spans.ms_per_decision(run, "prep.launch")
