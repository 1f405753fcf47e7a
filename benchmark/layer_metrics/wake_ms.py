"""verify.wake record (the executor's done.set() to the sleeping caller running
again), per decision."""

from benchmark.harness import spans


def read(run):
    return spans.ms_per_decision(run, "verify.wake")
