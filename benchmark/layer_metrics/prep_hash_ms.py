"""prep.scalars span (SHA-512 / merlin in C, mod L, windows), per decision."""

from benchmark.harness import spans


def read(run):
    return spans.ms_per_decision(run, "prep.scalars")
