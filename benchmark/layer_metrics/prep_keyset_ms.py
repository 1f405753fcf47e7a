"""prep.keyset span (pubkey join, key-set cache lookup, on a miss the build),
per decision."""

from benchmark.harness import spans


def read(run):
    return spans.ms_per_decision(run, "prep.keyset")
