"""prep.keyset span per decision, where decisions_per_s is the metric. On a
miss build_keyset waits for the tables inside the span, so it holds the whole
rebuild."""

from benchmark.harness import spans


def read(run):
    return spans.ms_per_decision(run, "prep.keyset")
