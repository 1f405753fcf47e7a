"""The entry point's own time per decision (sign bytes, item lists, tally)."""

from benchmark.harness import layers


def read(run):
    return layers.decision_self_ms(run)
