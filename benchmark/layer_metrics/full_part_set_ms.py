"""fastsync.part_set span (a pooled block marshalled and cut into parts
before its dispatch), per decision."""

from benchmark.harness import spans


def read(run):
    return spans.ms_per_decision(run, "fastsync.part_set")
