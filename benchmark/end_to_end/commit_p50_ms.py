"""Median wall time of one commit decision in the window, ms."""

from benchmark.harness import stats


def read(run):
    return stats.median(run.latencies_ms())
