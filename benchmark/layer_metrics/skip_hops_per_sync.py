"""Attempts of a bisection that verified (light.skip.hop tagged accepted=1),
per session: the blocks a sync trusted on its way, its target included."""

from benchmark.harness import skip


def read(run):
    return skip.attempts_per_sync(run, 1)
