"""Headers verified by the window's whole sessions over the verify service's
launches in the window (counters): how many light prefixes share one launch.
Nothing where the service launched nothing (a per-header client's prefixes
stay under the host crossover and never reach it)."""

from benchmark.harness import light


def read(run):
    launches = run.counter_delta("launches")
    if not launches or not light.headers(run):
        return None
    return light.headers(run) / launches
