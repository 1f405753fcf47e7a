"""device_idle_share where decisions_per_s is the metric."""

from benchmark.harness import layers


def read(run):
    return layers.device_idle_share(run)
