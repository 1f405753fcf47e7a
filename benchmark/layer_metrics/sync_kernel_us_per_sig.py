"""kernel_us_per_sig where decisions_per_s is the metric."""

from benchmark.harness import layers


def read(run):
    return layers.kernel_us_per_sig(run)
