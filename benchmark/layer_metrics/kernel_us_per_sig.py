"""Device time of the programs in the traced slice per real signature."""

from benchmark.harness import layers


def read(run):
    return layers.kernel_us_per_sig(run)
