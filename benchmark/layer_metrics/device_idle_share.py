"""1 - device busy union over the traced slice, %, mean over chips."""

from benchmark.harness import layers


def read(run):
    return layers.device_idle_share(run)
