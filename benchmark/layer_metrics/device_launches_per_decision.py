"""Verify-kernel executions on the device per decision; 0 = the host answered."""

from benchmark.harness import layers


def read(run):
    return layers.device_launches_per_decision(run)
