"""Least time the chip could take for the verify kernels' calls over the time
they took, %. Which side bounds is on an earlier line of the run."""

from benchmark.harness import layers


def read(run):
    roof = layers.kernel_roofline(run, run.setup["device"]["kind"])
    return None if roof is None else roof["share_pct"]
