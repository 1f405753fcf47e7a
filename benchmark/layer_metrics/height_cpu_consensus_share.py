"""The consensus.thread_cpu marks of the window: CPU seconds of the consensus
thread (cs-receive) over the wall seconds of the heights, %."""

from benchmark.harness import cpu


def read(run):
    return cpu.share(run, "consensus")
