"""The consensus.thread_cpu marks of the window: CPU seconds of every other
thread, and what the process got that no live Python thread accounts for
(rest_s), over the wall seconds of the heights, %."""

from benchmark.harness import cpu


def read(run):
    return cpu.share(run, "other")
