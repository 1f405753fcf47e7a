"""The consensus.thread_cpu marks of the window: CPU seconds of the whole
process over the wall seconds of the heights, % (~100: the interpreter lock is
never free; above: C code off the lock; well below: the process sleeps
somewhere)."""

from benchmark.harness import cpu


def read(run):
    return cpu.share(run, "process")
