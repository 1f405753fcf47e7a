"""The consensus.thread_cpu marks of the window: CPU seconds of the threads
that called ConsensusReactor.receive (the consensus.recv marks name them) over
the wall seconds of the heights, %."""

from benchmark.harness import cpu


def read(run):
    return cpu.share(run, "recv")
