"""The consensus.recv marks: CPU seconds the threads that called
ConsensusReactor.receive got over a height (receive and what they do between
two calls; their clocks are read from outside, once a height), over the
messages they brought (recv_us_per_msg is the wall time inside receive)."""

from benchmark.harness import cpu


def read(run):
    return cpu.recv_cpu_us_per_msg(run)
