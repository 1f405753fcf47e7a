"""ConsensusReactor.recv_stats: seconds inside receive over messages, on the
receiving thread: the wire decode, the peer-state update and the queue put."""

from benchmark.harness import drain


def read(run):
    return drain.recv_us_per_msg(run)
