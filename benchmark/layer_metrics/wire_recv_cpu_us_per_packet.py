"""CPU seconds of the mconn-recv* threads (the fastsync.thread_cpu census) over
the packets the p2p.wire marks, written beside it, counted as received."""

from benchmark.harness import wire


def read(run):
    return wire.recv_cpu_us_per_packet(run)
