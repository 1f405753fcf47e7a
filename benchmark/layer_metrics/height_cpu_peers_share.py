"""The consensus.thread_cpu marks of the window: CPU seconds of the gossip
routines and the connections' send and receive threads (cs-gossip*, mconn-*)
that the receive side does not claim, over the wall seconds of the heights, %."""

from benchmark.harness import cpu


def read(run):
    return cpu.share(run, "peers")
