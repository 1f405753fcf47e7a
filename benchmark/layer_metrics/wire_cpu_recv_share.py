"""The fastsync.thread_cpu marks of the window: CPU seconds of the connections'
receive threads (mconn-recv*) over the wall seconds the marks cover, %."""

from benchmark.harness import wire


def read(run):
    return wire.cpu_recv_share(run)
