"""The fastsync.thread_cpu marks of the window: CPU seconds of the thread that
calls process_next over the wall seconds the marks cover, %."""

from benchmark.harness import fullsync


def read(run):
    return fullsync.cpu_share(run, "sync")
