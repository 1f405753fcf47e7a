"""The fastsync.thread_cpu marks of the window: CPU seconds of the indexer
thread over the wall seconds the marks cover, %."""

from benchmark.harness import fullsync


def read(run):
    return fullsync.cpu_share(run, fullsync.INDEXER_THREAD)
