"""The fastsync.thread_cpu marks of the window: CPU seconds of the post-commit
thread (event publishing) over the wall seconds the marks cover, %."""

from benchmark.harness import fullsync


def read(run):
    return fullsync.cpu_share(run, fullsync.POST_COMMIT_THREAD)
