"""The fastsync.thread_cpu marks of the window: CPU seconds of the whole
process over the wall seconds the marks cover, % (~100: the interpreter lock
is never free; above: C code off the lock, sqlite and SHA-256 among it)."""

from benchmark.harness import fullsync


def read(run):
    return fullsync.cpu_share(run, "process")
