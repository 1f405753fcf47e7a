"""indexer.height spans' cpu_s (the indexer thread's own CPU seconds) over the
transactions they indexed (tag txs)."""

from benchmark.harness import cpu


def read(run):
    return cpu.cpu_us_per(run, "indexer.height", "txs")
