"""indexer.height spans (one height through the indexer service, on its own
thread) over the transactions they indexed (tag txs): wall time, so the waits
for a slower publisher and for the interpreter lock are in it."""

from benchmark.harness import drain


def read(run):
    return drain.us_per(run, "indexer.height", "txs")
