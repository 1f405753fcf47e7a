"""startup.table_build spans that began inside the window, per decision: the
device half of a key-set miss, from the first 256-key tile's upload to the
tables being ready (build_keyset waits for them). 0 where no lookup missed."""

from benchmark.harness import spans


def read(run):
    return spans.window_ring_ms_per_decision(run, "startup.table_build")
