"""startup.key_decode spans that began inside the window, per decision: the
host half of a key-set miss (the loop over the batch's unique keys; a key
decoded before comes from the program's memo). 0 where no lookup missed."""

from benchmark.harness import spans


def read(run):
    return spans.window_ring_ms_per_decision(run, "startup.key_decode")
