"""startup.key_decode of the start-up ring before the window: the Python
decompression of every key set's unique keys, s."""

from benchmark.harness import spans


def read(run):
    return spans.startup_s(run, "startup.key_decode")
