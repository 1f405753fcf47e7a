"""startup.jit_compile of the start-up ring before the window: backend
compiles, or their load from the persistent cache, s."""

from benchmark.harness import spans


def read(run):
    return spans.startup_s(run, "startup.jit_compile")
