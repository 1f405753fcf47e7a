"""startup.jit_trace of the start-up ring before the window: jax tracing
functions and lowering them to MLIR (a nested trace counted once), s."""

from benchmark.harness import spans


def read(run):
    return spans.startup_s(run, "startup.jit_trace")
