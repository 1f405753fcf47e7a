"""startup.table_build of the start-up ring before the window, less the jit
tracing and compiling inside it (its self time), s."""

from benchmark.harness import spans


def read(run):
    return spans.startup_s(run, "startup.table_build",
                           minus=("startup.jit_trace", "startup.jit_compile"))
