"""startup.jit_trace of the start-up ring before the window: the CPU seconds
the tracing thread got (jit_trace_s is the wall time), s."""

from benchmark.harness import cpu


def read(run):
    return cpu.startup_cpu_s(run, "startup.jit_trace")
