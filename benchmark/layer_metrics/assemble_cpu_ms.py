"""commit.assemble: the caller's own CPU time inside it, per decision
(assemble_ms is the wall time)."""

from benchmark.harness import cpu


def read(run):
    return cpu.cpu_ms_per_decision(run, "commit.assemble")
