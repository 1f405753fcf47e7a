"""consensus.finalize_commit: the consensus thread's own CPU time inside it,
per decision (finalize_ms is the wall time)."""

from benchmark.harness import cpu


def read(run):
    return cpu.cpu_ms_per_decision(run, "consensus.finalize_commit")
