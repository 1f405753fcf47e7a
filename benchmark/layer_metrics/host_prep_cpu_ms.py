"""verify.host_prep: its own thread's CPU time inside it, per decision
(host_prep_ms is the wall time)."""

from benchmark.harness import cpu


def read(run):
    return cpu.cpu_ms_per_decision(run, "verify.host_prep")
