"""consensus.vote_apply: the consensus thread's own CPU time inside it over
the votes it applied (drain_apply_us_per_vote is the wall time)."""

from benchmark.harness import cpu


def read(run):
    return cpu.cpu_us_per(run, "consensus.vote_apply", "votes")
