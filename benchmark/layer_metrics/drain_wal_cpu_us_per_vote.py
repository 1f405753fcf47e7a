"""consensus.wal_write: the consensus thread's own CPU time inside it over the
messages it wrote (drain_wal_us_per_vote is the wall time)."""

from benchmark.harness import cpu


def read(run):
    return cpu.cpu_us_per(run, "consensus.wal_write", "msgs")
