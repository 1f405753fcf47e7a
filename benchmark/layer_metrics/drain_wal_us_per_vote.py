"""consensus.wal_write over the messages it wrote: every vote of a drain,
copies included, into the WAL before any is verified."""

from benchmark.harness import drain


def read(run):
    return drain.us_per(run, "consensus.wal_write", "msgs")
