"""fastsync.dispatch spans over the decisions applied: 1.0 when no
speculative dispatch was thrown away (a static validator set discards none; a
set that changes under the pipeline re-dispatches what it had in flight)."""

from benchmark.harness import spans


def read(run):
    return spans.count_per_decision(run, "fastsync.dispatch")
