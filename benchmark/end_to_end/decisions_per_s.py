"""Commit decisions completed in height order per second: blocks verified AND
applied inside VerifyAheadPipeline.process_next, whole passes only, over the
time those passes took (building a pass's pool is the benchmark's work and
is left out)."""


def read(run):
    if not run.passes:
        return None
    return (sum(n for _t0, _t1, n in run.passes)
            / sum(t1 - t0 for t0, t1, _n in run.passes))
