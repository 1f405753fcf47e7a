"""fastsync.apply span, per decision."""


def read(run):
    return run.span_ms_per_decision("fastsync.apply")
