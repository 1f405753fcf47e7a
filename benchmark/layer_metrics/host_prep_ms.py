"""verify.host_prep span, per decision."""


def read(run):
    return run.span_ms_per_decision("verify.host_prep")
