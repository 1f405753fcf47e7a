"""verify.host_prep span, per decision, where decisions_per_s is the metric."""


def read(run):
    return run.span_ms_per_decision("verify.host_prep")
