"""verify.queue span, per decision."""


def read(run):
    return run.span_ms_per_decision("verify.queue")
