"""Verify-service requests per shared launch over the window (counters)."""


def read(run):
    launches = run.counter_delta("launches")
    if not launches:
        return None
    return run.counter_delta("requests") / launches
