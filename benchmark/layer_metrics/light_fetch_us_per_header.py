"""light.fetch span, per header verified in the window's whole sessions, us:
light blocks from the primary, validate_basic included (the windows of a sync and its target)."""

from benchmark.harness import light


def read(run):
    return light.us_per_header(run, "light.fetch")
