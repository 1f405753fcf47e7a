"""light.replay span, per header verified in the window's whole sessions, us:
each header's serial tally over its slice of the bitmaps."""

from benchmark.harness import light


def read(run):
    return light.us_per_header(run, "light.replay")
