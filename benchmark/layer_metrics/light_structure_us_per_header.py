"""light.structure span, per header verified in the window's whole sessions, us:
the linkage walk, verifier.check_adjacent per header."""

from benchmark.harness import light


def read(run):
    return light.us_per_header(run, "light.structure")
