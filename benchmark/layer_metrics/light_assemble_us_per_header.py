"""light.assemble span, per header verified in the window's whole sessions, us:
a window's commit checks, light prefixes, sign bytes, add per signature and one dispatch per kernel chunk."""

from benchmark.harness import light


def read(run):
    return light.us_per_header(run, "light.assemble")
