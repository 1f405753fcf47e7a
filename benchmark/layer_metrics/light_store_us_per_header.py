"""light.store span, per header verified in the window's whole sessions, us:
trusted-store writes of a window's verified headers."""

from benchmark.harness import light


def read(run):
    return light.us_per_header(run, "light.store")
