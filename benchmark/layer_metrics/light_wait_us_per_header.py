"""light.wait span, per header verified in the window's whole sessions, us:
the caller blocked on the bitmaps of a window's launches."""

from benchmark.harness import light


def read(run):
    return light.us_per_header(run, "light.wait")
