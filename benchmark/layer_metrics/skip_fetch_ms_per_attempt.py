"""light.skip.fetch spans (a pivot from the primary, validate_basic
included), ms per attempt of the window's bisections."""

from benchmark.harness import skip


def read(run):
    return skip.ms_per(run, "light.skip.fetch", None)
