"""Windows of a sequential sync that were re-run header by header because the
range path itself failed (light.range spans tagged fallback=1), per session:
0 in a healthy run."""

from benchmark.harness import light


def read(run):
    return light.tagged_per_decision(run, "light.range", "fallback")
