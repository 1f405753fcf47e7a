"""light.skip.light spans (the hop's verify_commit_light on the new set,
after the trusting check passed), ms per accepted hop."""

from benchmark.harness import skip


def read(run):
    return skip.ms_per(run, "light.skip.light", 1)
