"""Attempts of a bisection that the trusted set could not vouch for
(light.skip.hop tagged accepted=0), per session: each cost a trusting scan,
and where the sets still overlapped a dispatch of the overlap's signatures."""

from benchmark.harness import skip


def read(run):
    return skip.attempts_per_sync(run, 0)
