"""Signatures under prep.host_verify over all that went through the registry
(prep.host_verify + prep.launch), %: what route_batch's host row took."""

from benchmark.harness import drain


def read(run):
    return drain.host_route_share(run)
