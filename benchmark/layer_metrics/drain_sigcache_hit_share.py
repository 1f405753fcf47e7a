"""Deliveries crypto/sigcache answered over those a drain looked up (the
cache_hits and queued tags of consensus.vote_drain), %. Two of three
deliveries are copies; a copy that shares a drain with its original misses."""

from benchmark.harness import drain


def read(run):
    return drain.sigcache_hit_share(run)
