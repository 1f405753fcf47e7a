"""Seconds the receive side's flow-rate limiter slept (Monitor.limit, the
p2p.wire marks' recv_blocked_s), per decision the marks cover."""

from benchmark.harness import wire


def read(run):
    return wire.throttle_wait_ms(run)
