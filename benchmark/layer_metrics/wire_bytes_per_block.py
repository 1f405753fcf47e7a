"""Message bytes received on channel 0x40 (the p2p.wire marks), per decision the
marks cover: the driver's check holds a pass's total to
benchmark/reference/wire_sync.py."""

from benchmark.harness import wire


def read(run):
    return wire.bytes_per_block(run)
