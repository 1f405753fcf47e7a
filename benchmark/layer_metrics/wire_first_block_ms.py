"""The fastsync.first_block marks of the window: start_sync to the first block
in the pool (listen, dial, handshake, status exchange, the first request, a
block's way over the wire), mean over its passes."""

from benchmark.harness import wire


def read(run):
    return wire.first_block_ms(run)
