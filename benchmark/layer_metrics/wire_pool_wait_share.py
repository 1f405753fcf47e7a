"""Sum of fastsync.pool_wait (the sync loop's sleeps while the next pair of blocks
was not in the pool) over the wall time of the window's whole passes, %: near
0 the apply sets the pace, high the wire or the peers do."""

from benchmark.harness import wire


def read(run):
    return wire.pool_wait_share(run)
