"""Deliveries that took the serial path (consensus.vote_serial: alone through
_handle_msg, or left out of the batch for their height, index or address)
over all deliveries the state machine handled, %."""

from benchmark.harness import drain


def read(run):
    return drain.serial_share(run)
