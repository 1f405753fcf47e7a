"""Sum of blockchain.recv_block (a BlockResponse through BlockchainReactor.receive:
envelope parse, Block.unmarshal, the pool's add_block) over the window, per
decision."""

from benchmark.harness import wire


def read(run):
    return wire.block_recv_ms(run)
