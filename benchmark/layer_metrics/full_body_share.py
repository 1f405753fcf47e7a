"""Wall time of the decisions that the thread applying them spends inside
what a block's body causes (fastsync.part_set, abci.deliver_txs,
store.save_block, state.save_responses, block.data_hash, apply.backlog_wait),
%: the number that says the body does the work in this cell."""

from benchmark.harness import fullsync


def read(run):
    return fullsync.body_share(run)
