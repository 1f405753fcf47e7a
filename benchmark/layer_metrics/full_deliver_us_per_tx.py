"""abci.deliver_txs spans over the transactions they delivered (tag n)."""

from benchmark.harness import drain, spans


def read(run):
    if not spans._program_has("store.save_block"):
        return None      # a cell of this program's: the span is older
    return drain.us_per(run, "abci.deliver_txs", "n")
