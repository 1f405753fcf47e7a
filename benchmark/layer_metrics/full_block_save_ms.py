"""store.save_block span (BlockStore.save_block: meta, 17 parts, commits and
the store's state in one sqlite batch), per decision."""

from benchmark.harness import spans


def read(run):
    return spans.ms_per_decision(run, "store.save_block")
