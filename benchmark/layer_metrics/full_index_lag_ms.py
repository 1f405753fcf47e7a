"""From a pass's last process_next to the index holding its last height
(IndexerService.wait_indexed), mean over the window's passes: how far the
indexer trails the sync thread when the sync ends."""

from benchmark.harness import fullsync, spans


def read(run):
    if not run.traced or not spans._program_has("indexer.height"):
        return None
    return fullsync.index_lag_ms(run)
