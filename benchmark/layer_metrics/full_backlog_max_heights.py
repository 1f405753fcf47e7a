"""The most heights that were ever behind apply_block in a pass of the
window: the larger of PostCommitWorker.backlog_max (tasks) and
IndexerService.backlog_heights_max (headers waiting)."""

from benchmark.harness import fullsync, spans


def read(run):
    if not run.traced or not spans._program_has("apply.backlog_wait"):
        return None
    return fullsync.note(run, "backlog_max_heights")
