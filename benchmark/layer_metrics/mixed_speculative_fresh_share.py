"""Handles of the commit->apply seam that validate_block consumed fresh over
those it was handed (fresh + stale), %, over the window's whole passes:
BlockExecutor.commit_verify_fresh / _stale as the driver read them after
each pass. 100 on a chain whose set never changes."""


def read(run):
    seam = run.notes.get("mixed", {}).get("seam")
    if not run.traced or not seam or not seam["fresh"] + seam["stale"]:
        return None
    return 100.0 * seam["fresh"] / (seam["fresh"] + seam["stale"])
