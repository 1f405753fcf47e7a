"""fastsync.discard marks with reason=valset of the window, per whole pass:
the changes of the validator set the pipeline met, each one discard of
everything in flight. The chain's own count of updates when healthy."""

from benchmark.harness import spans


def read(run):
    if (not run.traced or not run.passes
            or not spans._program_has("fastsync.discard")):
        return None
    marks = [s for s in run.spans if s["name"] == "fastsync.discard"
             and s["tags"].get("reason") == "valset"]
    return len(marks) / len(run.passes)
