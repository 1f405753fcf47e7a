"""Speculative commit verifications thrown away unresolved over those
dispatched, %, by the pipelines' own counters over the window's whole passes
(VerifyAheadPipeline.discarded / .dispatched, which the churn-sync driver
notes): 0 on a chain whose set never changes, 4 of every 4 + n at
depth 4 where the set changes every n heights."""


def read(run):
    counted = run.notes.get("pipeline")
    if not counted or not counted["dispatched"]:
        return None
    return 100.0 * counted["discarded"] / counted["dispatched"]
