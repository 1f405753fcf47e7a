"""state.save span (StateStore.save: the history rows and the whole State,
three validator sets, marshalled and written) of the heights applied, per
decision. A new node's own saves of the genesis state (tag height 0: every
pass builds its node inside the window) are left out."""

from benchmark.harness import spans


def read(run):
    if not run.traced or not run.decisions \
            or not spans._program_has("state.save"):
        return None
    return sum(s["duration_s"] for s in run.spans
               if s["name"] == "state.save"
               and s["tags"].get("height", 0) > 0) * 1e3 / len(run.decisions)
