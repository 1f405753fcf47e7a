"""consensus.vote_apply over the votes it applied: _add_vote in arrival
order, with the event and the HasVote broadcast of every vote added."""

from benchmark.harness import drain


def read(run):
    return drain.us_per(run, "consensus.vote_apply", "votes")
