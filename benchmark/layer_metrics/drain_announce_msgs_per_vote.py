"""The consensus.announce marks of the window: the messages the node offered
each peer to say which votes it had added (HasVotes and VoteSetBits arrays)
over the votes they announced. 1.0 is one HasVote a vote; a program that
writes no such mark reads as nothing."""

from benchmark.harness import drain

MARK = "consensus.announce"


def read(run):
    votes = drain.tag_sum(run, MARK, "votes")
    if not votes:
        return None
    return (drain.tag_sum(run, MARK, "has_votes")
            + drain.tag_sum(run, MARK, "bit_arrays")) / votes
