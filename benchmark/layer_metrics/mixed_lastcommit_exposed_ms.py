"""What of the LastCommit's device round trip stayed exposed: the seconds
validate_block waited in the resolve of the handle dispatched ahead (or in
verify_commit where none was fresh), tag last_commit_s of apply.validate, per
decision. The commit->apply seam exists to make this small."""

from benchmark.harness import spans


def read(run):
    if not spans._program_has("state.save"):
        return None     # a program from before the tag
    return spans.tag_ms_per_decision(run, "apply.validate", "last_commit_s")
