"""apply.post_commit span (a height's events published on the post-commit
thread), per decision."""

from benchmark.harness import spans


def read(run):
    if not spans._program_has("events.publish_block"):
        return None      # a program from before the span's txs / events tags
    return spans.ms_per_decision(run, "apply.post_commit")
