"""Times the key table forgot every row because a build would have passed
KeyTable.MAX_ROWS (prep.keyset's `cleared` tag), per session."""

from benchmark.harness import skip


def read(run):
    return skip.keyset_tag_per_sync(run, "cleared")
