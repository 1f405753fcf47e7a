"""Keys whose device tables were built (prep.keyset's `built` tag, the
KeyTable's count), per session: keys a hop's prefixes met for the first
time, and after an overflow clear every key of the batch again."""

from benchmark.harness import skip


def read(run):
    return skip.keyset_tag_per_sync(run, "built")
