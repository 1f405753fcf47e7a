"""Key-set lookups of the window that rebuilt the comb tables, %, where
decisions_per_s is the metric (keyset_miss_share's reader)."""

from benchmark.harness import spans


def read(run):
    return spans.tag_share(run, "prep.keyset", "hit", "miss")
