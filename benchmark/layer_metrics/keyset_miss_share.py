"""Key-set lookups that found neither the exact pubkey sequence nor the key
set and rebuilt the comb tables, over all lookups of the window, %: the hit
tag ("sequence", "set", "miss") of every prep.keyset. Nothing where the host
answered (no key set is looked up there)."""

from benchmark.harness import spans


def read(run):
    return spans.tag_share(run, "prep.keyset", "hit", "miss")
