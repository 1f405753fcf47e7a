"""The sign_bytes_s tag of commit.assemble (clock readings around every
vote_sign_bytes, summed by the program on its traced path), per decision."""

from benchmark.harness import spans


def read(run):
    return spans.tag_ms_per_decision(run, "commit.assemble", "sign_bytes_s")
