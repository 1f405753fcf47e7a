"""ISSUE 24's per-layer reader, benchmark/layer_metrics/sign_bytes_spliced.py,
on synthetic runs: the share over the window's commit.assemble spans, nothing
from an untraced run, and nothing (not zero) from a program whose spans do
not carry the tag, which is how a parent commit reads."""

import pytest

from benchmark.layer_metrics import sign_bytes_spliced
from tests.benchmark.test_trace_metrics import _span, _synthetic_run


@pytest.mark.parametrize("tags, want", [
    ([dict(sigs=9999, spliced=9999)] * 2, 100.0),
    # a commit with nil votes beside one that is spliced whole
    ([dict(sigs=999, spliced=666), dict(sigs=667, spliced=667)],
     100.0 * 1333 / 1666),
    ([dict(sigs=999, spliced=0)], 0.0),            # no template: all fell back
    ([dict(sigs=999)], None),                      # a program before the tag
    ([dict(sigs=0, spliced=0)], None),             # nothing signed: no share
    ([], None),
], ids=["whole", "partial", "all_fallback", "parent", "no_sigs", "no_spans"])
def test_share_of_spliced_sign_bytes(tags, want):
    run = _synthetic_run(
        [_span("commit.assemble", 10.0 + k, 0.02, sign_bytes_s=0.005, **t)
         for k, t in enumerate(tags)]
        + [_span("prep.launch", 10.1, 0.001, sigs=4096, lanes=4096, spliced=1)])
    got = sign_bytes_spliced.read(run)
    assert got == (want if want is None else pytest.approx(want))


def test_an_untraced_run_reads_nothing():
    run = _synthetic_run([_span("commit.assemble", 10.0, 0.02, sigs=9, spliced=9)])
    run.traced = False
    assert sign_bytes_spliced.read(run) is None
