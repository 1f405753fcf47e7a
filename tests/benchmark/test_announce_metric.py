"""ISSUE 36's per-layer metric: ``drain_announce_msgs_per_vote`` on synthetic
``consensus.announce`` marks with known sums, that a program which writes no
such mark (a parent commit) reads as nothing and not as zero, the entry as
the issue gives it, and a traced rehearsal that prints it."""

import json
import os

import pytest

from benchmark.harness import spec
from tendermint_tpu.utils import trace
from tests.benchmark.test_harness import _last_line, _run
from tests.benchmark.test_trace_metrics import _span, _synthetic_run

NAME = "drain_announce_msgs_per_vote"
MARK = "consensus.announce"
VOTE_DRAIN = "localnet-5k.vote-drain"


def _reader():
    return spec._module(os.path.join(spec.BENCH_DIR, "layer_metrics",
                                     NAME + ".py"), "read").read


def _announce(start, votes, has_votes, bit_arrays, nbytes):
    return _span(MARK, start, 0.0, votes=votes, has_votes=has_votes,
                 bit_arrays=bit_arrays, bytes=nbytes)


RUNS = {
    # one HasVote a vote, as the parent's wire would count
    "a-has-vote-a-vote": ([_announce(10.1, 1, 1, 0, 10),
                           _announce(10.2, 3, 3, 0, 30)], 1.0),
    # two drains: an array each, and one with two HasVotes beside it
    "an-array-a-drain": ([_announce(10.1, 650, 0, 1, 715),
                          _announce(10.6, 348, 2, 1, 735),
                          _announce(11.2, 2, 2, 0, 20)], 6 / 1000),
    # spans of other names are not counted
    "beside-other-spans": ([_span("consensus.vote_apply", 10.0, 0.1, votes=900),
                            _announce(10.1, 200, 0, 2, 800)], 0.01),
}


@pytest.mark.parametrize("name", sorted(RUNS))
def test_the_reader_on_synthetic_marks_with_known_sums(name):
    marks, want = RUNS[name]
    assert _reader()(_synthetic_run(marks)) == pytest.approx(want)


@pytest.mark.parametrize("how", ["no-such-mark-in-the-program", "untraced",
                                 "none-written-in-the-window"])
def test_the_reader_reads_nothing_where_there_is_nothing_to_read(
        how, monkeypatch):
    """Laid over the parent commit: the mark is in no table and nobody
    writes it. The driver takes None as "leaves the metric out"."""
    run = _synthetic_run(RUNS["an-array-a-drain"][0])
    if how == "no-such-mark-in-the-program":
        run.spans = [s for s in run.spans if s["name"] != MARK]
        monkeypatch.setattr(trace, "CANONICAL_SPANS", {
            k: v for k, v in trace.CANONICAL_SPANS.items() if k != MARK})
    elif how == "untraced":
        run.traced = False
    else:
        run.spans = []
    assert _reader()(run) is None


def test_the_program_has_the_mark_in_its_table():
    assert MARK in trace.CANONICAL_SPANS


def test_the_benchmark_lists_the_metric_as_issue_36_says():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    # appended: after every entry PR 35 left (later entries may follow)
    assert names.index(NAME) > names.index("jit_trace_cpu_s")
    assert bench["per_layer"][names.index(NAME)] == {
        "name": NAME, "unit": "msgs/vote", "better": "lower",
        "source": "program_counter", "layer": "consensus",
        "moves": "commit_p50_ms", "workloads": [VOTE_DRAIN]}
    cell = next(w for w in bench["workloads"] if w["name"] == VOTE_DRAIN)
    assert cell["chips"] == 1


def test_a_traced_rehearsal_carries_the_metric():
    """24 validators: a bit array is ~85 bytes there, so a drain of more
    than a few votes of one kind sends it; every vote is announced."""
    out = _run(["--workload", VOTE_DRAIN, "--seed", "3600000011",
                "--seconds", "1", "--trace", "1", "--rehearse"])
    line = _last_line(out)
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"][NAME]
    assert got["unit"] == "msgs/vote" and 0.0 < got["value"] < 1.0
    assert line["metrics"]["drain_shed_share"]["value"] == 0.0
