"""PR 35's per-layer metrics: the arithmetic of benchmark/harness/cpu.py on
synthetic span dicts with known sums, that a program whose spans carry no
``cpu_s`` and that writes no census (a parent commit) reads as nothing and
not as zero, that the shares of one census add up to the process's, that the
``sync_`` and ``catchup_`` twins are the original's reader, what each cell
lists, and traced rehearsals that print every new name."""

import json
import os

import pytest

from benchmark.harness import cpu, spec
from tendermint_tpu.utils import trace
from tests.benchmark.test_harness import _last_line, _run
from tests.benchmark.test_trace_metrics import _span, _synthetic_run

VOTE_DRAIN = "localnet-5k.vote-drain"
TIP_CELLS = ["hub-10k.tip", "hub-10k.tip-4chip", "fastsync-1k-mixed.tip",
             "hub-10k-live.tip"]
SHARES = ["height_cpu_consensus_share", "height_cpu_recv_share",
          "height_cpu_peers_share", "height_cpu_verify_share",
          "height_cpu_other_share"]
DRAIN = ["drain_apply_cpu_us_per_vote", "drain_wal_cpu_us_per_vote",
         "finalize_cpu_ms", "recv_cpu_us_per_msg", *SHARES,
         "height_cpu_process_share"]
LISTS = {
    **{name: [VOTE_DRAIN] for name in DRAIN},
    "host_prep_cpu_ms": TIP_CELLS + [VOTE_DRAIN],
    "assemble_cpu_ms": TIP_CELLS,
    "sync_host_prep_cpu_ms": ["fastsync-1k-mixed.replay"],
    "catchup_host_prep_cpu_ms": ["hub-150.fastsync"],
    "jit_trace_cpu_s": None,
}
UNITS = {"vote": "us/vote", "msg": "us/msg", "ms": "ms", "s": "s",
         "share": "%"}
MOVES = {"sync_host_prep_cpu_ms": "decisions_per_s",
         "catchup_host_prep_cpu_ms": "catchup_blocks_per_s",
         "jit_trace_cpu_s": "setup_s"}


def _reader(name):
    return spec._module(os.path.join(spec.BENCH_DIR, "layer_metrics",
                                     name + ".py"), "read").read


def _cpu_span(name, start, dur, cpu_s, thread="cs-receive", **tags):
    return {**_span(name, start, dur, **tags), "thread": thread, "cpu_s": cpu_s}


def _census(start, height, wall_s, threads, rest_s=0.0, lost=0):
    process_s = sum(threads.values()) + rest_s
    return _cpu_span("consensus.thread_cpu", start, 0.0, None, height=height,
                     wall_s=wall_s, process_s=process_s, rest_s=rest_s,
                     lost=lost, threads=threads)


def _recv(start, height, msgs, seconds, cpu_s, threads):
    mark = _cpu_span("consensus.recv", start, 0.0, None, height=height,
                     msgs=msgs, seconds=seconds, bytes=200 * msgs,
                     threads=threads)
    mark["tags"]["cpu_s"] = cpu_s
    return mark


def _drain_run(monkeypatch):
    """Two heights of a traced vote-drain window, with known sums."""
    ring = trace.Tracer(name="startup", cap=64, cold=True)
    monkeypatch.setattr(trace, "STARTUP", ring)
    # a nested region is inside its caller's, on the caller's thread
    ring.record("startup.jit_trace", 4.0, start=1.0, cpu_s=3.0, fun="outer")
    ring.record("startup.jit_trace", 1.5, start=2.0, cpu_s=1.25, fun="inner")
    ring.record("startup.jit_trace", 1.0, start=6.0, cpu_s=0.5)
    ring.record("startup.jit_trace", 2.0, start=7.5)          # no CPU given
    ring.record("startup.jit_trace", 9.0, start=10.5, cpu_s=8.0)  # the window
    heights = []
    for k, at in enumerate((10.0, 11.0)):
        heights += [
            _cpu_span("consensus.vote_apply", at, 0.120, 0.030, votes=1000,
                      added=500),
            _cpu_span("consensus.vote_apply", at + 0.2, 0.080, 0.010,
                      votes=1000, added=500),
            _cpu_span("consensus.wal_write", at + 0.3, 0.064, 0.020, msgs=2000,
                      bytes=400000),
            _cpu_span("consensus.finalize_commit", at + 0.4, 0.050, 0.040,
                      height=k + 1),
            _cpu_span("verify.host_prep", at + 0.1, 0.012, 0.009,
                      thread="verify-service", sigs=1000),
            # a record() on another thread than the work's: no CPU of its own
            _cpu_span("verify.host_prep", at + 0.1, 0.012, None,
                      thread="verify-service", sigs=1000),
            _cpu_span("commit.assemble", at + 0.41, 0.030, 0.027,
                      thread="cs-receive", decision=7),
            _recv(at + 0.5, k + 1, 2000, 0.090, 0.060, ["MainThread"]),
            _census(at + 0.5, k + 1, 0.5, {
                "cs-receive": 0.20, "MainThread": 0.07,
                "cs-gossip-abcdef01": 0.03, "mconn-send": 0.04,
                "mconn-recv": 0.03, "verify-service": 0.02,
                "votedrain-far-end": 0.01, "post-commit": 0.02},
                rest_s=0.03, lost=k),
        ]
    return _synthetic_run(heights, decisions=2)


WANT = {
    "drain_apply_cpu_us_per_vote": 0.080 * 1e6 / 4000,
    "drain_wal_cpu_us_per_vote": 0.040 * 1e6 / 4000,
    "finalize_cpu_ms": 40.0,
    "recv_cpu_us_per_msg": 0.120 * 1e6 / 4000,
    "height_cpu_consensus_share": 40.0,
    "height_cpu_recv_share": 14.0,
    "height_cpu_peers_share": 20.0,
    "height_cpu_verify_share": 4.0,
    "height_cpu_other_share": 12.0,       # two threads and rest_s
    "height_cpu_process_share": 90.0,
    "host_prep_cpu_ms": 9.0,
    "assemble_cpu_ms": 27.0,
    "sync_host_prep_cpu_ms": 9.0,
    "catchup_host_prep_cpu_ms": 9.0,
    "jit_trace_cpu_s": 3.5,               # outermost only, before the window
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_on_synthetic_spans_with_known_sums(name, monkeypatch):
    assert set(WANT) == set(LISTS)
    assert _reader(name)(_drain_run(monkeypatch)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_reads_nothing_from_a_program_without_cpu_or_census(
        name, monkeypatch):
    """Laid over the parent commit: ``Span`` has no ``cpu_s``, ``as_dict()``
    no such key, the two marks are in no table and nobody writes them."""
    run = _drain_run(monkeypatch)
    run.spans = [{k: v for k, v in s.items() if k not in ("cpu_s", "thread")}
                 for s in run.spans
                 if s["name"] not in (cpu.CENSUS, cpu.RECV)]
    monkeypatch.setattr(cpu, "_program_has_cpu", lambda: False)
    monkeypatch.setattr(trace, "CANONICAL_SPANS", {
        k: v for k, v in trace.CANONICAL_SPANS.items()
        if k not in (cpu.CENSUS, cpu.RECV)})
    assert _reader(name)(run) is None
    # and an untraced run, whatever the program
    monkeypatch.undo()
    run = _drain_run(monkeypatch)
    run.traced = False
    if name != "jit_trace_cpu_s":         # the ring is always on
        assert _reader(name)(run) is None


def test_the_parent_is_told_from_the_change_by_the_spans_own_field():
    assert cpu._program_has_cpu()
    assert {"thread", "cpu_s"} <= set(trace.Span("x", 0.0, 0.0, {}).as_dict())


def test_a_window_without_a_census_mark_has_no_shares(monkeypatch):
    run = _drain_run(monkeypatch)
    run.spans = [s for s in run.spans if s["name"] != cpu.CENSUS]
    for name in SHARES + ["height_cpu_process_share"]:
        assert _reader(name)(run) is None, name
    # the spans' own CPU is still there, and a span nobody wrote is a true 0
    assert _reader("finalize_cpu_ms")(run) == pytest.approx(40.0)
    run.spans = [s for s in run.spans if s["name"] != "commit.assemble"]
    assert _reader("assemble_cpu_ms")(run) == 0.0
    assert _reader("recv_cpu_us_per_msg")(_synthetic_run([])) is None


def test_the_shares_of_one_census_add_up_to_the_process_share(monkeypatch):
    run = _drain_run(monkeypatch)
    shares = [_reader(name)(run) for name in SHARES]
    assert sum(shares) == pytest.approx(
        _reader("height_cpu_process_share")(run))
    # every thread is in one group; a connection's receive thread that calls
    # receive is the receive side's and not the peers'
    recv = {"mconn-recv"}
    assert cpu.group_of("mconn-recv", recv) == "recv"
    assert cpu.group_of("mconn-recv", set()) == "peers"
    assert cpu.group_of("mconn-send", recv) == "peers"
    assert cpu.group_of("cs-gossip-0a1b2c3d", recv) == "peers"
    assert cpu.group_of("cs-receive", recv) == "consensus"
    assert cpu.group_of("verify-service", recv) == "verify"
    assert cpu.group_of("votedrain-far-end", recv) == "other"
    for s in run.spans:
        if s["name"] == cpu.RECV:
            s["tags"]["threads"] = ["mconn-recv"]
    assert _reader("height_cpu_recv_share")(run) == pytest.approx(6.0)
    assert _reader("height_cpu_peers_share")(run) == pytest.approx(14.0)
    assert _reader("height_cpu_other_share")(run) == pytest.approx(26.0)


@pytest.mark.parametrize("twin", ["sync_host_prep_cpu_ms",
                                  "catchup_host_prep_cpu_ms"])
def test_a_twin_is_the_reader_of_the_metric_it_is_named_after(twin):
    from benchmark.layer_metrics import host_prep_cpu_ms

    assert _reader(twin) is host_prep_cpu_ms.read


@pytest.mark.parametrize("name", sorted(LISTS))
def test_the_benchmark_lists_the_metric_as_issue_35_says(name):
    """What the entry protects, never where it stands: the metric is listed
    once, for the cells ISSUE 35 named (a later PR may add cells after
    them), with its ``moves`` and its unit."""
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(entries) == 1
    entry = entries[0]
    if LISTS[name] is None:
        assert "workloads" not in entry               # read in every cell
    else:
        assert entry["workloads"][:len(LISTS[name])] == LISTS[name]
    assert entry["moves"] == MOVES.get(name, "commit_p50_ms")
    assert entry["unit"] == UNITS[name.rsplit("_", 1)[-1]]
    # the two cells whose lists tests/benchmark pins get none of them
    for pinned in ("light-hub-150.sync", "hub-150-churn.fastsync"):
        assert pinned not in (entry.get("workloads") or [])


@pytest.mark.parametrize("cell, seed, names", [
    (VOTE_DRAIN, "3500000011", DRAIN + ["host_prep_cpu_ms", "jit_trace_cpu_s"]),
    ("hub-10k.tip", "3500000012",
     ["host_prep_cpu_ms", "assemble_cpu_ms", "jit_trace_cpu_s"]),
], ids=["vote-drain", "hub-10k.tip"])
def test_a_traced_rehearsal_carries_every_new_name_of_its_cell(cell, seed, names):
    out = _run(["--workload", cell, "--seed", seed, "--seconds", "1",
                "--trace", "1", "--rehearse"])
    line = _last_line(out)
    assert line["correct"] is True and line["failed"] == 0
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(names) <= set(got)
    if cell == VOTE_DRAIN:
        assert sum(got[n] for n in SHARES) == pytest.approx(
            got["height_cpu_process_share"], abs=2.0)
        assert 0.0 < got["drain_apply_cpu_us_per_vote"] <= \
            got["drain_apply_us_per_vote"]
        # the callers' whole CPU over a height, not only inside receive
        assert 0.0 < got["recv_cpu_us_per_msg"]
        assert got["height_cpu_consensus_share"] > 0.0
        assert got["height_cpu_recv_share"] > 0.0
    else:
        assert 0.0 < got["assemble_cpu_ms"] <= got["assemble_ms"] * 1.05
    assert got["host_prep_cpu_ms"] <= got["host_prep_ms"] * 1.05
