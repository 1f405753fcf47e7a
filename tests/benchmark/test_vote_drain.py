"""PR 30: the ``localnet-5k.vote-drain`` cell on the CPU, tiny: its rehearsal
traced and untraced, the two controls (a drain that accepts every signature,
a peer queue too small for one step's votes), the refusal of a program
without the drain's spans, the generator's digest for a fixed seed, the new
per-layer readers on synthetic spans, and what the cell lists."""

import json
import os

import pytest

from benchmark.harness import datagen, drain, spec
from tests.benchmark.test_harness import _last_line, _run
from tests.benchmark.test_trace_metrics import _span, _synthetic_run

CELL = "localnet-5k.vote-drain"
NEW = ["drain_votes_per_flush", "drain_build_us_per_vote", "drain_flush_wait_ms",
       "drain_apply_us_per_vote", "drain_wal_us_per_vote",
       "drain_sigcache_hit_share", "drain_serial_share",
       "drain_host_route_share", "drain_shed_share", "recv_us_per_msg",
       "finalize_ms"]


@pytest.mark.parametrize("traced", ["0", "1"], ids=["untraced", "traced"])
def test_rehearsal_prints_the_contracts_last_line(traced):
    out = _run(["--workload", CELL, "--seed", f"300000011{traced}",
                "--seconds", "1", "--trace", traced, "--rehearse"])
    line = _last_line(out)
    notes = json.loads(out.stdout.strip().splitlines()[-2])["notes"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    got = line["metrics"]
    if traced == "0":
        assert set(got) == {"commit_p50_ms", "setup_s"}
    else:
        # every new reader reads on the CPU; of the accepted ones those that
        # need no device (the C verifier answers a rehearsal's 24 votes)
        assert set(NEW) <= set(got)
        assert got["drain_shed_share"]["value"] == 0.0
        assert got["drain_host_route_share"]["value"] == 100.0
        assert got["recv_us_per_msg"]["value"] > 0.0
        for name in ("host_prep_ms", "prep_hash_ms", "datagen_s", "warmup_s"):
            assert name in got
        assert "commit_p50_ms" not in got and "lane_fill" not in got
    assert notes["stream"]["heights_a_pass"] == 4
    assert notes["shed_in_window"] == {"live": {}, "stale": {}, "future": {}}
    passes = notes["passes"]
    assert [p["pass"] for p in passes[:2]] == ["corrupted pass", "warm-up"]
    assert passes[0]["verdicts"]["invalid"] == 4
    assert passes[0]["verdicts"]["conflict"] == 2
    assert passes[0]["signatures_checked"] == passes[0]["wal"]["votes"]
    assert len(notes["corrupted_deliveries"]) == 10
    assert notes["links"]["throttled_s"] == 0.0


def _drain_accepts_every_signature(monkeypatch):
    from tendermint_tpu.consensus.state_machine import ConsensusState

    def broken(self, queued, dc, pending):
        pending.resolve()
        return dc.commit(queued, [True] * len(queued))

    monkeypatch.setattr(ConsensusState, "_resolve_vote_flush", broken)


def _queue_too_small_for_a_step(monkeypatch):
    from tendermint_tpu.consensus import state_machine

    monkeypatch.setattr(state_machine, "MSG_QUEUE_MIN", 40)
    monkeypatch.setattr(state_machine, "MSG_QUEUE_PER_VALIDATOR", 0)
    real = spec._read_json

    def short_timeout(path):
        out = real(path)
        if path.endswith("vote-drain.json"):
            out["height_timeout_s"] = 2
        return out

    monkeypatch.setattr(spec, "_read_json", short_timeout)


@pytest.mark.parametrize("break_it, correct", [
    (None, True),
    (_drain_accepts_every_signature, False),
    (_queue_too_small_for_a_step, False),
], ids=["sound", "drain_accepts_every_signature", "queue_too_small_for_a_step"])
def test_a_broken_drain_comes_out_not_correct(break_it, correct, monkeypatch,
                                              capsys):
    bench_run = spec._module(os.path.join(spec.ROOT, "benchmark", "run.py"), "main")
    if break_it is not None:
        break_it(monkeypatch)
    rc = bench_run.main(["--workload", CELL, "--seed", "3000000113",
                         "--seconds", "0.3", "--trace", "0", "--rehearse"])
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    line = json.loads(lines[-1])
    assert line["correct"] is correct, lines[-2]
    if not correct:
        assert json.loads(lines[-2])["failures"]


def test_a_program_without_the_drains_spans_is_refused_at_load(monkeypatch, capsys):
    """The parent commit: the driver's file refuses to load there, and run.py
    exits 2 before it makes any data, traced or not."""
    from tendermint_tpu.utils import trace

    monkeypatch.setattr(trace, "CANONICAL_SPANS", {
        k: v for k, v in trace.CANONICAL_SPANS.items()
        if k not in ("consensus.vote_apply", "consensus.flush_wait")})
    bench_run = spec._module(os.path.join(spec.ROOT, "benchmark", "run.py"), "main")
    for traced in ("0", "1"):
        rc = bench_run.main(["--workload", CELL, "--seed", "3000000114",
                             "--seconds", "0.3", "--trace", traced, "--rehearse"])
        out = capsys.readouterr()
        assert rc == bench_run.EXIT_REFUSED
        assert "consensus.vote_apply" in out.err and not out.out.strip()


def test_the_generators_digest_for_a_fixed_seed(tmp_path):
    """The live chain is a function of the seed: keys, who votes, every
    signature, every block. Signed in this process by the benchmark's own
    signer or by OpenSSL, the bytes are the same (RFC 8032)."""
    from benchmark.drivers import livechain

    cell = spec.Cell(CELL)
    cfg = dict(cell.config)
    cfg["dataset"] = {**cfg["dataset"], **cfg["rehearse"]}
    ds = datagen.load_or_generate("digest", cfg, 30, data_dir=str(tmp_path),
                                  workers=0)
    chain = livechain.load_or_generate("digest", ds, cfg, 30, 4,
                                       data_dir=str(tmp_path), workers=0)
    again = livechain.load_or_generate("digest", ds, cfg, 30, 4,
                                       data_dir=str(tmp_path), workers=0)
    assert not chain.meta["cached"] and again.meta["cached"]
    assert livechain.content_digest(chain) == livechain.content_digest(again)
    assert livechain.content_digest(chain) == DIGEST_SEED_30
    assert len(chain.heights) == 4
    n = ds.vals.size()
    window = {(chain.rotate + h) % n for h in range(5)}
    assert ds.off_idx not in window and chain.node_slot not in window
    for hd in chain.heights:
        assert hd.votes[1][chain.node_slot] is None
        assert hd.votes[2][ds.off_idx] is None
        assert hd.parts.header().total >= 1


DIGEST_SEED_30 = "99a1e1d8ee234867cde12b10691611c6c078cf93f1ca59e2a07906bba4f04e46"


# --- the new readers on synthetic spans ----------------------------------------


def _drain_run():
    spans_ = [
        dict(_span("consensus.vote_drain", 10.0, 0.050, votes=1000, queued=600,
                   cache_hits=300, in_drain_copies=40, skipped=100), span_id=7),
        dict(_span("consensus.flush_wait", 10.01, 0.010, sigs=500), parent_id=7),
        dict(_span("consensus.vote_apply", 10.02, 0.020, votes=900, added=300),
             parent_id=7),
        dict(_span("consensus.vote_drain", 10.2, 0.010, votes=1000, queued=0,
                   cache_hits=1000, in_drain_copies=0, skipped=0), span_id=8),
        dict(_span("consensus.vote_apply", 10.3, 0.070, votes=1000, added=0),
             parent_id=0),
        _span("consensus.flush_wait", 10.4, 0.030, sigs=600),
        _span("consensus.wal_write", 10.0, 0.040, msgs=1000, bytes=200000),
        _span("consensus.wal_write", 10.2, 0.060, msgs=1000, bytes=200000),
        _span("consensus.vote_serial", 10.5, 0.001, why="single", votes=1),
        _span("consensus.vote_serial", 10.6, 0.010, why="late", votes=99),
        _span("consensus.finalize_commit", 10.7, 0.120),
        _span("consensus.finalize_commit", 11.7, 0.080),
        _span("prep.host_verify", 10.1, 0.001, sigs=100),
        _span("prep.launch", 10.1, 0.001, sigs=4900, lanes=8192),
    ]
    run = _synthetic_run(spans_)
    run.notes = {"shed_in_window": {"live": {"vote": 5}, "stale": {"vote": 9},
                                    "future": {}},
                 "deliveries": {"made": 1000, "dropped_at_commit": 3},
                 "recv": {"0x21": {"msgs": 10, "seconds": 0.001, "bytes": 1},
                          "0x22": {"msgs": 990, "seconds": 0.039, "bytes": 1}}}
    return run


def _reader(name):
    return spec._module(os.path.join(spec.BENCH_DIR, "layer_metrics",
                                     name + ".py"), "read").read


@pytest.mark.parametrize("name, want", [
    ("drain_votes_per_flush", 600.0),
    ("drain_build_us_per_vote", (0.050 - 0.030 + 0.010) * 1e6 / 2000),
    ("drain_flush_wait_ms", 20.0),
    ("drain_apply_us_per_vote", 0.090 * 1e6 / 1900),
    ("drain_wal_us_per_vote", 50.0),
    ("drain_sigcache_hit_share", 100.0 * 1300 / 1900),
    ("drain_serial_share", 100.0 * 100 / 2000),
    ("drain_host_route_share", 2.0),
    ("drain_shed_share", 0.5),
    ("recv_us_per_msg", 40.0),
    ("finalize_ms", 100.0),
])
def test_a_drain_reader_on_synthetic_spans(name, want):
    assert _reader(name)(_drain_run()) == pytest.approx(want)


def test_drain_readers_read_nothing_from_a_program_without_the_spans(monkeypatch):
    """Laid over the parent commit: none of the drain's new spans exists
    there, the old one carries no new tag, and no driver wrote any note."""
    from tendermint_tpu.utils import trace

    monkeypatch.setattr(trace, "CANONICAL_SPANS", {
        k: v for k, v in trace.CANONICAL_SPANS.items()
        if k not in ("consensus.vote_apply", "consensus.flush_wait",
                     "consensus.wal_write", "consensus.vote_serial",
                     "consensus.finalize_commit")})
    run = _drain_run()
    run.spans = [dict(_span("consensus.vote_drain", 10.0, 0.05, votes=1000))]
    run.notes = {}
    for name in NEW:
        assert _reader(name)(run) is None, name
    assert drain.tag_sum(run, "consensus.vote_drain", "votes") == 1000


def test_the_cell_lists_what_issue_30_says():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "localnet-5k", "vote-drain", 1)
    # by membership, never by position (D14): later PRs appended cells,
    # configurations and metrics after these
    assert [w["name"] for w in bench["workloads"]].count(CELL) == 1
    assert [c["name"] for c in bench["configs"]].count("localnet-5k") == 1
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    # what ISSUE 30 listed; PRs 35 and 36 listed more for the cell since
    assert listed >= set(NEW) | {
        "commit_p50_ms", "host_prep_ms", "prep_hash_ms", "prep_launch_ms",
        "prep_keyset_ms", "wake_ms", "kernel_us_per_sig",
        "verify_kernel_roofline", "lane_fill", "device_launches_per_decision",
        "keyset_miss_share", "device_idle_share"}
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"][0] == CELL and m["moves"] == "commit_p50_ms"
            assert m["layer"] == "consensus"
    config = spec.Cell(CELL).config
    assert config["dataset"]["validators"] == {"ed25519": 5000, "sr25519": 0}
    assert config["dataset"]["live_heights"] == 16
    assert config["assumed"]["consensus_config"]["timeout_commit_s"] == 0.0
    assert list(config["reduced"]) == ["heights"]
    assert len(config["guarantees"]) == 6
