"""PR 45: a light client in its default mode, skipping verification, on a
chain whose validator set rotates.

At the rehearsal size (48 validators, one replaced every two heights, 121
heights) the ``lightskip`` driver's session and the benchmark's plain
reference (benchmark/reference/light_skipping.py) give the same attempts and
stored heights on a clean chain and on every seeded corruption, with the C
verifier standing in for the kernel behind the verify service; the generator
names the heights that need a light block before it signs anything; the new
readers read synthetic spans; and the cell, the configuration and every new
metric are listed as ISSUE 45 says."""

import ast
import json
import os

import pytest

from benchmark.drivers import rotatingchain
from benchmark.harness import datagen, record, skip, spec
from benchmark.reference import canonical, light_skipping, light_sync
from tests.benchmark.test_harness import _last_line, _run
from tests.benchmark.test_trace_metrics import _span, _synthetic_run

CELL = "light-10k-rotating.skipping"
CONFIG = "light-10k-rotating"
lightskip = spec._module(os.path.join(spec.BENCH_DIR, "drivers", "lightskip.py"),
                         "Driver")
NEW_METRICS = {
    "skip_hops_per_sync": ("count", "program_span", "light client"),
    "skip_refused_per_sync": ("count", "program_span", "light client"),
    "skip_trusting_ms_per_hop": ("ms", "program_span", "light client"),
    "skip_light_ms_per_hop": ("ms", "program_span", "light client"),
    "skip_fetch_ms_per_attempt": ("ms", "program_span", "light client"),
    "skip_keys_built_per_sync": ("count", "program_counter", "registry"),
    "skip_keytable_clears_per_sync": ("count", "program_counter", "registry"),
}
# accepted metrics whose spans, counters or ring records the cell writes.
# Not catchup_device_idle_share, catchup_kernel_us_per_sig: the profiler
# keeps a tenth of an 11.8 s session's device work (PERF.md section 7)
SHARED_METRICS = {
    "catchup_blocks_per_s", "catchup_lane_fill", "catchup_keyset_miss_share",
    "catchup_requests_per_launch", "light_verify_kernel_roofline",
    "catchup_prep_keyset_ms", "catchup_host_prep_ms", "catchup_queue_ms",
    "churn_table_fill", "churn_table_build_ms"}


@pytest.fixture(scope="module")
def drv(tmp_path_factory):
    """The driver over a seeded rehearsal chain: its light blocks, its plan,
    its corruptions."""
    cell = spec.Cell(CELL)
    cfg = dict(cell.config)
    cfg["dataset"] = {**cfg["dataset"], **cfg["rehearse"]}
    data = str(tmp_path_factory.mktemp("data"))
    ds = datagen.load_or_generate("skip-test", cfg, 4501, data_dir=data, workers=0)
    run = record.Run(cell=cell, seed=4501, seconds=1.0, traced=False,
                     rehearse=True)
    real = rotatingchain.load_or_generate
    rotatingchain.load_or_generate = lambda name, cfg, seed: real(
        name, cfg, seed, data_dir=data, workers=0)
    try:
        return lightskip.Driver(run, ds, cell.traffic)
    finally:
        rotatingchain.load_or_generate = real


@pytest.fixture
def service_route(monkeypatch):
    """A crossover of 8 and the C verifier standing in for the kernel: every
    commit check of a hop leaves through the verify service, as at 10,000
    validators, and is answered by the host library."""
    from tendermint_tpu.ops import ed25519_batch

    launches = []
    monkeypatch.setattr(ed25519_batch, "host_crossover", lambda: 8)

    def stand_in(items, force_device=False):
        launches.append(len(items))
        return ed25519_batch._dispatch_host(items, len(items))

    monkeypatch.setattr(ed25519_batch, "dispatch_batch", stand_in)
    return launches


def _outcome(s):
    return {"attempts": s.attempts, "stored": s.stored(),
            "error": None if s.error is None else type(s.error).__name__}


# --- the reference stands alone -------------------------------------------------


@pytest.mark.parametrize("name", ["light_skipping.py", "canonical.py"])
def test_the_reference_imports_nothing_of_the_program(name):
    path = os.path.join(spec.BENCH_DIR, "reference", name)
    with open(path) as f:
        tree = ast.parse(f.read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    names += [n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    assert names and all(n == "__future__" or n.startswith("benchmark.reference")
                         for n in names)


def test_the_generators_set_hash_is_the_references_and_the_programs(drv):
    rot = drv.rot
    for h in (1, 2, 3, drv.target):
        rec = rot.unsigned_record(h)
        assert light_sync.validators_hash(rec["validators"]) == rot.set_hash[h - 1]
    for h, lb in drv.chain.items():
        assert lb.validator_set.hash() == rot.set_hash[h - 1]
        assert lb.signed_header.header.next_validators_hash == rot.set_hash[h]
    # every header is there and chained, signed or not, by the benchmark's
    # own hash of it, which is the program's
    assert sorted(rot.headers) == list(range(1, drv.target + 1))
    assert all(rot.headers[h].last_block_id.hash == rot.block_ids[h - 1].hash
               == rot.headers[h - 1].hash() for h in range(2, drv.target + 1))


def test_the_references_records_state_the_benchmarks_own_encodings(drv):
    """Nothing the program computed reaches the plain reference: a record's
    hash and sign bytes are canonical.py's, which the chain was hashed and
    signed with; the program's encoders give the same bytes."""
    rot = drv.rot
    for h, lb in drv.chain.items():
        rec = drv._fetch(drv.chain)(h)
        assert rec["hash"] == rot.block_ids[h].hash == lb.signed_header.header.hash()
        commit = lb.signed_header.commit
        live = [i for i, cs in enumerate(commit.signatures) if not cs.absent()]
        assert [i for i, s in enumerate(rec["slots"]) if s is not None] == live
        msgs, _spliced = commit.sign_bytes_many(rot.chain_id, live)
        assert [rec["slots"][i][2] for i in live] == msgs
    # votes for the block and votes for nil were both compared
    flags = {s[1] for h in drv.chain
             for s in drv._fetch(drv.chain)(h)["slots"] if s is not None}
    assert flags == {rotatingchain.FLAG_COMMIT, rotatingchain.FLAG_NIL}
    assert canonical.block_id(b"", 0, b"") == b"\x12\x00"


@pytest.mark.parametrize("what", ["sign bytes", "header hash"])
def test_a_program_that_encodes_otherwise_refuses_the_chain(
        drv, service_route, monkeypatch, what):
    """The chain is hashed and signed by canonical.py: a fault in the
    program's vote encoding or header hashing cannot pass on both sides."""
    import dataclasses

    from tendermint_tpu.types.block import Commit, Header
    from tendermint_tpu.types.light_block import LightBlock, SignedHeader

    # headers of its own: a hash memoised under the fault stays off drv's
    chain = {h: LightBlock(SignedHeader(
        dataclasses.replace(lb.signed_header.header, _hash_cache=None),
        lb.signed_header.commit), lb.validator_set)
        for h, lb in drv.chain.items()}
    if what == "sign bytes":
        real = Commit.sign_bytes_many

        def other(self, chain_id, idxs):
            return real(self, chain_id + "x", idxs)

        monkeypatch.setattr(Commit, "sign_bytes_many", other)
    else:
        real_fields = Header.hash_fields
        monkeypatch.setattr(Header, "hash_fields",
                            lambda self: real_fields(self)[::-1])
    s = drv._session(chain)
    assert s.error is not None and s.block is None
    attempts, _fetched, _stored, refusal = drv._reference(drv.chain)
    assert refusal is None and len(attempts) == len(drv.plan[0])


def test_the_chain_rotates_as_the_configuration_says(drv):
    d = drv.rot.d
    assert (d["rotate_keys"], d["rotate_every"], d["chain_heights"]) == (1, 2, 121)
    first, later = set(drv.rot.members(1)), set(drv.rot.members(41))
    assert len(first) == len(later) == 48
    assert first - later == set(range(20)) and later - first == set(range(48, 68))


def test_only_the_heights_a_sync_asks_for_are_signed(drv):
    attempts, fetched, stored, refusal = drv.rot.plan
    assert refusal is None and stored[0] == 1 and stored[-1] == drv.target
    assert sorted(drv.chain) == drv.rot.visited == sorted({1, *fetched})
    assert len(drv.chain) < 10
    assert fetched[0] == drv.target and len(set(fetched)) == len(fetched)
    # every accepted hop is a stored height; a refused attempt stores nothing
    assert stored[1:] == [t for _f, t, v in attempts if v is None]
    assert drv.sigs == drv.rot.plan_sigs > 0


# --- differential: the client == the plain reference -----------------------------


def test_a_clean_sync_makes_the_references_attempts(drv, service_route):
    attempts, fetched, stored, refusal = drv._reference(drv.chain)
    assert refusal is None
    s = drv._session(drv.chain)
    assert s.error is None and s.block.hash() == drv.target_hash
    assert (s.attempts, s.stored()) == drv.plan == (
        [(f, t, v is None) for f, t, v in attempts], stored)
    assert len(drv.hops) >= 3 and len(attempts) > len(drv.hops)
    # both commit checks of a hop went through the service's dispatch
    assert len(service_route) >= 2 * len(drv.hops)


@pytest.mark.parametrize("name", [name for name, _fn in lightskip.CORRUPTIONS])
def test_each_corruption_is_refused_as_the_reference_refuses_it(
        drv, service_route, name):
    failures = []
    drv.verified_in_full, drv._verified = 0, {}
    note = drv._check_corruption(
        name, dict(lightskip.CORRUPTIONS)[name], failures.append)
    assert failures == []
    height, kind, index = note["reference"]
    assert note["program"] == lightskip.KINDS[kind][0]
    assert height == note["height"]
    assert index == note["lane"]
    # the reference believed no signature of the corrupted commit: every one
    # it consulted on its way to the refusal was verified in pure Python
    if note["lane"] is not None:
        verdicts = [ok for (_pub, msg, _sig), ok in drv._verified.items()
                    if msg in drv._in_full]
        assert verdicts.count(False) == (0 if name == "signer listed twice" else 1)
        if "past it" in name:       # the trusting prefix stands before it
            assert verdicts.count(True) >= 2
    else:
        assert not drv._in_full


def test_a_corruption_the_program_misses_fails_the_check(drv, monkeypatch):
    """The comparison bites: a program whose trusting check believes every
    signature is caught by the first corruption."""
    from tendermint_tpu.ops import ed25519_batch

    import numpy as np

    def believing(items, n, route="host_c"):
        return None, lambda _f: np.ones((n,), dtype=bool)

    monkeypatch.setattr(ed25519_batch, "_dispatch_host", believing)
    monkeypatch.setattr(ed25519_batch, "_host_fallback", believing)
    failures = []
    drv.verified_in_full = 0
    name, corrupt = lightskip.CORRUPTIONS[0]
    drv._check_corruption(name, corrupt, failures.append)
    assert failures and "accepted" in failures[0]


def test_the_sampled_verifier_verifies_what_is_not_the_generators(drv):
    drv._reference(drv.chain)                   # registers the clean bytes
    rec = drv._fetch(drv.chain)(drv.target)
    i, slot = next((i, s) for i, s in enumerate(rec["slots"]) if s is not None)
    pub = rec["validators"][i][1]
    _addr, _flag, msg, sig = slot
    drv.verified_in_full = 0
    assert drv._verify_sig(pub, msg, sig)
    flipped = bytes([sig[0] ^ 1]) + sig[1:]
    assert not drv._verify_sig(pub, msg, flipped)
    assert not drv._verify_sig(pub, msg + b"x", sig)
    assert drv.verified_in_full >= 2


# --- the rehearsals ----------------------------------------------------------------


def test_rehearsal_prints_the_contracts_last_line():
    out = _run(["--workload", CELL, "--seed", "4500000111", "--seconds", "1",
                "--trace", "0", "--rehearse"])
    line = _last_line(out)
    notes = json.loads(out.stdout.strip().splitlines()[-2])["notes"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"catchup_blocks_per_s", "setup_s"}
    assert notes["session"]["headers"] == 120
    assert notes["session"]["hops"] + notes["session"]["refused"] == \
        notes["session"]["attempts"]
    sessions = notes["corrupted_sessions"]
    assert [s["reference"][1] for s in sessions] == [
        "wrong_signature", "invalid_header.wrong_signature", "double_vote",
        "validators_hash_supplied", "trusted_header_expired"]
    assert notes["stored"][0] == 1 and notes["stored"][-1] == 121


def test_traced_rehearsal_prints_every_skip_metric_the_cpu_can_carry():
    line = _last_line(_run(["--workload", CELL, "--seed", "4500000112",
                            "--seconds", "1", "--trace", "1", "--rehearse"]))
    assert line["correct"] is True
    got = line["metrics"]
    assert got["skip_hops_per_sync"]["value"] >= 3
    assert got["skip_refused_per_sync"]["value"] >= 1
    for name in ("skip_trusting_ms_per_hop", "skip_light_ms_per_hop",
                 "skip_fetch_ms_per_attempt"):
        assert got[name]["value"] > 0.0 and got[name]["unit"] == "ms"
    # the C verifier answered: no key set, no table, no launch, no trace
    for name in ("skip_keys_built_per_sync", "skip_keytable_clears_per_sync",
                 "churn_table_fill", "churn_table_build_ms",
                 "catchup_lane_fill", "light_verify_kernel_roofline"):
        assert name not in got


# --- the readers, on synthetic spans -------------------------------------------------


def _skip_run():
    """Two sessions of three attempts each, two of them accepted; a check
    is written inside its attempt (its parent is the attempt's span)."""
    spans_ = []
    ids = iter(range(1, 100))

    def attempt(t, dur, accepted, depth, frm, to, *inside):
        hop = _span("light.skip.hop", t, dur, accepted=accepted, depth=depth,
                    to=to, **{"from": frm})
        hop["span_id"] = next(ids)
        for child in inside:
            child["span_id"], child["parent_id"] = next(ids), hop["span_id"]
        return [hop, *inside]

    for k in (0, 1):
        t = 10.0 + k
        spans_ += attempt(t, 0.010, 0, 0, 1, 9,
                          _span("light.skip.trusting", t, 0.008, n=0))
        spans_ += [_span("light.skip.fetch", t + 0.01, 0.003, height=5)]
        spans_ += attempt(t + 0.02, 0.200, 1, 1, 1, 5,
                          _span("light.skip.trusting", t + 0.02, 0.080, n=17),
                          _span("light.skip.light", t + 0.10, 0.100))
        spans_ += attempt(t + 0.30, 0.300, 1, 0, 5, 9,
                          _span("light.skip.trusting", t + 0.30, 0.120, n=17),
                          _span("light.skip.light", t + 0.42, 0.140))
        spans_ += [
            _span("prep.keyset", t + 0.03, 0.05, hit="miss", built=17, cleared=0),
            _span("prep.keyset", t + 0.11, 0.05, hit="miss", built=33, cleared=1),
            _span("prep.keyset", t + 0.31, 0.001, hit="set", built=0, cleared=0),
        ]
    return _synthetic_run(spans_, decisions=2)


def _reader(name):
    return spec._module(os.path.join(spec.BENCH_DIR, "layer_metrics",
                                     name + ".py"), "read").read


@pytest.mark.parametrize("name, want", [
    ("skip_hops_per_sync", 2.0),
    ("skip_refused_per_sync", 1.0),
    ("skip_trusting_ms_per_hop", 100.0),     # the refused attempt's 8 ms: not a hop's
    ("skip_light_ms_per_hop", 120.0),
    ("skip_fetch_ms_per_attempt", 1.0),
    ("skip_keys_built_per_sync", 50.0),
    ("skip_keytable_clears_per_sync", 1.0),
])
def test_a_skip_reader_on_synthetic_spans(name, want):
    assert _reader(name)(_skip_run()) == pytest.approx(want)


def test_the_accepted_table_build_readers_read_this_cells_ring(monkeypatch):
    """churn_table_build_ms and churn_table_fill, listed for this cell too:
    a key's cost and the share of built tile rows that hold one, over the
    builds that began inside the window, whatever a decision is."""
    from tendermint_tpu.utils import trace

    ring = trace.Tracer(name="startup-test", cap=64, cold=True)
    monkeypatch.setattr(trace, "STARTUP", ring)
    ring.record("startup.table_build", 0.5, start=9.0, keys=100, rows=256)   # before
    ring.record("startup.table_build", 0.4, start=10.2, keys=100, rows=256)
    ring.record("startup.table_build", 0.8, start=11.2, keys=300, rows=512)
    run = _skip_run()
    assert _reader("churn_table_build_ms")(run) == pytest.approx(3.0)
    assert _reader("churn_table_fill")(run) == pytest.approx(100.0 * 400 / 768)
    assert _reader("catchup_prep_keyset_ms")(run) == pytest.approx(101.0)


def test_skip_readers_read_nothing_from_a_program_without_the_spans(monkeypatch):
    """Laid over the parent commit: no light.skip.* span exists there."""
    from tendermint_tpu.utils import trace

    monkeypatch.setattr(trace, "CANONICAL_SPANS", {
        k: v for k, v in trace.CANONICAL_SPANS.items()
        if not k.startswith("light.skip.")})
    run = _skip_run()
    for name in NEW_METRICS:
        assert _reader(name)(run) is None
    assert skip.attempts_per_sync(run, 1) is None


def test_a_keyset_tag_the_program_does_not_write_reads_nothing():
    run = _skip_run()
    for s in run.spans:
        s["tags"].pop("cleared", None)
    assert _reader("skip_keytable_clears_per_sync")(run) is None
    assert _reader("skip_keys_built_per_sync")(run) == pytest.approx(50.0)


def test_a_program_without_the_skipping_spans_is_refused_whole(monkeypatch, capsys):
    """The parent commit: its bisection is another sequence of attempts and
    nothing of it can be read. The driver's file refuses to load there, and
    run.py exits 2 before it makes any data, traced or not."""
    from tendermint_tpu.utils import trace

    monkeypatch.setattr(trace, "CANONICAL_SPANS", {
        k: v for k, v in trace.CANONICAL_SPANS.items() if k != "light.skip.hop"})
    bench_run = spec._module(os.path.join(spec.ROOT, "benchmark", "run.py"), "main")
    for traced in ("0", "1"):
        rc = bench_run.main(["--workload", CELL, "--seed", "4500000114",
                             "--seconds", "0.3", "--trace", traced, "--rehearse"])
        out = capsys.readouterr()
        assert rc == bench_run.EXIT_REFUSED
        assert "light.skip.hop" in out.err and not out.out.strip()


# --- the listing ---------------------------------------------------------------------


def test_the_roofline_twin_is_the_accepted_reader():
    from benchmark.layer_metrics import verify_kernel_roofline

    assert _reader("light_verify_kernel_roofline") is verify_kernel_roofline.read


def test_the_cell_lists_what_issue_45_says():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "light-skip", 1)
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert listed == SHARED_METRICS | set(NEW_METRICS)
    for name, (unit, source, layer) in NEW_METRICS.items():
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry == {"name": name, "unit": unit, "better": "lower",
                         "source": source, "layer": layer,
                         "moves": "catchup_blocks_per_s", "workloads": [CELL]}
    # one configuration, one cell, eight metrics: no other cell reads them
    assert [w["name"] for w in bench["workloads"]
            if w["config"] == CONFIG] == [CELL]


def test_the_configuration_states_its_source_and_its_guarantees():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    config = spec.Cell(CELL).config
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    for words in ("light/client_benchmark_test.go", "BenchmarkBisection",
                  "genMockNodeWithKeys", "1000, 100, 1"):
        assert words in entry["source"]
    assert entry["reduced"] == [] and config["reduced"] == {}
    for key in ("deployment", "dataset", "assumed", "device_state",
                "guarantees", "rehearse"):
        assert config[key]
    d = config["dataset"]
    assert d["validators"] == {"ed25519": 10000, "sr25519": 0}
    assert (d["voting_power"], d["absent_share"], d["nil_share"]) == (10, 0.02, 0.002)
    assert (d["chain_heights"], d["rotate_keys"], d["rotate_every"]) == (1000, 100, 1)
    assert config["assumed"]["client"] == {
        "trust_level": [1, 3], "trusting_period_s": 604800,
        "max_clock_drift_s": 10, "pruning_size": 1000, "now_after_target_s": 5}
    traffic = spec.Cell(CELL).traffic
    assert (traffic["driver"], traffic["warmup_sessions"],
            traffic["profile_decisions"]) == ("lightskip", 1, 1)
