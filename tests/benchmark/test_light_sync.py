"""PR 26: a sequential light client through range_verify's windows.

The batched ``Client`` against the per-header loop (``Client.
_verify_sequential_per_header``) AND against the benchmark's plain reference
(benchmark/reference/light_sync.py) on seeded chains of tens of validators
with Zipf power, absent and nil votes: a clean chain and every corruption of
the ``lightsync`` driver, at window boundaries, with a validator-set change
inside a window, with two defects in one range, with the trusting period
run out, and with a primary that fails in mid range. Then the driver itself
(rehearsals, the two controls) and the new per-layer readers on synthetic
spans."""

import ast
import json
import os

import pytest

from benchmark.harness import datagen, light, record, spec
from benchmark.reference import light_sync
from tendermint_tpu.light import SEQUENTIAL, Client, DBStore, MockProvider, TrustOptions
from tendermint_tpu.light import range_verify
from tendermint_tpu.light import verifier as lv
from tendermint_tpu.light.provider import ErrNoResponse
from tendermint_tpu.store.db import MemDB
from tendermint_tpu.types.ttime import Time
from tests.benchmark.test_harness import _last_line, _run
from tests.benchmark.test_trace_metrics import _span, _synthetic_run

CELL = "light-hub-150.sync"
lightsync = spec._module(os.path.join(spec.BENCH_DIR, "drivers", "lightsync.py"),
                         "Driver")
VALIDATORS, HEIGHTS, WINDOW = 24, 41, 5


@pytest.fixture(scope="module")
def drv(tmp_path_factory):
    """The driver over a seeded 41-height chain of 24 validators (Zipf
    power, 10% absent, 5% nil): its light blocks, prefixes, corruptions."""
    cell = spec.Cell(CELL)
    cfg = dict(cell.config)
    cfg["dataset"] = {**cfg["dataset"], **cfg["rehearse"], "heights": HEIGHTS}
    ds = datagen.load_or_generate("light-test", cfg, 2601,
                                  data_dir=str(tmp_path_factory.mktemp("data")),
                                  workers=0)
    run = record.Run(cell=cell, seed=2601, seconds=1.0, traced=False,
                     rehearse=True)
    return lightsync.Driver(run, ds, cell.traffic)


@pytest.fixture
def small_windows(monkeypatch):
    """Five light blocks a window: heights 2-6, 7-11, 12-16, ..."""
    monkeypatch.setattr(range_verify, "window_slots",
                        lambda: WINDOW * VALIDATORS)


def _per_header(monkeypatch):
    monkeypatch.setattr(Client, "_verify_sequential",
                        Client._verify_sequential_per_header)


def _outcome(drv, chain, primary=None, witness=None, *spare):
    """One session -> what a caller can see of it."""
    if primary is None:
        primary, witness = drv._providers(chain)
    client, store, err = drv._session(chain, primary, witness, *spare)
    first = store.first_light_block_height()
    latest = store.latest_light_block()
    return {
        "error": None if err is None else (type(err).__name__, str(err)),
        "index": getattr(getattr(err, "reason", None), "index", None),
        "stored": [first, latest.height, store.size()],
        "hashes": [store.light_block(h).hash()
                   for h in range(first, latest.height + 1)],
        "primary_is_witness": client is not None and client.primary is witness,
        "fallbacks": getattr(client, "range_fallbacks", 0),
    }


def _both(drv, chain, monkeypatch, providers=lambda: (None, None)):
    batched = _outcome(drv, chain, *providers())
    with monkeypatch.context() as m:
        _per_header(m)
        loop = _outcome(drv, chain, *providers())
    assert batched == loop
    assert batched["fallbacks"] == 0
    return batched


def _refused_height(outcome) -> int:
    return outcome["stored"][1] + 1


# --- the reference stands alone -------------------------------------------------


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(spec.BENCH_DIR, "reference", "light_sync.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    names += [n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    assert names and all(n == "hashlib" or n == "__future__"
                         or n.startswith("benchmark.reference") for n in names)


def test_the_reference_hashes_a_validator_set_as_the_program_does(drv):
    vals = drv.ds.vals
    assert light_sync.validators_hash(
        [(v.address, v.pub_key.bytes(), v.voting_power)
         for v in reversed(vals.validators)]) == vals.hash()


# --- differential: batched client == per-header loop == plain reference ----------


def test_a_clean_chain_is_accepted_by_all_three(drv, small_windows, monkeypatch):
    out = _both(drv, drv.chain, monkeypatch)
    assert out["error"] is None and out["stored"] == [1, HEIGHTS, HEIGHTS]
    assert out["hashes"] == [drv.chain[h].hash() for h in range(1, HEIGHTS + 1)]
    assert drv._reference(drv.chain, 2, HEIGHTS) == (
        list(range(2, HEIGHTS + 1)), None)


@pytest.mark.parametrize("name", [name for name, _fn in lightsync.CORRUPTIONS])
def test_each_corruption_of_the_driver(drv, small_windows, monkeypatch, name):
    chain = dict(drv.chain)
    want = dict(lightsync.CORRUPTIONS)[name](drv, chain)
    out = _both(drv, chain, monkeypatch)
    accepted, refusal = drv._reference(chain, 2, min(HEIGHTS, want["span"][1] + 1))
    if refusal is None:
        assert out["error"] is None and out["stored"][1] == HEIGHTS
        return
    height, kind, index = refusal
    assert height == want["height"] == _refused_height(out)
    assert out["stored"] == [1, height - 1, height - 1]
    assert out["error"][0] == lightsync.KINDS[kind][0]
    assert lightsync.KINDS[kind][1] in out["error"][1]
    assert out["index"] == index
    assert accepted == list(range(2, height))


# windows hold heights 2-6, 7-11, 12-16: first, last, and one either side
@pytest.mark.parametrize("heights", [(7,), (11,), (6,), (11, 12), (12, 11),
                                     (16, 17, 9)],
                         ids=["first", "last", "last_of_the_first_window",
                              "straddling", "straddling_given_in_reverse",
                              "three_defects_the_lowest_wins"])
def test_bad_signatures_at_window_boundaries(drv, small_windows, monkeypatch,
                                             heights):
    chain = dict(drv.chain)
    lanes = {}
    for h in heights:
        lanes[h] = drv.prefixes[h][h % len(drv.prefixes[h])]
        sig = chain[h].signed_header.commit.signatures[lanes[h]].signature
        lightsync._replace_sig(chain, h, lanes[h],
                               lightsync._flip(sig, 2601, h))
    out = _both(drv, chain, monkeypatch)
    low = min(heights)
    assert out["stored"] == [1, low - 1, low - 1]
    assert out["error"][0] == "ErrInvalidHeader" and out["index"] == lanes[low]
    assert drv._reference(chain, 2, low + 1)[1] == (
        low, "wrong_signature", lanes[low])


@pytest.mark.parametrize("structural, signature", [(9, 8), (8, 9), (11, 12)])
def test_two_defects_of_different_kinds_the_lower_height_is_reported(
        drv, small_windows, monkeypatch, structural, signature):
    """A broken next_validators_hash at ``structural - 1`` (refused at
    ``structural``) and a bad signature at ``signature``."""
    chain = dict(drv.chain)
    lightsync._break_next_validators_hash(drv, chain, structural - 1)
    idx = drv.prefixes[signature][0]
    sig = chain[signature].signed_header.commit.signatures[idx].signature
    lightsync._replace_sig(chain, signature, idx,
                           lightsync._flip(sig, 2601, "two"))
    out = _both(drv, chain, monkeypatch)
    low = min(structural, signature)
    assert _refused_height(out) == low
    want_kind = "validators_hash_chain" if low == structural else "wrong_signature"
    assert out["error"][0] == lightsync.KINDS[want_kind][0]
    assert drv._reference(chain, 2, low + 1)[1][:2] == (low, want_kind)


def test_a_structural_defect_and_a_bad_commit_at_one_height(drv, small_windows,
                                                             monkeypatch):
    """At the refused height the structural checks come first, as in
    verify_adjacent: a header that does not follow AND whose commit has the
    wrong number of slots reports the linkage."""
    from tendermint_tpu.types.block import Commit
    from tendermint_tpu.types.light_block import LightBlock, SignedHeader

    chain = dict(drv.chain)
    lightsync._break_next_validators_hash(drv, chain, 8)
    sh = chain[9].signed_header
    short = Commit(height=sh.commit.height, round=sh.commit.round,
                   block_id=sh.commit.block_id,
                   signatures=list(sh.commit.signatures[:-1]))
    chain[9] = LightBlock(SignedHeader(sh.header, short), chain[9].validator_set)
    out = _both(drv, chain, monkeypatch)
    assert _refused_height(out) == 9 and out["error"][0] == "LightClientError"
    # and alone, the short commit is the per-header loop's wrapped size error
    chain[8] = drv.chain[8]
    out = _both(drv, chain, monkeypatch)
    assert _refused_height(out) == 9
    assert out["error"][0] == "ErrInvalidHeader" and "wrong set size" in out["error"][1]
    assert drv._reference(chain, 2, 10)[1] == (9, "commit_size", None)


def test_the_trusting_period_runs_out(drv, small_windows, monkeypatch):
    """With one `now` for a whole call, only the header a range starts from
    can have expired (each later one is younger): a client that synced to
    height 10 in time and asks for the tip a trusting period later."""
    period = drv.options["trusting_period_s"]

    def late_session():
        primary, witness = drv._providers(drv.chain)
        store = DBStore(MemDB())
        client = Client(drv.ds.chain_id,
                        TrustOptions(period, 1, drv.chain[1].hash()), primary,
                        [witness], store, verification_mode=SEQUENTIAL)
        t10 = drv.chain[10].signed_header.header.time
        client.verify_light_block_at_height(10, Time(t10.seconds + 5, 0))
        with pytest.raises(lv.ErrOldHeaderExpired) as ei:
            client.verify_light_block_at_height(
                HEIGHTS, Time(t10.seconds + int(period), 0))
        return str(ei.value), store.latest_light_block().height, store.size()

    batched = late_session()
    with monkeypatch.context() as m:
        _per_header(m)
        assert late_session() == batched
    assert batched[1:] == (10, 10)
    t10_ns = drv.chain[10].signed_header.header.time.unix_ns()
    records = [lightsync._record(drv.chain[h], drv.ds.chain_id) for h in (10, 11)]
    assert light_sync.sync(records[0], records[1:], int(period * 1e9),
                           t10_ns + int(period * 1e9), 10 ** 10)[1] == (
        11, "trusted_header_expired", None)


class _FailsFrom(MockProvider):
    """A primary that answers for the target (a sync asks for it first) and
    for nothing else from a height on."""

    def __init__(self, chain_id, blocks, dead_from):
        super().__init__(chain_id, blocks)
        self.dead_from = dead_from

    def light_block(self, height):
        if self.dead_from <= height < HEIGHTS:
            raise ErrNoResponse(f"no answer for height {height}")
        return super().light_block(height)


@pytest.mark.parametrize("dead_from", [9, 12], ids=["mid_window", "window_start"])
def test_the_primary_fails_in_mid_range_and_a_witness_takes_over(
        drv, small_windows, monkeypatch, dead_from):
    def providers():
        # two witnesses: the detector needs one left after the promotion
        return (_FailsFrom(drv.ds.chain_id, drv.chain, dead_from),
                MockProvider(drv.ds.chain_id, drv.chain),
                MockProvider(drv.ds.chain_id, drv.chain))

    out = _both(drv, drv.chain, monkeypatch, providers)
    assert out["error"] is None and out["stored"][1] == HEIGHTS
    assert out["primary_is_witness"]


def test_a_primary_that_fails_above_a_refused_header_is_not_replaced(
        drv, small_windows, monkeypatch):
    """The window 7-11 is fetched whole before it is verified: the primary
    dies at 10 and a witness is promoted, but height 8 is refused, and the
    per-header loop never asked for 10."""
    chain = dict(drv.chain)
    idx = drv.prefixes[8][0]
    sig = chain[8].signed_header.commit.signatures[idx].signature
    lightsync._replace_sig(chain, 8, idx, lightsync._flip(sig, 2601, 8))

    def providers():
        return (_FailsFrom(drv.ds.chain_id, chain, 10),
                MockProvider(drv.ds.chain_id, chain))

    out = _both(drv, chain, monkeypatch, providers)
    assert _refused_height(out) == 8 and not out["primary_is_witness"]


def test_nobody_answers_for_a_height_the_headers_below_it_are_kept(
        drv, small_windows, monkeypatch):
    def providers():
        return (_FailsFrom(drv.ds.chain_id, drv.chain, 9),
                _FailsFrom(drv.ds.chain_id, drv.chain, 9))

    out = _both(drv, drv.chain, monkeypatch, providers)
    assert out["error"][0] == "ErrNoResponse" and out["stored"] == [1, 8, 8]


# --- a validator set that changes inside a window ---------------------------------


def _changing_chain(n=16, change_at=9):
    """Heights 1..n; from ``change_at`` on another set signs (one validator
    gone, one new, one re-weighted): header change_at - 1 names it as next."""
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.types.light_block import LightBlock, SignedHeader
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import ValidatorSet
    from tests.test_light import _mk_header, _sign_commit, CHAIN_ID

    keys = [ed25519.gen_priv_key(bytes([40 + i]) * 32) for i in range(13)]
    powers = [max(1, round(1000 / i ** 0.8)) for i in range(1, 14)]

    def signers(members):
        """(private keys in the set's order, the set) of {key index: power}."""
        vals = ValidatorSet([Validator.new(keys[i].pub_key(), power)
                             for i, power in members.items()])
        by_addr = {keys[i].pub_key().address(): keys[i] for i in members}
        return [by_addr[v.address] for v in vals.validators], vals

    old = signers({i: powers[i] for i in range(12)})
    new = signers({**{i: powers[i] for i in range(11)}, 5: 700, 12: 55})
    chain = {}
    for h in range(1, n + 1):
        privs, vals = old if h < change_at else new
        nxt = old[1] if h + 1 < change_at else new[1]
        header = _mk_header(h, 10 * h, vals, nxt)
        skip = (h % len(privs),) if h % 3 else ()
        chain[h] = LightBlock(SignedHeader(header, _sign_commit(
            header, vals, privs, skip=skip)), vals.copy())
    return CHAIN_ID, chain


def _sync(chain_id, chain, target, now_s):
    from tests.test_light import TRUST_PERIOD, t

    store = DBStore(MemDB())
    client = Client(chain_id, TrustOptions(TRUST_PERIOD, 1, chain[1].hash()),
                    MockProvider(chain_id, chain),
                    [MockProvider(chain_id, chain)], store,
                    verification_mode=SEQUENTIAL)
    try:
        client.verify_light_block_at_height(target, t(now_s))
        err = None
    except Exception as e:  # noqa: BLE001 - compared, not handled
        err = (type(e).__name__, str(e))
    return err, store.latest_light_block().height, store.size(), client.range_fallbacks


@pytest.mark.parametrize("tamper", [None, "old_set_signs_on", "bad_signature_after"])
def test_the_validator_set_changes_inside_a_window(monkeypatch, tamper):
    monkeypatch.setattr(range_verify, "window_slots", lambda: 6 * 12)
    chain_id, chain = _changing_chain()     # windows 2-7, 8-13: change at 9
    refused = None
    if tamper == "old_set_signs_on":
        # height 9 as the OLD set would have written it: it does not follow
        # from header 8, which names the new set as next
        _id, unchanged = _changing_chain(change_at=99)
        chain[9], refused = unchanged[9], 9
    elif tamper == "bad_signature_after":
        sh = chain[10].signed_header
        commit = sh.commit
        bad = bytearray(commit.signatures[0].signature)
        bad[3] ^= 4
        commit.signatures[0].signature = bytes(bad)
        refused = 10
    batched = _sync(chain_id, chain, 16, 500)
    with monkeypatch.context() as m:
        _per_header(m)
        assert _sync(chain_id, chain, 16, 500) == batched
    err, latest, size, fallbacks = batched
    assert fallbacks == 0
    if refused is None:
        assert err is None and (latest, size) == (16, 16)
    else:
        assert err is not None and (latest, size) == (refused - 1, refused - 1)
    records = [lightsync._record(chain[h], chain_id) for h in range(1, 17)]
    accepted, refusal = light_sync.sync(records[0], records[1:],
                                        3 * 3600 * 10 ** 9,
                                        (1_700_000_000 + 500) * 10 ** 9, 10 ** 10)
    assert (refusal[0] if refusal else None) == refused
    assert accepted == list(range(2, refused or 17))


# --- the range path is the path: launches, and no per-header commit check ---------


def test_a_device_sized_range_goes_out_in_chunk_sized_launches(drv, monkeypatch):
    """Chunks of 64 lanes and a crossover of 32, the C verifier standing in
    for the kernel: the sync leaves in launches of at most 64 signatures
    through the verify service, pinned to the device route, and
    verify_commit_light runs once (the trust root's own check)."""
    from tendermint_tpu.crypto import verify_service
    from tendermint_tpu.ops import ed25519_batch
    from tendermint_tpu.types.validator_set import ValidatorSet

    launches, light_calls = [], []
    monkeypatch.setattr(range_verify, "_chunk_lanes", lambda: 64)
    monkeypatch.setattr(ed25519_batch, "host_crossover", lambda: 32)

    def stand_in(items, force_device=False):
        launches.append((len(items), force_device))
        return ed25519_batch._dispatch_host(items, len(items))

    monkeypatch.setattr(ed25519_batch, "dispatch_batch", stand_in)
    real = ValidatorSet.verify_commit_light
    monkeypatch.setattr(ValidatorSet, "verify_commit_light",
                        lambda self, *a: (light_calls.append(1), real(self, *a))[1])
    svc = verify_service.get()
    before, requests = svc.launches, svc.requests
    out = _outcome(drv, drv.chain)
    assert out["error"] is None and out["stored"][1] == HEIGHTS
    assert out["fallbacks"] == 0 and len(light_calls) == 1
    sizes = [n for n, _forced in launches]
    assert sum(sizes) == drv.sigs + len(drv.prefixes[1])
    ranged = [(n, forced) for n, forced in launches if forced]
    assert sum(n for n, _f in ranged) == drv.sigs
    # one request a chunk of at most 64 signatures; requests that wait
    # together share a launch, so there are no more launches than requests
    assert svc.requests - requests >= -(-drv.sigs // 64)
    assert 1 <= len(ranged) == svc.launches - before <= svc.requests - requests
    # and a refusal found by a later launch leaves the store as the loop does
    chain = dict(drv.chain)
    want = lightsync.flipped_bit_in_prefix(drv, chain)
    out = _outcome(drv, chain)
    assert out["stored"] == [1, want["height"] - 1, want["height"] - 1]
    assert out["index"] == want["lane"]


def test_a_range_under_the_crossover_is_one_flush_on_the_host(drv, monkeypatch):
    from tendermint_tpu.crypto import verify_service
    from tendermint_tpu.ops import ed25519_batch

    flushes = []
    real = ed25519_batch.dispatch_batch
    monkeypatch.setattr(ed25519_batch, "host_crossover", lambda: 2048)
    monkeypatch.setattr(
        ed25519_batch, "dispatch_batch",
        lambda items, force_device=False: (
            flushes.append((len(items), force_device)),
            real(items, force_device=force_device))[1])
    before = verify_service.get().launches
    assert _outcome(drv, drv.chain)["error"] is None
    assert flushes == [(len(drv.prefixes[1]), False), (drv.sigs, False)]
    assert verify_service.get().launches == before


def test_machinery_that_fails_falls_back_to_the_loop_and_says_so(drv, monkeypatch):
    def broken(*_a, **_kw):
        raise RuntimeError("the range path broke")

    monkeypatch.setattr(range_verify, "verify_window", broken)
    out = _outcome(drv, drv.chain)
    assert out["error"] is None and out["stored"][1] == HEIGHTS
    assert out["fallbacks"] == 1


# --- the driver -----------------------------------------------------------------------


def test_a_session_starts_with_no_key_set_on_the_device(drv):
    """Sessions replay one chain; a client that starts behind has seen none
    of it. The driver empties both key-set caches before each session."""
    from tendermint_tpu.ops import ed25519_batch

    ed25519_batch._KS_CACHE[b"sequence"] = ("tables", "index")
    ed25519_batch._KS_UNIQ_CACHE[b"set"] = "tables"
    drv._forget_key_sets()
    assert not ed25519_batch._KS_CACHE and not ed25519_batch._KS_UNIQ_CACHE
    assert drv.run.notes["key_set_caches_emptied"] == 2


def test_rehearsal_prints_the_contracts_last_line():
    out = _run(["--workload", CELL, "--seed", "2600000111", "--seconds", "1",
                "--trace", "0", "--rehearse"])
    line = _last_line(out)
    notes = json.loads(out.stdout.strip().splitlines()[-2])["notes"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"catchup_blocks_per_s", "setup_s"}
    assert notes["session"]["headers"] == 40 and notes["reference_heights"] == 32
    sessions = notes["corrupted_sessions"]
    assert len(sessions) == 7
    assert [s["reference"] is None for s in sessions] == [
        False, False, True, False, False, False, False]
    assert sessions[-1]["reference"][1] == "validators_hash_chain"


def test_traced_rehearsal_prints_every_light_metric_the_cpu_can_carry():
    line = _last_line(_run(["--workload", CELL, "--seed", "2600000112",
                            "--seconds", "1", "--trace", "1", "--rehearse"]))
    assert line["correct"] is True
    got = line["metrics"]
    for phase in ("fetch", "assemble", "structure", "wait", "replay", "store"):
        assert got[f"light_{phase}_us_per_header"]["value"] >= 0.0
        assert got[f"light_{phase}_us_per_header"]["unit"] == "us/header"
    assert got["light_store_us_per_header"]["value"] > 0.0
    assert got["light_range_fallbacks"]["value"] == 0.0
    # the C verifier answered: no launch, no lane, no key set, no trace
    for name in ("light_headers_per_launch", "catchup_lane_fill",
                 "catchup_keyset_miss_share", "light_verify_kernel_roofline"):
        assert name not in got


def _accepts_every_signature(monkeypatch):
    monkeypatch.setattr(range_verify, "_tally", lambda *_a: None)


def _saves_a_window_before_it_verified(monkeypatch):
    real = range_verify.verify_window

    def eager(trusted, chain, period, now, drift=10.0, save=None, tr=None):
        if save is not None:
            for lb in chain:
                save(lb)
        return real(trusted, chain, period, now, drift, save, tr)

    monkeypatch.setattr(range_verify, "verify_window", eager)


@pytest.mark.parametrize("break_it, correct", [
    (None, True),
    (_accepts_every_signature, False),
    (_saves_a_window_before_it_verified, False),
], ids=["sound", "range_path_accepts_every_signature",
        "saves_a_window_before_it_verified"])
def test_a_broken_range_path_comes_out_not_correct(break_it, correct,
                                                   monkeypatch, capsys):
    bench_run = spec._module(os.path.join(spec.ROOT, "benchmark", "run.py"), "main")
    if break_it is not None:
        break_it(monkeypatch)
    rc = bench_run.main(["--workload", CELL, "--seed", "2600000113",
                         "--seconds", "0.3", "--trace", "0", "--rehearse"])
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    line = json.loads(lines[-1])
    assert line["correct"] is correct, lines[-2]
    if not correct:
        assert json.loads(lines[-2])["failures"]


# --- the new readers on synthetic spans ---------------------------------------------------


def _light_run():
    run = _synthetic_run([
        _span("light.fetch", 10.0, 0.010), _span("light.fetch", 10.5, 0.030),
        _span("light.assemble", 10.1, 0.200),
        _span("light.structure", 10.2, 0.050),
        _span("light.wait", 10.3, 0.008), _span("light.wait", 10.4, 0.0),
        _span("light.replay", 10.3, 0.004),
        _span("light.store", 10.4, 1.2), _span("light.store", 11.4, 1.2),
        _span("light.range", 10.0, 1.5, headers=437),
        _span("light.range", 11.0, 0.5, headers=90, fallback=1),
    ])
    run.passes = [(10.0, 10.9, 2000), (11.0, 11.9, 2000)]
    run.counters = {"launches": (100, 148)}
    return run


def _reader(name):
    return spec._module(os.path.join(spec.BENCH_DIR, "layer_metrics",
                                     name + ".py"), "read").read


@pytest.mark.parametrize("name, want", [
    ("light_fetch_us_per_header", 10.0),
    ("light_assemble_us_per_header", 50.0),
    ("light_structure_us_per_header", 12.5),
    ("light_wait_us_per_header", 2.0),
    ("light_replay_us_per_header", 1.0),
    ("light_store_us_per_header", 600.0),
    ("light_headers_per_launch", 4000 / 48),
    ("light_range_fallbacks", 0.5),
])
def test_a_light_reader_on_synthetic_spans(name, want):
    assert _reader(name)(_light_run()) == pytest.approx(want)


def test_light_readers_read_nothing_from_a_program_without_the_spans(monkeypatch):
    """Laid over the parent commit: no light.* span exists there, and its
    per-header client never reaches the verify service."""
    from tendermint_tpu.utils import trace

    monkeypatch.setattr(trace, "CANONICAL_SPANS", {
        k: v for k, v in trace.CANONICAL_SPANS.items()
        if k.startswith("light.gateway") or not k.startswith("light.")})
    run = _light_run()
    run.counters = {"launches": (0, 0)}
    for name in ("fetch", "assemble", "structure", "wait", "replay", "store"):
        assert _reader(f"light_{name}_us_per_header")(run) is None
    assert _reader("light_range_fallbacks")(run) is None
    assert _reader("light_headers_per_launch")(run) is None
    assert light.headers(run) == 4000


def test_a_program_without_the_range_path_is_refused_whole(monkeypatch, capsys):
    """The parent commit: its sync stays on the host, so its traced run could
    show no device operation. The driver's file refuses to load there, and
    run.py exits 2 before it makes any data, traced or not."""
    from tendermint_tpu.utils import trace

    monkeypatch.setattr(trace, "CANONICAL_SPANS", {
        k: v for k, v in trace.CANONICAL_SPANS.items() if k != "light.range"})
    bench_run = spec._module(os.path.join(spec.ROOT, "benchmark", "run.py"), "main")
    for traced in ("0", "1"):
        rc = bench_run.main(["--workload", CELL, "--seed", "2600000114",
                             "--seconds", "0.3", "--trace", traced, "--rehearse"])
        out = capsys.readouterr()
        assert rc == bench_run.EXIT_REFUSED
        assert "light.range" in out.err and not out.out.strip()


def test_the_roofline_twin_is_the_accepted_reader():
    from benchmark.layer_metrics import verify_kernel_roofline

    assert _reader("light_verify_kernel_roofline") is verify_kernel_roofline.read


def test_the_cell_lists_what_issue_26_says_and_nothing_per_decision():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "light-hub-150", "light-sync", 1)
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert listed == {
        "catchup_blocks_per_s", "catchup_requests_per_launch",
        "catchup_kernel_us_per_sig", "catchup_device_idle_share",
        "catchup_lane_fill", "catchup_keyset_miss_share",
        "light_fetch_us_per_header", "light_assemble_us_per_header",
        "light_structure_us_per_header", "light_wait_us_per_header",
        "light_replay_us_per_header", "light_store_us_per_header",
        "light_headers_per_launch", "light_range_fallbacks",
        "light_verify_kernel_roofline"}
    config = spec.Cell(CELL).config
    hub = spec.Cell("hub-150.fastsync").config
    same = {k: v for k, v in config["dataset"].items()
            if k not in ("chain_id", "heights")}
    assert same == {k: v for k, v in hub["dataset"].items()
                    if k not in ("chain_id", "heights")}
    assert config["dataset"]["heights"] == 2001
    assert spec.Cell(CELL).traffic["warmup_sessions"] == 1
