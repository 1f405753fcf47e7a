"""Light client: verifier semantics (port of light/verifier_test.go cases),
client sequential/skipping verification, witness detector, trusted store,
and the batched header-range verify (BASELINE config 3)."""

import pytest

import wire_reference as ref
from tendermint_tpu.crypto import ed25519
from tendermint_tpu.light import verifier as lv
from tendermint_tpu.light.client import Client, TrustOptions, SEQUENTIAL, SKIPPING
from tendermint_tpu.light.detector import ErrConflictingHeaders
from tendermint_tpu.light.provider import (
    ErrHeightTooHigh,
    ErrLightBlockNotFound,
    MockProvider,
)
from tendermint_tpu.light.range_verify import verify_header_range
from tendermint_tpu.light.store import DBStore
from tendermint_tpu.store.db import MemDB
from tendermint_tpu.types.block import Commit, CommitSig, Header
from tendermint_tpu.types.block_id import BlockID, PartSetHeader
from tendermint_tpu.types.light_block import LightBlock, SignedHeader
from tendermint_tpu.types.ttime import Time
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.types.validator_set import ValidatorSet
from tendermint_tpu.types.vote import BLOCK_ID_FLAG_COMMIT, PRECOMMIT_TYPE, Vote
from tendermint_tpu.utils import trace

CHAIN_ID = "light-test-chain"
TRUST_PERIOD = 3 * 3600.0
DRIFT = 10.0
T0 = 1_700_000_000


def t(sec):
    return Time(T0 + sec, 0)


def _mk_keys(n, power=10, seed=0):
    """power: one int for all validators, or a per-validator list."""
    powers = power if isinstance(power, (list, tuple)) else [power] * n
    pairs = []
    for i in range(n):
        priv = ed25519.gen_priv_key(bytes([(seed * 37 + i + 1) % 256]) * 32)
        pairs.append((priv, Validator.new(priv.pub_key(), powers[i])))
    vs = ValidatorSet([v for _, v in pairs])
    by_addr = {v.address: p for p, v in pairs}
    privs = [by_addr[v.address] for v in vs.validators]
    return privs, vs


def _sign_commit(header, vals, privs, *, skip=(), bad_sig=()):
    bid = BlockID(hash=header.hash(),
                  part_set_header=PartSetHeader(total=1, hash=b"\xcd" * 32))
    sigs = []
    for i, (priv, val) in enumerate(zip(privs, vals.validators)):
        if i in skip:
            sigs.append(CommitSig.new_absent())
            continue
        ts = Time(header.time.seconds, 0)
        vote = Vote(type=PRECOMMIT_TYPE, height=header.height, round=1,
                    block_id=bid, timestamp=ts,
                    validator_address=val.address, validator_index=i)
        sig = priv.sign(vote.sign_bytes(CHAIN_ID))
        if i in bad_sig:
            sig = sig[:-1] + bytes([sig[-1] ^ 1])
        sigs.append(CommitSig(BLOCK_ID_FLAG_COMMIT, val.address, ts, sig))
    return Commit(height=header.height, round=1, block_id=bid, signatures=sigs)


def _mk_header(height, time_s, vals, next_vals, last_bid=None):
    return Header(
        chain_id=CHAIN_ID, height=height, time=t(time_s),
        last_block_id=last_bid or BlockID(),
        validators_hash=vals.hash(), next_validators_hash=next_vals.hash(),
        proposer_address=vals.validators[0].address,
    )


def gen_chain(n, privs, vs, start_time=0, step_s=10):
    """n adjacent light blocks (heights 1..n) under one validator set."""
    out = []
    last_bid = BlockID()
    for h in range(1, n + 1):
        header = _mk_header(h, start_time + h * step_s, vs, vs, last_bid)
        commit = _sign_commit(header, vs, privs)
        out.append(LightBlock(signed_header=SignedHeader(header, commit),
                              validator_set=vs.copy()))
        last_bid = commit.block_id
    return out


@pytest.fixture(scope="module")
def keys():
    return _mk_keys(4)


@pytest.fixture(scope="module")
def chain(keys):
    privs, vs = keys
    return gen_chain(12, privs, vs)


# --- verifier (reference: light/verifier_test.go) --------------------------

def test_verify_adjacent_happy(chain):
    lv.verify_adjacent(chain[0].signed_header, chain[1].signed_header,
                       chain[1].validator_set, TRUST_PERIOD, t(100), DRIFT)


def test_verify_adjacent_expired_trusted(chain):
    with pytest.raises(lv.ErrOldHeaderExpired):
        lv.verify_adjacent(chain[0].signed_header, chain[1].signed_header,
                           chain[1].validator_set, 1.0, t(1000), DRIFT)


def test_verify_adjacent_future_time(chain):
    # New header time is beyond now + drift.
    with pytest.raises(lv.ErrInvalidHeader):
        lv.verify_adjacent(chain[0].signed_header, chain[1].signed_header,
                           chain[1].validator_set, TRUST_PERIOD, t(5), DRIFT)


def test_verify_adjacent_vals_hash_mismatch(chain, keys):
    privs, vs = keys
    other_privs, other_vs = _mk_keys(4, seed=9)
    header = _mk_header(2, 20, other_vs, other_vs)
    commit = _sign_commit(header, other_vs, other_privs)
    sh = SignedHeader(header, commit)
    with pytest.raises(lv.LightClientError):
        lv.verify_adjacent(chain[0].signed_header, sh, other_vs,
                           TRUST_PERIOD, t(100), DRIFT)


def test_verify_adjacent_insufficient_power(keys):
    privs, vs = keys
    c = gen_chain(2, privs, vs)
    # Re-sign height 2's commit with 3 of 4 absent: 10 of 40 power < 2/3.
    header = c[1].signed_header.header
    commit = _sign_commit(header, vs, privs, skip=(1, 2, 3))
    sh = SignedHeader(header, commit)
    with pytest.raises(lv.ErrInvalidHeader):
        lv.verify_adjacent(c[0].signed_header, sh, vs, TRUST_PERIOD, t(100), DRIFT)


def test_verify_non_adjacent_happy(chain):
    # Skip straight from height 1 to height 8; same valset so 1/3 trust holds.
    lv.verify_non_adjacent(chain[0].signed_header, chain[0].validator_set,
                           chain[7].signed_header, chain[7].validator_set,
                           TRUST_PERIOD, t(200), DRIFT)


def test_verify_non_adjacent_untrusted_valset():
    privs, vs = _mk_keys(4)
    c = gen_chain(1, privs, vs)
    # Entirely new validator set at height 5: 0 of trusted power signed.
    new_privs, new_vs = _mk_keys(4, seed=5)
    header = _mk_header(5, 50, new_vs, new_vs)
    commit = _sign_commit(header, new_vs, new_privs)
    sh = SignedHeader(header, commit)
    with pytest.raises(lv.ErrNewValSetCantBeTrusted):
        lv.verify_non_adjacent(c[0].signed_header, c[0].validator_set,
                               sh, new_vs, TRUST_PERIOD, t(200), DRIFT)


def test_validate_trust_level():
    for num, den in ((1, 3), (1, 2), (2, 3), (1, 1)):
        lv.validate_trust_level((num, den))
    for num, den in ((0, 1), (1, 4), (2, 1), (1, 0)):
        with pytest.raises(lv.LightClientError):
            lv.validate_trust_level((num, den))


def test_verify_backwards(chain):
    lv.verify_backwards(chain[1].signed_header.header,
                        chain[2].signed_header.header)
    # Wrong linkage: height 1 is not the parent of height 3.
    with pytest.raises(lv.ErrInvalidHeader):
        lv.verify_backwards(chain[0].signed_header.header,
                            chain[2].signed_header.header)


# --- trusted store ---------------------------------------------------------

def test_store_roundtrip_and_prune(chain):
    store = DBStore(MemDB())
    for lb in chain[:5]:
        store.save_light_block(lb)
    assert store.size() == 5
    assert store.latest_light_block().height == 5
    assert store.first_light_block_height() == 1
    assert store.light_block_before(4).height == 3
    store.prune(2)
    assert store.size() == 2
    assert store.first_light_block_height() == 4
    got = store.light_block(5)
    assert got.signed_header.header.hash() == chain[4].hash()


@pytest.fixture(scope="module")
def rotating_chain():
    """12 heights under 24 validators of unequal power whose proposer
    priorities move on between heights, as on a live chain: each height
    hands the store another ValidatorSet object that encodes to other bytes
    (the priorities are in the encoding, field 4, and not in the hash)."""
    privs, vs = _mk_keys(24, power=[10 + 7 * i for i in range(24)])
    out, last_bid = [], BlockID()
    for h in range(1, 13):
        header = _mk_header(h, h * 10, vs, vs, last_bid)
        commit = _sign_commit(header, vs, privs, skip=(h % 24,))
        out.append(LightBlock(SignedHeader(header, commit), vs))
        last_bid = commit.block_id
        vs = vs.copy_increment_proposer_priority(1)
    return out


def test_store_writes_the_reference_encoding_of_a_rotating_set(rotating_chain):
    db = MemDB()
    store = DBStore(db)
    for lb in rotating_chain:
        want = ref.light_block(lb)
        assert store.save_light_block(lb) == len(want)
        assert db.get(b"lb/" + lb.height.to_bytes(8, "big")) == want
        got = store.light_block(lb.height)
        assert got.signed_header == lb.signed_header
        assert got.validator_set.validators == lb.validator_set.validators
        assert got.validator_set.proposer == lb.validator_set.proposer
        assert got.marshal() == want
    sets = [ref.validator_set(lb.validator_set) for lb in rotating_chain]
    assert len(set(sets)) == len(sets)  # an identity or hash memo would be wrong
    assert len({lb.validator_set.hash() for lb in rotating_chain}) == 1
    assert any(v.proposer_priority < 0 for v in rotating_chain[3].validator_set.validators)


@pytest.mark.parametrize("through", ["verify_header_range", "client"])
def test_light_store_span_counts_blocks_and_bytes(rotating_chain, through):
    """light.store's tags: headers written inside the span and the bytes
    handed to the db for them, as the db holds them afterwards."""
    c = rotating_chain
    db = MemDB()
    store = DBStore(db)
    tracer = trace.Tracer("light-store", cap=256, enabled=True)
    try:
        with tracer.activate():
            if through == "client":
                # the trust root and the target are written outside the span
                client, _ = _client(c, SEQUENTIAL, store=store)
                client.verify_light_block_at_height(len(c), t(500))
                inside = range(2, len(c))
            else:
                verify_header_range(c[0], c[1:], TRUST_PERIOD, t(500), DRIFT, store=store)
                inside = range(2, len(c) + 1)
        spans = [s for s in tracer.dump() if s.name == "light.store"]
    finally:
        tracer.disable()
    assert spans and all({"blocks", "bytes"} <= set(s.tags) for s in spans)
    assert sum(s.tags["blocks"] for s in spans) == len(inside)
    assert sum(s.tags["bytes"] for s in spans) == sum(
        len(db.get(b"lb/" + h.to_bytes(8, "big"))) for h in inside)
    assert store.size() == len(c) - (through != "client")
    for h in inside:
        assert db.get(b"lb/" + h.to_bytes(8, "big")) == ref.light_block(c[h - 1])


# --- client ----------------------------------------------------------------

def _client(chain, mode, witnesses=(), store=None, height=1):
    primary = MockProvider(CHAIN_ID, {lb.height: lb for lb in chain})
    return Client(
        CHAIN_ID,
        TrustOptions(period_s=TRUST_PERIOD, height=height,
                     hash=chain[height - 1].hash()),
        primary, list(witnesses), store or DBStore(MemDB()),
        verification_mode=mode,
    ), primary


def test_client_sequential_catchup(chain):
    client, _ = _client(chain, SEQUENTIAL)
    lb = client.verify_light_block_at_height(10, t(500))
    assert lb.height == 10
    # All intermediate headers were persisted.
    assert client.trusted_store.light_block(5) is not None
    assert client.latest_trusted.height == 10


def test_client_skipping_catchup(chain):
    client, _ = _client(chain, SKIPPING)
    lb = client.verify_light_block_at_height(12, t(500))
    assert lb.height == 12
    assert client.latest_trusted.height == 12


def test_client_update(chain):
    client, _ = _client(chain, SKIPPING)
    lb = client.update(t(500))
    assert lb is not None and lb.height == 12
    assert client.update(t(501)) is None  # already at tip


def test_client_historical_and_backwards(chain):
    client, _ = _client(chain, SEQUENTIAL, height=5)
    client.verify_light_block_at_height(9, t(500))
    # Height 3 < first trusted (5): backwards hash-linked walk.
    lb = client.verify_light_block_at_height(3, t(500))
    assert lb.height == 3


def test_client_trust_anchor_mismatch(chain):
    primary = MockProvider(CHAIN_ID, {lb.height: lb for lb in chain})
    with pytest.raises(lv.LightClientError):
        Client(CHAIN_ID,
               TrustOptions(period_s=TRUST_PERIOD, height=1, hash=b"\x11" * 32),
               primary, [], DBStore(MemDB()))


def test_client_detector_conflicting_witness(chain, keys):
    privs, vs = keys
    # A forked chain: same heights, different app state (different time step).
    fork = gen_chain(12, privs, vs, start_time=1, step_s=10)
    assert fork[5].hash() != chain[5].hash()
    # Witness agrees on the trust anchor (height 1) but forks afterwards.
    witness_blocks = {lb.height: lb for lb in fork}
    witness_blocks[1] = chain[0]
    witness = MockProvider(CHAIN_ID, witness_blocks)
    client, primary = _client(chain, SEQUENTIAL, witnesses=[witness])
    with pytest.raises(ErrConflictingHeaders):
        client.verify_light_block_at_height(6, t(500))
    # Evidence was reported to both sides and the witness was dropped.
    assert witness.evidences and primary.evidences
    assert client.witnesses == []
    # A client that HAD witnesses refuses to continue without any.
    from tendermint_tpu.light.detector import ErrNoWitnesses
    with pytest.raises(ErrNoWitnesses):
        client.verify_light_block_at_height(8, t(500))


def test_mock_provider_errors(chain):
    p = MockProvider(CHAIN_ID, {lb.height: lb for lb in chain[:3]})
    with pytest.raises(ErrHeightTooHigh):
        p.light_block(99)
    p.remove(2)
    with pytest.raises(ErrLightBlockNotFound):
        p.light_block(2)


# --- batched range verify (BASELINE config 3 shape) ------------------------

def test_range_verify_happy(keys):
    privs, vs = keys
    c = gen_chain(60, privs, vs)
    store = DBStore(MemDB())
    verify_header_range(c[0], c[1:], TRUST_PERIOD, t(900), DRIFT, store=store)
    assert store.size() == 59


def test_range_verify_matches_sequential_failure(keys):
    privs, vs = keys
    c = gen_chain(20, privs, vs)
    # Corrupt one signature inside the serial 2/3 prefix at height 9.
    bad_header = c[8].signed_header.header
    c[8].signed_header.commit = _sign_commit(bad_header, vs, privs, bad_sig=(0,))
    store = DBStore(MemDB())
    with pytest.raises(lv.ErrInvalidHeader) as ei:
        verify_header_range(c[0], c[1:], TRUST_PERIOD, t(900), DRIFT, store=store)
    assert ei.value.reason.index == 0
    # what the per-header loop raises for height 9 after height 8
    with pytest.raises(lv.ErrInvalidHeader) as want:
        lv.verify_adjacent(c[7].signed_header, c[8].signed_header,
                           c[8].validator_set, TRUST_PERIOD, t(900), DRIFT)
    assert str(ei.value) == str(want.value)
    # the headers below the refused one are saved, nothing at or above it
    assert store.latest_light_block().height == 8 and store.size() == 7


def test_range_verify_broken_linkage(keys):
    privs, vs = keys
    c = gen_chain(5, privs, vs)
    with pytest.raises(lv.LightClientError, match="adjacent in height"):
        verify_header_range(c[0], [c[1], c[3]], TRUST_PERIOD, t(900), DRIFT)


def test_light_proxy_serves_verified_data(tmp_path):
    """LightProxy: commit/validators/light_block come from verified light
    blocks; raw blocks are accepted only when they hash to the verified
    header (reference: light/proxy/proxy.go)."""
    import json
    import os
    import time as _time
    import urllib.request

    from tendermint_tpu.config.config import test_config
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.light.proxy import LightProxy
    from tendermint_tpu.node.node import Node
    from tendermint_tpu.p2p.key import NodeKey
    from tendermint_tpu.privval.file_pv import MockPV
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
    from tendermint_tpu.types.light_block import LightBlock

    priv = ed25519.gen_priv_key(b"\x53" * 32)
    genesis = GenesisDoc(chain_id="lp-chain", genesis_time=Time(1700003000, 0),
                         validators=[GenesisValidator(b"", priv.pub_key(), 10)])
    cfg = test_config()
    cfg.set_root(str(tmp_path / "node"))
    os.makedirs(cfg.base.root_dir, exist_ok=True)
    cfg.base.fast_sync_mode = False
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.consensus.wal_path = ""
    node = Node(cfg, genesis=genesis, priv_validator=MockPV(priv),
                node_key=NodeKey(ed25519.gen_priv_key(b"\x54" * 32)))
    node.start()
    proxy = None
    try:
        deadline = _time.monotonic() + 60
        while _time.monotonic() < deadline and node.block_store.height < 4:
            _time.sleep(0.1)
        base = "http://" + node.rpc_server.laddr.split("://", 1)[1]
        from tendermint_tpu.light import Client, DBStore, HTTPProvider, TrustOptions
        from tendermint_tpu.store.db import MemDB

        primary = HTTPProvider("lp-chain", base)
        anchor = primary.light_block(1)
        client = Client("lp-chain",
                        TrustOptions(period_s=10 * 365 * 24 * 3600.0, height=1,
                                     hash=anchor.hash()),
                        primary, [], DBStore(MemDB()), max_clock_drift_s=120.0)
        proxy = LightProxy(client, base)
        proxy.start()

        def rpc(method, params=None):
            body = json.dumps({"jsonrpc": "2.0", "id": 1, "method": method,
                               "params": params or {}}).encode()
            addr = "http://" + proxy.laddr.split("://", 1)[1]
            with urllib.request.urlopen(urllib.request.Request(
                    addr, data=body,
                    headers={"Content-Type": "application/json"}), timeout=10) as r:
                doc = json.loads(r.read())
            if doc.get("error"):
                raise RuntimeError(doc["error"])
            return doc["result"]

        assert rpc("health") == {}
        st = rpc("status")
        assert st["node_info"]["network"] == "lp-chain"

        c = rpc("commit", {"height": 3})
        assert c["verified"] and c["signed_header"]["height"] == "3"

        v = rpc("validators", {"height": 3})
        assert v["verified"] and v["total"] == "1"

        lb_doc = rpc("light_block", {"height": 3})
        lb = LightBlock.unmarshal(bytes.fromhex(lb_doc["light_block"]))
        lb.validate_basic("lp-chain")

        b = rpc("block", {"height": 3})
        assert b["verified"]
        assert b["block"]["header"]["height"] == "3"

        # URI-style GET works like the node RPC
        addr = "http://" + proxy.laddr.split("://", 1)[1]
        with urllib.request.urlopen(f"{addr}/status", timeout=10) as r:
            doc = json.loads(r.read())
        assert doc["result"]["node_info"]["network"] == "lp-chain"

        # a primary lying about block content is caught: tamper with the
        # forwarded block and run the binding check directly
        lb3 = client.trusted_store.light_block(3)
        tampered = json.loads(json.dumps(b))
        tampered["block"]["data"]["txs"] = [
            __import__("base64").b64encode(b"forged=tx").decode()]
        try:
            proxy._check_block_against_header(tampered, lb3)
            raise AssertionError("tampered txs accepted")
        except ValueError as e:
            assert "merkle" in str(e)
        tampered2 = json.loads(json.dumps(b))
        tampered2["block"]["header"]["app_hash"] = "AB" * 32
        try:
            proxy._check_block_against_header(tampered2, lb3)
            raise AssertionError("tampered app_hash accepted")
        except ValueError as e:
            assert "app_hash" in str(e)

        # the proxy's trusted store grew through these verifications
        assert client.trusted_store.light_block(3) is not None
    finally:
        if proxy is not None:
            proxy.stop()
        node.stop()


def test_exhaustive_threshold_boundaries():
    """Enumerate EVERY signer subset at several set sizes/powers and pin
    the exact acceptance boundaries of the two light-client verifies:
    verify_commit_light needs voting power > 2/3 of the set
    (types/validator_set.go:722), verify_commit_light_trusting at level
    (1,3) needs > 1/3 of the TRUSTED set's power (:772-830). The batched
    kernel path must agree with pure arithmetic on all 2^n subsets."""
    import itertools

    from tendermint_tpu.types.validator_set import ErrNotEnoughVotingPowerSigned

    for seed, powers in enumerate(
            ([10, 10, 10, 10], [1, 2, 3, 10], [5, 5, 5, 5, 5])):
        n = len(powers)
        privs, vals = _mk_keys(n, power=powers, seed=seed + 9)
        header = _mk_header(7, 800, vals, vals)
        total = vals.total_voting_power()
        for mask in itertools.product([0, 1], repeat=n):
            absent = tuple(i for i, m in enumerate(mask) if not m)
            commit = _sign_commit(header, vals, privs, skip=absent)
            signed = sum(v.voting_power
                         for v, m in zip(vals.validators, mask) if m)

            def expect(ok_fn, needed_gt):
                try:
                    ok_fn()
                    accepted = True
                except ErrNotEnoughVotingPowerSigned:
                    accepted = False
                want = signed * 3 > needed_gt  # strict >
                assert accepted == want, (powers, mask, signed)

            expect(lambda: vals.verify_commit_light(
                CHAIN_ID, commit.block_id, 7, commit), 2 * total)
            expect(lambda: vals.verify_commit_light_trusting(
                CHAIN_ID, commit, (1, 3)), total)
