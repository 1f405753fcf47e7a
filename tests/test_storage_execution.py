"""Storage, privval, mempool, and block execution: unit + end-to-end apply."""

import pytest

from tendermint_tpu.abci import types as abci
from tendermint_tpu.abci.kvstore import KVStoreApplication
from tendermint_tpu.crypto import ed25519
from tendermint_tpu.mempool.mempool import (
    ErrMempoolIsFull,
    ErrTxInCache,
    Mempool,
)
from tendermint_tpu.privval.file_pv import DoubleSignError, FilePV, MockPV
from tendermint_tpu.state.execution import BlockExecutor
from tendermint_tpu.state.state import make_genesis_state
from tendermint_tpu.state.store import ABCIResponses, StateStore
from tendermint_tpu.store.block_store import BlockStore
from tendermint_tpu.store.db import MemDB, SQLiteDB
from tendermint_tpu.types.block import Commit, CommitSig
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
from tendermint_tpu.types.part_set import PartSet
from tendermint_tpu.types.ttime import Time
from tendermint_tpu.types.vote import (
    BLOCK_ID_FLAG_COMMIT,
    PRECOMMIT_TYPE,
    PREVOTE_TYPE,
    Vote,
)


def test_db_backends(tmp_path):
    for db in (MemDB(), SQLiteDB(str(tmp_path / "kv.db"))):
        db.set(b"a", b"1")
        db.set(b"b", b"2")
        db.set(b"c", b"3")
        db.delete(b"b")
        assert db.get(b"a") == b"1" and db.get(b"b") is None
        assert [k for k, _ in db.iterator(b"a", b"c")] == [b"a"]
        assert [k for k, _ in db.iterator()] == [b"a", b"c"]
        assert [k for k, _ in db.reverse_iterator()] == [b"c", b"a"]
        db.close()


def _genesis(n_vals=1, chain_id="exec-chain"):
    privs = [ed25519.gen_priv_key(bytes([40 + i]) * 32) for i in range(n_vals)]
    gvals = [GenesisValidator(b"", p.pub_key(), 10) for p in privs]
    gd = GenesisDoc(chain_id=chain_id, validators=gvals,
                    genesis_time=Time(1700000000, 0))
    gd.validate_and_complete()
    return gd, privs


def _commit_for(state, block, privs, round_=0, spread_ns=0):
    """Every validator's precommit for `block`, 1 ms after its time, and
    `spread_ns` later for each place down the set."""
    bid = BlockID(hash=block.hash(),
                  part_set_header=PartSet.from_data(block.marshal()).header())
    sigs = []
    by_addr = {p.pub_key().address(): p for p in privs}
    for i, val in enumerate(state.validators.validators):
        priv = by_addr[val.address]
        v = Vote(type=PRECOMMIT_TYPE, height=block.header.height, round=round_,
                 block_id=bid,
                 timestamp=block.header.time.add_ns(1_000_000 + i * spread_ns),
                 validator_address=val.address,
                 validator_index=state.validators.get_by_address(val.address)[0])
        v.signature = priv.sign(v.sign_bytes(state.chain_id))
        sigs.append(CommitSig(BLOCK_ID_FLAG_COMMIT, val.address, v.timestamp, v.signature))
    return bid, Commit(height=block.header.height, round=round_, block_id=bid,
                       signatures=sigs)


def test_block_executor_applies_chain():
    """Drive three blocks through BlockExecutor + kvstore end to end."""
    gd, privs = _genesis(3)
    state = make_genesis_state(gd)
    app = KVStoreApplication()
    store = StateStore(MemDB())
    store.save(state)
    mp = Mempool(app)
    bx = BlockExecutor(store, app, mempool=mp)

    last_commit = Commit(height=0, round=0, block_id=BlockID(), signatures=[])
    for h in range(1, 4):
        mp.check_tx(b"k%d=v%d" % (h, h))
        proposer = state.validators.get_proposer()
        block = bx.create_proposal_block(h, state, last_commit, proposer.address)
        bid, commit = _commit_for(state, block, privs)
        state, _ = bx.apply_block(state, bid, block)
        assert state.last_block_height == h
        assert mp.size() == 0  # committed tx removed
        last_commit = commit

    assert app.size == 3
    assert state.app_hash == (3).to_bytes(8, "big")
    # validator history is queryable per height
    assert store.load_validators(2).hash() == store.load_validators(3).hash()
    resp = store.load_abci_responses(2)
    assert len(resp.deliver_txs) == 1 and resp.deliver_txs[0].code == 0
    # reload state from disk
    assert store.load().last_block_height == 3


def test_validator_power_change_propagates_and_batch_verifies():
    """ISSUE 9 satellite: a voting-power change submitted as the kvstore
    ``val:`` tx flows EndBlock validator_updates -> state/execution.py
    update_state -> the height+2 ValidatorSet, and the changed validator's
    votes then verify through the batched vote path (VoteSet.add_votes)
    with the NEW power tallied — the unit-level shape of the fabric's
    churn scenario (docs/SOAK.md)."""
    from tendermint_tpu.types.vote_set import VoteSet

    gd, privs = _genesis(3)
    state = make_genesis_state(gd)
    app = KVStoreApplication()
    store = StateStore(MemDB())
    store.save(state)
    bx = BlockExecutor(store, app)

    # height 1: a plain tx
    last_commit = Commit(height=0, round=0, block_id=BlockID(), signatures=[])
    proposer = state.validators.get_proposer()
    block1 = state.make_block(1, [b"k=v"], last_commit, [], proposer.address)
    bid1, commit1 = _commit_for(state, block1, privs)
    state, _ = bx.apply_block(state, bid1, block1)

    # height 2 carries the power change: validator 0's power 10 -> 33
    target = privs[0].pub_key()
    tx = KVStoreApplication.make_val_tx(target.bytes(), 33)
    block2 = state.make_block(
        2, [tx], commit1, [], state.validators.get_proposer().address)
    bid2, commit2 = _commit_for(state, block2, privs)
    state, _ = bx.apply_block(state, bid2, block2)

    # scheduled, not immediate: validators(h+1) still carry 10, the
    # h+2 set carries 33 (reference: state/execution.go updateState)
    cur = {v.pub_key.bytes(): v.voting_power for v in state.validators.validators}
    nxt = {v.pub_key.bytes(): v.voting_power
           for v in state.next_validators.validators}
    assert cur[target.bytes()] == 10
    assert nxt[target.bytes()] == 33
    assert state.last_height_validators_changed == 4

    # height 3 commits -> the 33-power set is the CURRENT set for height 4
    block3 = state.make_block(
        3, [], commit2, [], state.validators.get_proposer().address)
    bid3, _commit3 = _commit_for(state, block3, privs)
    state, _ = bx.apply_block(state, bid3, block3)
    vals4 = state.next_validators
    assert {v.pub_key.bytes(): v.voting_power
            for v in vals4.validators}[target.bytes()] == 33
    # and the per-height store agrees
    assert store.load_validators(4).hash() == vals4.hash()

    # the changed validator's votes verify through the BATCH path
    # (VoteSet.add_votes: one dispatch()/resolve for the whole slice) and
    # its NEW power is what tips the 2/3 tally
    vs = VoteSet(state.chain_id, 4, 0, PRECOMMIT_TYPE, vals4)
    votes = []
    for p in privs:
        idx, _val = vals4.get_by_address(p.pub_key().address())
        v = Vote(type=PRECOMMIT_TYPE, height=4, round=0, block_id=bid3,
                 timestamp=Time(1700000500, 0),
                 validator_address=p.pub_key().address(),
                 validator_index=idx)
        v.signature = p.sign(v.sign_bytes(state.chain_id))
        votes.append(v)
    # validator 0 alone: 33 of 53 total is under 2/3 — no majority yet
    res0 = vs.add_votes(votes[:1])
    assert res0[0][0] and res0[0][1] is None
    assert vs.two_thirds_majority()[1] is False
    # +validator 1 (10): 43/53 > 2/3 — the new power is what tipped it
    # (old powers 10+10=20/33 would NOT have)
    res1 = vs.add_votes(votes[1:2])
    assert res1[0][0] and res1[0][1] is None
    maj, ok = vs.two_thirds_majority()
    assert ok and maj == bid3
    # a tampered signature from the changed validator is still rejected
    bad = Vote(type=PRECOMMIT_TYPE, height=4, round=0, block_id=bid3,
               timestamp=Time(1700000501, 0),
               validator_address=privs[2].pub_key().address(),
               validator_index=vals4.get_by_address(
                   privs[2].pub_key().address())[0])
    bad.signature = bytes(64)
    res_bad = vs.add_votes([bad])
    assert not res_bad[0][0] and res_bad[0][1] is not None


def _chain_with_a_changing_set(heights=9):
    """Four validators; a fifth joins with most of the power by a `val:`
    transaction at height 2, and validator 0 leaves at height 5. Every
    precommit has a time of its own, so a block's time says whose power the
    median weighed. Returns what each height committed and its
    apply.validate span's tags."""
    from tendermint_tpu.utils import trace

    gd, privs = _genesis(5)
    gd.validators = gd.validators[:4]
    state = make_genesis_state(gd)
    app = KVStoreApplication()
    store = StateStore(MemDB())
    store.save(state)
    bx = BlockExecutor(store, app)
    txs = {2: [KVStoreApplication.make_val_tx(privs[4].pub_key().bytes(), 70)],
           5: [KVStoreApplication.make_val_tx(privs[0].pub_key().bytes(), 0)]}
    tracer = trace.Tracer("guard", cap=256, enabled=True)
    committed = []
    last_commit = Commit(height=0, round=0, block_id=BlockID(), signatures=[])
    try:
        with tracer.activate():
            for h in range(1, heights + 1):
                block = state.make_block(
                    h, txs.get(h, []), last_commit, [],
                    state.validators.get_proposer().address)
                bid = BlockID(hash=block.hash(), part_set_header=PartSet.from_data(
                    block.marshal()).header())
                new_state, _ = bx.apply_block(state, bid, block)
                # signed after the apply: _commit_for asks the set for addresses
                _, last_commit = _commit_for(state, block, privs,
                                             spread_ns=1_000_000)
                committed.append((block.header.time, block.header.validators_hash,
                                  new_state.app_hash, state.validators.size()))
                state = new_state
    finally:
        tracer.disable()
    tags = [s.tags for s in tracer.dump() if s.name == "apply.validate"]
    return committed, tags


def test_a_changing_set_commits_what_a_scan_for_addresses_commits(monkeypatch):
    """PR 39: the address index changes no block time, no validators_hash and
    no app hash of a chain whose set changes, and apply.validate says what the
    block time cost and how many indexes were built under it."""
    from tendermint_tpu.types.validator_set import ValidatorSet

    committed, tags = _chain_with_a_changing_set()
    assert [size for *_, size in committed] == [4, 4, 4, 5, 5, 5, 4, 4, 4]
    assert len({t for t, *_ in committed}) == len(committed)
    assert len({vh for _, vh, *_ in committed}) == 3
    # the median is weighed: under equal powers it falls on the set's third
    # place, and on the newcomer's, the first, once it holds 70 of 110
    gaps = [(b[0].unix_ns() - a[0].unix_ns()) // 1_000_000
            for a, b in zip(committed, committed[1:])]
    assert gaps == [3, 3, 3, 1, 1, 1, 1, 1]
    assert all(t["median_s"] >= 0 for t in tags[1:])
    assert "median_s" not in tags[0]      # the first block has no LastCommit
    # an index is built where a membership is first asked for an address:
    # heights 1, 4 (the newcomer's set validates) and 7 (validator 0 is gone)
    assert [t["index_builds"] for t in tags] == [1, 0, 0, 1, 0, 0, 1, 0, 0]

    def scan(self, address):
        for i, v in enumerate(self.validators):
            if v.address == address:
                return i, v.copy()
        return -1, None

    monkeypatch.setattr(ValidatorSet, "get_by_address", scan)
    monkeypatch.setattr(ValidatorSet, "has_address",
                        lambda self, address: scan(self, address)[0] >= 0)
    scanned, scanned_tags = _chain_with_a_changing_set()
    assert scanned == committed
    assert [t["index_builds"] for t in scanned_tags] == [0] * len(committed)


def test_block_store_roundtrip():
    gd, privs = _genesis(1)
    state = make_genesis_state(gd)
    app = KVStoreApplication()
    ss = StateStore(MemDB())
    ss.save(state)
    bx = BlockExecutor(ss, app, mempool=Mempool(app))
    bs = BlockStore(MemDB())

    last_commit = Commit(height=0, round=0, block_id=BlockID(), signatures=[])
    block = bx.create_proposal_block(1, state, last_commit,
                                     state.validators.get_proposer().address,
                                     block_time=Time(1700000100, 0))
    ps = PartSet.from_data(block.marshal())
    bid, commit = _commit_for(state, block, privs)
    bs.save_block(block, ps, commit)

    assert bs.height == 1 and bs.base == 1
    loaded = bs.load_block(1)
    assert loaded.hash() == block.hash()
    assert bs.load_block_by_hash(block.hash()).header.height == 1
    assert bs.load_seen_commit(1).block_id == bid
    meta = bs.load_block_meta(1)
    assert meta.block_id.hash == block.hash()
    part = bs.load_block_part(1, 0)
    assert part.bytes_ == ps.get_part(0).bytes_


def test_file_pv_double_sign_protection(tmp_path):
    kf, sf = str(tmp_path / "key.json"), str(tmp_path / "state.json")
    pv = FilePV.generate(kf, sf, seed=b"\x21" * 32)
    bid = BlockID(hash=b"\xcc" * 32)
    from tendermint_tpu.types.block_id import PartSetHeader

    bid = BlockID(hash=b"\xcc" * 32, part_set_header=PartSetHeader(1, b"\xdd" * 32))

    v = Vote(type=PREVOTE_TYPE, height=5, round=0, block_id=bid,
             timestamp=Time(1700000000, 0), validator_address=pv.get_address(),
             validator_index=0)
    pv.sign_vote("pv-chain", v)
    sig1 = v.signature

    # same vote, later timestamp -> reuses previous timestamp + signature
    v2 = Vote(type=PREVOTE_TYPE, height=5, round=0, block_id=bid,
              timestamp=Time(1700000009, 0), validator_address=pv.get_address(),
              validator_index=0)
    pv.sign_vote("pv-chain", v2)
    assert v2.signature == sig1 and v2.timestamp == Time(1700000000, 0)

    # DIFFERENT block at same HRS -> refuses
    v3 = Vote(type=PREVOTE_TYPE, height=5, round=0, block_id=BlockID(),
              timestamp=Time(1700000000, 0), validator_address=pv.get_address(),
              validator_index=0)
    with pytest.raises(DoubleSignError):
        pv.sign_vote("pv-chain", v3)

    # height regression after reload -> refuses
    pv2 = FilePV.load(kf, sf)
    v4 = Vote(type=PREVOTE_TYPE, height=4, round=0, block_id=bid,
              timestamp=Time(1700000000, 0), validator_address=pv.get_address(),
              validator_index=0)
    with pytest.raises(DoubleSignError):
        pv2.sign_vote("pv-chain", v4)


def test_mempool_fifo_and_cache():
    app = KVStoreApplication()
    mp = Mempool(app, max_txs=3)
    mp.check_tx(b"a=1")
    mp.check_tx(b"b=2")
    with pytest.raises(ErrTxInCache):
        mp.check_tx(b"a=1")
    assert mp.size() == 2
    assert mp.reap_max_bytes_max_gas(1000, -1) == [b"a=1", b"b=2"]
    # max_bytes limits the reap
    assert len(mp.reap_max_bytes_max_gas(6, -1)) == 1
    mp.lock()
    mp.update(1, [b"a=1"], [abci.ResponseDeliverTx(code=0)])
    mp.unlock()
    assert mp.size() == 1
    # committed tx stays cached -> rejected on re-add
    with pytest.raises(ErrTxInCache):
        mp.check_tx(b"a=1")


def test_mempool_priority_ordering():
    class PrioApp(KVStoreApplication):
        def check_tx(self, req):
            return abci.ResponseCheckTx(code=0, priority=len(req.tx))

    mp = Mempool(PrioApp(), version="v1")
    mp.check_tx(b"s")
    mp.check_tx(b"looooong")
    mp.check_tx(b"mid")
    assert mp.reap_max_txs(-1) == [b"looooong", b"mid", b"s"]
    # gossip iteration stays insertion-ordered
    assert [m.tx for m in mp.iter_txs()] == [b"s", b"looooong", b"mid"]


def test_state_store_abci_responses_roundtrip():
    ss = StateStore(MemDB())
    rs = ABCIResponses(deliver_txs=[
        abci.ResponseDeliverTx(code=0, data=b"ok", gas_wanted=5),
        abci.ResponseDeliverTx(code=7, log="fail"),
    ])
    ss.save_abci_responses(9, rs)
    out = ss.load_abci_responses(9)
    assert out.deliver_txs[0].data == b"ok"
    assert out.deliver_txs[1].code == 7


def test_mempool_ttl_num_blocks_eviction():
    """ttl-num-blocks: a tx older than N blocks is purged on update and
    leaves the cache so it can be resubmitted (reference:
    mempool/v1/mempool.go purgeExpiredTxs)."""
    app = KVStoreApplication()
    mp = Mempool(app, ttl_num_blocks=2)
    mp.check_tx(b"old=1")  # enters at height 0
    mp.lock(); mp.update(1, []); mp.unlock()
    mp.lock(); mp.update(2, []); mp.unlock()
    assert mp.size() == 1  # age exactly 2: strict > keeps it one more block
    mp.check_tx(b"young=1")  # enters at height 2
    mp.lock(); mp.update(3, []); mp.unlock()
    assert [m.tx for m in mp.iter_txs()] == [b"young=1"]  # old age 3 > 2
    # expired tx left the cache: resubmission is accepted, not ErrTxInCache
    assert mp.check_tx(b"old=1").is_ok()
    assert mp.size() == 2


def test_mempool_ttl_duration_eviction(monkeypatch):
    import time as _time

    from tendermint_tpu.mempool import mempool as mpmod

    app = KVStoreApplication()
    mp = Mempool(app, ttl_duration_s=10.0)
    t0 = _time.monotonic()
    monkeypatch.setattr(mpmod.time, "monotonic", lambda: t0)
    mp.check_tx(b"aging=1")
    mp.check_tx(b"fresh=1")
    # first tx is now 11s old (> 10), second only 5s (re-stamped younger)
    mp._txs[mpmod.tx_key(b"fresh=1")].time = t0 + 6
    monkeypatch.setattr(mpmod.time, "monotonic", lambda: t0 + 11)
    mp.lock(); mp.update(1, []); mp.unlock()
    assert [m.tx for m in mp.iter_txs()] == [b"fresh=1"]


def test_mempool_ttl_disabled_by_default():
    app = KVStoreApplication()
    mp = Mempool(app)
    mp.check_tx(b"keep=1")
    for h in range(1, 8):
        mp.lock(); mp.update(h, []); mp.unlock()
    assert mp.size() == 1


def test_mempool_v1_priority_eviction_when_full():
    """v1 full-pool admission (reference: mempool/v1/mempool.go:505-577):
    a higher-priority arrival evicts the lowest-priority txs (ties: newest
    first); an arrival no better than everything resident is rejected and
    un-cached so it can be retried later. v0 keeps reject-when-full."""
    class PrioApp(KVStoreApplication):
        def check_tx(self, req):
            # priority = numeric suffix after '~'
            return abci.ResponseCheckTx(code=0,
                                        priority=int(req.tx.split(b"~")[1]))

    mp = Mempool(PrioApp(), version="v1", max_txs=3)
    mp.check_tx(b"a~5")
    mp.check_tx(b"b~1")
    mp.check_tx(b"c~3")
    # full; priority 4 > {1,3}: evicts the single lowest (b~1)
    assert mp.check_tx(b"d~4").is_ok()
    assert sorted(m.tx for m in mp.iter_txs()) == [b"a~5", b"c~3", b"d~4"]
    # evicted tx left the cache: immediate retry is not ErrTxInCache
    # (still full, and priority 1 beats nothing -> full again)
    with pytest.raises(ErrMempoolIsFull):
        mp.check_tx(b"b~1")
    with pytest.raises(ErrMempoolIsFull):
        mp.check_tx(b"b~1")  # NOT ErrTxInCache: reject removed it from cache
    # another arrival evicts the current lowest priority (c~3)
    assert mp.check_tx(b"e~9").is_ok()
    assert sorted(m.tx for m in mp.iter_txs()) == [b"a~5", b"d~4", b"e~9"]

    # v0: reject-when-full regardless of priority
    mp0 = Mempool(PrioApp(), version="v0", max_txs=1)
    mp0.check_tx(b"x~1")
    with pytest.raises(ErrMempoolIsFull):
        mp0.check_tx(b"y~9")


def test_tx_filters_from_consensus_state():
    """state/tx_filter.py: pre-check bounds tx size to the block data
    budget, post-check bounds gas to block.max_gas; both typed as
    ErrPreCheck and un-cached so a retry isn't a cache hit (reference:
    state/tx_filter.go, mempool/mempool.go:111-141)."""
    from dataclasses import replace as dc_replace

    from tendermint_tpu.mempool.mempool import ErrPreCheck
    from tendermint_tpu.state.tx_filter import tx_post_check, tx_pre_check
    from tendermint_tpu.types.params import BlockParams

    gd, _ = _genesis(1)
    state = make_genesis_state(gd)

    class GasApp(KVStoreApplication):
        def check_tx(self, req):
            return abci.ResponseCheckTx(code=0, gas_wanted=len(req.tx))

    # post-check: max_gas=5 rejects a 6-byte (gas 6) tx, accepts gas 5
    state5 = dc_replace(
        state, consensus_params=dc_replace(
            state.consensus_params, block=BlockParams(max_gas=5)))
    mp = Mempool(GasApp())
    mp.post_check = tx_post_check(state5)
    assert mp.check_tx(b"five!").is_ok()
    with pytest.raises(ErrPreCheck, match="max gas"):
        mp.check_tx(b"sixsix")
    assert mp.check_tx(b"5char").is_ok()  # gas exactly at the bound passes
    # rejected tx is NOT cached: same bytes later raise the same filter
    # error, not ErrTxInCache
    with pytest.raises(ErrPreCheck, match="max gas"):
        mp.check_tx(b"sixsix")

    # pre-check: a tiny block budget rejects big txs before the app runs
    tiny = dc_replace(
        state, consensus_params=dc_replace(
            state.consensus_params, block=BlockParams(max_bytes=1000)))
    mp2 = Mempool(KVStoreApplication())
    mp2.pre_check = tx_pre_check(tiny)
    assert mp2.check_tx(b"ok=1").is_ok()
    with pytest.raises(ErrPreCheck, match="too big"):
        mp2.check_tx(b"z" * 900)

    # recheck applies post-check: tightening max_gas evicts resident txs
    mp3 = Mempool(GasApp())
    mp3.check_tx(b"sevennn")  # gas 7, admitted (no filter yet)
    mp3.lock()
    mp3.update(1, [], pre_check=tx_pre_check(state5),
               post_check=tx_post_check(state5))
    mp3.unlock()
    assert mp3.size() == 0  # gas 7 > 5: evicted on recheck
