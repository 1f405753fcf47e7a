"""The fast-sync verify-ahead pipeline (blockchain/pipeline.py): in-order
resolve, speculative-work discard, two-peer punishment, and convergence to
the depth-1 app hash — with and without device-failure injection inside the
pipeline (the ISSUE 2 acceptance matrix)."""

import types as pytypes

import pytest

from tendermint_tpu.blockchain.replay import ReplayCtx, make_chain
from tendermint_tpu.blockchain import pipeline as bpipe
from tendermint_tpu.crypto import ed25519
from tendermint_tpu.types.block import Block, Commit, CommitSig
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.part_set import PartSet
from tendermint_tpu.types.ttime import Time
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.types.validator_set import ValidatorSet
from tendermint_tpu.types.vote import BLOCK_ID_FLAG_COMMIT, PRECOMMIT_TYPE, Vote

CHAIN_ID = "pipe-chain"
N_BLOCKS = 10  # pool holds 10 blocks -> 9 appliable heights


def _mk_vals(n):
    privs = [ed25519.gen_priv_key((i + 1).to_bytes(2, "big") * 16)
             for i in range(n)]
    vals = ValidatorSet([Validator.new(p.pub_key(), 10) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    return [by_addr[v.address] for v in vals.validators], vals




def _tampered_copy(block):
    """Deep copy with the first LastCommit signature corrupted (inside the
    +2/3 serial stopping prefix, so resolve raises ErrWrongSignature)."""
    bad = Block.unmarshal(block.marshal())
    sig = bytearray(bad.last_commit.signatures[0].signature)
    sig[0] ^= 0xFF
    bad.last_commit.signatures[0].signature = bytes(sig)
    return bad


@pytest.fixture()
def chain():
    privs, vals = _mk_vals(4)
    return vals, make_chain(CHAIN_ID, N_BLOCKS, vals, privs)


def _reference_run(vals, blocks, monkeypatch):
    """Depth-1 (serial-behavior) run over a pristine pool: the convergence
    oracle every pipeline scenario must match."""
    monkeypatch.setenv("TM_TPU_VERIFY_AHEAD", "1")
    ctx = ReplayCtx(vals, CHAIN_ID)
    for b in blocks:
        ctx.pool.add_block("good", b)
    pipe = bpipe.VerifyAheadPipeline()
    while pipe.process_next(ctx):
        pass
    assert ctx.applied == list(range(1, N_BLOCKS)) and not ctx.punished
    return ctx


def _bad_commit_scenario(vals, blocks, monkeypatch):
    """Depth-4 pipeline over a pool where block 5 (sent by bad2) carries a
    corrupted LastCommit for block 4 (sent by bad1): heights 1..3 resolve
    in order, height 4's resolve fails mid-pipeline."""
    monkeypatch.setenv("TM_TPU_VERIFY_AHEAD", "4")
    ctx = ReplayCtx(vals, CHAIN_ID)
    for b in blocks:
        h = b.header.height
        peer = {4: "bad1", 5: "bad2"}.get(h, "good")
        ctx.pool.add_block(peer, _tampered_copy(b) if h == 5 else b)
    pipe = bpipe.VerifyAheadPipeline()
    while pipe.process_next(ctx):
        pass
    # In-order resolve up to the failure; all speculation discarded.
    assert ctx.applied == [1, 2, 3]
    assert len(pipe) == 0
    # BOTH senders punished (the bad LastCommit rides in the SECOND block),
    # and their blocks were dropped for re-request — exactly the serial path.
    assert ctx.punished == ["bad1", "bad2"]
    assert ctx.pool.peek_block(4) is None and ctx.pool.peek_block(5) is None
    assert ctx.pool.height == 4
    # "Re-requested" blocks arrive clean from a good peer: the pipeline
    # converges.
    ctx.pool.add_block("good", blocks[3])
    ctx.pool.add_block("good", blocks[4])
    while pipe.process_next(ctx):
        pass
    assert ctx.applied == list(range(1, N_BLOCKS))
    return ctx


def test_mid_pipeline_bad_commit_matches_serial(chain, monkeypatch):
    vals, blocks = chain
    ref = _reference_run(vals, blocks, monkeypatch)
    ctx = _bad_commit_scenario(vals, blocks, monkeypatch)
    assert ctx.app_hash == ref.app_hash


def test_mid_pipeline_bad_commit_with_device_fault(chain, monkeypatch):
    """TMTPU_FAULTS device failure INSIDE the pipeline: the injected raise
    at the speculative dispatch degrades through the circuit breaker to the
    host path within the same call — decisions, punishments, and the final
    app hash are byte-identical to the fault-free pipeline and the serial
    path."""
    from tendermint_tpu.ops import ed25519_batch
    from tendermint_tpu.utils import faults

    ref = _reference_run(*chain, monkeypatch)
    vals, blocks = chain
    # Route flushes at the device (crossover 0 pins the device path, the
    # verify-ahead force_device heuristic then applies) and make the FIRST
    # speculative dispatch die; the breaker keeps later flushes on host.
    # A huge cooldown keeps the background re-probe from touching the
    # device (and compiling kernels) during the test.
    monkeypatch.setenv("TM_TPU_HOST_CROSSOVER", "0")
    monkeypatch.setenv("TM_TPU_BREAKER_COOLDOWN_S", "3600")
    faults.configure(["ops.ed25519.device:raise@1"], seed=7)
    try:
        ctx = _bad_commit_scenario(vals, blocks, monkeypatch)
    finally:
        faults.clear()
        ed25519_batch.BREAKER.reset()
    assert ed25519_batch.BREAKER.failures >= 1  # the fault really fired
    assert ctx.app_hash == ref.app_hash


def test_depth_env_clamped(monkeypatch):
    monkeypatch.setenv("TM_TPU_VERIFY_AHEAD", "0")
    assert bpipe.verify_ahead_depth() == 1
    monkeypatch.setenv("TM_TPU_VERIFY_AHEAD", "junk")
    assert bpipe.verify_ahead_depth() == bpipe.DEFAULT_DEPTH
    monkeypatch.delenv("TM_TPU_VERIFY_AHEAD")
    assert bpipe.verify_ahead_depth() == bpipe.DEFAULT_DEPTH


def test_real_reactor_end_to_end_depths_agree(monkeypatch):
    """The REAL v0 reactor glue (no sockets): a chain built by a source
    BlockExecutor is replayed through BlockchainReactor._try_sync with a
    real executor + stores, at depth 1 and depth 4. Both must apply every
    block and land on the source's app hash."""
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.blockchain.reactor import BlockchainReactor
    from tendermint_tpu.mempool.mempool import Mempool
    from tendermint_tpu.state.execution import BlockExecutor
    from tendermint_tpu.state.state import make_genesis_state
    from tendermint_tpu.state.store import StateStore
    from tendermint_tpu.store.block_store import BlockStore
    from tendermint_tpu.store.db import MemDB
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator

    privs = [ed25519.gen_priv_key(bytes([80 + i]) * 32) for i in range(2)]
    gd = GenesisDoc(chain_id="pipe-e2e", genesis_time=Time(1700000000, 0),
                    validators=[GenesisValidator(b"", p.pub_key(), 10)
                                for p in privs])
    gd.validate_and_complete()
    by_addr = {p.pub_key().address(): p for p in privs}

    def commit_for(state, block):
        bid = BlockID(hash=block.hash(),
                      part_set_header=PartSet.from_data(block.marshal()).header())
        sigs = []
        for i, val in enumerate(state.validators.validators):
            v = Vote(type=PRECOMMIT_TYPE, height=block.header.height, round=0,
                     block_id=bid, timestamp=block.header.time.add_ns(1_000_000),
                     validator_address=val.address, validator_index=i)
            sig = by_addr[val.address].sign(v.sign_bytes(state.chain_id))
            sigs.append(CommitSig(BLOCK_ID_FLAG_COMMIT, val.address,
                                  v.timestamp, sig))
        return bid, Commit(height=block.header.height, round=0, block_id=bid,
                           signatures=sigs)

    # Source chain: 8 blocks through a real executor.
    state = make_genesis_state(gd)
    app = KVStoreApplication()
    ss = StateStore(MemDB())
    ss.save(state)
    bx = BlockExecutor(ss, app, mempool=Mempool(app))
    last_commit = Commit(height=0, round=0, block_id=BlockID(), signatures=[])
    blocks = []
    block_time = Time(1700000010, 0)
    for h in range(1, 9):
        block = bx.create_proposal_block(
            h, state, last_commit, state.validators.get_proposer().address,
            block_time=block_time)
        bid, commit = commit_for(state, block)
        state, _ = bx.apply_block(state, bid, block)
        last_commit = commit
        # validation pins h+1's time to the weighted median of h's commit
        # timestamps (block time + 1 ms, per commit_for)
        block_time = block.header.time.add_ns(1_000_000)
        blocks.append(block)

    results = {}
    for depth in (1, 4):
        monkeypatch.setenv("TM_TPU_VERIFY_AHEAD", str(depth))
        rstate = make_genesis_state(gd)
        rapp = KVStoreApplication()
        rss = StateStore(MemDB())
        rss.save(rstate)
        rbx = BlockExecutor(rss, rapp, mempool=Mempool(rapp))
        rbs = BlockStore(MemDB())
        reactor = BlockchainReactor(rstate, rbx, rbs, fast_sync=True)
        for b in blocks:
            reactor.pool.add_block("p", b)
        applied = 0
        while reactor._try_sync():
            applied += 1
        # 8 pooled blocks -> 7 appliable heights (the last needs a successor)
        assert applied == 7 and rbs.height == 7
        assert reactor.state.last_block_height == 7
        results[depth] = reactor.state.app_hash
        assert rbs.load_block(7).hash() == blocks[6].hash()
    assert results[1] == results[4]


def test_validator_set_change_discards_speculation(chain, monkeypatch):
    """An apply that changes the validator-set hash must invalidate
    speculative dispatches made against the old set: the pipeline discards
    them, re-dispatches against the new set, and converges — decisions
    can't drift from serial. (The power bump keeps sort order, so the old
    commits still verify under the new set; what changes is the hash the
    guard watches.)"""
    vals, blocks = chain
    ref = _reference_run(vals, blocks, monkeypatch)
    monkeypatch.setenv("TM_TPU_VERIFY_AHEAD", "4")
    ctx = ReplayCtx(vals, CHAIN_ID)
    for b in blocks:
        ctx.pool.add_block("good", b)
    real_exec = ctx.block_exec

    class _RotatingExec:
        def apply_block(self, state, block_id, block):
            state, rh = real_exec.apply_block(state, block_id, block)
            if block.header.height == 2:
                rotated = state.validators.copy()
                rotated.update_with_change_set(
                    [Validator.new(rotated.validators[0].pub_key, 20)])
                state = pytypes.SimpleNamespace(validators=rotated,
                                                chain_id=CHAIN_ID)
            return state, rh

    ctx.block_exec = _RotatingExec()
    pipe = bpipe.VerifyAheadPipeline()
    while pipe.process_next(ctx):
        pass
    assert pipe.discarded == 4, "stale-valset speculation was never discarded"
    assert pipe.dispatched - pipe.discarded == N_BLOCKS - 1
    assert ctx.applied == list(range(1, N_BLOCKS)) and not ctx.punished
    assert ctx.app_hash == ref.app_hash


# --- a validator set that changes through the chain's own transactions --------


def _churn_chain(n_vals=24, n_blocks=14):
    """A chain built by a source BlockExecutor over the kvstore whose blocks
    carry real ``val:`` updates: block 3 a join and a leave, block 7 a
    re-weighting of two sitting validators that reorders the top of the set.
    Every height is signed by the set in force there (updates of H at H+2).
    -> (genesis doc, blocks, {height: validators hash}, keys by address)."""
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.state.execution import BlockExecutor
    from tendermint_tpu.state.state import make_genesis_state
    from tendermint_tpu.state.store import StateStore
    from tendermint_tpu.store.db import MemDB
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator

    privs = [ed25519.gen_priv_key(bytes([40 + i]) * 32) for i in range(n_vals + 1)]
    joiner = privs.pop()
    gd = GenesisDoc(chain_id="pipe-churn", genesis_time=Time(1700000000, 0),
                    validators=[GenesisValidator(b"", p.pub_key(), 100 - i)
                                for i, p in enumerate(privs)])
    by_addr = {p.pub_key().address(): p for p in privs + [joiner]}
    state = make_genesis_state(gd)
    top, second, last = (state.validators.validators[i].pub_key.bytes()
                         for i in (0, 1, -1))
    val_tx = KVStoreApplication.make_val_tx
    txs_at = {3: [val_tx(joiner.pub_key().bytes(), 150), val_tx(last, 0)],
              7: [val_tx(top, 20), val_tx(second, 200)]}
    store = StateStore(MemDB())
    store.save(state)
    bx = BlockExecutor(store, KVStoreApplication())
    last_commit = Commit(height=0, round=0, block_id=BlockID(), signatures=[])
    blocks, hashes = [], {}
    for h in range(1, n_blocks + 1):
        block = state.make_block(h, txs_at.get(h, []), last_commit, [],
                                 state.validators.get_proposer().address)
        bid = BlockID(hash=block.hash(),
                      part_set_header=PartSet.from_data(block.marshal()).header())
        last_commit = _signed_commit(state.validators, by_addr, state.chain_id,
                                     h, bid)
        hashes[h] = state.validators.hash()
        state, _ = bx.apply_block(state, bid, block)
        blocks.append(block)
    bx.stop()
    return gd, blocks, hashes, by_addr


def _signed_commit(vals, by_addr, chain_id, height, bid):
    sigs = []
    for i, val in enumerate(vals.validators):
        v = Vote(type=PRECOMMIT_TYPE, height=height, round=0, block_id=bid,
                 timestamp=Time(1700000000 + height, 1000 * i),
                 validator_address=val.address, validator_index=i)
        sigs.append(CommitSig(BLOCK_ID_FLAG_COMMIT, val.address, v.timestamp,
                              by_addr[val.address].sign(v.sign_bytes(chain_id))))
    return Commit(height=height, round=0, block_id=bid, signatures=sigs)


def _sync_node(gd, blocks):
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.blockchain.reactor import BlockchainReactor
    from tendermint_tpu.state.execution import BlockExecutor
    from tendermint_tpu.state.state import make_genesis_state
    from tendermint_tpu.state.store import StateStore
    from tendermint_tpu.store.block_store import BlockStore
    from tendermint_tpu.store.db import MemDB

    state = make_genesis_state(gd)
    store = StateStore(MemDB())
    store.save(state)
    bs = BlockStore(MemDB())
    reactor = BlockchainReactor(state, BlockExecutor(store, KVStoreApplication()),
                                bs, fast_sync=True)
    for i, b in enumerate(blocks):
        reactor.pool.add_block("pA" if i % 2 == 0 else "pB",
                               Block.unmarshal(b.marshal()))
    return reactor


@pytest.fixture(scope="module")
def churn_chain():
    return _churn_chain()


def test_real_val_updates_through_the_reactor_depths_agree(churn_chain,
                                                           monkeypatch):
    """The real v0 reactor and BlockExecutor over a chain whose set changes
    twice through ``val:`` transactions: depth 1 and depth 4 apply every
    block and reach the same state, app hash and validator sets, every
    stored header naming the set the chain's own updates define. Each change
    (in force at H+2: heights 5 and 9) throws away what was in flight, one
    dispatch at depth 1 (it tops its window up before the apply too) and
    four at depth 4, and every one is dispatched again."""
    gd, blocks, hashes, _ = churn_chain
    assert hashes[4] != hashes[5] and hashes[8] != hashes[9]
    assert len({hashes[h] for h in (1, 5, 9)}) == 3
    seen = {}
    for depth in (1, 4):
        monkeypatch.setenv("TM_TPU_VERIFY_AHEAD", str(depth))
        reactor = _sync_node(gd, blocks)
        pipe = bpipe.VerifyAheadPipeline()
        applied = 0
        while pipe.process_next(reactor):
            applied += 1
        assert applied == len(blocks) - 1 == reactor.block_store.height
        for h in range(1, applied + 1):
            header = reactor.block_store.load_block_meta(h).header
            assert header.validators_hash == hashes[h], (depth, h)
        st = reactor.state
        assert st.last_height_validators_changed == 9
        assert pipe.dispatched - pipe.discarded == applied and len(pipe) == 0
        assert pipe.discarded == {1: 2, 4: 8}[depth]
        seen[depth] = (st.app_hash, st.validators.hash(),
                       st.next_validators.hash(), st.last_validators.hash(),
                       [(v.address, v.voting_power)
                        for v in st.validators.validators])
    assert seen[1] == seen[4]
    assert seen[4][1] == hashes[len(blocks)]


@pytest.mark.parametrize("depth", [1, 4])
def test_a_commit_signed_by_the_previous_set_is_rejected_at_the_change(
        churn_chain, depth, monkeypatch):
    """Height 9 is the first of the re-weighted set (the top two places
    swap). A commit for it signed by the set of height 8, slot for slot,
    verified under the old set as the speculation dispatched before the
    change did: the pipeline must discard that speculation and reject the
    block at height 9, at the first slot whose key changed, punishing both
    senders (guarantee: a verification made against a set that is no longer
    the height's is never resolved)."""
    from tendermint_tpu.types.validator_set import ErrWrongSignature

    gd, blocks, _hashes, by_addr = churn_chain
    monkeypatch.setenv("TM_TPU_VERIFY_AHEAD", str(depth))
    # the set of height 8, from a node of its own synced that far
    probe = _sync_node(gd, blocks[:9])
    pipe = bpipe.VerifyAheadPipeline()
    while pipe.process_next(probe):
        pass
    assert probe.state.last_block_height == 8
    old, new = probe.state.last_validators, probe.state.validators
    first_moved = next(i for i, (a, b) in enumerate(
        zip(old.validators, new.validators)) if a.address != b.address)
    carrier = Block.unmarshal(blocks[9].marshal())          # block 10
    carrier.last_commit = _signed_commit(
        old, by_addr, gd.chain_id, 9, carrier.last_commit.block_id)
    old.verify_commit_light(gd.chain_id, carrier.last_commit.block_id, 9,
                            carrier.last_commit)             # sound under old
    reactor = _sync_node(gd, blocks[:9] + [carrier] + blocks[10:])
    rejected = []
    real = reactor._punish_invalid
    reactor._punish_invalid = lambda h, e: (rejected.append((h, e)), real(h, e))
    pipe = bpipe.VerifyAheadPipeline()
    applied = 0
    while pipe.process_next(reactor):
        applied += 1
    assert applied == 8 and reactor.state.last_block_height == 8
    (height, err), = rejected
    assert height == 9 and isinstance(err, ErrWrongSignature)
    assert err.index == first_moved == 0
    assert not reactor.pool.blocks          # both peers' blocks were dropped


def test_a_change_of_the_set_is_marked_and_the_apply_is_timed_in_phases(
        churn_chain, monkeypatch):
    """Under a tracer the same sync writes one fastsync.discard mark per
    change (entries, reason=valset, the height of the first entry thrown
    away) and the four phases of every apply, apply.update_state tagged with
    what EndBlock changed (docs/OBSERVABILITY.md)."""
    from tendermint_tpu.utils import trace

    gd, blocks, _hashes, _ = churn_chain
    monkeypatch.setenv("TM_TPU_VERIFY_AHEAD", "4")
    reactor = _sync_node(gd, blocks)
    reactor.tracer = trace.Tracer("churn", cap=1024, enabled=True)
    pipe = bpipe.VerifyAheadPipeline()
    try:
        while pipe.process_next(reactor):
            pass
    finally:
        reactor.tracer.disable()
    spans = reactor.tracer.dump()
    marks = [s.tags for s in spans if s.name == "fastsync.discard"]
    assert [(t["entries"], t["reason"], t["height"]) for t in marks] == [
        (4, "valset", 5), (4, "valset", 9)]
    assert sum(t["entries"] for t in marks) == pipe.discarded
    applied = len(blocks) - 1
    phases = ("apply.validate", "apply.exec", "apply.update_state", "apply.save")
    for name in phases:
        assert sum(1 for s in spans if s.name == name) == applied, name
    whole = sum(s.duration_s for s in spans if s.name == "fastsync.apply")
    assert sum(s.duration_s for s in spans if s.name in phases) <= whole
    changed = {s.tags["height"]: s.tags for s in spans
               if s.name == "apply.update_state" and "updates" in s.tags}
    assert {h: (t["updates"], t["joined"], t["left"])
            for h, t in changed.items()} == {3: (2, 1, 1), 7: (2, 0, 0)}


# --- what a block's body causes, traced (PR 37) -----------------------------------


def test_a_traced_sync_times_the_body_and_counts_its_threads(churn_chain,
                                                            monkeypatch):
    """Under a tracer a sync writes one fastsync.part_set a dispatch (bytes,
    parts), one store.save_block and one state.save_responses a height
    applied, a block.data_hash for every body that has transactions, and one
    fastsync.thread_cpu mark every ten heights, counted from the baseline its
    first step read (docs/OBSERVABILITY.md)."""
    import threading

    from tendermint_tpu.utils import trace

    gd, blocks, _hashes, _ = churn_chain
    monkeypatch.setenv("TM_TPU_VERIFY_AHEAD", "4")
    reactor = _sync_node(gd, blocks)
    reactor.tracer = trace.Tracer("body", cap=2048, enabled=True)
    pipe = bpipe.VerifyAheadPipeline()
    try:
        while pipe.process_next(reactor):
            pass
    finally:
        reactor.tracer.disable()
    applied = len(blocks) - 1
    assert pipe.applied == applied == 13
    by_name = {}
    for s in reactor.tracer.dump():
        by_name.setdefault(s.name, []).append(s)
    cut = by_name["fastsync.part_set"]
    assert len(cut) == pipe.dispatched
    sizes = {b.header.height: len(b.marshal()) for b in blocks}
    for s in cut:
        assert s.tags["bytes"] == sizes[s.tags["height"]]
        assert s.tags["parts"] == 1 and s.cpu_s is not None
    saved = by_name["store.save_block"]
    assert [s.tags["height"] for s in saved] == list(range(1, applied + 1))
    for s in saved:
        # meta, hash index, one part, seen commit, store state; LastCommit
        # from height 1 on (an empty one at height 1)
        assert s.tags["rows"] == 6 and s.tags["parts"] == 1
        assert s.tags["bytes"] > sizes[s.tags["height"]]
    responses = by_name["state.save_responses"]
    assert [(s.tags["height"], s.tags["txs"]) for s in responses] == [
        (h, 2 if h in (3, 7) else 0) for h in range(1, applied + 1)]
    # only a body with transactions is hashed under a span: once, inside
    # apply.validate
    assert [s.tags["txs"] for s in by_name["block.data_hash"]] == [2, 2]
    (mark,) = by_name["fastsync.thread_cpu"]
    me = threading.current_thread().name
    assert mark.tags["height"] == 10
    assert mark.tags["sync_thread"] == me and me in mark.tags["threads"]
    assert 0 < mark.tags["wall_s"] and 0 < mark.tags["process_s"]
    assert mark.duration_s == 0.0


def test_an_untraced_sync_counts_heights_and_reads_no_clock(churn_chain,
                                                           monkeypatch):
    from tendermint_tpu.utils import trace

    gd, blocks, _hashes, _ = churn_chain
    reads = []
    monkeypatch.setattr(trace.ThreadCensus, "read",
                        lambda self: reads.append(1))
    reactor = _sync_node(gd, blocks)
    pipe = bpipe.VerifyAheadPipeline()
    while pipe.process_next(reactor):
        pass
    assert pipe.applied == 13 and not reads and pipe._census is None


def test_the_reactor_remembers_what_it_refused(churn_chain):
    """BlockchainReactor.last_invalid: the height, the exception and the
    peers whose blocks were dropped, for whoever asks afterwards."""
    from tendermint_tpu.types.validator_set import ErrWrongSignature

    gd, blocks, _hashes, _ = churn_chain
    reactor = _sync_node(gd, blocks[:5] + [_tampered_copy(blocks[5])]
                         + blocks[6:])
    assert reactor.last_invalid is None
    pipe = bpipe.VerifyAheadPipeline()
    applied = 0
    while pipe.process_next(reactor):
        applied += 1
    height, err, peers = reactor.last_invalid
    assert applied == 4 and height == 5 and peers == ["pA", "pB"]
    assert isinstance(err, ErrWrongSignature) and err.index == 0
    assert reactor.block_store.height == 4
