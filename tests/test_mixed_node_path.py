"""PR 50, the program's side of the mixed-set served path: the kernel warm-up
follows the genesis validators' key types (on one chip as on four), the
commit->apply seam counts what became of its handles, and the flight
recorder says how a LastCommit was answered and what StateStore.save wrote."""

import os
import time

import numpy as np
import pytest

from tendermint_tpu.config.config import test_config as make_test_config
from tendermint_tpu.crypto import batch as cbatch
from tendermint_tpu.crypto import ed25519, sr25519
from tendermint_tpu.node.node import Node
from tendermint_tpu.ops import ed25519_batch as edb
from tendermint_tpu.ops import sr25519_batch as srb
from tendermint_tpu.p2p.key import NodeKey
from tendermint_tpu.state.state import make_genesis_state
from tendermint_tpu.state.store import StateStore
from tendermint_tpu.store.db import MemDB
from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
from tendermint_tpu.types.ttime import Time
from tendermint_tpu.utils import trace
from tests.test_execution_batch import _commit_for, _genesis


@pytest.fixture
def warm_calls(monkeypatch):
    """The warm-up with nothing to compile: both kernels' verify_batch
    record their call, the calibration is a constant, the status is new."""
    calls = []

    def recorder(kind):
        def verify_batch(items, force_device=False):
            calls.append((kind, len(items), force_device))
            return np.ones(len(items), bool)
        return verify_batch

    monkeypatch.delenv("TM_TPU_SKIP_WARMUP", raising=False)
    monkeypatch.setattr(cbatch, "WARMUP", cbatch.WarmupStatus())
    monkeypatch.setattr(edb, "calibrate_host_crossover", lambda: 256)
    monkeypatch.setattr(edb, "verify_batch", recorder("ed25519"))
    monkeypatch.setattr(srb, "verify_batch", recorder("sr25519"))
    return calls


def _warm_spans(since: float) -> list:
    return [(s.tags["kind"], s.tags["sigs"]) for s in trace.STARTUP.dump()
            if s.name == "startup.warm_kernel" and s.start >= since]


def _node(tmp_path, pub_keys):
    genesis = GenesisDoc(
        chain_id="warm-chain", genesis_time=Time(1700000000, 0),
        validators=[GenesisValidator(b"", pk, 10) for pk in pub_keys])
    genesis.validate_and_complete()
    cfg = make_test_config()
    cfg.set_root(str(tmp_path / "node"))
    os.makedirs(cfg.base.root_dir, exist_ok=True)
    cfg.base.fast_sync_mode = False
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.pex = False
    cfg.rpc.laddr = ""
    cfg.consensus.wal_path = ""
    return Node(cfg, genesis=genesis, priv_validator=None,
                node_key=NodeKey(ed25519.gen_priv_key(b"\x51" * 32)))


@pytest.mark.parametrize("mixed", [False, True],
                         ids=["ed25519_genesis", "mixed_genesis"])
def test_a_node_warms_the_kernels_of_its_genesis_key_types(tmp_path, mixed,
                                                           warm_calls):
    keys = [ed25519.gen_priv_key(bytes([70 + i]) * 32).pub_key()
            for i in range(2)]
    if mixed:
        keys.append(sr25519.gen_priv_key(b"\x61" * 32).pub_key())
    t0 = time.monotonic()
    node = _node(tmp_path, keys)
    node.start()
    try:
        assert cbatch.WARMUP.join(30)
    finally:
        node.stop()
    assert cbatch.WARMUP.state == "done", cbatch.WARMUP.error
    assert ("ed25519", 64, True) in warm_calls
    assert (("sr25519", 64, True) in warm_calls) is mixed
    assert cbatch.WARMUP.key_types == (
        {"ed25519", "sr25519"} if mixed else {"ed25519"})
    # recorded in the start-up ring, with tracing off
    assert _warm_spans(t0) == [("ed25519", 64)] + (
        [("sr25519", 64)] if mixed else [])


def test_a_later_warm_up_compiles_only_what_no_earlier_one_did(warm_calls):
    t0 = time.monotonic()
    assert cbatch.warmup(background=False) is None
    assert warm_calls == [("ed25519", 64, True)]
    # the same key types again: nothing; a key type without a kernel: nothing
    cbatch.warmup(background=False, key_types=("ed25519", "secp256k1"))
    assert warm_calls == [("ed25519", 64, True)]
    # a new one: its kernel alone, in a thread that waits for no one
    thread = cbatch.warmup(key_types=("sr25519", "ed25519"))
    assert cbatch.WARMUP.join(30) and not thread.is_alive()
    assert warm_calls == [("ed25519", 64, True), ("sr25519", 64, True)]
    assert cbatch.warmup(key_types=("sr25519",)) is None
    assert cbatch.WARMUP.state == "done"
    assert _warm_spans(t0) == [("ed25519", 64), ("sr25519", 64)]


def test_a_failed_warm_up_stays_failed_when_a_later_one_succeeds(
        warm_calls, monkeypatch):
    def boom():
        raise RuntimeError("no calibration today")

    monkeypatch.setattr(edb, "calibrate_host_crossover", boom)
    cbatch.warmup(background=False)
    assert cbatch.WARMUP.state == "failed"
    cbatch.warmup(background=False, key_types=("sr25519",))
    assert warm_calls == [("sr25519", 64, True)]
    assert cbatch.WARMUP.state == "failed"
    assert "no calibration today" in str(cbatch.WARMUP.error)


def test_sr25519_verify_batch_can_be_pinned_to_the_device(monkeypatch):
    """What the warm-up needs of the ops module: 64 signatures are under the
    host crossover, and force_device sends them to the kernel all the same."""
    seen = []
    monkeypatch.setattr(edb, "route_batch", lambda n, force=False, scalar_min=0:
                        seen.append((n, force)) or "host")
    priv = sr25519.gen_priv_key(b"\x62" * 32)
    item = (priv.pub_key().bytes(), b"m", priv.sign(b"m"))
    assert srb.verify_batch([item] * 3, force_device=True).all()
    assert srb.verify_batch([item] * 3).all()
    assert seen == [(3, True), (3, False)]


# --- the commit->apply seam's counters ---------------------------------------------


def _executor():
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.state.execution import BlockExecutor

    gd, privs = _genesis()
    state = make_genesis_state(gd)
    store = StateStore(MemDB())
    store.save(state)
    return BlockExecutor(store, KVStoreApplication()), state, privs


def _counts(bx):
    return (bx.commit_verify_dispatched, bx.commit_verify_fresh,
            bx.commit_verify_stale)


def test_the_seam_counts_a_fresh_and_a_stale_handle():
    from tendermint_tpu.types.block import Commit
    from tendermint_tpu.types.block_id import BlockID

    bx, state, privs = _executor()
    empty = Commit(height=0, round=0, block_id=BlockID(), signatures=[])
    block1 = state.make_block(1, [b"a=1"], empty, [],
                              state.validators.get_proposer().address)
    bid1, commit1 = _commit_for(state, block1, privs)
    # the initial block has no LastCommit: no handle, nothing counted
    assert bx.dispatch_commit_verify(state, block1) is None
    state, _ = bx.apply_block(state, bid1, block1)
    assert _counts(bx) == (0, 0, 0)

    block2 = state.make_block(2, [b"b=2"], commit1, [],
                              state.validators.get_proposer().address)
    bid2, commit2 = _commit_for(state, block2, privs)
    fresh = bx.dispatch_commit_verify(state, block2)
    assert _counts(bx) == (1, 0, 0)
    state, _ = bx.apply_block(state, bid2, block2, commit_pending=fresh)
    assert _counts(bx) == (1, 1, 0)

    block3 = state.make_block(3, [b"c=3"], commit2, [],
                              state.validators.get_proposer().address)
    bid3, _commit3 = _commit_for(state, block3, privs)
    handle = bx.dispatch_commit_verify(state, block3)
    stale = type(handle)(pending=handle.pending, height=handle.height,
                         last_block_id=handle.last_block_id,
                         vals_hash=b"\x00" * 32)
    state, _ = bx.apply_block(state, bid3, block3, commit_pending=stale)
    assert state.last_block_height == 3     # verified anew, synchronously
    assert _counts(bx) == (2, 1, 1)
    # no handle at all counts nothing
    block4 = state.make_block(4, [], _commit3, [],
                              state.validators.get_proposer().address)
    bid4, _ = _commit_for(state, block4, privs)
    state, _ = bx.apply_block(state, bid4, block4)
    assert _counts(bx) == (2, 1, 1)


# --- what the flight recorder says of an apply ---------------------------------------


@pytest.fixture
def traced():
    trace.dump(clear=True)
    trace.enable()
    yield
    trace.disable()
    trace.dump(clear=True)


def test_apply_validate_says_how_the_last_commit_was_answered(traced):
    from tendermint_tpu.types.block import Commit
    from tendermint_tpu.types.block_id import BlockID

    bx, state, privs = _executor()
    last_commit = Commit(height=0, round=0, block_id=BlockID(), signatures=[])
    for h, speculate in ((1, False), (2, True), (3, False)):
        block = state.make_block(h, [], last_commit, [],
                                 state.validators.get_proposer().address)
        bid, commit = _commit_for(state, block, privs)
        handle = bx.dispatch_commit_verify(state, block) if speculate else None
        state, _ = bx.apply_block(state, bid, block, commit_pending=handle)
        last_commit = commit
    spans = [s for s in trace.dump() if s.name == "apply.validate"]
    assert [s.tags["last_commit"] for s in spans] == ["none", "pending", "sync"]
    assert "last_commit_s" not in spans[0].tags
    for s in spans[1:]:
        assert 0 <= s.tags["last_commit_s"] <= s.duration_s
        assert s.tags["sigs"] == 2


def test_a_refused_last_commit_is_tagged_too(traced):
    """The wait is recorded whether the resolve accepts or raises."""
    from tendermint_tpu.types.block import Commit, CommitSig
    from tendermint_tpu.types.block_id import BlockID
    from tendermint_tpu.types.validator_set import ErrWrongSignature

    bx, state, privs = _executor()
    empty = Commit(height=0, round=0, block_id=BlockID(), signatures=[])
    block1 = state.make_block(1, [], empty, [],
                              state.validators.get_proposer().address)
    bid1, commit1 = _commit_for(state, block1, privs)
    state, _ = bx.apply_block(state, bid1, block1)
    cs = commit1.signatures[1]
    commit1.signatures[1] = CommitSig(
        cs.block_id_flag, cs.validator_address, cs.timestamp,
        cs.signature[:10] + bytes([cs.signature[10] ^ 0x40]) + cs.signature[11:])
    block2 = state.make_block(2, [], commit1, [],
                              state.validators.get_proposer().address)
    bid2, _ = _commit_for(state, block2, privs)
    handle = bx.dispatch_commit_verify(state, block2)
    with pytest.raises(ErrWrongSignature) as e:
        bx.apply_block(state, bid2, block2, commit_pending=handle)
    assert e.value.index == 1
    refused = [s for s in trace.dump() if s.name == "apply.validate"][-1]
    assert refused.tags["last_commit"] == "pending"
    assert refused.tags["last_commit_s"] >= 0


def test_state_save_carries_the_states_bytes(traced):
    from tendermint_tpu.state.store import _marshal_state

    gd, _privs = _genesis(n_vals=5)
    state = make_genesis_state(gd)
    store = StateStore(MemDB())
    store.save(state)
    span = [s for s in trace.dump() if s.name == "state.save"][-1]
    assert span.tags["bytes"] == len(_marshal_state(state)) > 5 * 3 * 40
    assert span.tags["validators"] == 5 and span.tags["height"] == 0
    assert store.load().validators.hash() == state.validators.hash()
    # with tracing off the save is the same save and writes no span
    trace.disable()
    trace.dump(clear=True)
    store.save(state)
    assert not trace.dump()


def test_the_new_spans_are_canonical():
    for name in ("state.save", "startup.warm_kernel"):
        assert name in trace.CANONICAL_SPANS
    assert "last_commit_s" in trace.CANONICAL_SPANS["apply.validate"]
