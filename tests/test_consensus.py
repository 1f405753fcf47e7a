"""In-process multi-validator consensus harness (the reference's
consensus/common_test.go pattern): N full consensus state machines in one
process, wired by direct message delivery instead of TCP, driving real blocks
through real ABCI apps. Plus WAL crash-recovery checks."""

import os
import tempfile
import time

import pytest

from tendermint_tpu.abci.kvstore import KVStoreApplication
from tendermint_tpu.config.config import test_config as make_test_config
from tendermint_tpu.consensus.state_machine import (
    BlockPartMessage,
    ConsensusState,
    ProposalMessage,
    VoteMessage,
)
from tendermint_tpu.consensus.wal import WAL
from tendermint_tpu.crypto import ed25519
from tendermint_tpu.mempool.mempool import Mempool
from tendermint_tpu.privval.file_pv import MockPV
from tendermint_tpu.state.execution import BlockExecutor
from tendermint_tpu.state.state import make_genesis_state
from tendermint_tpu.state.store import StateStore
from tendermint_tpu.store.block_store import BlockStore
from tendermint_tpu.store.db import MemDB
from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
from tendermint_tpu.types.ttime import Time


class Node:
    def __init__(self, genesis, pv, cfg, wal_dir=None):
        self.app = KVStoreApplication()
        self.state_store = StateStore(MemDB())
        self.block_store = BlockStore(MemDB())
        self.mempool = Mempool(self.app)
        state = make_genesis_state(genesis)
        self.state_store.save(state)
        self.block_exec = BlockExecutor(
            self.state_store, self.app, mempool=self.mempool,
            block_store=self.block_store,
        )
        wal = WAL(wal_dir) if wal_dir else None
        self.cs = ConsensusState(
            cfg.consensus, state, self.block_exec, self.block_store,
            mempool=self.mempool, priv_validator=pv, wal=wal,
        )


def make_net(n, wal_base=None):
    privs = [ed25519.gen_priv_key(bytes([50 + i]) * 32) for i in range(n)]
    pvs = [MockPV(p) for p in privs]
    genesis = GenesisDoc(
        chain_id="harness-chain",
        genesis_time=Time(1700001000, 0),
        validators=[GenesisValidator(b"", p.pub_key(), 10) for p in privs],
    )
    cfg = make_test_config()
    nodes = [
        Node(genesis, pvs[i], cfg,
             wal_dir=os.path.join(wal_base, f"wal{i}") if wal_base else None)
        for i in range(n)
    ]

    # the in-memory "switch": deliver every internally-generated message to
    # every other node as if gossiped
    def wire(i):
        def bcast(msg):
            for j, other in enumerate(nodes):
                if j == i:
                    continue
                if isinstance(msg, VoteMessage):
                    other.cs.add_vote(msg.vote.copy(), peer_id=f"peer{i}")
                elif isinstance(msg, ProposalMessage):
                    other.cs.set_proposal(msg.proposal, peer_id=f"peer{i}")
                elif isinstance(msg, BlockPartMessage):
                    other.cs.add_proposal_block_part(
                        msg.height, msg.round, msg.part, peer_id=f"peer{i}")
        nodes[i].cs.broadcast = bcast

    for i in range(n):
        wire(i)
    return nodes


def wait_height(nodes, h, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if all(n.block_store.height >= h for n in nodes):
            return True
        time.sleep(0.05)
    return False


def test_single_validator_chain():
    nodes = make_net(1)
    nodes[0].mempool.check_tx(b"solo=1")
    for n in nodes:
        n.cs.start()
    try:
        assert wait_height(nodes, 3, timeout=30), (
            f"heights: {[n.block_store.height for n in nodes]}"
        )
        b1 = nodes[0].block_store.load_block(1)
        assert b1 is not None
    finally:
        for n in nodes:
            n.cs.stop()


def test_four_validator_net_commits_blocks():
    nodes = make_net(4)
    nodes[0].mempool.check_tx(b"a=1")
    nodes[1].mempool.check_tx(b"b=2")
    for n in nodes:
        n.cs.start()
    try:
        assert wait_height(nodes, 3, timeout=60), (
            f"heights: {[n.block_store.height for n in nodes]}"
        )
        # all nodes committed identical blocks
        for h in range(1, 4):
            hashes = {n.block_store.load_block(h).hash() for n in nodes}
            assert len(hashes) == 1, f"fork at height {h}!"
        # applied state trails the block store by at most the in-flight block
        st = nodes[0].state_store.load()
        assert st.last_block_height >= 2
    finally:
        for n in nodes:
            n.cs.stop()


def test_net_progresses_with_one_node_down():
    """3 of 4 validators (>2/3) must still make progress."""
    nodes = make_net(4)
    for n in nodes[:3]:
        n.cs.start()
    try:
        assert wait_height(nodes[:3], 2, timeout=60), (
            f"heights: {[n.block_store.height for n in nodes[:3]]}"
        )
    finally:
        for n in nodes[:3]:
            n.cs.stop()


def test_wal_written_and_replayable():
    with tempfile.TemporaryDirectory() as d:
        nodes = make_net(1, wal_base=d)
        for n in nodes:
            n.cs.start()
        try:
            assert wait_height(nodes, 2, timeout=30)
        finally:
            for n in nodes:
                n.cs.stop()
        # WAL contains EndHeight markers for committed heights
        wal = WAL(os.path.join(d, "wal0"))
        from tendermint_tpu.consensus.wal import EndHeightMessage

        heights = [tm.msg.height for tm, _ in wal.iter_messages()
                   if isinstance(tm.msg, EndHeightMessage)]
        assert 1 in heights and 2 in heights


def test_drained_votes_are_in_the_wal_as_delivered_and_replay_to_the_same_state():
    """ISSUE 41: a drain's votes are written in one pass, a message each. One
    real validator (power 100) that needs two of twelve simulated ones (5
    each) to commit; at its own prevote they deliver their prevotes and
    precommits, nine for the block and three for nil, three copies from three
    peers, so every height commits through a drain. The WAL then holds every
    delivery, copies included, in order, and a fresh machine fed the WAL alone
    in replay mode reaches the same height and app hash."""
    from tendermint_tpu.consensus.state_machine import MsgInfo, wal_blob_to_msg
    from tendermint_tpu.consensus.ticker import TimeoutInfo
    from tendermint_tpu.consensus.wal import WALMessageBlob
    from tendermint_tpu.types.block_id import BlockID
    from tendermint_tpu.types.vote import PRECOMMIT_TYPE, PREVOTE_TYPE, Vote
    from tendermint_tpu.utils import trace

    heights, peers = 2, ("peerA", "peerB", "peerC")
    priv = ed25519.gen_priv_key(b"\x41" * 32)
    ghosts = [ed25519.gen_priv_key(bytes([0x60 + i]) * 32) for i in range(12)]
    genesis = GenesisDoc(
        chain_id="harness-chain", genesis_time=Time(1700001000, 0),
        validators=[GenesisValidator(b"", priv.pub_key(), 100)]
        + [GenesisValidator(b"", g.pub_key(), 5) for g in ghosts])
    delivered: list = []

    def ghost_votes(cs, own: Vote):
        vals = cs.rs.validators
        for vtype in (PREVOTE_TYPE, PRECOMMIT_TYPE):
            for k, g in enumerate(ghosts):
                address = g.pub_key().address()
                v = Vote(type=vtype, height=own.height, round=own.round,
                         block_id=own.block_id if k < 9 else BlockID(),
                         timestamp=Time(1700001000 + own.height, 1000 * k),
                         validator_address=address,
                         validator_index=vals.get_by_address(address)[0])
                v.signature = g.sign(v.sign_bytes("harness-chain"))
                yield v

    with tempfile.TemporaryDirectory() as d:
        node = Node(genesis, MockPV(priv), make_test_config(),
                    wal_dir=os.path.join(d, "wal"))
        cs = node.cs
        cs.tracer = trace.Tracer("drain-replay", enabled=True)

        def on_own_message(msg):
            # on the consensus thread, so the whole burst is queued before
            # the receive loop takes its first vote: one drain a height
            if (isinstance(msg, VoteMessage) and msg.vote.type == PREVOTE_TYPE
                    and msg.vote.round == 0 and msg.vote.height <= heights):
                votes = list(ghost_votes(cs, msg.vote))
                for copy, peer in enumerate(peers):
                    for v in votes[copy:] + votes[:copy]:
                        delivered.append((peer, v))
                        cs.add_vote(v.copy(), peer_id=peer)

        cs.broadcast = on_own_message
        node.mempool.check_tx(b"drained=1")
        cs.start()
        try:
            assert wait_height([node], heights, timeout=30)
        finally:
            cs.stop()
            cs.tracer.disable()
        assert len(delivered) == heights * 2 * len(ghosts) * len(peers)
        writes = [s.tags for s in cs.tracer.dump()
                  if s.name == "consensus.wal_write"]
        assert [t["writes"] for t in writes] == [1] * heights
        assert sum(t["msgs"] for t in writes) == len(delivered)

        records = [tm.msg for tm, _ in WAL(os.path.join(d, "wal")).iter_messages()]
        from_peers = [(m.peer_id, wal_blob_to_msg(m).vote) for m in records
                      if isinstance(m, WALMessageBlob) and m.peer_id]
        assert from_peers == delivered

        fresh_node = Node(genesis, None, make_test_config())
        fresh = fresh_node.cs
        fresh._ticker.stop()        # the WAL holds the timeouts that fired
        fresh.replay_mode = True
        for m in records:
            msg = wal_blob_to_msg(m) if isinstance(m, WALMessageBlob) else None
            if isinstance(msg, TimeoutInfo):
                fresh._do_handle_timeout(msg)
            elif msg is not None:
                with fresh._mtx:
                    fresh._handle_msg(MsgInfo(msg, m.peer_id))
        ran, replayed = node.state_store.load(), fresh_node.state_store.load()
        assert ran.last_block_height >= heights
        assert ((replayed.last_block_height, replayed.app_hash)
                == (ran.last_block_height, ran.app_hash))
