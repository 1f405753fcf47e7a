"""ISSUE 43: ``ConsensusReactor.receive`` decodes a vote's copies once. The
``Vote`` built from a wire message is handed to every later delivery of the
same bytes; what each delivery does (the peer's bit, the state machine's
queue, the WAL) stays one a delivery and a peer."""

import random
import sys
import threading

import pytest

from benchmark.drivers.votedrain import PEERS, _step_deliveries
from tendermint_tpu.config.config import test_config as _test_config
from tendermint_tpu.consensus import cstypes
from tendermint_tpu.consensus import reactor as cr
from tendermint_tpu.consensus.state_machine import ConsensusState, VoteMessage
from tendermint_tpu.consensus.wal import WAL
from tendermint_tpu.encoding import proto
from tendermint_tpu.state.state import make_genesis_state
from tendermint_tpu.types.block_id import BlockID, PartSetHeader
from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
from tendermint_tpu.types.ttime import Time
from tendermint_tpu.types.vote import PRECOMMIT_TYPE, PREVOTE_TYPE, Vote
from tendermint_tpu.utils import trace
from tests.test_vote_batching import CHAIN_ID, _net, _signed_vote

HEIGHT = 5
BLOCK = BlockID(hash=b"\x77" * 32,
                part_set_header=PartSetHeader(total=1, hash=b"\x88" * 32))
NIL = BlockID()


@pytest.fixture(scope="module")
def nets():
    return {n: _net(n)[0] for n in (8, 24, 700)}


@pytest.fixture
def tracer():
    t = trace.Tracer("vote-memo", cap=256, enabled=True)
    yield t
    t.disable()


class _Peer:
    """Stands where a ``p2p.Peer`` stands for ``receive``: an id and the
    reactor's ``PeerState``, whose ``set_has_vote`` calls it records."""

    def __init__(self, name):
        self.id = name
        self.ps = cr.PeerState(self)
        self.has_vote = []
        real = self.ps.set_has_vote

        def set_has_vote(*args):
            self.has_vote.append(args)
            real(*args)

        self.ps.set_has_vote = set_has_vote

    def get(self, key):
        return self.ps if key == "consensus_peer_state" else None

    def try_send(self, ch_id, msg):
        return True


class _Node:
    """A state machine with ``privs`` as its validators, set to ``height``,
    behind its reactor and three peers. ``queued`` is what ``receive``
    handed to ``cs.add_vote``, in order: (peer id, the ``Vote`` object)."""

    def __init__(self, privs, height=HEIGHT, wal=None, tracer=None):
        self.privs = privs
        state = make_genesis_state(GenesisDoc(
            chain_id=CHAIN_ID, genesis_time=Time(1700001000, 0),
            validators=[GenesisValidator(b"", p.pub_key(), 10) for p in privs]))
        self.cs = cs = ConsensusState(_test_config().consensus, state, None,
                                      None, wal=wal)
        if tracer is not None:
            cs.tracer = tracer
        self.vals = cs.rs.votes.val_set
        cs.rs.height = height
        cs.rs.votes = cstypes.HeightVoteSet(CHAIN_ID, height, self.vals)
        cs.rs.step = cstypes.STEP_PREVOTE
        self.reactor = cr.ConsensusReactor(cs)
        self.memo = self.reactor.vote_memo
        self.peers = [_Peer(f"peer{p}") for p in range(PEERS)]
        self.queued = []
        real = cs.add_vote

        def add_vote(vote, peer_id=""):
            self.queued.append((peer_id, vote))
            real(vote, peer_id=peer_id)

        cs.add_vote = add_vote

    def vote(self, priv, type_=PREVOTE_TYPE, block_id=BLOCK, height=HEIGHT,
             round_=0, nanos=0):
        v = _signed_vote(priv, self.vals, type_, block_id)
        v.height, v.round = height, round_
        v.timestamp = Time(1700001000 + height, nanos)
        v.signature = priv.sign(v.sign_bytes(CHAIN_ID))
        return v

    def receive(self, p, msg, ch_id=cr.VOTE_CHANNEL):
        self.reactor.receive(ch_id, self.peers[p], msg)

    def step_to(self, height):
        """The state machine's own step into ``height``, hooks and all."""
        self.cs.rs.height = height
        self.cs.rs.step = cstypes.STEP_NEW_HEIGHT
        self.cs._new_step()


def _parent(msg: bytes):
    """What the vote channel's branch did with a message before the memo:
    -> the ``Vote``, or None where the message carries none; raises what the
    generic reader raises."""
    f = proto.fields(msg)
    if 6 not in f:
        return None
    m = proto.fields(f[6][-1])
    return Vote.unmarshal(m.get(1, [b""])[-1])


def _round_order(slots, burst):
    """The vote-drain cell's own order of one step's deliveries
    (``benchmark/drivers/votedrain.py``): in round k a peer first delivers
    its copies of the other two peers' bursts of round k - 1, then a burst
    of the votes it originates (slot mod 3) -> [(peer, slot)]."""
    present = set(slots)
    votes = [i if i in present else None for i in range(max(present) + 1)]
    return [(p, i) for p, i, _ in _step_deliveries(
        votes, dict.fromkeys(present), burst)]


def _flip_signature_byte(msg: bytes, rng) -> bytes:
    """The same message with one byte of the signature (its last 64 bytes)
    changed."""
    at = len(msg) - 1 - rng.randrange(64)
    return msg[:at] + bytes([msg[at] ^ (1 + rng.randrange(255))]) + msg[at + 1:]


def _stream(node, seed):
    """A seeded stream on the vote channel -> [(peer, wire bytes)]: a step of
    prevotes and one of precommits (some for nil, some of round 1), every
    vote three times in the driver's round order, and among them a copy with
    one signature byte changed, a truncated message, a message that carries
    no vote, a vote of the height two back and one of the next height."""
    rng = random.Random(seed)
    out = []
    for type_ in (PREVOTE_TYPE, PRECOMMIT_TYPE):
        wire = {}
        for i, priv in enumerate(node.privs):
            if rng.random() < 0.1:
                continue                                    # absent
            wire[i] = cr.msg_vote(node.vote(
                priv, type_, NIL if rng.random() < 0.2 else BLOCK,
                round_=int(rng.random() < 0.2), nanos=rng.randrange(10**9)))
        out += [(p, wire[i]) for p, i in _round_order(sorted(wire), burst=3)]
    odd = [
        _flip_signature_byte(rng.choice(out)[1], rng),
        rng.choice(out)[1][:-rng.randrange(1, 100)],
        cr.msg_has_vote(HEIGHT, 0, PREVOTE_TYPE, 3),
        cr.msg_vote(node.vote(node.privs[0], PRECOMMIT_TYPE, height=HEIGHT - 2)),
        cr.msg_vote(node.vote(node.privs[1], PREVOTE_TYPE, height=HEIGHT + 1)),
    ]
    for msg in odd:
        for p in rng.sample(range(PEERS), 2):     # each from two of the peers
            out.insert(rng.randrange(len(out) // 2, len(out) + 1), (p, msg))
    return out


@pytest.mark.parametrize("seed", [43, 2147483690, 3000000043])
def test_every_delivery_gets_the_vote_the_generic_reader_builds(nets, seed):
    node = _Node(nets[24])
    stream = _stream(node, seed)
    want_queued = []
    want_has_vote = [[] for _ in range(PEERS)]
    raised = 0
    for p, msg in stream:
        try:
            want = _parent(msg)
        except ValueError as e:
            with pytest.raises(ValueError, match=str(e)):
                node.receive(p, msg)
            raised += 1
            continue
        node.receive(p, msg)
        if want is not None:
            want_queued.append((f"peer{p}", want))
            want_has_vote[p].append((want.height, want.round, want.type,
                                     want.validator_index, 24))
    assert raised == 2                      # the truncated one, from two peers
    assert node.queued == want_queued       # equal votes, delivery by delivery
    assert [peer.has_vote for peer in node.peers] == want_has_vote
    # and the state machine's queue holds a message a delivery, in order
    held = []
    while not node.cs._msg_queue.empty():
        mi = node.cs._msg_queue.get_nowait()
        assert isinstance(mi.msg, VoteMessage)
        held.append((mi.peer_id, mi.msg.vote))
    assert sorted(held, key=lambda pv: pv[0]) == sorted(
        want_queued, key=lambda pv: pv[0])  # per peer, the order it sent them
    hits, misses, full, size = node.memo.counts()
    distinct = {msg for _, msg in stream}
    assert hits + misses == len(stream) and full == 0
    # kept: every distinct message but the truncated one, the one without a
    # vote and the two of heights the node is not at
    assert size == len(distinct) - 4
    assert misses == size + 2 * 4


def test_a_copy_is_the_same_object_and_a_corrupted_copy_is_not(nets):
    node = _Node(nets[8])
    msg = cr.msg_vote(node.vote(node.privs[2], PRECOMMIT_TYPE))
    for p in range(PEERS):
        node.receive(p, msg)
    first, second, third = (v for _, v in node.queued)
    assert first is second is third
    assert first == _parent(msg)
    bad = _flip_signature_byte(msg, random.Random(1))
    node.receive(0, bad)
    node.receive(1, bad)
    fourth, fifth = (v for _, v in node.queued[3:])
    assert fourth is not first and fourth != first
    assert fourth is fifth and fourth == _parent(bad)
    assert (fourth.height, fourth.validator_index) == (first.height, 2)
    assert node.memo.counts() == (3, 2, 0, 2)
    # a copy costs the peer its own bit all the same
    assert [len(peer.has_vote) for peer in node.peers] == [2, 2, 1]


def test_during_fast_sync_nothing_is_looked_up_kept_or_queued(nets):
    node = _Node(nets[8])
    node.reactor.wait_sync = True
    msg = cr.msg_vote(node.vote(node.privs[0]))
    for p in range(PEERS):
        node.receive(p, msg)
    assert node.memo.counts() == (0, 0, 0, 0)
    assert node.queued == [] and node.cs._msg_queue.empty()
    assert all(peer.has_vote == [] for peer in node.peers)
    with pytest.raises(ValueError):             # malformed is still refused
        node.receive(0, msg[:-5])
    node.reactor.wait_sync = False
    node.receive(0, msg)
    assert node.memo.counts() == (0, 1, 0, 1) and len(node.queued) == 1


def test_at_the_bound_nothing_more_is_kept_and_every_vote_still_arrives(nets):
    node = _Node(nets[8])
    assert cr.VoteMemo.bound(8) == 32
    wire = [cr.msg_vote(node.vote(priv, type_, round_=r))
            for r in range(4) for type_ in (PREVOTE_TYPE, PRECOMMIT_TYPE)
            for priv in node.privs]                       # 64 distinct votes
    for p in range(PEERS):
        for msg in wire:
            node.receive(p, msg)
    hits, misses, full, size = node.memo.counts()
    assert size == 32                                     # never past it
    assert (hits, misses, full) == (2 * 32, 32 + 3 * 32, 3 * 32)
    assert [v for _, v in node.queued] == [_parent(m) for m in wire] * PEERS
    kept = [v for _, v in node.queued[:32]]
    assert all(a is b for a, b in zip(kept, (v for _, v in node.queued[64:96])))
    # the room comes back with the next height but one
    node.step_to(HEIGHT + 2)
    assert node.memo.counts()[3] == 0
    node.receive(0, cr.msg_vote(node.vote(node.privs[0], height=HEIGHT + 2)))
    assert node.memo.counts()[2:] == (full, 1)


def test_no_validator_set_no_memo(nets):
    node = _Node(nets[8])
    node.cs.rs.validators = None
    msg = cr.msg_vote(node.vote(node.privs[0]))
    node.receive(0, msg)
    node.receive(1, msg)
    assert node.memo.counts() == (0, 2, 2, 0)
    assert [v for _, v in node.queued] == [_parent(msg)] * 2


def test_a_step_into_a_new_height_drops_all_but_the_height_before(nets):
    node = _Node(nets[8])
    by_height = {h: cr.msg_vote(node.vote(node.privs[h % 8], PRECOMMIT_TYPE,
                                          height=h))
                 for h in (HEIGHT - 1, HEIGHT, HEIGHT + 1)}
    for msg in by_height.values():
        node.receive(0, msg)
    assert node.memo.counts() == (0, 3, 0, 2)     # HEIGHT + 1 is not kept yet
    node.step_to(HEIGHT + 1)
    held = {v.height for v in node.memo._votes.values()}
    assert held == {HEIGHT}                       # HEIGHT - 1 went
    node.receive(1, by_height[HEIGHT])            # a late precommit's copy
    node.receive(1, by_height[HEIGHT + 1])
    node.receive(2, by_height[HEIGHT + 1])
    assert node.memo.counts() == (2, 4, 0, 2)
    assert node.queued[3][1] is node.queued[1][1]
    assert node.queued[5][1] is node.queued[4][1]
    # a step within the height drops nothing
    node.cs.rs.step = cstypes.STEP_PROPOSE
    node.cs._new_step()
    assert node.memo.counts()[3] == 2
    node.step_to(HEIGHT + 3)
    assert node.memo.counts()[3] == 0


def test_three_threads_with_the_same_messages_keep_one_vote_a_message(nets):
    node = _Node(nets[700])
    rng = random.Random(43)
    wire = []
    for k in range(2000):           # the reactor never looks at a signature
        v = Vote(type=PRECOMMIT_TYPE if k % 2 else PREVOTE_TYPE, height=HEIGHT,
                 round=k // 1400, block_id=BLOCK,
                 timestamp=Time(1700001000, rng.randrange(10**9)),
                 validator_address=rng.randbytes(20),
                 validator_index=k % 700, signature=rng.randbytes(64))
        wire.append(cr.msg_vote(v))
    assert len(set(wire)) == 2000 <= cr.VoteMemo.bound(700)
    start = threading.Barrier(PEERS)
    errors = []

    def deliver(p):
        try:
            start.wait(timeout=30)
            for msg in wire:
                node.receive(p, msg)
        except Exception as e:  # noqa: BLE001 - the assertion below names it
            errors.append(e)

    threads = [threading.Thread(target=deliver, args=(p,), name=f"recv-{p}")
               for p in range(PEERS)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)     # the lock changes hands inside a decode
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads) and not errors
    hits, misses, full, size = node.memo.counts()
    assert size == 2000 and full == 0 and hits + misses == 6000
    assert misses >= 2000           # a lost race decodes twice, and keeps one
    assert len(node.queued) == node.cs._msg_queue.qsize() == 6000
    by_peer = {f"peer{p}": [] for p in range(PEERS)}
    for peer_id, vote in node.queued:
        by_peer[peer_id].append(vote)
    for votes in by_peer.values():
        assert len(votes) == 2000   # every peer's, in the order it sent them
        assert all(a is b for a, b in zip(votes, by_peer["peer0"]))
    assert by_peer["peer0"] == [_parent(m) for m in wire]
    assert [len(peer.has_vote) for peer in node.peers] == [2000] * PEERS


def test_the_recv_mark_carries_the_memo_s_counts_of_the_height(nets, tracer):
    node = _Node(nets[8], tracer=tracer)
    node.step_to(HEIGHT)                          # the baseline mark
    wire = [cr.msg_vote(node.vote(priv)) for priv in node.privs]
    for p in range(PEERS):
        for msg in wire[:5 + p]:
            node.receive(p, msg)
    node.step_to(HEIGHT + 1)
    (mark,) = [s for s in tracer.dump() if s.name == "consensus.recv"]
    tags = {k: v for k, v in mark.tags.items() if k.startswith("vote_memo")}
    assert tags == {"vote_memo_hits": 11, "vote_memo_misses": 7,
                    "vote_memo_full": 0, "vote_memo_size": 7}
    assert mark.tags["height"] == HEIGHT
    # the next height's mark counts from this one; the size is what is held
    tracer.clear()
    node.receive(0, wire[0])
    node.step_to(HEIGHT + 2)
    (mark,) = [s for s in tracer.dump() if s.name == "consensus.recv"]
    assert (mark.tags["vote_memo_hits"], mark.tags["vote_memo_misses"],
            mark.tags["vote_memo_size"]) == (1, 0, 7)
    assert node.memo.counts()[3] == 0             # dropped after the mark


def test_copies_through_the_drain_are_counted_once_and_logged_each(
        nets, tmp_path):
    """The objects are shared from ``receive`` to the vote set and the WAL:
    every delivery is a WAL message of its own under its peer's id, written
    before it is handled, and the vote is counted once."""
    node = _Node(nets[24], height=1, wal=WAL(str(tmp_path / "wal")))
    cs = node.cs
    counted = []
    cs.on_vote.append(lambda v: counted.append(v.validator_index))
    votes = [node.vote(p, PREVOTE_TYPE, height=1) for p in node.privs[:15]]
    wire = {v.validator_index: cr.msg_vote(v) for v in votes}
    order = _round_order(sorted(wire), burst=2)
    for p, i in order:
        node.receive(p, wire[i])
    assert node.memo.counts() == (30, 15, 0, 15)
    done = threading.Event()
    cs._msg_queue.put(("__sync__", done))
    cs._running = True
    loop = threading.Thread(target=cs._receive_routine, name="cs-receive")
    loop.start()
    try:
        assert done.wait(timeout=60)
    finally:
        cs._running = False
        cs._msg_queue.put(None)
        loop.join(timeout=30)
    assert not loop.is_alive()
    cs.wal.close()
    logged = [(tm.msg.peer_id, Vote.unmarshal(tm.msg.payload))
              for tm, _at in WAL(str(tmp_path / "wal")).iter_messages()
              if getattr(tm.msg, "kind", "") == "vote"]
    assert logged == [(f"peer{p}", _parent(wire[i])) for p, i in order]
    assert sorted(counted) == sorted(wire) and len(counted) == 15
    held = cs.rs.votes.prevotes(0)
    assert all(held.get_by_index(i) is node.queued[k][1]
               for k, (p, i) in enumerate(order) if p == i % PEERS)
