"""ISSUE 36: the reactor tells its peers once a unit of work which votes the
state machine added. A group of votes of one (height, round, type, block id)
leaves as its ``HasVote``s or as one ``VoteSetBits`` with every vote the
node holds for that block id, whichever is fewer bytes; ``cs.on_vote`` and
the ``Vote`` event stay one a vote, in place."""

import threading

import pytest

from tendermint_tpu.config.config import test_config as _test_config
from tendermint_tpu.consensus import cstypes
from tendermint_tpu.consensus import reactor as cr
from tendermint_tpu.consensus.state_machine import (
    ConsensusState,
    MsgInfo,
    VoteMessage,
)
from tendermint_tpu.encoding import proto
from tendermint_tpu.state.state import make_genesis_state
from tendermint_tpu.types import events as tmevents
from tendermint_tpu.types.block_id import BlockID, PartSetHeader
from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
from tendermint_tpu.types.ttime import Time
from tendermint_tpu.types.vote import PRECOMMIT_TYPE, PREVOTE_TYPE
from tendermint_tpu.types.vote_set import VoteSet
from tendermint_tpu.utils import trace
from tendermint_tpu.utils.bits import BitArray
from tests.test_vote_batching import CHAIN_ID, _net, _signed_vote

LARGE = 200
BLOCK = BlockID(hash=b"\x77" * 32,
                part_set_header=PartSetHeader(total=1, hash=b"\x88" * 32))
NIL = BlockID()


@pytest.fixture(scope="module")
def nets():
    return {n: _net(n)[0] for n in (4, 50, LARGE)}


class _Wire:
    """Stands where the ``Switch`` stands: what the reactor offers every
    peer, in the order it offers it. ``full`` plays a send queue that takes
    nothing (``try_send`` drops the message for good)."""

    def __init__(self):
        self.sent, self.full, self.logger = [], False, None

    def broadcast(self, ch_id, msg):
        if not self.full:
            self.sent.append((ch_id, msg))

    def take(self):
        sent, self.sent = self.sent, []
        return sent


class _Node:
    """A state machine at height 1, step prevote (``late``: at height 2,
    step new-height, over a last commit of height 1 that lacks the votes to
    come), behind its reactor on a ``_Wire``."""

    def __init__(self, privs, late=False, tracer=None):
        self.privs = privs
        state = make_genesis_state(GenesisDoc(
            chain_id=CHAIN_ID, genesis_time=Time(1700001000, 0),
            validators=[GenesisValidator(b"", p.pub_key(), 10) for p in privs]))
        self.cs = cs = ConsensusState(_test_config().consensus, state, None, None)
        if tracer is not None:
            cs.tracer = tracer
        self.vals = cs.rs.votes.val_set
        cs.rs.step = cstypes.STEP_PREVOTE
        if late:
            commit = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT_TYPE, self.vals)
            for p in privs[:2 * len(privs) // 3 + 1]:
                commit.add_vote(self.vote(p, PRECOMMIT_TYPE, BLOCK), verified=True)
            assert commit.has_two_thirds_majority()
            cs.rs.last_commit, cs.rs.height = commit, 2
            cs.rs.votes = cstypes.HeightVoteSet(CHAIN_ID, 2, self.vals)
            cs.rs.step = cstypes.STEP_NEW_HEIGHT
        self.counted = []
        cs.on_vote.append(lambda v: self.counted.append(
            (v.type, v.height, v.validator_index)))
        self.reactor = cr.ConsensusReactor(cs)
        self.wire = self.reactor.switch = _Wire()

    def vote(self, priv, type_, block_id):
        return _signed_vote(priv, self.vals, type_, block_id)

    def drain(self, votes):
        """One drain's apply, every signature good, as the receive loop
        calls it."""
        msgs = [MsgInfo(VoteMessage(v), "peerX") for v in votes]
        with self.cs._mtx:
            self.cs._apply_vote_results(msgs, dict.fromkeys(range(len(msgs)), True))

    def single(self, vote):
        with self.cs._mtx:
            self.cs._handle_msg(MsgInfo(VoteMessage(vote), "peerY"))

    def held(self, type_, block_id=None) -> BitArray:
        """What the node holds: of one block id, or of the whole set."""
        rs = self.cs.rs
        if rs.height == 2:
            vote_set = rs.last_commit
        else:
            vote_set = (rs.votes.prevotes(0) if type_ == PREVOTE_TYPE
                        else rs.votes.precommits(0))
        if block_id is None:
            return vote_set.bit_array()
        return vote_set.bit_array_by_block_id(block_id)


def _decode(ch_id, msg):
    """-> (kind, height, round, type, index or (block id, BitArray))."""
    f = proto.fields(msg)
    if ch_id == cr.STATE_CHANNEL and 7 in f:
        m = proto.fields(f[7][-1])
        return ("has_vote", *(proto.as_sint64(m.get(k, [0])[-1])
                              for k in (1, 2, 3, 4)))
    if ch_id == cr.VOTE_SET_BITS_CHANNEL and 9 in f:
        m = proto.fields(f[9][-1])
        return ("bits", *(proto.as_sint64(m.get(k, [0])[-1]) for k in (1, 2, 3)),
                (BlockID.unmarshal(m.get(4, [b""])[-1]),
                 cr.bits_unmarshal(m.get(5, [b""])[-1])))
    if ch_id == cr.STATE_CHANNEL and 1 in f:
        m = proto.fields(f[1][-1])
        return ("step", proto.as_sint64(m.get(1, [0])[-1]))
    raise AssertionError((ch_id, msg))


class _StockEnd:
    """The reference's ``PeerState`` as far as vote bit arrays go
    (consensus/reactor.go ``getVoteBitArray``, ``SetHasVote``,
    ``ApplyVoteSetBitsMessage``; ``Receive`` passes ``ourVotes`` only for the
    peer's own height), on Python sets. ``ours``: the votes the stock node
    itself holds, by block id."""

    def __init__(self, height, last_commit_round=-1, ours=None):
        self.height, self.last_commit_round = height, last_commit_round
        self.views = {}         # (height, type) -> set of indices, round 0
        self.ours = ours or {}

    def _view(self, height, round_, type_):
        if height == self.height and round_ == 0:
            return self.views.setdefault((height, type_), set())
        if (height + 1 == self.height and type_ == PRECOMMIT_TYPE
                and round_ == self.last_commit_round):
            return self.views.setdefault("last_commit", set())
        return None

    def receive(self, ch_id, msg):
        kind, height, round_, type_, what = _decode(ch_id, msg)
        view = self._view(height, round_, type_)
        if view is None:
            return
        if kind == "has_vote":
            view.add(what)
            return
        block_id, bits = what
        said = {i for i, b in enumerate(bits) if b}
        ours = self.ours.get(block_id.key()) if height == self.height else None
        # votes.Update(...) copies: the view becomes what is computed here
        new = said if ours is None else (view - ours) | said
        view.clear()
        view.update(new)

    def view(self, node, type_):
        key = "last_commit" if node.cs.rs.height == 2 else (1, type_)
        got = self.views.get(key, set())
        return BitArray.from_bools([i in got for i in range(len(node.privs))])


class _ProgramEnd:
    """This program's own receiving side: a second node's reactor and the
    ``PeerState`` it keeps of the first, fed through ``_receive``."""

    class _Peer:
        id = "the-node"

        def __init__(self):
            self._data = {}

        def get(self, key):
            return self._data.get(key)

        def set(self, key, value):
            self._data[key] = value

    def __init__(self, node):
        self.far = _Node(node.privs, late=node.cs.rs.height == 2)
        self.peer = self._Peer()
        self.ps = cr.PeerState(self.peer)
        self.peer.set("consensus_peer_state", self.ps)
        # the node's NewRoundStep: the far end learns its height and round
        self.receive(cr.STATE_CHANNEL,
                     node.reactor._new_round_step_msg(node.cs.rs))

    def receive(self, ch_id, msg):
        self.far.reactor._receive(ch_id, self.peer, msg)

    def view(self, node, type_):
        prs = self.ps.prs
        if node.cs.rs.height == 2:
            return prs.last_commit
        table = prs.prevotes if type_ == PREVOTE_TYPE else prs.precommits
        return table.get(0, BitArray(len(node.privs)))


def _end(kind, node, votes=()):
    if kind == "program":
        return _ProgramEnd(node)
    late = node.cs.rs.height == 2
    # a stock node that itself holds every second of the votes to come (one
    # that holds none for a block id takes an array as the round's whole view)
    ours = {}
    for v in votes:
        if v.validator_index % 2 == 0:
            ours.setdefault(v.block_id.key(), set()).add(v.validator_index)
    return _StockEnd(node.cs.rs.height, 0 if late else -1, ours)


# --- a large set: one cumulative bit array a group ----------------------------


DRAINS = {
    # name -> (late, type, [the block id of each vote of a drain, in order])
    "block": (False, PREVOTE_TYPE, [BLOCK] * 60),
    "nil": (False, PRECOMMIT_TYPE, [NIL] * 60),
    "block-and-nil": (False, PREVOTE_TYPE, [BLOCK, BLOCK, NIL] * 20),
    "late-precommits": (True, PRECOMMIT_TYPE, [BLOCK] * 30),
}


@pytest.mark.parametrize("end_kind", ["stock", "program"])
@pytest.mark.parametrize("name", sorted(DRAINS))
def test_a_drain_offers_each_peer_one_cumulative_bit_array_a_group(
        name, end_kind, nets):
    late, type_, plan = DRAINS[name]
    node = _Node(nets[LARGE], late=late)
    absent = [p for p in node.privs
              if node.held(type_)[node.vals.get_by_address(
                  p.pub_key().address())[0]] is False]
    first = [node.vote(p, type_, bid) for p, bid in zip(absent, plan)]
    second = [node.vote(p, type_, bid)
              for p, bid in zip(absent[len(plan):], plan[:len(plan) // 2])]
    end = _end(end_kind, node, first + second)
    for votes in (first, second):
        before = len(node.counted)
        node.drain(votes)
        assert len(node.counted) - before == len(votes)
        sent = node.wire.take()
        got = [_decode(*m) for m in sent]
        # one VoteSetBits per (round, type, block id), no HasVote, the vote's
        # own height (h - 1 for a late precommit), the whole array held
        assert [g[0] for g in got] == ["bits"] * len(set(map(BlockID.key, plan)))
        for _kind, height, round_, t, (block_id, bits) in got:
            assert (height, round_, t) == (1, 0, type_)
            assert bits == node.held(type_, block_id)
            assert len(bits) == LARGE
        assert {g[4][0].key() for g in got} == {b.key() for b in plan}
        assert all(ch == cr.VOTE_SET_BITS_CHANNEL for ch, _ in sent)
        for m in sent:
            end.receive(*m)
        # the far end holds exactly the node's votes: nothing lost, nothing
        # it was not told (a delta would have wiped the stock end's view)
        assert end.view(node, type_) == node.held(type_)
    assert sum(node.held(type_)) > len(first) + len(second) - 1


@pytest.mark.parametrize("end_kind", ["stock", "program"])
def test_a_message_dropped_on_a_full_queue_is_healed_by_the_next_flush(
        end_kind, nets):
    node = _Node(nets[LARGE])
    votes = [node.vote(p, PREVOTE_TYPE, BLOCK) for p in node.privs[:90]]
    end = _end(end_kind, node, votes)
    node.wire.full = True
    node.drain(votes[:50])              # offered and dropped: never retried
    assert node.wire.take() == []
    node.wire.full = False
    node.drain(votes[50:])
    sent = node.wire.take()
    assert len(sent) == 1
    end.receive(*sent[0])
    assert sum(end.view(node, PREVOTE_TYPE)) == 90
    assert end.view(node, PREVOTE_TYPE) == node.held(PREVOTE_TYPE)


# --- whichever is fewer bytes --------------------------------------------------


@pytest.mark.parametrize("n_votes", [1, 2, 6, 8, 9, 10, 11, 14, 30])
def test_a_group_leaves_as_whichever_is_fewer_bytes(n_votes, nets):
    """50 validators: a bit array costs ~95 bytes, a HasVote ~8-9; no
    constant decides, the lengths do, and a tie keeps the HasVotes."""
    node = _Node(nets[50])
    votes = [node.vote(p, PREVOTE_TYPE, BLOCK) for p in node.privs[:n_votes]]
    node.drain(votes)
    sent = node.wire.take()
    each = [cr.msg_has_vote(1, 0, PREVOTE_TYPE, v.validator_index) for v in votes]
    whole = cr.msg_vote_set_bits(1, 0, PREVOTE_TYPE, BLOCK,
                                 node.held(PREVOTE_TYPE, BLOCK))
    if sum(map(len, each)) <= len(whole):
        assert sent == [(cr.STATE_CHANNEL, m) for m in each]
    else:
        assert sent == [(cr.VOTE_SET_BITS_CHANNEL, whole)]
    assert sum(len(m) for _, m in sent) == min(sum(map(len, each)), len(whole))
    # both sides of the choice occur among the cases
    assert len(each[0]) <= len(whole) < 30 * len(each[0])


# --- four validators: the parent's bytes ---------------------------------------


def _parent_wire(node):
    """What the parent sent: one HasVote per vote added, as it was added, and
    the NewRoundStep of a step where it was taken."""
    sent = []
    node.cs.on_vote.insert(0, lambda v: sent.append(cr.msg_has_vote(
        v.height, v.round, v.type, v.validator_index)))
    node.cs.on_new_round_step.insert(0, lambda rs: sent.append("step"))
    return sent


def _steps_named(sent):
    return ["step" if _decode(ch_id, msg)[0] == "step" else msg
            for ch_id, msg in sent]


@pytest.mark.parametrize("unit", ["drain", "single-messages", "own-vote"])
def test_four_validators_put_the_parents_bytes_on_the_wire_in_its_order(
        unit, nets):
    node = _Node(nets[4])
    parent = _parent_wire(node)
    p = node.privs
    if unit == "drain":
        # two block ids interleaved, then a copy that adds nothing; the
        # third vote is +2/3 of any, and the node steps into prevote-wait
        node.drain([node.vote(p[0], PREVOTE_TYPE, BLOCK),
                    node.vote(p[1], PREVOTE_TYPE, NIL),
                    node.vote(p[2], PREVOTE_TYPE, BLOCK),
                    node.vote(p[0], PREVOTE_TYPE, BLOCK)])
        assert len(node.counted) == 3
    elif unit == "single-messages":
        for priv in p[:2]:
            node.single(node.vote(priv, PRECOMMIT_TYPE, NIL))
            assert len(node.wire.sent) == len(node.counted)   # left at once
    else:
        # the node's own vote comes through the internal queue as a message
        node.single(node.vote(p[3], PREVOTE_TYPE, BLOCK))
    assert _steps_named(node.wire.sent) == parent and parent
    assert ("step" in parent) == (unit == "drain")
    assert all(ch == cr.STATE_CHANNEL for ch, _ in node.wire.sent)


def test_a_node_without_a_switch_keeps_nothing(nets):
    node = _Node(nets[4])
    node.reactor.switch = None
    node.drain([node.vote(p, PREVOTE_TYPE, NIL) for p in node.privs[:2]])
    assert node.reactor._added == [] and len(node.counted) == 2


# --- before the step -------------------------------------------------------------


@pytest.mark.parametrize("n", [4, LARGE])
def test_the_flush_precedes_the_new_round_step_on_the_wire(n, nets):
    """A step taken in the middle of a unit of work (a +2/3 inside a drain)
    first tells the peers of the votes added so far."""
    node = _Node(nets[n])
    votes = [node.vote(p, PREVOTE_TYPE, NIL) for p in node.privs[:n // 2]]
    with node.cs._mtx:
        for v in votes:
            assert node.cs._try_add_vote(v, "peerX", verified=True)
        assert node.wire.sent == []         # the unit has not ended
        node.cs._new_step()
    kinds = [_decode(*m)[0] for m in node.wire.take()]
    assert kinds[-1] == "step" and len(kinds) > 1
    assert set(kinds[:-1]) == ({"has_vote"} if n == 4 else {"bits"})
    # and nothing is told twice at the end of the unit
    for cb in node.cs.on_work_done:
        cb()
    assert node.wire.sent == []


# --- this program's receiving side ---------------------------------------------


def test_a_bit_array_for_the_height_before_marks_the_last_commit_as_has_votes_do(
        nets):
    node = _Node(nets[50], late=True)
    by_bits, by_has_votes = _ProgramEnd(node), _ProgramEnd(node)
    late = [node.vote(p, PRECOMMIT_TYPE, BLOCK) for p in node.privs[36:48]]
    node.drain(late)
    sent = node.wire.take()
    assert [_decode(*m)[:4] for m in sent] == [("bits", 1, 0, PRECOMMIT_TYPE)]
    by_bits.receive(*sent[0])
    for i, b in enumerate(node.held(PRECOMMIT_TYPE)):
        if b:
            by_has_votes.receive(cr.STATE_CHANNEL,
                                 cr.msg_has_vote(1, 0, PRECOMMIT_TYPE, i))
    assert by_bits.ps.prs.last_commit == by_has_votes.ps.prs.last_commit
    assert sum(by_bits.ps.prs.last_commit) == 34 + 12
    assert not by_bits.ps.prs.precommits        # nothing of height 2 was said


@pytest.mark.parametrize("claim", ["longer", "2**62 bits", "another round",
                                   "another height"])
def test_an_array_is_never_trusted_beyond_the_view(claim, nets):
    node = _Node(nets[4])
    end = _ProgramEnd(node)
    bits = BitArray.from_bools([True] * 9)
    height, round_ = 1, 0
    raw = None
    if claim == "2**62 bits":
        raw = proto.Writer().varint(1, 1 << 62).packed_varints(2, [0b1111]).out()
        assert len(BitArray.unmarshal(raw)) == 1 << 62     # and no such mask
    elif claim == "another round":
        round_ = 7
    elif claim == "another height":
        height = 5
    msg = cr.msg_vote_set_bits(height, round_, PREVOTE_TYPE, BLOCK, bits)
    if raw is not None:
        msg = cr._wrap(9, proto.Writer().varint(1, 1).varint(3, PREVOTE_TYPE)
                       .message(4, BLOCK.marshal(), always=True)
                       .message(5, raw, always=True).out())
    end.receive(cr.VOTE_SET_BITS_CHANNEL, msg)
    prs = end.ps.prs
    if claim in ("longer", "2**62 bits"):
        assert prs.prevotes[0] == [True] * 4 and len(prs.prevotes[0]) == 4
    else:
        assert not prs.prevotes and not prs.last_commit
    assert not prs.precommits


# --- on_vote and the event: one a vote, in place -------------------------------


def _through_the_receive_loop(node, deliveries):
    """``deliveries``: lists of votes; each list is queued whole (a drain
    when longer than one) and handled before the next is queued."""
    cs = node.cs
    cs._running = True
    loop = threading.Thread(target=cs._receive_routine, name="cs-receive")
    loop.start()
    try:
        for votes in deliveries:
            done = threading.Event()
            # queue the unit whole before the loop looks: hold its lock
            with cs._mtx:
                for v in votes:
                    cs._msg_queue.put(MsgInfo(VoteMessage(v), "peerX"))
                cs._msg_queue.put(("__sync__", done))
            assert done.wait(timeout=30)
    finally:
        cs._running = False
        cs._msg_queue.put(None)
        loop.join(timeout=10)
        assert not loop.is_alive()


@pytest.mark.parametrize("traced", [False, True], ids=["tracer-off", "tracer-on"])
def test_on_vote_sees_every_vote_once_in_arrival_order(traced, nets):
    t = trace.Tracer("announce", enabled=traced)
    node = _Node(nets[LARGE], tracer=t)
    sub = node.cs.event_bus.subscribe("test", "tm.event='Vote'", out_capacity=500)
    p = node.privs
    drain = ([node.vote(x, PREVOTE_TYPE, BLOCK) for x in p[:40]]
             + [node.vote(p[3], PREVOTE_TYPE, BLOCK)]              # a copy
             + [node.vote(x, PREVOTE_TYPE, NIL) for x in p[40:42]])
    alone = node.vote(p[50], PREVOTE_TYPE, BLOCK)
    try:
        _through_the_receive_loop(node, [drain, [alone]])
    finally:
        t.disable()
    want = [(v.type, v.height, v.validator_index)
            for v in drain[:40] + drain[41:] + [alone]]
    assert node.counted == want
    # every EventDataVote still arrives, one a vote, in the same order
    events = [sub.next(timeout=1).data.vote for _ in want]
    assert [(v.type, v.height, v.validator_index) for v in events] == want
    assert not sub.queue
    kinds = [_decode(*m)[0] for m in node.wire.sent]
    assert kinds == ["bits"] + ["has_vote"] * 2 + ["has_vote"]
    marks = [s for s in t.dump() if s.name == "consensus.announce"]
    if not traced:
        assert t.dump() == []
        return
    assert [(m.tags["votes"], m.tags["has_votes"], m.tags["bit_arrays"])
            for m in marks] == [(42, 2, 1), (1, 1, 0)]
    assert [m.tags["bytes"] for m in marks] == [
        sum(len(m) for _, m in node.wire.sent[:3]), len(node.wire.sent[3][1])]
    # the drain's announcement is inside its apply span
    apply_ = next(s for s in t.dump() if s.name == "consensus.vote_apply")
    assert marks[0].parent_id == apply_.span_id


def test_an_event_bus_without_a_subscription_does_no_work(monkeypatch):
    bus = tmevents.EventBus()

    def boom(*_a, **_kw):
        raise AssertionError("a message was built for nobody")

    monkeypatch.setattr(tmevents, "PubSubMessage", boom)
    monkeypatch.setattr(bus, "_mtx", None)              # and no lock is taken
    bus.publish_event_vote(tmevents.EventDataVote(vote=None))
    bus.publish_event_new_round_step(
        tmevents.EventDataRoundState(height=1, round=0, step="x"))
    monkeypatch.undo()
    sub = bus.subscribe("s", "tm.event='Vote'")
    bus.publish_event_vote(tmevents.EventDataVote(vote="v"))
    assert sub.next(timeout=1).data.vote == "v"
    bus.unsubscribe("s", "tm.event='Vote'")
    assert not bus._subs


def test_the_per_block_bit_array_follows_the_votes(nets):
    """``bit_array_by_block_id`` is kept as votes are added (the reference's
    ``blockVotes.bitArray``): a copy, equal to a scan of the votes held."""
    node = _Node(nets[50])
    node.drain([node.vote(p, PREVOTE_TYPE, bid) for p, bid in
                zip(node.privs[:30], [BLOCK, NIL, BLOCK] * 10)])
    vote_set = node.cs.rs.votes.prevotes(0)
    for bid in (BLOCK, NIL):
        bits = vote_set.bit_array_by_block_id(bid)
        assert bits == [v is not None
                        for v in vote_set.votes_by_block[bid.key()].votes]
        bits[49] = True
        assert not vote_set.bit_array_by_block_id(bid)[49]
    assert vote_set.bit_array_by_block_id(
        BlockID(hash=b"\x01" * 32, part_set_header=BLOCK.part_set_header)) is None
