"""Consensus reactor: gossips round state, proposals, block parts, and votes
(reference: consensus/reactor.go:142 channels, :199-201 per-peer gossip
routines, :1065+ PeerState).

Channels (priorities as in reference GetChannels):
  State 0x20 (prio 6), Data 0x21 (10), Vote 0x22 (7), VoteSetBits 0x23 (1).

Wire: tendermint.consensus.Message oneof (proto/tendermint/consensus/types.proto).
"""

from __future__ import annotations

import random
import threading
import time
from dataclasses import dataclass, field

from tendermint_tpu.consensus import cstypes
from tendermint_tpu.consensus.state_machine import ConsensusState, commit_to_vote_set
from tendermint_tpu.encoding import proto
from tendermint_tpu.utils.bits import BitArray
from tendermint_tpu.p2p.connection import ChannelDescriptor
from tendermint_tpu.p2p.switch import Peer, Reactor
from tendermint_tpu.store.envelope import CorruptedStoreError
from tendermint_tpu.types.block_id import BlockID, PartSetHeader
from tendermint_tpu.types.part_set import Part, PartSet
from tendermint_tpu.types.proposal import Proposal
from tendermint_tpu.types.vote import PRECOMMIT_TYPE, PREVOTE_TYPE, Vote
from tendermint_tpu.utils import trace as _trace

STATE_CHANNEL = 0x20
DATA_CHANNEL = 0x21
VOTE_CHANNEL = 0x22
VOTE_SET_BITS_CHANNEL = 0x23


# --- bit array wire helpers (proto/tendermint/libs/bits/types.proto) --------


def bits_marshal(bits) -> bytes:
    """Any iterable of bools or a BitArray -> proto bits encoding."""
    if not isinstance(bits, BitArray):
        bits = BitArray.from_bools(list(bits))
    return bits.marshal()


def bits_unmarshal(buf: bytes) -> BitArray:
    return BitArray.unmarshal(buf)


# --- message codecs ----------------------------------------------------------


def _wrap(field_num: int, body: bytes) -> bytes:
    return proto.Writer().message(field_num, body, always=True).out()


def msg_new_round_step(height, round_, step, secs_since_start, last_commit_round) -> bytes:
    return _wrap(1, proto.Writer().varint(1, height).varint(2, round_)
                 .uvarint(3, step).varint(4, secs_since_start)
                 .varint(5, last_commit_round).out())


def msg_new_valid_block(height, round_, psh: PartSetHeader, parts_bits, is_commit) -> bytes:
    return _wrap(2, proto.Writer().varint(1, height).varint(2, round_)
                 .message(3, psh.marshal(), always=True)
                 .message(4, bits_marshal(parts_bits))
                 .bool(5, is_commit).out())


def msg_proposal(p: Proposal) -> bytes:
    return _wrap(3, proto.Writer().message(1, p.marshal(), always=True).out())


def msg_block_part(height, round_, part: Part) -> bytes:
    return _wrap(5, proto.Writer().varint(1, height).varint(2, round_)
                 .message(3, part.marshal(), always=True).out())


def msg_vote(v: Vote) -> bytes:
    return _wrap(6, proto.Writer().message(1, v.marshal(), always=True).out())


def msg_has_vote(height, round_, type_, index) -> bytes:
    return _wrap(7, proto.Writer().varint(1, height).varint(2, round_)
                 .varint(3, type_).varint(4, index).out())


def msg_vote_set_maj23(height, round_, type_, block_id: BlockID) -> bytes:
    return _wrap(8, proto.Writer().varint(1, height).varint(2, round_)
                 .varint(3, type_).message(4, block_id.marshal(), always=True).out())


def msg_vote_set_bits(height, round_, type_, block_id: BlockID, votes_bits) -> bytes:
    return _wrap(9, proto.Writer().varint(1, height).varint(2, round_)
                 .varint(3, type_).message(4, block_id.marshal(), always=True)
                 .message(5, bits_marshal(votes_bits), always=True).out())


# --- per-peer state (reference: consensus/reactor.go:1065 PeerState) --------


@dataclass
class PeerRoundState:
    height: int = 0
    round: int = -1
    step: int = 0
    proposal: bool = False
    proposal_block_psh: PartSetHeader | None = None
    proposal_block_parts: BitArray = field(default_factory=BitArray)
    proposal_pol_round: int = -1
    prevotes: dict[int, BitArray] = field(default_factory=dict)      # round -> bits
    precommits: dict[int, BitArray] = field(default_factory=dict)
    last_commit_round: int = -1
    last_commit: BitArray = field(default_factory=BitArray)
    catchup_commit_round: int = -1
    catchup_commit: BitArray = field(default_factory=BitArray)


class PeerState:
    def __init__(self, peer: Peer):
        self.peer = peer
        self.prs = PeerRoundState()
        self.mtx = threading.RLock()
        self.running = True
        self.thread: threading.Thread | None = None   # its gossip routine

    def apply_new_round_step(self, height, round_, step, last_commit_round, n_vals) -> None:
        with self.mtx:
            prs = self.prs
            init_height = prs.height
            if prs.height != height or prs.round != round_:
                prs.proposal = False
                prs.proposal_block_psh = None
                prs.proposal_block_parts = BitArray()
                prs.proposal_pol_round = -1
            if prs.height != height:
                if prs.height + 1 == height and prs.round == last_commit_round:
                    prs.last_commit_round = last_commit_round
                    prs.last_commit = prs.precommits.get(last_commit_round, BitArray())
                else:
                    prs.last_commit_round = last_commit_round
                    prs.last_commit = BitArray()
                prs.prevotes = {}
                prs.precommits = {}
                prs.catchup_commit_round = -1
                prs.catchup_commit = BitArray()
            prs.height = height
            prs.round = round_
            prs.step = step
            _ = init_height

    def set_has_proposal(self, proposal: Proposal) -> None:
        with self.mtx:
            prs = self.prs
            if prs.height != proposal.height or prs.round != proposal.round:
                return
            if prs.proposal:
                return
            prs.proposal = True
            if not prs.proposal_block_parts:  # otherwise NewValidBlock set it
                prs.proposal_block_psh = proposal.block_id.part_set_header
                prs.proposal_block_parts = BitArray(proposal.block_id.part_set_header.total)
            prs.proposal_pol_round = proposal.pol_round

    def set_has_block_part(self, height, round_, index) -> None:
        with self.mtx:
            prs = self.prs
            if prs.height != height or prs.round != round_:
                return
            if 0 <= index < len(prs.proposal_block_parts):
                prs.proposal_block_parts[index] = True

    def set_has_vote(self, height, round_, type_, index, n_vals) -> None:
        with self.mtx:
            bits = self._votes_bits(height, round_, type_, n_vals)
            if bits is not None and 0 <= index < len(bits):
                bits[index] = True

    def apply_vote_set_bits(self, height, round_, type_, bits: BitArray,
                            n_vals) -> None:
        """The peer holds these votes: the same view a ``HasVote`` for each
        would mark (the last commit's for the height before the peer's), and
        no bit beyond the view's own length."""
        with self.mtx:
            view = self._votes_bits(height, round_, type_, n_vals)
            if view is not None:
                view.update(bits)

    def _votes_bits(self, height, round_, type_, n_vals) -> BitArray | None:
        prs = self.prs
        if prs.height == height:
            table = prs.prevotes if type_ == PREVOTE_TYPE else prs.precommits
            if round_ not in table and round_ in (prs.round, prs.round + 1,
                                                 prs.catchup_commit_round):
                table[round_] = BitArray(n_vals)
            return table.get(round_)
        if prs.height == height + 1 and type_ == PRECOMMIT_TYPE and round_ == prs.last_commit_round:
            if not prs.last_commit:
                prs.last_commit = BitArray(n_vals)
            return prs.last_commit
        return None


# --- a vote's copies, decoded once --------------------------------------------


class VoteMemo:
    """A vote message's wire bytes -> the ``Vote`` decoded from them. Gossip
    delivers a vote once from every peer that has not seen the node's
    ``HasVote`` yet; equal bytes decode to equal votes, so the copies are
    handed the object the first delivery built. Only ``Vote.unmarshal``
    builds an entry, and nothing between ``receive`` and the vote sets, the
    WAL and the evidence pool writes to a received vote (the signers write
    to the node's own votes, which never come through ``receive``), so
    several ``MsgInfo``s may carry one.

    Kept: votes of the node's height and the one before it (late
    precommits' copies come after the commit; any other height the state
    machine drops), until the node steps two heights past them. At most
    ``bound(n_vals)`` entries; at the bound nothing is inserted and a miss
    costs what a delivery cost without the memo. Every connection's receive
    thread calls ``get`` and ``put`` at once: two that miss on the same
    bytes both decode, and ``put`` keeps the first object for both."""

    def __init__(self):
        self._mtx = threading.Lock()
        self._votes: dict[bytes, Vote] = {}
        self._floor = 0
        # lookups answered, lookups not answered, and of those the votes
        # not stored because the memo was full
        self.hits = self.misses = self.full = 0

    @staticmethod
    def bound(n_vals: int) -> int:
        """A prevote and a precommit from every validator, for two heights:
        every honest vote of a height decided in its first round and of the
        one before it. Later rounds' votes and whatever else a peer invents
        share that room, and once it is taken they are decoded each time."""
        return 4 * n_vals

    def get(self, msg_bytes: bytes) -> Vote | None:
        with self._mtx:
            vote = self._votes.get(msg_bytes)
            if vote is None:
                self.misses += 1
            else:
                self.hits += 1
            return vote

    def put(self, msg_bytes: bytes, vote: Vote, node_height: int,
            n_vals: int) -> Vote:
        """-> the vote every delivery of these bytes is to carry."""
        if not node_height - 1 <= vote.height <= node_height:
            return vote
        with self._mtx:
            held = self._votes.get(msg_bytes)
            if held is not None:
                return held
            if len(self._votes) >= self.bound(n_vals):
                self.full += 1
            else:
                self._votes[msg_bytes] = vote
            return vote

    def forget_below(self, height: int) -> None:
        with self._mtx:
            if height != self._floor:
                self._floor = height
                self._votes = {k: v for k, v in self._votes.items()
                               if v.height >= height}

    def counts(self) -> tuple[int, int, int, int]:
        """-> (hits, misses, full, entries held)."""
        with self._mtx:
            return self.hits, self.misses, self.full, len(self._votes)


# --- the reactor -------------------------------------------------------------


class ConsensusReactor(Reactor):
    def __init__(self, cs: ConsensusState, wait_sync: bool = False):
        super().__init__("CONSENSUS")
        self.cs = cs
        self.wait_sync = wait_sync  # True while fast sync is running
        self._peer_states: dict[str, PeerState] = {}
        self._ending: list[threading.Thread] = []  # removed peers' routines
        self._mtx = threading.RLock()
        # channel id -> [messages, seconds in receive, bytes], counted on
        # the receiving threads while tracing is on (docs/OBSERVABILITY.md)
        self.recv_stats: dict[int, list] = {}
        # beside it, for the once-a-height consensus.recv mark: the threads
        # that called receive since the mark before, every thread's CPU
        # clock and the totals (messages, seconds, bytes) at that mark, and
        # the height it closed
        self._recv_callers: set[threading.Thread] = set()
        self._recv_cpu_at: dict | None = None
        self._recv_marked = (0, 0.0, 0)
        self._recv_height = None
        self.vote_memo = VoteMemo()
        self._memo_marked = (0, 0, 0)     # its hits, misses, full at that mark
        # the votes the state machine added since the peers were last told,
        # in the order it added them (written and read under its lock)
        self._added: list[Vote] = []
        cs.on_new_round_step.append(self._mark_recv)
        # after the mark, which reads what the memo held at its fullest
        cs.on_new_round_step.append(
            lambda rs: self.vote_memo.forget_below(rs.height - 1))
        cs.on_new_round_step.append(self._broadcast_new_round_step)
        cs.on_vote.append(self._note_vote)
        cs.on_work_done.append(self._announce_votes)
        cs.on_valid_block.append(self._broadcast_new_valid_block)
        cs.broadcast = self._cs_broadcast

    def get_channels(self) -> list[ChannelDescriptor]:
        """reference: consensus/reactor.go:142-178."""
        return [
            ChannelDescriptor(STATE_CHANNEL, priority=6),
            ChannelDescriptor(DATA_CHANNEL, priority=10),
            ChannelDescriptor(VOTE_CHANNEL, priority=7),
            ChannelDescriptor(VOTE_SET_BITS_CHANNEL, priority=1),
        ]

    def switch_to_consensus(self, state, skip_wal: bool = False) -> None:
        """Called by the fast-sync reactor when caught up (reference:
        consensus/reactor.go:108-140)."""
        if state.last_block_height > self.cs.state.last_block_height:
            # Reconstruct LastCommit from the stored seen commit (reference:
            # reactor.go:120 reconstructLastCommit): whatever rs.last_commit
            # held belongs to a height fast sync just skipped past, and a
            # stale vote set must never be packed into a future proposal.
            if state.last_block_height > 0:
                try:
                    seen = self.cs.block_store.load_seen_commit(
                        state.last_block_height)
                except CorruptedStoreError:
                    seen = None  # quarantined; consensus restarts without
                    # the reconstructed LastCommit (same as missing)
                if seen is not None and state.last_validators is not None:
                    self.cs.rs.last_commit = commit_to_vote_set(
                        state.chain_id, seen, state.last_validators)
            self.cs.update_to_state(state)
        self.wait_sync = False
        self.cs.start()

    # --- peer lifecycle ----------------------------------------------------

    def add_peer(self, peer: Peer) -> None:
        ps = PeerState(peer)
        with self._mtx:
            self._peer_states[peer.id] = ps
        peer.set("consensus_peer_state", ps)
        # ONE gossip thread per peer (was three: data, votes, maj23 each
        # owned a thread). Per-peer thread count is the limiting resource
        # for the in-process scenario fabric (e2e/fabric.py budgets it at
        # PER_PEER_THREADS per link side); the three loops all poll on the
        # same peer-gossip cadence, so they share one loop with the maj23
        # pass kept on its own slower clock.
        ps.thread = threading.Thread(
            target=self._gossip_routine, args=(peer, ps),
            name=f"cs-gossip-{str(peer.id)[:8]}", daemon=True)
        ps.thread.start()
        if not self.wait_sync:
            self._send_new_round_step(peer)

    def remove_peer(self, peer: Peer, reason) -> None:
        with self._mtx:
            ps = self._peer_states.pop(peer.id, None)
            if ps is not None:
                ps.running = False
                self._ending = [t for t in self._ending if t.is_alive()]
                if ps.thread is not None:
                    self._ending.append(ps.thread)

    def wait_gossip_ended(self, timeout_s: float) -> bool:
        """Join the gossip routines of the peers that were removed (they end
        within one gossip sleep): a stopped node's threads must be gone
        before its stores are closed under them."""
        deadline = time.monotonic() + timeout_s
        with self._mtx:
            ending, self._ending = self._ending, []
        for t in ending:
            t.join(max(0.0, deadline - time.monotonic()))
        return not any(t.is_alive() for t in ending)

    # --- receive -----------------------------------------------------------

    def receive(self, ch_id: int, peer: Peer, msg_bytes: bytes) -> None:
        """Decode one wire message and hand it to the state machine. With
        tracing on, ``recv_stats`` counts it: at a step's 15,000 votes a span
        per message would turn the flight recorder's ring over. Who called
        is noted for the ``consensus.recv`` mark of the height; the CPU clock
        is not read here (a system call of 10 us and more where the
        benchmark runs)."""
        if not _trace.ENABLED:
            self._receive(ch_id, peer, msg_bytes)
            return
        t0 = time.monotonic()
        try:
            self._receive(ch_id, peer, msg_bytes)
        finally:
            dt = time.monotonic() - t0
            with self._mtx:
                st = self.recv_stats.setdefault(ch_id, [0, 0.0, 0])
                st[0] += 1
                st[1] += dt
                st[2] += len(msg_bytes)
                self._recv_callers.add(threading.current_thread())

    def _mark_recv(self, rs) -> None:
        """Once a height, with tracing on: what ``receive`` took since the
        mark before and the CPU seconds the threads that called it got since
        then (their clocks read from outside: ``receive`` and whatever they
        do between two calls), into the flight recorder (on the consensus
        thread, at the step into a new height)."""
        if not self.cs.tracer.enabled or rs.height == self._recv_height:
            return
        cpu_now = _trace.thread_cpu_times()
        *memo_now, memo_size = self.vote_memo.counts()
        with self._mtx:
            first, self._recv_height = self._recv_height is None, rs.height
            stats = list(self.recv_stats.values())
            now = tuple(sum(st[k] for st in stats) for k in range(3))
            before, self._recv_marked = self._recv_marked, now
            callers, self._recv_callers = self._recv_callers, set()
            cpu_before, self._recv_cpu_at = self._recv_cpu_at, cpu_now
            memo_before, self._memo_marked = self._memo_marked, memo_now
        if first:
            return
        msgs, seconds, nbytes = (a - b for a, b in zip(now, before))
        hits, misses, full = (a - b for a, b in zip(memo_now, memo_before))
        cpu_s = None if cpu_now is None or cpu_before is None else sum(
            cpu_now[t] - cpu_before.get(t, 0.0) for t in callers if t in cpu_now)
        self.cs.tracer.mark(
            "consensus.recv", height=rs.height - 1, msgs=msgs, seconds=seconds,
            cpu_s=cpu_s, bytes=nbytes, threads=sorted({t.name for t in callers}),
            vote_memo_hits=hits, vote_memo_misses=misses, vote_memo_full=full,
            vote_memo_size=memo_size)

    def _receive(self, ch_id: int, peer: Peer, msg_bytes: bytes) -> None:
        ps: PeerState = peer.get("consensus_peer_state")
        if ps is None:
            return
        n_vals = self.cs.rs.validators.size() if self.cs.rs.validators else 0
        if ch_id == VOTE_CHANNEL and not self.wait_sync:
            # before any parsing: two deliveries in three are copies
            self._receive_vote(peer, ps, msg_bytes, n_vals)
            return
        # (a vote that arrives during fast sync is parsed here and dropped)
        f = proto.fields(msg_bytes)
        if ch_id == STATE_CHANNEL:
            if 1 in f:  # NewRoundStep
                m = proto.fields(f[1][-1])
                height = proto.as_sint64(m.get(1, [0])[-1])
                round_ = proto.as_sint64(m.get(2, [0])[-1])
                step = m.get(3, [0])[-1]
                lcr = proto.as_sint64(m.get(5, [0])[-1])
                ps.apply_new_round_step(height, round_, step, lcr, n_vals)
            elif 2 in f:  # NewValidBlock
                m = proto.fields(f[2][-1])
                with ps.mtx:
                    if ps.prs.height == proto.as_sint64(m.get(1, [0])[-1]):
                        ps.prs.proposal_block_psh = PartSetHeader.unmarshal(m.get(3, [b""])[-1])
                        ps.prs.proposal_block_parts = bits_unmarshal(m.get(4, [b""])[-1]) if 4 in m else []
            elif 7 in f:  # HasVote
                m = proto.fields(f[7][-1])
                ps.set_has_vote(
                    proto.as_sint64(m.get(1, [0])[-1]),
                    proto.as_sint64(m.get(2, [0])[-1]),
                    proto.as_sint64(m.get(3, [0])[-1]),
                    proto.as_sint64(m.get(4, [0])[-1]),
                    n_vals,
                )
            elif 8 in f:  # VoteSetMaj23
                m = proto.fields(f[8][-1])
                height = proto.as_sint64(m.get(1, [0])[-1])
                round_ = proto.as_sint64(m.get(2, [0])[-1])
                type_ = proto.as_sint64(m.get(3, [0])[-1])
                bid = BlockID.unmarshal(m.get(4, [b""])[-1])
                self._handle_vote_set_maj23(peer, ps, height, round_, type_, bid)
        elif ch_id == DATA_CHANNEL:
            if self.wait_sync:
                return
            if 3 in f:  # Proposal
                m = proto.fields(f[3][-1])
                p = Proposal.unmarshal(m.get(1, [b""])[-1])
                ps.set_has_proposal(p)
                self.cs.set_proposal(p, peer_id=peer.id)
            elif 4 in f:  # ProposalPOL
                m = proto.fields(f[4][-1])
                with ps.mtx:
                    if ps.prs.height == proto.as_sint64(m.get(1, [0])[-1]):
                        ps.prs.proposal_pol_round = proto.as_sint64(m.get(2, [0])[-1])
            elif 5 in f:  # BlockPart
                m = proto.fields(f[5][-1])
                height = proto.as_sint64(m.get(1, [0])[-1])
                round_ = proto.as_sint64(m.get(2, [0])[-1])
                part = Part.unmarshal(m.get(3, [b""])[-1])
                ps.set_has_block_part(height, round_, part.index)
                self.cs.add_proposal_block_part(height, round_, part, peer_id=peer.id)
        elif ch_id == VOTE_SET_BITS_CHANNEL:
            if 9 in f:  # VoteSetBits: the votes the peer holds for a block id
                m = proto.fields(f[9][-1])
                ps.apply_vote_set_bits(
                    proto.as_sint64(m.get(1, [0])[-1]),
                    proto.as_sint64(m.get(2, [0])[-1]),
                    proto.as_sint64(m.get(3, [0])[-1]),
                    bits_unmarshal(m.get(5, [b""])[-1]),
                    n_vals,
                )

    def _receive_vote(self, peer: Peer, ps: PeerState, msg_bytes: bytes,
                      n_vals: int) -> None:
        """A message on the vote channel: the ``Vote`` an earlier delivery
        of the same bytes built, or the one decoded from them now; then,
        per delivery and per peer, the peer's bit and the state machine's
        queue. A message with no vote in it, or one whose decode raises, is
        never kept."""
        vote = self.vote_memo.get(msg_bytes)
        if vote is None:
            f = proto.fields(msg_bytes)
            if 6 not in f:
                return
            m = proto.fields(f[6][-1])
            vote = self.vote_memo.put(
                msg_bytes, Vote.unmarshal(m.get(1, [b""])[-1]),
                self.cs.rs.height, n_vals)
        ps.set_has_vote(vote.height, vote.round, vote.type,
                        vote.validator_index, n_vals)
        self.cs.add_vote(vote, peer_id=peer.id)

    def _handle_vote_set_maj23(self, peer, ps, height, round_, type_, bid) -> None:
        """reference: consensus/reactor.go:300-340."""
        rs = self.cs.rs
        if rs.height != height or rs.votes is None:
            return
        try:
            if type_ == PREVOTE_TYPE:
                votes = rs.votes.prevotes(round_)
            else:
                votes = rs.votes.precommits(round_)
            if votes is None:
                return
            votes.set_peer_maj23(peer.id, bid)
            our_bits = votes.bit_array_by_block_id(bid) or []
            peer.try_send(VOTE_SET_BITS_CHANNEL,
                          msg_vote_set_bits(height, round_, type_, bid, our_bits))
        except Exception:  # noqa: BLE001
            pass

    # --- broadcasts from our own state machine ------------------------------

    def _cs_broadcast(self, msg) -> None:
        """Internally-generated proposal/parts/votes: peers get them via the
        gossip routines; nothing to do eagerly (reference relies on gossip).
        Votes the state machine adds are announced by _announce_votes."""

    def _broadcast_new_round_step(self, rs) -> None:
        # a peer hears of the votes of the step the node leaves first
        self._announce_votes()
        if self.switch is None:
            return
        self.switch.broadcast(STATE_CHANNEL, self._new_round_step_msg(rs))

    def _broadcast_new_valid_block(self, rs) -> None:
        if self.switch is None or rs.proposal_block_parts is None:
            return
        self.switch.broadcast(STATE_CHANNEL, msg_new_valid_block(
            rs.height, rs.round, rs.proposal_block_parts.header(),
            rs.proposal_block_parts.bit_array(), rs.step == cstypes.STEP_COMMIT))

    def _note_vote(self, vote: Vote) -> None:
        """``cs.on_vote``: nothing is sent per vote; see _announce_votes."""
        self._added.append(vote)

    def _announce_votes(self) -> None:
        """Tell every peer which votes the state machine added since the last
        call: at the end of a drain's apply, at the end of a single message,
        and before a ``NewRoundStep``. The votes of one (height, round, type,
        block id) go out as whichever is fewer bytes: their ``HasVote``s, or
        one ``VoteSetBits`` with every vote the node holds for that block id
        (a stock peer replaces its view for the block id with the array, so
        only the whole array is right, and one dropped on a full send queue
        is made good by the next). Messages leave in the order the votes
        were added, an array where the first vote of its group stood."""
        votes = self._added
        if not votes:
            return
        self._added = []
        if self.switch is None:
            return
        groups: dict[tuple, list] = {}
        for at, v in enumerate(votes):
            groups.setdefault((v.height, v.round, v.type, v.block_id.key()),
                              []).append((at, v))
        out: list[tuple[int, int, bytes]] = []   # (place, channel, message)
        arrays = 0
        for (height, round_, type_, _), group in groups.items():
            first, block_id = group[0][0], group[0][1].block_id
            held = self._held_bits(height, round_, type_, block_id)
            whole = None if held is None else msg_vote_set_bits(
                height, round_, type_, block_id, held)
            has_votes, size = [], 0
            for at, v in group:
                msg = msg_has_vote(height, round_, type_, v.validator_index)
                size += len(msg)
                if whole is not None and size > len(whole):
                    has_votes = [(first, VOTE_SET_BITS_CHANNEL, whole)]
                    arrays += 1
                    break
                has_votes.append((at, STATE_CHANNEL, msg))
            out += has_votes
        out.sort()
        for _, ch_id, msg in out:
            self.switch.broadcast(ch_id, msg)
        tr = self.cs.tracer
        if tr.enabled:
            tr.mark("consensus.announce", votes=len(votes),
                    has_votes=len(out) - arrays, bit_arrays=arrays,
                    bytes=sum(len(msg) for _, _, msg in out))

    def _held_bits(self, height, round_, type_, block_id) -> BitArray | None:
        """The votes the node holds for this block id, where it still holds
        the set: the height's, or the last commit's for a late precommit."""
        rs = self.cs.rs
        vote_set = None
        if height == rs.height and rs.votes is not None:
            vote_set = (rs.votes.prevotes(round_) if type_ == PREVOTE_TYPE
                        else rs.votes.precommits(round_))
        elif (height + 1 == rs.height and type_ == PRECOMMIT_TYPE
              and rs.last_commit is not None and rs.last_commit.round == round_):
            vote_set = rs.last_commit
        return None if vote_set is None else vote_set.bit_array_by_block_id(block_id)

    def _new_round_step_msg(self, rs) -> bytes:
        import time as _t

        secs = max(0, int(_t.time() - rs.start_time.seconds)) if rs.start_time else 0
        lcr = rs.last_commit.round if rs.last_commit is not None else -1
        return msg_new_round_step(rs.height, rs.round, rs.step, secs, lcr)

    def _send_new_round_step(self, peer: Peer) -> None:
        peer.try_send(STATE_CHANNEL, self._new_round_step_msg(self.cs.rs))

    # --- gossip routines (reference: consensus/reactor.go:540-1050) --------

    def _gossip_routine(self, peer: Peer, ps: PeerState) -> None:
        """The per-peer gossip loop: data (proposal/parts) + votes each
        pass, the VoteSetMaj23 query on its own slower cadence. Busy
        passes (something sent) loop immediately; idle passes sleep one
        peer-gossip interval — same observable behavior as the former
        three dedicated threads at a third of the thread bill."""
        try:
            maj23_sleep = self.cs.config.peer_query_maj23_sleep_duration_s
            next_maj23 = time.monotonic() + maj23_sleep
            while ps.running and self.switch is not None:
                if self.wait_sync:
                    time.sleep(0.1)
                    continue
                sent = self._gossip_data_step(peer, ps)
                sent = self._gossip_votes_step(peer, ps) or sent
                now = time.monotonic()
                if now >= next_maj23:
                    next_maj23 = now + maj23_sleep
                    self._query_maj23_step(peer, ps)
                if not sent:
                    time.sleep(self.cs.config.peer_gossip_sleep_duration_s)
        except Exception as e:  # noqa: BLE001 - a gossip-thread death ends
            # like a disconnect (peer teardown mid-send starts a fresh
            # routine on re-add), but a systematic bug here would silently
            # starve the peer of proposals and votes — leave a trail
            logger = getattr(self.switch, "logger", None)
            if logger:
                logger.error("consensus gossip routine ended",
                             peer=peer.id, err=e)

    def _gossip_data_step(self, peer: Peer, ps: PeerState) -> bool:
        """One data-gossip pass; True when something was sent."""
        rs = self.cs.rs
        prs = ps.prs
        # send block parts the peer lacks for the current proposal
        if (rs.proposal_block_parts is not None and prs.height == rs.height
                and prs.proposal_block_psh == rs.proposal_block_parts.header()):
            ours = rs.proposal_block_parts.bit_array()
            theirs = prs.proposal_block_parts
            want = [i for i, have in enumerate(ours)
                    if have and (i >= len(theirs) or not theirs[i])]
            if want:
                i = random.choice(want)
                part = rs.proposal_block_parts.get_part(i)
                if part is not None and peer.try_send(
                        DATA_CHANNEL, msg_block_part(rs.height, rs.round, part)):
                    ps.set_has_block_part(prs.height, prs.round, i)
                    return True
        # catchup: peer is on an older height -> send stored block parts
        elif (0 < prs.height < rs.height
              and prs.height >= self.cs.block_store.base):
            return self._gossip_data_for_catchup(peer, ps)
        # send proposal
        if (rs.proposal is not None and prs.height == rs.height
                and prs.round == rs.round and not prs.proposal):
            if peer.try_send(DATA_CHANNEL, msg_proposal(rs.proposal)):
                ps.set_has_proposal(rs.proposal)
                return True
        return False

    def _gossip_data_for_catchup(self, peer: Peer, ps: PeerState) -> bool:
        """reference: consensus/reactor.go:631-700. True when a part was
        sent (the caller's loop owns the idle sleep)."""
        prs = ps.prs
        try:
            meta = self.cs.block_store.load_block_meta(prs.height)
        except CorruptedStoreError:
            return False  # quarantined + repair scheduled by the store hook
        if meta is None:
            return False
        with ps.mtx:
            if prs.proposal_block_psh != meta.block_id.part_set_header:
                prs.proposal_block_psh = meta.block_id.part_set_header
                prs.proposal_block_parts = BitArray(meta.block_id.part_set_header.total)
            want = [i for i, have in enumerate(prs.proposal_block_parts) if not have]
        if not want:
            return False
        i = random.choice(want)
        try:
            part = self.cs.block_store.load_block_part(prs.height, i)
        except CorruptedStoreError:
            # never gossip a rotten part; the repair hook already has the
            # height, and a healed part flows on a later pass
            return False
        if part is None:
            return False
        if peer.try_send(DATA_CHANNEL, msg_block_part(prs.height, prs.round, part)):
            ps.set_has_block_part(prs.height, prs.round, i)
            return True
        return False

    def _gossip_votes_step(self, peer: Peer, ps: PeerState) -> bool:
        """One vote-gossip pass; True when a vote was sent."""
        rs = self.cs.rs
        if rs.votes is None:
            return False
        return self._pick_send_vote(peer, ps, rs, ps.prs)

    def _pick_send_vote(self, peer, ps, rs, prs) -> bool:
        """Pick one vote the peer lacks and send it (reference:
        consensus/reactor.go:716-830 gossipVotesRoutine + PickSendVote)."""
        def send_from(vote_set, their_bits) -> bool:
            if vote_set is None:
                return False
            for i, v in enumerate(vote_set.votes):
                if v is None:
                    continue
                if their_bits is not None and i < len(their_bits) and their_bits[i]:
                    continue
                if peer.try_send(VOTE_CHANNEL, msg_vote(v)):
                    ps.set_has_vote(v.height, v.round, v.type, i,
                                    vote_set.val_set.size())
                    return True
                return False
            return False

        if prs.height == rs.height:
            # current round prevotes/precommits + POL prevotes
            if prs.proposal_pol_round >= 0:
                pv = rs.votes.prevotes(prs.proposal_pol_round)
                if send_from(pv, prs.prevotes.get(prs.proposal_pol_round)):
                    return True
            pv = rs.votes.prevotes(prs.round) if prs.round >= 0 else None
            if send_from(pv, prs.prevotes.get(prs.round)):
                return True
            pc = rs.votes.precommits(prs.round) if prs.round >= 0 else None
            if send_from(pc, prs.precommits.get(prs.round)):
                return True
        if prs.height + 1 == rs.height and rs.last_commit is not None:
            # Peer is one height behind: send last-commit precommits. For the
            # peer these are CURRENT-height precommits, so the have-bits live
            # in prs.precommits[commit round] (reference: PeerState
            # getVoteBitArray, consensus/reactor.go:1170-1210).
            if send_from(rs.last_commit, prs.precommits.get(rs.last_commit.round)):
                return True
        if prs.height < rs.height and prs.height >= max(self.cs.block_store.base, 1):
            # catchup: send precommits from the stored commit
            try:
                commit = self.cs.block_store.load_block_commit(prs.height)
            except CorruptedStoreError:
                commit = None  # quarantined; repair scheduled
            if commit is not None:
                with ps.mtx:
                    # EnsureCatchupCommitRound (reference: reactor.go:1120-1140)
                    prs.catchup_commit_round = commit.round
                their_bits = prs.precommits.get(commit.round)
                for i, cs_sig in enumerate(commit.signatures):
                    if cs_sig.absent():
                        continue
                    if their_bits and i < len(their_bits) and their_bits[i]:
                        continue
                    vote = commit.get_vote(i)
                    if peer.try_send(VOTE_CHANNEL, msg_vote(vote)):
                        ps.set_has_vote(vote.height, vote.round, vote.type, i,
                                        len(commit.signatures))
                        return True
                    return False
        return False

    def _query_maj23_step(self, peer: Peer, ps: PeerState) -> None:
        """One VoteSetMaj23 announcement pass (reference:
        consensus/reactor.go:870-950); paced by _gossip_routine's
        peer_query_maj23_sleep_duration_s clock."""
        rs = self.cs.rs
        prs = ps.prs
        if rs.votes is None or prs.height != rs.height:
            return
        for type_, vs in ((PREVOTE_TYPE, rs.votes.prevotes(prs.round)),
                          (PRECOMMIT_TYPE, rs.votes.precommits(prs.round))):
            if vs is None:
                continue
            maj, ok = vs.two_thirds_majority()
            if ok:
                peer.try_send(STATE_CHANNEL,
                              msg_vote_set_maj23(rs.height, prs.round, type_, maj))
