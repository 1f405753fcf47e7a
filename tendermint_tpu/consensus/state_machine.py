"""The Tendermint BFT consensus state machine.

A single consumer thread serializes every input (peer messages, own messages,
timeouts) exactly like the reference's receiveRoutine (reference:
consensus/state.go:707-790); all enter* transitions run on that thread. The
round step grammar, POL locking/unlocking rules, and WAL write points follow
consensus/state.go line-by-line semantics (citations inline), re-derived
against spec/consensus/consensus.md.

Differences from the reference are TPU-era, not semantic:
 - vote verification inside VoteSet can run through the batched TPU verifier;
 - goroutine fans are replaced by one input queue + a timer thread.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time as _time
from dataclasses import dataclass

from tendermint_tpu.consensus import cstypes
from tendermint_tpu.consensus.cstypes import (
    STEP_COMMIT,
    STEP_NEW_HEIGHT,
    STEP_NEW_ROUND,
    STEP_PRECOMMIT,
    STEP_PRECOMMIT_WAIT,
    STEP_PREVOTE,
    STEP_PREVOTE_WAIT,
    STEP_PROPOSE,
    HeightVoteSet,
)
from tendermint_tpu.consensus.ticker import TimeoutInfo, TimeoutTicker
from tendermint_tpu.consensus.wal import WAL, EndHeightMessage, WALMessageBlob
from tendermint_tpu.config.config import ConsensusConfig
from tendermint_tpu.encoding import proto as proto_enc
from tendermint_tpu.types import events as tmevents
from tendermint_tpu.types.block import Block, Commit
from tendermint_tpu.types.block_id import BlockID, PartSetHeader
from tendermint_tpu.types.part_set import Part, PartSet
from tendermint_tpu.types.proposal import Proposal
from tendermint_tpu.types.ttime import Time
from tendermint_tpu.types.validator_set import ValidatorSet
from tendermint_tpu.types.vote import (
    PRECOMMIT_TYPE,
    PREVOTE_TYPE,
    ErrVoteConflictingVotes,
    ErrVoteInvalidSignature,
    Vote,
)
from tendermint_tpu.types.vote_set import VoteSet
from tendermint_tpu.utils import clock as tmclock
from tendermint_tpu.utils import peerscore
from tendermint_tpu.utils import trace as _trace


# Bound of the peer message queue (docs/OVERLOAD.md): the reference's
# msgQueueSize, and on top of it what gossip can have in flight for a node
# that keeps up with the chain -- per validator the prevote and the precommit
# of the live height and the late precommit of the height before it (queued
# as live, stale by the time it is drained), a copy from each of three peers.
MSG_QUEUE_MIN = 1000
MSG_QUEUE_PER_VALIDATOR = 9


class ConsensusError(Exception):
    pass


class ErrInvalidProposalPOLRound(ConsensusError):
    pass


class ErrInvalidProposalSignature(ConsensusError):
    pass


class ErrAddingVote(ConsensusError):
    pass


# --- message types (reference: consensus/msgs.go) ---------------------------


@dataclass
class ProposalMessage:
    proposal: Proposal

    def wal_blob(self) -> WALMessageBlob:
        return WALMessageBlob("proposal", self.proposal.marshal())


@dataclass
class BlockPartMessage:
    height: int
    round: int
    part: Part

    def wal_blob(self) -> WALMessageBlob:
        body = (
            proto_enc.Writer()
            .varint(1, self.height)
            .varint(2, self.round)
            .message(3, self.part.marshal(), always=True)
            .out()
        )
        return WALMessageBlob("block_part", body)


@dataclass
class VoteMessage:
    vote: Vote

    def wal_blob(self) -> WALMessageBlob:
        return WALMessageBlob("vote", self.vote.marshal())


def wal_blob_to_msg(blob: WALMessageBlob):
    if blob.kind == "proposal":
        return ProposalMessage(Proposal.unmarshal(blob.payload))
    if blob.kind == "block_part":
        f = proto_enc.fields(blob.payload)
        return BlockPartMessage(
            height=proto_enc.as_sint64(f.get(1, [0])[-1]),
            round=proto_enc.as_sint64(f.get(2, [0])[-1]),
            part=Part.unmarshal(f.get(3, [b""])[-1]),
        )
    if blob.kind == "vote":
        return VoteMessage(Vote.unmarshal(blob.payload))
    if blob.kind == "timeout":
        f = proto_enc.fields(blob.payload)
        return TimeoutInfo(
            duration_s=proto_enc.as_sint64(f.get(1, [0])[-1]) / 1e9,
            height=proto_enc.as_sint64(f.get(2, [0])[-1]),
            round=proto_enc.as_sint64(f.get(3, [0])[-1]),
            step=proto_enc.as_sint64(f.get(4, [0])[-1]),
        )
    return None


def timeout_wal_blob(ti: TimeoutInfo) -> WALMessageBlob:
    body = (
        proto_enc.Writer()
        .varint(1, int(ti.duration_s * 1e9))
        .varint(2, ti.height)
        .varint(3, ti.round)
        .varint(4, ti.step)
        .out()
    )
    return WALMessageBlob("timeout", body)


@dataclass
class MsgInfo:
    msg: object
    peer_id: str = ""


def commit_to_vote_set(chain_id: str, commit: Commit, vals: ValidatorSet) -> VoteSet:
    """reference: types/vote_set.go CommitToVoteSet (via types/block.go)."""
    vote_set = VoteSet(chain_id, commit.height, commit.round, PRECOMMIT_TYPE, vals)
    for idx, cs_sig in enumerate(commit.signatures):
        if cs_sig.absent():
            continue
        added = vote_set.add_vote(commit.get_vote(idx))
        if not added:
            raise ConsensusError("failed to reconstruct LastCommit: duplicate vote")
    return vote_set


class ConsensusState:
    """reference: consensus/state.go:149 State."""

    def __init__(self, config: ConsensusConfig, state, block_exec, block_store,
                 mempool=None, evidence_pool=None, priv_validator=None,
                 event_bus=None, wal: WAL | None = None, logger=None,
                 clock=None):
        self.config = config
        # per-node time source (utils/clock.py, docs/NEMESIS.md): every
        # wall-clock read consensus makes — proposal/vote/commit timestamps,
        # round-0 scheduling, WAL frame times — goes through this clock so
        # a chaos harness can skew one fabric node without touching the host
        self.clock = clock if clock is not None else tmclock.DEFAULT
        self.block_exec = block_exec
        self.block_store = block_store
        self.mempool = mempool
        self.evpool = evidence_pool
        self.priv_validator = priv_validator
        self.priv_validator_pub_key = (
            priv_validator.get_pub_key() if priv_validator else None
        )
        self.event_bus = event_bus if event_bus is not None else tmevents.EventBus()
        self.wal = wal
        self.logger = logger
        # Flight recorder (utils/trace.py): node wiring swaps in the node's
        # instance tracer so a 50-node in-process mesh never interleaves
        # spans; a standalone machine records into the process default.
        self.tracer = _trace.DEFAULT
        # what every thread of the process got, height over height (traced)
        self._census = _trace.ThreadCensus()

        self.rs = cstypes.RoundState()
        self.state = None  # sm.State; set by update_to_state

        # Peer gossip enters through a priority shed queue (docs/OVERLOAD.md):
        # at capacity, stale-height gossip sheds first and live-height votes
        # survive, and gossip threads NEVER block on a saturated consensus
        # consumer. Internal messages (own votes/proposals) keep a plain
        # bounded queue — they are never shed. The bound follows the
        # validator set (update_to_state): a queue smaller than one round's
        # votes sheds votes of the live height whenever gossip outruns the
        # drain, and a peer never re-sends what it has marked as delivered.
        self._msg_queue = peerscore.ShedQueue(maxsize=MSG_QUEUE_MIN,
                                              on_shed=self._count_shed)
        self._internal_queue: queue.Queue = queue.Queue(maxsize=1000)
        self._ticker = TimeoutTicker(self._on_timeout_fired, clock=self.clock)
        self._timeout_queue: queue.Queue = queue.Queue()
        self._mtx = threading.RLock()
        self._holdover: object | None = None  # non-vote msg dequeued mid-drain
        # In-flight batched vote flush: (msgs, queued, PendingVerify).  The
        # drain dispatches a batch and keeps consuming the queue while the
        # device verifies; the result is applied before ANY other state
        # transition (next batch, timeout, non-vote message) so side-effect
        # order stays exactly arrival order (VERDICT r4 item 1b).
        self._pending_flush: tuple | None = None
        self._thread: threading.Thread | None = None
        self._running = False
        self.replay_mode = False
        self._n_steps = 0
        # Peer misbehavior scoreboard (utils/peerscore.py), set by node
        # wiring to the switch's board: invalid-signature lanes out of the
        # batched vote-drain bitmap (and the serial VoteError path) are
        # attributed to the delivering peer. None = scoring disabled
        # (standalone/replay machines).
        self.scoreboard = None
        # Maverick-style misbehavior hooks for adversarial testing
        # (reference: test/maverick/consensus/misbehavior.go:16;
        # consensus/misbehavior.py is the behavior catalog). Keys
        # "prevote" / "precommit" / "propose" -> fn(cs, height, round);
        # a truthy return means the hook HANDLED the action (the default
        # behavior is skipped), falsy falls through to the honest default
        # so height-windowed behavior maps can play honest outside their
        # window. Production nodes never set this.
        self.misbehaviors: dict = {}
        # decided-block callback fans (reactor hooks; reference evsw usage)
        self.on_new_round_step = []  # callbacks(rs)
        self.on_vote = []  # callbacks(vote): once per vote added, in order
        # callbacks(): a unit of the receive loop's work has ended (one
        # drain's apply, one message): what on_vote gathered can leave
        self.on_work_done = []
        self.on_valid_block = []  # callbacks(rs)
        # called with each internally-generated message (own proposal, parts,
        # votes) for the reactor / test harness to gossip to peers
        self.broadcast = None

        if state is not None:
            # reconstruct LastCommit when resuming mid-chain (reference:
            # consensus/state.go:540-570 reconstructLastCommit)
            if state.last_block_height > 0:
                from tendermint_tpu.store.envelope import CorruptedStoreError

                try:
                    seen = block_store.load_seen_commit(state.last_block_height)
                except CorruptedStoreError:
                    # quarantined + repair scheduled by the store hook; the
                    # canonical commit row (written with block h+1) carries
                    # the same +2/3, so resume from it when it survives
                    try:
                        seen = block_store.load_block_commit(
                            state.last_block_height)
                    except CorruptedStoreError:
                        seen = None  # both rows rotten: fail typed below
                if seen is None:
                    raise ConsensusError(
                        f"failed to reconstruct last commit; seen commit for height "
                        f"{state.last_block_height} not found"
                    )
                last_precommits = commit_to_vote_set(
                    state.chain_id, seen, state.last_validators
                )
                if not last_precommits.has_two_thirds_majority():
                    raise ConsensusError(
                        "failed to reconstruct last commit; does not have +2/3 maj"
                    )
                self.rs.last_commit = last_precommits
            self.update_to_state(state)

    # --- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        """reference: consensus/state.go:299-420 OnStart + startRoutines."""
        self._ticker.resume()  # no-op unless pause() stopped it
        if self.wal is not None and self.state is not None:
            # Empty WAL gets a height-0 end marker so crash replay works for
            # the very first height (reference: consensus/wal.go OnStart).
            if next(iter(self.wal.iter_messages()), None) is None:
                self.wal.write_sync(EndHeightMessage(0), self.clock.now_ns())
            self._catchup_replay(self.rs.height)
        self._running = True
        if self._thread is not None and self._thread.is_alive():
            # a pause() timed out joining a blocked receive routine: it
            # re-reads _running when it unblocks and simply resumes —
            # adopting it keeps the one-drainer invariant
            self._schedule_round_0()
            return
        self._thread = threading.Thread(
            target=self._receive_routine, name="cs-receive", daemon=True
        )
        self._thread.start()
        self._schedule_round_0()

    def stop(self) -> None:
        self._running = False
        self._ticker.stop()
        self._msg_queue.put(None)
        if self._thread is not None:
            self._thread.join(timeout=5)
        if self.wal is not None:
            self.wal.close()

    def pause(self) -> None:
        """Stop the receive routine and ticker WITHOUT closing the WAL, so
        a later start() resumes cleanly. This is the stall watchdog's
        hand-back: consensus pauses, fast sync pulls the missing blocks,
        and switch_to_consensus restarts this machine at the tip."""
        self._running = False
        self._ticker.stop()
        self._msg_queue.put(None)
        if self._thread is not None:
            self._thread.join(timeout=5)
            if not self._thread.is_alive():
                self._thread = None
            # else: the routine is blocked past the join budget — KEEP the
            # handle so start() can adopt it instead of racing a second
            # drainer against it (two threads mutating rs would fork us)

    def rewind_for_catchup(self) -> None:
        """Drop in-height commit progress so a fast-sync catchup can
        update_to_state PAST this height. A node stalled mid-commit (2/3
        precommits seen but the block never arrived — the classic
        partition stall) holds commit_round > -1, which update_to_state
        treats as \"about to commit THIS height\" and refuses to skip;
        after the hand-back the pipeline applies the height from a peer's
        stored commit instead, so that claim is void."""
        with self._mtx:
            self.rs.commit_round = -1
            self.rs.triggered_timeout_precommit = False

    def wait_sync(self, timeout: float = 1.0) -> None:
        """Drain the queues (test helper): returns once queued work at call
        time has been handled."""
        done = threading.Event()
        self._msg_queue.put(("__sync__", done))
        done.wait(timeout)

    # --- external input (reference: consensus/state.go:430-520) ------------

    def _gossip_priority(self, height: int) -> int:
        """Shed class for a peer gossip message: live-height messages
        survive overload, stale-height gossip (re-derivable from stores
        and gossip re-delivery) sheds first. The unlocked rs.height read
        only biases shedding, never correctness."""
        rs_h = self.rs.height
        if height == rs_h:
            return peerscore.PRIO_LIVE
        if height > rs_h:
            return peerscore.PRIO_FUTURE
        return peerscore.PRIO_STALE

    def _count_shed(self, channel: str) -> None:
        board = self.scoreboard
        if board is not None:
            board.count_shed(channel)

    def shed_counts(self) -> dict[str, dict[str, int]]:
        """Messages the peer queue shed, by class (``live`` / ``stale`` /
        ``future``, judged against the height at arrival) and channel."""
        out: dict[str, dict[str, int]] = {"live": {}, "stale": {}, "future": {}}
        names = {peerscore.PRIO_LIVE: "live", peerscore.PRIO_STALE: "stale",
                 peerscore.PRIO_FUTURE: "future"}
        for (prio, channel), n in self._msg_queue.shed_by_class.items():
            out[names[prio]][channel] = n
        return out

    def add_vote(self, vote: Vote, peer_id: str = "") -> None:
        if peer_id == "":
            self._internal_queue.put(MsgInfo(VoteMessage(vote), peer_id))
        else:
            self._msg_queue.put(MsgInfo(VoteMessage(vote), peer_id),
                                priority=self._gossip_priority(vote.height),
                                channel="vote")

    def set_proposal(self, proposal: Proposal, peer_id: str = "") -> None:
        if peer_id == "":
            self._internal_queue.put(MsgInfo(ProposalMessage(proposal), peer_id))
        else:
            self._msg_queue.put(MsgInfo(ProposalMessage(proposal), peer_id),
                                priority=self._gossip_priority(proposal.height),
                                channel="proposal")

    def add_proposal_block_part(self, height: int, round_: int, part: Part,
                                peer_id: str = "") -> None:
        if peer_id == "":
            self._internal_queue.put(
                MsgInfo(BlockPartMessage(height, round_, part), peer_id))
        else:
            self._msg_queue.put(
                MsgInfo(BlockPartMessage(height, round_, part), peer_id),
                priority=self._gossip_priority(height), channel="block_part")

    def handle_txs_available(self) -> None:
        self._msg_queue.put(("__txs_available__", None))

    # --- round state snapshot ---------------------------------------------

    def get_round_state(self) -> cstypes.RoundState:
        with self._mtx:
            import copy

            return copy.copy(self.rs)

    # --- the serialized event loop -----------------------------------------

    def _receive_routine(self) -> None:
        """Crash shield around the drain loop: a stray exception must not
        kill the one consensus drainer silently (with ``_running`` still
        True nothing would ever restart it). Fail-stop instead: log, mark
        the machine stopped, and let the stall watchdog hand the node to
        fast-sync catchup (consensus/watchdog.py), which restarts a fresh
        machine at the tip."""
        try:
            # every span recorded on the consensus thread — including the
            # crypto-layer verify phases dispatched from it — lands in THIS
            # node's tracer (thread-local activation, utils/trace.py)
            with self.tracer.activate():
                if self.tracer.enabled:
                    self._census.read()  # the first height's baseline
                self._receive_loop()
        except Exception as e:  # noqa: BLE001 - fail-stop, never die silent
            if self.logger is not None:
                self.logger.error("consensus receive routine crashed; "
                                  "halting this machine for watchdog "
                                  "recovery", err=e)
            self._running = False

    def _receive_loop(self) -> None:
        """reference: consensus/state.go:707-790. Strict ordering: internal
        queue drains before the peer queue; timeouts interleave."""
        while self._running:
            mi = None
            try:
                mi = self._internal_queue.get_nowait()
                internal = True
            except queue.Empty:
                internal = False
            if mi is None:
                try:
                    ti = self._timeout_queue.get_nowait()
                except queue.Empty:
                    ti = None
                if ti is not None:
                    # timeout decisions read round state: apply any
                    # in-flight vote flush first
                    self._flush_pending_votes()
                    # WAL the timeout HERE, at dequeue time, so WAL order
                    # matches processing order (reference consensus/state.go
                    # writes it in receiveRoutine immediately before
                    # handleTimeout) — writing at fire time on the ticker
                    # thread could log it ahead of messages handled first.
                    if self.wal is not None and not self.replay_mode:
                        self.wal.write(timeout_wal_blob(ti), _time.time_ns())
                    self._do_handle_timeout(ti)
                    continue
                if self._holdover is not None:
                    mi, self._holdover = self._holdover, None
                else:
                    try:
                        mi = self._msg_queue.get_nowait()
                    except queue.Empty:
                        # idle: nothing left to overlap the in-flight flush
                        # with, resolve it now
                        self._flush_pending_votes()
                        try:
                            mi = self._msg_queue.get(timeout=0.02)
                        except queue.Empty:
                            continue
            if mi is None:
                self._flush_pending_votes()
                if not self._running:
                    return  # stop sentinel
                # stale wake-up sentinel from a previous pause()/stop():
                # a RESTARTED routine (watchdog hand-back) must not let it
                # silently kill the new thread
                continue
            if isinstance(mi, tuple):
                kind, payload = mi
                if kind == "__sync__":
                    self._flush_pending_votes()
                    if not self._internal_queue.empty() or not self._timeout_queue.empty():
                        self._msg_queue.put(mi)  # drain internals first
                    else:
                        payload.set()
                elif kind == "__txs_available__":
                    self._flush_pending_votes()
                    with self._mtx:
                        self._handle_txs_available()
                continue
            # Batched vote drain (the deferred batched addVote mode the
            # reference lacks; BASELINE config 5): when peer votes have piled
            # up, pull them all and verify their signatures in ONE
            # BatchVerifier flush instead of one scalar verify per vote.
            if (not internal and isinstance(mi.msg, VoteMessage)
                    and not self._msg_queue.empty()):
                votes = self._drain_votes(mi)
                if len(votes) > 1:
                    tr = self.tracer
                    if self.wal is not None and not self.replay_mode:
                        with (tr.span("consensus.wal_write", msgs=len(votes))
                              if tr.enabled else _trace.NULL_SPAN):
                            n_bytes, writes = self._wal_write_votes(votes)
                            tr.annotate(bytes=n_bytes, writes=writes)
                    # the drain span carries the height; verify phases
                    # dispatched inside inherit it
                    with self._mtx, (
                            tr.span("consensus.vote_drain",
                                    height=self.rs.height, round=self.rs.round,
                                    votes=len(votes))
                            if tr.enabled else _trace.NULL_SPAN):
                        self._handle_vote_batch(votes)
                    continue
            # Any other message mutates state through _handle_msg: apply the
            # in-flight vote flush first so side effects stay arrival-order.
            self._flush_pending_votes()
            # WAL discipline (reference: state.go:753-780): internal messages
            # are fsync'd, peer messages buffered.
            if self.wal is not None and not self.replay_mode:
                blob = mi.msg.wal_blob()
                blob.peer_id = mi.peer_id
                if internal:
                    self.wal.write_sync(blob, _time.time_ns())
                else:
                    self.wal.write(blob, _time.time_ns())
            with self._mtx, (
                    self.tracer.span("consensus.vote_serial", why="single",
                                     votes=1)
                    if (self.tracer.enabled and not internal
                        and isinstance(mi.msg, VoteMessage))
                    else _trace.NULL_SPAN):
                self._handle_msg(mi)

    def _wal_write_votes(self, votes: list[MsgInfo]) -> tuple[int, int]:
        """A drain's votes into the WAL, buffered, every copy a message of
        its own, in arrival order, before any is verified -> (payload bytes
        written, write calls issued on the file)."""
        payloads = Vote.marshal_many([m.msg.vote for m in votes])
        writes = self.wal.write_blobs(
            [("vote", p, m.peer_id) for p, m in zip(payloads, votes)],
            _time.time_ns())
        return sum(map(len, payloads)), writes

    def _drain_votes(self, first: MsgInfo) -> list[MsgInfo]:
        """Pull immediately-available peer VoteMessages (bounded so internal
        messages and timeouts are not starved). A non-vote message ends the
        drain and is held over for the next loop iteration."""
        batch = [first]
        while len(batch) < 1024:
            try:
                nxt = self._msg_queue.get_nowait()
            except queue.Empty:
                break
            if isinstance(nxt, MsgInfo) and isinstance(nxt.msg, VoteMessage):
                batch.append(nxt)
            else:
                self._holdover = nxt
                break
        return batch

    def _handle_vote_batch(self, msgs: list[MsgInfo]) -> None:
        """Verify the batch's signatures in one BatchVerifier flush, then
        apply each vote IN ARRIVAL ORDER through the normal addVote path with
        the signature check skipped. Per-vote side effects (conflict/evidence
        detection, maj23 bookkeeping, round transitions) are bit-identical to
        serial processing: the batch verifies exactly the triple
        (val_set[index].pub_key, sign_bytes(chain_id), signature) that
        VoteSet.add_vote would check (reference: types/vote_set.go:205).

        Device flushes are applied ASYNCHRONOUSLY: the dispatch is issued
        here, the drain keeps consuming the queue while the device works,
        and the result is applied by _flush_pending_votes before any later
        state transition (the round trip overlaps consensus work).
        Verification inputs are state-independent --
        (pubkey, sign bytes, signature) fixed at dispatch -- and batch k is
        always applied before batch k+1, so observable ordering is exactly
        the serial drain's.

        A DEVICE-BOUND dispatch lands on the continuous-batching verify
        service (crypto/verify_service.py): this drain's flush coalesces
        with any concurrent fast-sync / range / fabric-peer dispatches into
        ONE shared kernel launch, so a drain racing other verify traffic
        pays one sync floor, not one each (sub-crossover host flushes keep
        verifying inline — they never pay a floor). has_device_output() on
        the returned handle sees through to an in-flight service request,
        so the stash-and-overlap path below engages exactly as with a raw
        device handle."""
        from tendermint_tpu.crypto import batch as crypto_batch
        from tendermint_tpu.crypto import sigcache

        # Apply the in-flight previous flush FIRST: if it commits and
        # advances the height, a snapshot taken before it would filter every
        # vote of this batch against the stale height and silently demote
        # the whole drain to serial verification exactly on the busiest
        # transition (ADVICE r5 item 3).
        self._flush_pending_votes(_locked=True)
        rs = self.rs
        val_set = rs.votes.val_set if rs.votes is not None else None
        height = rs.height
        dc = sigcache.DrainCache()
        try:
            verifier = crypto_batch.create_batch_verifier()
            queued: list[int] = []
            sb_memo: dict[tuple, bytes] = {}
            chain_id = self.state.chain_id
            # votes the batch leaves to the serial path: {msg index: why}
            serial: dict[int, str] = {}
            for i, m in enumerate(msgs):
                v = m.msg.vote
                if val_set is None or v.height != height:
                    # serial path handles late/early votes
                    serial[i] = "late" if v.height < height else "early"
                    continue
                if not (0 <= v.validator_index < val_set.size()):
                    # precheck will raise the right error serially
                    serial[i] = "precheck"
                    continue
                addr, val = val_set.get_by_index(v.validator_index)
                if val is None or addr != v.validator_address:
                    serial[i] = "precheck"
                    continue
                sb_key = (v.height, v.round, v.type, v.block_id.key(),
                          v.timestamp)
                sb = sb_memo.get(sb_key)
                if sb is None:
                    sb = sb_memo[sb_key] = v.sign_bytes(chain_id)
                # Gossip re-delivers the same vote from several peers; a
                # known-verified triple skips straight to the serial
                # accept-replay (duplicate detection happens there).
                if dc.check(i, val.pub_key.bytes(), sb, v.signature):
                    continue
                verifier.add(val.pub_key, sb, v.signature)
                queued.append(i)
            if self.tracer.enabled:
                self.tracer.annotate(
                    queued=len(queued), cache_hits=len(dc.cached_ok),
                    in_drain_copies=dc.copies_queued(), skipped=len(serial))
            if not queued:
                # commit with an empty flush: applies the cache hits and
                # flushes the batched hit/miss metrics deltas
                self._apply_vote_results(msgs, dc.commit([], []), serial)
                return
            pending = verifier.dispatch()
            if pending.has_device_output():
                # stash; the drain loop applies it before the next state
                # transition, overlapping the round trip with more draining
                self._pending_flush = (msgs, queued, dc, pending, serial)
                return
            ok_by_i = self._resolve_vote_flush(queued, dc, pending)
        except Exception as e:  # noqa: BLE001
            # A flush failure (device OOM, runtime hiccup) must not kill the
            # consensus thread; fall back to per-vote scalar verification.
            # Cache hits stay verified -- they never touched this flush --
            # and the empty commit caches nothing but still flushes the
            # batched hit/miss metric deltas (counters must stay honest
            # exactly when degradation makes operators read them).
            ok_by_i = dc.commit([], [])
            if self.logger is not None:
                self.logger.error("batched vote verify failed; falling back "
                                  "to serial", err=e)
        self._apply_vote_results(msgs, ok_by_i, serial)

    def _resolve_vote_flush(self, queued, dc, pending):
        """Resolve a dispatched vote flush into {msg index: verified}.
        Positively verified triples enter the signature cache in
        DrainCache.commit -- only from a resolved bitmap, so a resolve that
        raises (propagated to the caller's serial fallback) can never
        poison the cache."""
        tr = self.tracer
        with (tr.span("consensus.flush_wait", sigs=len(queued))
              if tr.enabled else _trace.NULL_SPAN):
            _, bitmap = pending.resolve()
        return dc.commit(queued, bitmap)

    def _flush_pending_votes(self, _locked: bool = False) -> None:
        """Fetch and apply the in-flight batched vote flush, if any.
        _locked=True when the caller already holds self._mtx."""
        pf = self._pending_flush
        if pf is None:
            return
        self._pending_flush = None
        msgs, queued, dc, pending, serial = pf
        try:
            ok_by_i = self._resolve_vote_flush(queued, dc, pending)
        except Exception as e:  # noqa: BLE001 - same fallback as the sync path
            ok_by_i = dc.commit([], [])
            if self.logger is not None:
                self.logger.error("batched vote verify failed; falling back "
                                  "to serial", err=e)
        if _locked:
            self._apply_vote_results(msgs, ok_by_i, serial)
        else:
            with self._mtx:
                self._apply_vote_results(msgs, ok_by_i, serial)

    def _apply_vote_results(self, msgs: list[MsgInfo], ok_by_i: dict[int, bool],
                            serial: dict[int, str] | None = None) -> None:
        """Apply a drain's votes in arrival order. ``serial`` names the
        votes the batch did not verify and why; with tracing on, the wall
        and CPU time they spend in the serial path is recorded per reason."""
        tr = self.tracer
        timed = serial if tr.enabled and serial else {}
        counts = {"added": 0, "not_added": 0, "invalid": 0, "errors": 0}
        spent: dict[str, list] = {}       # why -> [votes, seconds, cpu s]
        with (tr.span("consensus.vote_apply", votes=len(msgs))
              if tr.enabled else _trace.NULL_SPAN):
            # the clocks are read once a run of serial votes with one reason,
            # not once a vote: the CPU clock is a system call
            for why, run in itertools.groupby(
                    enumerate(msgs), key=lambda im: timed.get(im[0])):
                if why is None:
                    for i, m in run:
                        counts[self._apply_vote_result(m, ok_by_i.get(i))] += 1
                    continue
                acc = spent.setdefault(why, [0, 0.0, 0.0])
                t0, c0 = _time.monotonic(), _time.thread_time()
                for _i, m in run:
                    counts[self._apply_vote_result(m, None)] += 1
                    acc[0] += 1
                acc[2] += _time.thread_time() - c0
                acc[1] += _time.monotonic() - t0
            for cb in self.on_work_done:
                cb()
            tr.annotate(added=counts["added"], invalid=counts["invalid"],
                        duplicates=counts["not_added"],
                        errors=counts["errors"])
        for why, (n, seconds, cpu_s) in spent.items():
            tr.record("consensus.vote_serial", seconds, cpu_s=cpu_s, why=why,
                      votes=n)

    def _apply_vote_result(self, m: MsgInfo, ok: bool | None) -> str:
        """One vote of a drain through the normal addVote path -> ``added``,
        ``not_added`` (a copy, or a vote the round state ignores),
        ``invalid`` or ``errors``."""
        if ok is False:
            # Same terminal state as the serial path's VoteError: vote
            # dropped, error logged, consensus thread lives on — but
            # the lane's FAILED bit is attributed to the delivering
            # peer: MsgInfo.peer_id traveled the whole drain, so the
            # batched bitmap sanctions exactly like serial verification
            self._punish_peer(m.peer_id)
            if self.logger is not None:
                self.logger.error(
                    "failed to process message", err="invalid signature",
                    peer=m.peer_id)
            return "invalid"
        try:
            added = self._try_add_vote(m.msg.vote, m.peer_id, verified=bool(ok))
        except Exception as e:  # noqa: BLE001 - mirror _handle_msg
            invalid = isinstance(e, ErrVoteInvalidSignature)
            if invalid:
                self._punish_peer(m.peer_id)
            if self.logger is not None:
                self.logger.error("failed to process message", err=e,
                                  peer=m.peer_id)
            return "invalid" if invalid else "errors"
        return "added" if added else "not_added"

    def _punish_peer(self, peer_id: str,
                     offense: str = "invalid_signature") -> None:
        board = self.scoreboard
        if board is not None and peer_id:
            board.record(peer_id, offense)

    def _on_timeout_fired(self, ti: TimeoutInfo) -> None:
        # hop onto the consensus thread; WAL write happens at dequeue
        self._timeout_queue.put(ti)

    def _handle_msg(self, mi: MsgInfo) -> None:
        """reference: consensus/state.go:799-890."""
        msg, peer_id = mi.msg, mi.peer_id
        try:
            if isinstance(msg, ProposalMessage):
                self._set_proposal(msg.proposal)
            elif isinstance(msg, BlockPartMessage):
                added = self._add_proposal_block_part(msg)
                if added and self.rs.proposal_block_parts.is_complete():
                    self._handle_complete_proposal(msg.height)
            elif isinstance(msg, VoteMessage):
                self._try_add_vote(msg.vote, peer_id)
        except Exception as e:  # noqa: BLE001
            # The reference logs and continues (consensus/state.go:880-890):
            # a bad peer message (invalid sig, wrong index, unwanted round...)
            # must never kill the consensus thread.
            if isinstance(e, ErrVoteInvalidSignature):
                self._punish_peer(peer_id)  # serial twin of the drain bitmap
            if self.logger is not None:
                self.logger.error("failed to process message", err=e, peer=peer_id)
        for cb in self.on_work_done:
            cb()

    def _do_handle_timeout(self, ti: TimeoutInfo) -> None:
        """reference: consensus/state.go:890-940 handleTimeout."""
        with self._mtx:
            rs = self.rs
            if (ti.height != rs.height or ti.round < rs.round
                    or (ti.round == rs.round and ti.step < rs.step)):
                return
            if ti.step == STEP_NEW_HEIGHT:
                self._enter_new_round(ti.height, 0)
            elif ti.step == STEP_NEW_ROUND:
                self._enter_propose(ti.height, 0)
            elif ti.step == STEP_PROPOSE:
                self.event_bus.publish_event_timeout_propose(self._round_state_event())
                self._enter_prevote(ti.height, ti.round)
            elif ti.step == STEP_PREVOTE_WAIT:
                self.event_bus.publish_event_timeout_wait(self._round_state_event())
                self._enter_precommit(ti.height, ti.round)
            elif ti.step == STEP_PRECOMMIT_WAIT:
                self.event_bus.publish_event_timeout_wait(self._round_state_event())
                self._enter_precommit(ti.height, ti.round)
                self._enter_new_round(ti.height, ti.round + 1)

    def _handle_txs_available(self) -> None:
        """reference: consensus/state.go:940-975."""
        if self.rs.round != 0:
            return
        if self.rs.step == STEP_NEW_HEIGHT:
            if self._need_proof_block(self.rs.height):
                return
            remain = max(self.rs.start_time.unix_ns() - self.clock.now_ns(), 0) / 1e9
            self._schedule_timeout(remain + 0.001, self.rs.height, 0, STEP_NEW_ROUND)
        elif self.rs.step == STEP_NEW_ROUND:
            self._enter_propose(self.rs.height, 0)

    # --- state update ------------------------------------------------------

    def update_to_state(self, state) -> None:
        """reference: consensus/state.go:573-700 updateToState."""
        rs = self.rs
        if rs.commit_round > -1 and 0 < rs.height and rs.height != state.last_block_height:
            raise ConsensusError(
                f"updateToState() expected state height of {rs.height} but found "
                f"{state.last_block_height}"
            )
        if self.state is not None and not self.state.is_empty():
            if state.last_block_height <= self.state.last_block_height:
                self._new_step()
                return

        validators = state.validators
        if state.last_block_height == 0:
            rs.last_commit = None
        elif rs.commit_round > -1 and rs.votes is not None:
            precommits = rs.votes.precommits(rs.commit_round)
            if not precommits.has_two_thirds_majority():
                raise ConsensusError("wanted to form a commit, but precommits didn't have 2/3+")
            rs.last_commit = precommits

        height = state.last_block_height + 1
        if height == 1:
            height = state.initial_height

        rs.height = height
        rs.round = 0
        rs.step = STEP_NEW_HEIGHT
        now_ns = self.clock.now_ns()
        base_ns = rs.commit_time.unix_ns() if not rs.commit_time.is_zero() else now_ns
        rs.start_time = Time.from_unix_ns(base_ns + int(self.config.commit_time_s() * 1e9))
        rs.validators = validators
        self._msg_queue.maxsize = (
            MSG_QUEUE_MIN + MSG_QUEUE_PER_VALIDATOR * validators.size())
        rs.proposal = None
        rs.proposal_block = None
        rs.proposal_block_parts = None
        rs.locked_round = -1
        rs.locked_block = None
        rs.locked_block_parts = None
        rs.valid_round = -1
        rs.valid_block = None
        rs.valid_block_parts = None
        rs.votes = HeightVoteSet(state.chain_id, height, validators)
        rs.commit_round = -1
        rs.last_validators = state.last_validators
        rs.triggered_timeout_precommit = False
        self.state = state
        self._new_step()

    def _new_step(self) -> None:
        if self.wal is not None and not self.replay_mode:
            self.wal.write(
                WALMessageBlob("round_state", b"%d/%d/%d" % (
                    self.rs.height, self.rs.round, self.rs.step)),
                self.clock.now_ns(),
            )
        self._n_steps += 1
        # step-duration tracing (no-op beyond the enabled attribute check +
        # timestamp bookkeeping; the timestamp/step update is unconditional
        # so a disable/enable cycle can't produce a span covering the gap)
        now = _time.monotonic()
        last = getattr(self, "_last_step_at", None)
        prev_step = getattr(self, "_last_step_name", None)
        self._last_step_at = now
        self._last_step_name = self.rs.step
        if self.tracer.enabled and last is not None and prev_step is not None:
            # the measured duration belongs to the step we LEFT; the name
            # (not the int) is the step_duration histogram's label
            self.tracer.record("consensus.step", now - last,
                               height=self.rs.height, round=self.rs.round,
                               step=cstypes.STEP_NAMES.get(prev_step,
                                                           str(prev_step)))
        self.event_bus.publish_event_new_round_step(self._round_state_event())
        for cb in self.on_new_round_step:
            cb(self.rs)

    def _round_state_event(self) -> tmevents.EventDataRoundState:
        return tmevents.EventDataRoundState(
            height=self.rs.height, round=self.rs.round, step=self.rs.step_name()
        )

    # --- timeout scheduling -------------------------------------------------

    def _schedule_timeout(self, duration_s: float, height: int, round_: int, step: int) -> None:
        self._ticker.schedule_timeout(TimeoutInfo(duration_s, height, round_, step))

    def _schedule_round_0(self) -> None:
        """reference: consensus/state.go:522-530."""
        sleep = max(self.rs.start_time.unix_ns() - self.clock.now_ns(), 0) / 1e9
        self._schedule_timeout(sleep, self.rs.height, 0, STEP_NEW_HEIGHT)

    # --- ENTER: transitions -------------------------------------------------

    def _enter_new_round(self, height: int, round_: int) -> None:
        """reference: consensus/state.go:976-1037."""
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
                rs.round == round_ and rs.step != STEP_NEW_HEIGHT):
            return

        validators = rs.validators
        if rs.round < round_:
            validators = validators.copy()
            validators.increment_proposer_priority(round_ - rs.round)

        rs.round = round_
        rs.step = STEP_NEW_ROUND
        rs.validators = validators
        if round_ != 0:
            rs.proposal = None
            rs.proposal_block = None
            rs.proposal_block_parts = None
        rs.votes.set_round(round_ + 1)  # track next round for round-skipping
        rs.triggered_timeout_precommit = False

        proposer = validators.get_proposer()
        self.event_bus.publish_event_new_round(tmevents.EventDataNewRound(
            height=height, round=round_, step=rs.step_name(),
            proposer_address=proposer.address if proposer else b"",
        ))

        wait_for_txs = (self.config.wait_for_txs() and round_ == 0
                        and not self._need_proof_block(height))
        if wait_for_txs:
            if self.config.create_empty_blocks_interval_s > 0:
                self._schedule_timeout(self.config.create_empty_blocks_interval_s,
                                       height, round_, STEP_NEW_ROUND)
            if self.mempool is not None and self.mempool.size() > 0:
                self._enter_propose(height, round_)
        else:
            self._enter_propose(height, round_)

    def _need_proof_block(self, height: int) -> bool:
        """reference: consensus/state.go:1040-1053."""
        if height == self.state.initial_height:
            return True
        from tendermint_tpu.store.envelope import CorruptedStoreError

        try:
            last_meta = self.block_store.load_block_meta(height - 1)
        except CorruptedStoreError:
            return True  # quarantined + repair scheduled; propose a proof
            # block conservatively rather than kill the round routine
        if last_meta is None:
            raise ConsensusError(f"needProofBlock: last block meta for height {height-1} not found")
        return self.state.app_hash != last_meta.header.app_hash

    def _enter_propose(self, height: int, round_: int) -> None:
        """reference: consensus/state.go:1060-1122."""
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
                rs.round == round_ and STEP_PROPOSE <= rs.step):
            return
        try:
            self._schedule_timeout(self.config.propose(round_), height, round_, STEP_PROPOSE)
            if self.priv_validator is None or self.priv_validator_pub_key is None:
                return
            address = self.priv_validator_pub_key.address()
            if not rs.validators.has_address(address):
                return
            if rs.validators.get_proposer().address == address:
                self._decide_proposal(height, round_)
        finally:
            rs.round = round_
            rs.step = STEP_PROPOSE
            self._new_step()
            if self._is_proposal_complete():
                self._enter_prevote(height, rs.round)

    def _decide_proposal(self, height: int, round_: int) -> None:
        """reference: consensus/state.go:1124-1180 defaultDecideProposal."""
        mb = self.misbehaviors.get("propose")
        if mb is not None and mb(self, height, round_):
            return
        rs = self.rs
        if rs.valid_block is not None:
            block, block_parts = rs.valid_block, rs.valid_block_parts
        else:
            created = self._create_proposal_block()
            if created is None:
                return
            block, block_parts = created
        if self.wal is not None:
            self.wal.flush_and_sync()
        prop_block_id = BlockID(hash=block.hash(), part_set_header=block_parts.header())
        proposal = Proposal(height=height, round=round_, pol_round=rs.valid_round,
                            block_id=prop_block_id,
                            timestamp=Time.from_unix_ns(self.clock.now_ns()))
        try:
            self.priv_validator.sign_proposal(self.state.chain_id, proposal)
        except Exception as e:  # noqa: BLE001 - failed signing is non-fatal
            # Non-fatal in BOTH modes (reference: state.go:1124-1180 logs
            # outside replay, stays silent inside it). In catchup replay
            # after a crash that lost WAL frames past the last signed step,
            # the double-sign guard refuses this HRS -- the node must skip
            # proposing and let the next round proceed, not die here.
            if not self.replay_mode and self.logger is not None:
                self.logger.error("error signing proposal", height=height,
                                  round=round_, err=e)
            return
        msgs = [MsgInfo(ProposalMessage(proposal), "")]
        for i in range(block_parts.header().total):
            part = block_parts.get_part(i)
            msgs.append(MsgInfo(BlockPartMessage(height, round_, part), ""))
        for m in msgs:
            self._internal_queue.put(m)
            if self.broadcast is not None:
                self.broadcast(m.msg)

    def _create_proposal_block(self):
        """reference: consensus/state.go:1189-1223."""
        rs = self.rs
        if rs.height == self.state.initial_height:
            commit = Commit(height=0, round=0, block_id=BlockID(), signatures=[])
        elif rs.last_commit is not None and rs.last_commit.has_two_thirds_majority():
            commit = rs.last_commit.make_commit()
        else:
            return None
        proposer_addr = self.priv_validator_pub_key.address()
        block = self.block_exec.create_proposal_block(
            rs.height, self.state, commit, proposer_addr
        )
        parts = PartSet.from_data(block.marshal())
        return block, parts

    def _is_proposal_complete(self) -> bool:
        """reference: consensus/state.go:1182-1196."""
        rs = self.rs
        if rs.proposal is None or rs.proposal_block is None:
            return False
        if rs.proposal.pol_round < 0:
            return True
        prevotes = rs.votes.prevotes(rs.proposal.pol_round)
        return prevotes is not None and prevotes.has_two_thirds_majority()

    def _enter_prevote(self, height: int, round_: int) -> None:
        """reference: consensus/state.go:1226-1250."""
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
                rs.round == round_ and STEP_PREVOTE <= rs.step):
            return
        self._do_prevote(height, round_)
        rs.round = round_
        rs.step = STEP_PREVOTE
        self._new_step()

    def _do_prevote(self, height: int, round_: int) -> None:
        """reference: consensus/state.go:1252-1284 defaultDoPrevote."""
        mb = self.misbehaviors.get("prevote")
        if mb is not None and mb(self, height, round_):
            return
        rs = self.rs
        if rs.locked_block is not None:
            self._sign_add_vote(PREVOTE_TYPE, rs.locked_block.hash(),
                                rs.locked_block_parts.header())
            return
        if rs.proposal_block is None:
            self._sign_add_vote(PREVOTE_TYPE, b"", PartSetHeader())
            return
        try:
            self.block_exec.validate_block(self.state, rs.proposal_block)
        except Exception:  # noqa: BLE001 - invalid proposal -> prevote nil
            self._sign_add_vote(PREVOTE_TYPE, b"", PartSetHeader())
            return
        self._sign_add_vote(PREVOTE_TYPE, rs.proposal_block.hash(),
                            rs.proposal_block_parts.header())

    def _enter_prevote_wait(self, height: int, round_: int) -> None:
        """reference: consensus/state.go:1286-1315."""
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
                rs.round == round_ and STEP_PREVOTE_WAIT <= rs.step):
            return
        if not rs.votes.prevotes(round_).has_two_thirds_any():
            raise ConsensusError(
                f"entering prevote wait step ({height}/{round_}), but prevotes "
                "does not have any +2/3 votes"
            )
        rs.round = round_
        rs.step = STEP_PREVOTE_WAIT
        self._new_step()
        self._schedule_timeout(self.config.prevote(round_), height, round_, STEP_PREVOTE_WAIT)

    def _enter_precommit(self, height: int, round_: int) -> None:
        """reference: consensus/state.go:1322-1417."""
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
                rs.round == round_ and STEP_PRECOMMIT <= rs.step):
            return
        self.tracer.mark("consensus.precommit", height=height, round=round_)

        def done():
            rs.round = round_
            rs.step = STEP_PRECOMMIT
            self._new_step()

        mb = self.misbehaviors.get("precommit")
        if mb is not None and mb(self, height, round_):
            done()
            return

        block_id, ok = rs.votes.prevotes(round_).two_thirds_majority()
        if not ok:
            # No polka: precommit nil.
            self._sign_add_vote(PRECOMMIT_TYPE, b"", PartSetHeader())
            done()
            return

        self.event_bus.publish_event_polka(self._round_state_event())
        pol_round, _ = rs.votes.pol_info()
        if pol_round < round_:
            raise ConsensusError(f"this POLRound should be {round_} but got {pol_round}")

        if len(block_id.hash) == 0:
            # +2/3 prevoted nil: unlock and precommit nil.
            if rs.locked_block is not None:
                rs.locked_round = -1
                rs.locked_block = None
                rs.locked_block_parts = None
                self.event_bus.publish_event_unlock(self._round_state_event())
            self._sign_add_vote(PRECOMMIT_TYPE, b"", PartSetHeader())
            done()
            return

        if rs.locked_block is not None and rs.locked_block.hashes_to(block_id.hash):
            # relock
            rs.locked_round = round_
            self.event_bus.publish_event_relock(self._round_state_event())
            self._sign_add_vote(PRECOMMIT_TYPE, block_id.hash, block_id.part_set_header)
            done()
            return

        if rs.proposal_block is not None and rs.proposal_block.hashes_to(block_id.hash):
            # lock the proposal block
            self.block_exec.validate_block(self.state, rs.proposal_block)
            rs.locked_round = round_
            rs.locked_block = rs.proposal_block
            rs.locked_block_parts = rs.proposal_block_parts
            self.event_bus.publish_event_lock(self._round_state_event())
            self._sign_add_vote(PRECOMMIT_TYPE, block_id.hash, block_id.part_set_header)
            done()
            return

        # Polka for a block we don't have: unlock, fetch, precommit nil.
        rs.locked_round = -1
        rs.locked_block = None
        rs.locked_block_parts = None
        if rs.proposal_block_parts is None or not rs.proposal_block_parts.has_header(
                block_id.part_set_header):
            rs.proposal_block = None
            rs.proposal_block_parts = PartSet.from_header(block_id.part_set_header)
        self.event_bus.publish_event_unlock(self._round_state_event())
        self._sign_add_vote(PRECOMMIT_TYPE, b"", PartSetHeader())
        done()

    def _enter_precommit_wait(self, height: int, round_: int) -> None:
        """reference: consensus/state.go:1419-1454."""
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
                rs.round == round_ and rs.triggered_timeout_precommit):
            return
        if not rs.votes.precommits(round_).has_two_thirds_any():
            raise ConsensusError(
                f"entering precommit wait step ({height}/{round_}), but precommits "
                "does not have any +2/3 votes"
            )
        rs.triggered_timeout_precommit = True
        self._new_step()
        self._schedule_timeout(self.config.precommit(round_), height, round_,
                               STEP_PRECOMMIT_WAIT)

    def _enter_commit(self, height: int, commit_round: int) -> None:
        """reference: consensus/state.go:1476-1537."""
        rs = self.rs
        if rs.height != height or STEP_COMMIT <= rs.step:
            return
        self.tracer.mark("consensus.commit", height=height,
                         round=commit_round)

        block_id, ok = rs.votes.precommits(commit_round).two_thirds_majority()
        if not ok:
            raise ConsensusError("RunActionCommit() expects +2/3 precommits")

        if rs.locked_block is not None and rs.locked_block.hashes_to(block_id.hash):
            rs.proposal_block = rs.locked_block
            rs.proposal_block_parts = rs.locked_block_parts

        if rs.proposal_block is None or not rs.proposal_block.hashes_to(block_id.hash):
            if rs.proposal_block_parts is None or not rs.proposal_block_parts.has_header(
                    block_id.part_set_header):
                rs.proposal_block = None
                rs.proposal_block_parts = PartSet.from_header(block_id.part_set_header)
                self.event_bus.publish_event_valid_block(self._round_state_event())
                for cb in self.on_valid_block:
                    cb(self.rs)

        rs.step = STEP_COMMIT
        rs.commit_round = commit_round
        rs.commit_time = Time.from_unix_ns(self.clock.now_ns())
        self._new_step()
        self._try_finalize_commit(height)

    def _try_finalize_commit(self, height: int) -> None:
        """reference: consensus/state.go:1539-1565."""
        rs = self.rs
        if rs.height != height:
            raise ConsensusError(f"tryFinalizeCommit() cs.Height: {rs.height} vs {height}")
        block_id, ok = rs.votes.precommits(rs.commit_round).two_thirds_majority()
        if not ok or len(block_id.hash) == 0:
            return
        if rs.proposal_block is None or not rs.proposal_block.hashes_to(block_id.hash):
            return
        self._finalize_commit(height)

    def _finalize_commit(self, height: int) -> None:
        """reference: consensus/state.go:1567-1692."""
        rs = self.rs
        if rs.height != height or rs.step != STEP_COMMIT:
            return
        block_id, ok = rs.votes.precommits(rs.commit_round).two_thirds_majority()
        block, block_parts = rs.proposal_block, rs.proposal_block_parts
        if not ok:
            raise ConsensusError("cannot finalize commit; commit does not have 2/3 majority")
        if not block_parts.has_header(block_id.part_set_header):
            raise ConsensusError("expected ProposalBlockParts header to be commit header")
        if not block.hashes_to(block_id.hash):
            raise ConsensusError("cannot finalize commit; proposal block does not hash to commit hash")
        # validate + save + apply: what a height costs after its +2/3
        with self.tracer.span("consensus.finalize_commit", height=height):
            # commit→apply overlap (docs/EXECUTION.md): dispatch the block's
            # LastCommit verification on-device now so the round trip rides
            # under the structural checks; the resolved handle then makes
            # apply_block's re-validation free (resolve() is idempotent),
            # collapsing the path's two synchronous verifies into one async one.
            commit_pending = self.block_exec.dispatch_commit_verify(self.state, block)
            self.block_exec.validate_block(self.state, block,
                                           commit_pending=commit_pending)

            from tendermint_tpu.utils import faults

            # crash site 1 (reference: state.go:1605)
            faults.fail_point("consensus.finalize.save_block")
            if self.block_store.height < block.header.height:
                seen_commit = rs.votes.precommits(rs.commit_round).make_commit()
                with self.tracer.span("consensus.store_save", height=height):
                    self.block_store.save_block(block, block_parts, seen_commit)

            # crash site 2 (reference: state.go:1619)
            faults.fail_point("consensus.finalize.end_height")
            if self.wal is not None:
                self.wal.write_sync(EndHeightMessage(height), self.clock.now_ns())

            # crash site 3 (reference: state.go:1642)
            faults.fail_point("consensus.finalize.apply_block")
            state_copy = self.state.copy()
            with self.tracer.span("consensus.abci_apply", height=height):
                state_copy, retain_height = self.block_exec.apply_block(
                    state_copy,
                    BlockID(hash=block.hash(), part_set_header=block_parts.header()),
                    block,
                    commit_pending=commit_pending,
                )

        # crash site 4 (reference: state.go:1667)
        faults.fail_point("consensus.finalize.prune")
        if retain_height > 0:
            try:
                self.block_store.prune_blocks(retain_height)
            except Exception:  # noqa: BLE001
                pass

        self.update_to_state(state_copy)

        # crash site 5 (reference: state.go:1685)
        faults.fail_point("consensus.finalize.done")
        if self.priv_validator is not None:
            self.priv_validator_pub_key = self.priv_validator.get_pub_key()
        self._schedule_round_0()
        if self.tracer.enabled:
            census = self._census.read()
            if census is not None:
                self.tracer.mark("consensus.thread_cpu", height=height,
                                 **census)

    # --- proposal handling --------------------------------------------------

    def _set_proposal(self, proposal: Proposal) -> None:
        """reference: consensus/state.go:1809-1850 defaultSetProposal."""
        rs = self.rs
        if rs.proposal is not None:
            return
        if proposal.height != rs.height or proposal.round != rs.round:
            return
        if proposal.pol_round < -1 or (
                proposal.pol_round >= 0 and proposal.pol_round >= proposal.round):
            raise ErrInvalidProposalPOLRound()
        proposer = rs.validators.get_proposer()
        if not proposer.pub_key.verify_signature(
                proposal.sign_bytes(self.state.chain_id), proposal.signature):
            raise ErrInvalidProposalSignature()
        rs.proposal = proposal
        if rs.proposal_block_parts is None:
            rs.proposal_block_parts = PartSet.from_header(proposal.block_id.part_set_header)
        self.tracer.mark("consensus.proposal", height=proposal.height,
                         round=proposal.round)

    def _add_proposal_block_part(self, msg: BlockPartMessage) -> bool:
        """reference: consensus/state.go:1850-1920."""
        rs = self.rs
        if rs.height != msg.height:
            return False
        if rs.proposal_block_parts is None:
            return False
        try:
            added = rs.proposal_block_parts.add_part(msg.part)
        except ValueError as e:
            raise ConsensusError(str(e)) from e
        if not added:
            return False
        if rs.proposal_block_parts.byte_size > self.state.consensus_params.block.max_bytes:
            raise ConsensusError("total size of proposal block parts exceeds maximum block bytes")
        if rs.proposal_block_parts.is_complete():
            rs.proposal_block = Block.unmarshal(rs.proposal_block_parts.assemble())
            self.tracer.mark("consensus.block_parts", height=rs.height,
                             round=rs.round,
                             parts=rs.proposal_block_parts.header().total)
            self.event_bus.publish_event_complete_proposal(
                tmevents.EventDataCompleteProposal(
                    height=rs.height, round=rs.round, step=rs.step_name(),
                    block_id=BlockID(hash=rs.proposal_block.hash(),
                                     part_set_header=rs.proposal_block_parts.header()),
                ))
        return True

    def _handle_complete_proposal(self, block_height: int) -> None:
        """reference: consensus/state.go:1920-1945."""
        rs = self.rs
        prevotes = rs.votes.prevotes(rs.round)
        block_id, has_two_thirds = (prevotes.two_thirds_majority()
                                    if prevotes else (None, False))
        if has_two_thirds and not block_id.is_zero() and rs.valid_round < rs.round:
            if rs.proposal_block.hashes_to(block_id.hash):
                rs.valid_round = rs.round
                rs.valid_block = rs.proposal_block
                rs.valid_block_parts = rs.proposal_block_parts
        if rs.step <= STEP_PROPOSE and self._is_proposal_complete():
            self._enter_prevote(block_height, rs.round)
            if has_two_thirds:
                self._enter_precommit(block_height, rs.round)
        elif rs.step == STEP_COMMIT:
            self._try_finalize_commit(block_height)

    # --- votes --------------------------------------------------------------

    def _try_add_vote(self, vote: Vote, peer_id: str, verified: bool = False) -> bool:
        """reference: consensus/state.go:1947-1995."""
        try:
            return self._add_vote(vote, peer_id, verified=verified)
        except ErrVoteConflictingVotes as e:
            if self.priv_validator_pub_key is not None and (
                    vote.validator_address == self.priv_validator_pub_key.address()):
                raise  # conflicting vote from ourselves
            if self.evpool is not None:
                self.evpool.report_conflicting_votes(e.vote_a, e.vote_b)
            return getattr(e, "added", False)

    def _add_vote(self, vote: Vote, peer_id: str, verified: bool = False) -> bool:
        """reference: consensus/state.go:1995-2168."""
        rs = self.rs

        # Late precommit for the previous height while in NewHeight step.
        if vote.height + 1 == rs.height and vote.type == PRECOMMIT_TYPE:
            if rs.step != STEP_NEW_HEIGHT:
                return False
            if rs.last_commit is None:
                return False
            added = rs.last_commit.add_vote(vote, verified=verified)
            if not added:
                return False
            self.event_bus.publish_event_vote(tmevents.EventDataVote(vote=vote))
            for cb in self.on_vote:
                cb(vote)
            if self.config.skip_timeout_commit and rs.last_commit.has_all():
                self._enter_new_round(rs.height, 0)
            return added

        if vote.height != rs.height:
            return False

        height = rs.height
        added = rs.votes.add_vote(vote, peer_id, verified=verified)
        if not added:
            return False
        self.event_bus.publish_event_vote(tmevents.EventDataVote(vote=vote))
        for cb in self.on_vote:
            cb(vote)

        if vote.type == PREVOTE_TYPE:
            prevotes = rs.votes.prevotes(vote.round)
            block_id, ok = prevotes.two_thirds_majority()
            if ok:
                # Unlock if cs.LockedRound < vote.Round <= cs.Round and the
                # POL is for something else (reference: state.go:2060-2083).
                if (rs.locked_block is not None
                        and rs.locked_round < vote.round <= rs.round
                        and not rs.locked_block.hashes_to(block_id.hash)):
                    rs.locked_round = -1
                    rs.locked_block = None
                    rs.locked_block_parts = None
                    self.event_bus.publish_event_unlock(self._round_state_event())
                # Update Valid* (reference: state.go:2085-2113).
                if (len(block_id.hash) != 0 and rs.valid_round < vote.round
                        and vote.round == rs.round):
                    if rs.proposal_block is not None and rs.proposal_block.hashes_to(block_id.hash):
                        rs.valid_round = vote.round
                        rs.valid_block = rs.proposal_block
                        rs.valid_block_parts = rs.proposal_block_parts
                    else:
                        rs.proposal_block = None
                    if rs.proposal_block_parts is None or not rs.proposal_block_parts.has_header(
                            block_id.part_set_header):
                        rs.proposal_block_parts = PartSet.from_header(block_id.part_set_header)
                    self.event_bus.publish_event_valid_block(self._round_state_event())
                    for cb in self.on_valid_block:
                        cb(rs)
            # Round transitions (reference: state.go:2115-2133).
            if rs.round < vote.round and prevotes.has_two_thirds_any():
                self._enter_new_round(height, vote.round)
            elif rs.round == vote.round and STEP_PREVOTE <= rs.step:
                block_id, ok = prevotes.two_thirds_majority()
                if ok and (self._is_proposal_complete() or len(block_id.hash) == 0):
                    self._enter_precommit(height, vote.round)
                elif prevotes.has_two_thirds_any():
                    self._enter_prevote_wait(height, vote.round)
            elif (rs.proposal is not None and 0 <= rs.proposal.pol_round == vote.round
                  and self._is_proposal_complete()):
                self._enter_prevote(height, rs.round)

        elif vote.type == PRECOMMIT_TYPE:
            precommits = rs.votes.precommits(vote.round)
            block_id, ok = precommits.two_thirds_majority()
            if ok:
                self._enter_new_round(height, vote.round)
                self._enter_precommit(height, vote.round)
                if len(block_id.hash) != 0:
                    self._enter_commit(height, vote.round)
                    if self.config.skip_timeout_commit and precommits.has_all():
                        self._enter_new_round(rs.height, 0)
                else:
                    self._enter_precommit_wait(height, vote.round)
            elif rs.round <= vote.round and precommits.has_two_thirds_any():
                self._enter_new_round(height, vote.round)
                self._enter_precommit_wait(height, vote.round)
        return added

    # --- signing ------------------------------------------------------------

    def _sign_vote(self, msg_type: int, hash_: bytes, header: PartSetHeader) -> Vote | None:
        """reference: consensus/state.go:2170-2215."""
        if self.wal is not None:
            self.wal.flush_and_sync()
        if self.priv_validator_pub_key is None:
            return None
        addr = self.priv_validator_pub_key.address()
        val_idx, _ = self.rs.validators.get_by_address(addr)
        vote = Vote(
            type=msg_type,
            height=self.rs.height,
            round=self.rs.round,
            block_id=BlockID(hash=hash_, part_set_header=header),
            timestamp=self._vote_time(),
            validator_address=addr,
            validator_index=val_idx,
        )
        self.priv_validator.sign_vote(self.state.chain_id, vote)
        return vote

    def _vote_time(self) -> Time:
        """BFT time monotonicity (reference: consensus/state.go:2216-2234)."""
        now = Time.from_unix_ns(self.clock.now_ns())
        min_vote_time = now
        time_iota_ns = self.state.consensus_params.block.time_iota_ms * 1_000_000
        if self.rs.locked_block is not None:
            min_vote_time = self.rs.locked_block.header.time.add_ns(time_iota_ns)
        elif self.rs.proposal_block is not None:
            min_vote_time = self.rs.proposal_block.header.time.add_ns(time_iota_ns)
        return now if now > min_vote_time else min_vote_time

    def _sign_add_vote(self, msg_type: int, hash_: bytes, header: PartSetHeader) -> Vote | None:
        """reference: consensus/state.go:2236-2263."""
        if self.priv_validator is None or self.priv_validator_pub_key is None:
            return None
        if not self.rs.validators.has_address(self.priv_validator_pub_key.address()):
            return None
        try:
            vote = self._sign_vote(msg_type, hash_, header)
        except Exception:  # noqa: BLE001 - double-sign guard etc: don't vote
            return None
        if vote is not None:
            self._internal_queue.put(MsgInfo(VoteMessage(vote), ""))
            if self.broadcast is not None:
                self.broadcast(VoteMessage(vote))
        return vote

    # --- WAL catchup replay -------------------------------------------------

    def _catchup_replay(self, cs_height: int) -> None:
        """Replay WAL messages from the last height boundary (reference:
        consensus/replay.go:93-160)."""
        # Sanity: the WAL must NOT already contain an ENDHEIGHT for cs_height —
        # that would mean the stores are behind the WAL (the height fully
        # committed but state/block store not reflecting it), which WAL replay
        # cannot fix (reference: consensus/replay.go:115-125).
        done = self.wal.search_for_end_height(cs_height)
        if done is not None:
            raise RuntimeError(
                f"WAL should not contain #ENDHEIGHT {cs_height}; "
                "the state store is behind the WAL"
            )
        after = self.wal.search_for_end_height(cs_height - 1)
        if after is None:
            # no in-height messages for this height; nothing to replay
            return
        self.replay_mode = True
        try:
            for tm in after:
                msg = wal_blob_to_msg(tm.msg) if isinstance(tm.msg, WALMessageBlob) else None
                if msg is None:
                    continue
                if isinstance(msg, TimeoutInfo):
                    self._do_handle_timeout(msg)
                elif isinstance(msg, (ProposalMessage, BlockPartMessage, VoteMessage)):
                    with self._mtx:
                        self._handle_msg(MsgInfo(msg, tm.msg.peer_id))
        finally:
            self.replay_mode = False
