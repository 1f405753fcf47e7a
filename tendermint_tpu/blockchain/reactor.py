"""Fast sync: block pool + sync loop (reference: blockchain/v0/pool.go,
blockchain/v0/reactor.go:309-419; channel 0x40;
proto/tendermint/blockchain/types.proto).

The hot loop verifies each fetched block with the NEXT block's LastCommit
via VerifyCommitLight (reference: reactor.go:366) - on TPU one batched
kernel call per block, pipelined ACROSS blocks by the depth-K verify-ahead
queue (blockchain/pipeline.py, TM_TPU_VERIFY_AHEAD) so the device sync
floor amortizes over K decisions instead of gating each one.

Messages: BlockRequest=1{height}, NoBlockResponse=2{height},
BlockResponse=3{block}, StatusRequest=4{}, StatusResponse=5{height, base}.
"""

from __future__ import annotations

import threading
import time

from tendermint_tpu.blockchain.pipeline import VerifyAheadPipeline
from tendermint_tpu.encoding import proto
from tendermint_tpu.p2p.connection import ChannelDescriptor
from tendermint_tpu.p2p.switch import Peer, Reactor, counters_since
from tendermint_tpu.store.envelope import CorruptedStoreError
from tendermint_tpu.types.block import Block
from tendermint_tpu.utils import trace as _trace

BLOCKCHAIN_CHANNEL = 0x40
TRY_SYNC_INTERVAL_S = 0.01
STATUS_UPDATE_INTERVAL_S = 10.0
SWITCH_TO_CONSENSUS_INTERVAL_S = 1.0
REQUEST_WINDOW = 16
# reference: blockchain/v0/pool.go peerTimeout. A peer that leaves a request
# unanswered this long is dropped from the pool and stopped, and what it was
# asked for is asked of another peer.
REQUEST_TIMEOUT_S = 15.0


def msg_block_request(height: int) -> bytes:
    return proto.Writer().message(1, proto.Writer().varint(1, height).out(), always=True).out()


def msg_no_block_response(height: int) -> bytes:
    return proto.Writer().message(2, proto.Writer().varint(1, height).out(), always=True).out()


def msg_block_response(block: Block) -> bytes:
    inner = proto.Writer().message(1, block.marshal(), always=True).out()
    return proto.Writer().message(3, inner, always=True).out()


def msg_status_request() -> bytes:
    return proto.Writer().message(4, b"", always=True).out()


def msg_status_response(height: int, base: int) -> bytes:
    return proto.Writer().message(
        5, proto.Writer().varint(1, height).varint(2, base).out(), always=True
    ).out()


class BlockPool:
    """reference: blockchain/v0/pool.go."""

    def __init__(self, start_height: int):
        self.height = start_height  # next height to sync
        self.peers: dict[str, tuple[int, int]] = {}  # id -> (base, height)
        self.blocks: dict[int, tuple[Block, str]] = {}  # height -> (block, peer)
        self.requested: dict[int, str] = {}
        self._asked_at: dict[int, float] = {}   # height -> when it was asked
        # height -> the peers whose block for it the sync refused: that
        # height is asked of another peer while there is one
        self.refused: dict[int, set[str]] = {}
        self.received = 0    # blocks taken into the pool
        self.timed_out = 0   # requests given up after REQUEST_TIMEOUT_S
        self._mtx = threading.RLock()

    def set_peer_range(self, peer_id: str, base: int, height: int) -> None:
        with self._mtx:
            self.peers[peer_id] = (base, height)

    def reset(self, start_height: int) -> None:
        """Re-arm the pool for a fresh sync round (the watchdog hand-back):
        forget peer ranges and buffered blocks. Ranges recorded before a
        partition sit at ≈ our own stalled height, so keeping them would
        fake an instant is_caught_up() and bounce the node straight back
        into stalled consensus; fresh StatusResponses repopulate them
        within one status broadcast."""
        with self._mtx:
            self.height = start_height
            self.peers = {}
            self.blocks = {}
            self.requested = {}
            self._asked_at = {}
            self.refused = {}

    def remove_peer(self, peer_id: str) -> None:
        with self._mtx:
            self.peers.pop(peer_id, None)
            for h in [h for h, p in self.requested.items() if p == peer_id]:
                del self.requested[h]
                self._asked_at.pop(h, None)
            for h in [h for h, (_, p) in self.blocks.items() if p == peer_id]:
                del self.blocks[h]

    def expire_requests(self, now: float | None = None) -> list[str]:
        """The peers that left a request unanswered for REQUEST_TIMEOUT_S
        (reference: pool.go peerTimeout, "peer did not send us anything").
        Each is forgotten as remove_peer forgets it, so its heights are open
        again for wanted_requests; a block it sends later is taken like any
        other, or ignored where another peer's came first."""
        with self._mtx:
            now = time.monotonic() if now is None else now
            late = sorted({p for h, p in self.requested.items()
                           if now - self._asked_at.get(h, now)
                           > REQUEST_TIMEOUT_S})
            for pid in late:
                self.timed_out += sum(1 for p in self.requested.values()
                                      if p == pid)
                self.remove_peer(pid)
            return late

    def sizes(self) -> tuple[int, int]:
        """(requests open, blocks pooled)."""
        with self._mtx:
            return len(self.requested), len(self.blocks)

    def max_peer_height(self) -> int:
        with self._mtx:
            return max((h for _, h in self.peers.values()), default=0)

    def is_caught_up(self) -> bool:
        with self._mtx:
            if not self.peers:
                return False
            return self.height >= self.max_peer_height()

    def add_block(self, peer_id: str, block: Block) -> None:
        with self._mtx:
            h = block.header.height
            if h < self.height or h in self.blocks:
                # a second or a late answer (the height was asked again of
                # another peer after a timeout): nothing to punish
                return
            self.blocks[h] = (block, peer_id)
            self.requested.pop(h, None)
            self._asked_at.pop(h, None)
            self.received += 1

    def peek_two_blocks(self) -> tuple[Block | None, Block | None]:
        with self._mtx:
            first = self.blocks.get(self.height, (None, None))[0]
            second = self.blocks.get(self.height + 1, (None, None))[0]
            return first, second

    def peek_block(self, height: int) -> Block | None:
        """Peek any pooled height without popping (the verify-ahead
        pipeline speculates past self.height)."""
        with self._mtx:
            return self.blocks.get(height, (None, None))[0]

    def pop_request(self) -> None:
        with self._mtx:
            self.blocks.pop(self.height, None)
            self.refused.pop(self.height, None)
            self.height += 1

    def redo_request(self, height: int) -> str | None:
        """Invalid block: drop it + the peer that sent it, and remember
        not to ask that peer for this height again (of an invalid pair one
        sender may be honest: it comes back and serves the other height)."""
        with self._mtx:
            bad_peer = None
            if height in self.blocks:
                bad_peer = self.blocks[height][1]
                self.refused.setdefault(height, set()).add(bad_peer)
            for h in [h for h, (_, p) in self.blocks.items() if p == bad_peer]:
                del self.blocks[h]
            return bad_peer

    def solicited(self, peer_id: str, height: int) -> bool:
        """True when this pool has an outstanding request for ``height``
        addressed to ``peer_id`` (mirrors the v2 scheduler's guard: other
        actors — notably the store repairer — send BlockRequests of their
        own, and a peer's honest NoBlock answer to one of those must not
        be punished)."""
        with self._mtx:
            return self.requested.get(height) == peer_id

    def wanted_requests(self) -> list[tuple[int, str]]:
        """Pick heights to request and a peer for each: of the peers whose
        range holds the height, in the order of their ids, the one the
        height falls on; one whose block for it was refused only when no
        other holds it."""
        with self._mtx:
            out = []
            now = time.monotonic()
            ordered = sorted(self.peers.items())
            for h in range(self.height, self.height + REQUEST_WINDOW):
                if h in self.blocks or h in self.requested:
                    continue
                candidates = [pid for pid, (b, ph) in ordered if b <= h <= ph]
                refused = self.refused.get(h)
                if refused:
                    candidates = [p for p in candidates
                                  if p not in refused] or candidates
                if not candidates:
                    continue
                pid = candidates[h % len(candidates)]
                self.requested[h] = pid
                self._asked_at[h] = now
                out.append((h, pid))
            return out


class BlockchainReactor(Reactor):
    def __init__(self, state, block_exec, block_store, fast_sync: bool,
                 consensus_reactor=None, logger=None):
        super().__init__("BLOCKCHAIN")
        self.initial_state = state
        self.state = state
        self.block_exec = block_exec
        self.block_store = block_store
        self.fast_sync = fast_sync
        self.consensus_reactor = consensus_reactor
        self.logger = logger
        self.pool = BlockPool(block_store.height + 1)
        self._pipeline = VerifyAheadPipeline()
        # the node's StoreRepairer (store/repair.py): BlockResponses feed
        # its fetch waiters, corrupt serving-side loads route to it
        self.repairer = None
        # what the invalid-block path last refused: (height, the exception,
        # the peers whose blocks were dropped for it)
        self.last_invalid: tuple | None = None
        # peers this reactor had the switch stop: the senders of an invalid
        # pair, and those that left a request unanswered
        self.peers_stopped = 0
        self._sync_started: float | None = None   # start_sync, until the
        #                                           first block is pooled
        self._wire_last: dict | None = None       # mark_wire's last reading
        self._running = False
        self._thread: threading.Thread | None = None
        self._synced = threading.Event()

    def get_channels(self) -> list[ChannelDescriptor]:
        return [ChannelDescriptor(BLOCKCHAIN_CHANNEL, priority=10,
                                  recv_message_capacity=50 * 1024 * 1024)]

    # --- peer lifecycle ----------------------------------------------------

    def add_peer(self, peer: Peer) -> None:
        peer.try_send(BLOCKCHAIN_CHANNEL,
                      msg_status_response(self.block_store.height, self.block_store.base))
        peer.try_send(BLOCKCHAIN_CHANNEL, msg_status_request())

    def remove_peer(self, peer: Peer, reason) -> None:
        self.pool.remove_peer(peer.id)

    # --- receive -----------------------------------------------------------

    def receive(self, ch_id: int, peer: Peer, msg_bytes: bytes) -> None:
        f = proto.fields(msg_bytes)
        if 1 in f:  # BlockRequest
            m = proto.fields(f[1][-1])
            height = proto.as_sint64(m.get(1, [0])[-1])
            try:
                block = self.block_store.load_block(height)
            except CorruptedStoreError:
                # thread-crash-surface rule: a rotten record must not kill
                # this receive path OR be served — the store's repair hook
                # has already quarantined + scheduled the height; answer
                # no-block so the peer retries elsewhere meanwhile
                block = None
            if block is not None:
                peer.try_send(BLOCKCHAIN_CHANNEL, msg_block_response(block))
            else:
                peer.try_send(BLOCKCHAIN_CHANNEL, msg_no_block_response(height))
        elif 3 in f:  # BlockResponse
            tracer = self._tracer() if _trace.ENABLED else None
            with (tracer.span("blockchain.recv_block", bytes=len(msg_bytes),
                              peer=peer.id[:12])
                  if tracer is not None else _trace.NULL_SPAN):
                m = proto.fields(f[3][-1])
                block = Block.unmarshal(m.get(1, [b""])[-1])
                rep = self.repairer
                if rep is not None:
                    rep.offer_block(peer.id, block)
                self.pool.add_block(peer.id, block)
                if tracer is not None:
                    tracer.annotate(height=block.header.height)
            if tracer is not None:
                self._mark_first_block(block)
        elif 4 in f:  # StatusRequest
            peer.try_send(BLOCKCHAIN_CHANNEL,
                          msg_status_response(self.block_store.height, self.block_store.base))
        elif 5 in f:  # StatusResponse
            m = proto.fields(f[5][-1])
            height = proto.as_sint64(m.get(1, [0])[-1])
            base = proto.as_sint64(m.get(2, [0])[-1])
            self.pool.set_peer_range(peer.id, base, height)

    def _tracer(self):
        """The node's recorder while it is on, else the thread's."""
        tracer = getattr(self, "tracer", None)
        if tracer is not None and tracer.enabled:
            return tracer
        return _trace.current()

    def _mark_first_block(self, block: Block) -> None:
        """fastsync.first_block: from start_sync to the first block in the
        pool (listen, dial, handshake, status exchange, the first request and
        a block's way over the wire). Once a sync, and only a traced one."""
        started, self._sync_started = self._sync_started, None
        if started is not None:
            self._tracer().mark("fastsync.first_block",
                                height=block.header.height,
                                seconds=time.monotonic() - started)

    def mark_wire(self, height: int | None) -> None:
        """p2p.wire: what the switch's connections moved since the mark
        before, summed over its peers (Switch.wire_totals), and the pool's two
        sizes. The pipeline calls it beside fastsync.thread_cpu; ``height``
        None only takes the baseline."""
        if self.switch is None:
            return
        now = self.switch.wire_totals()
        last, self._wire_last = self._wire_last, now
        if height is None or last is None:
            return
        requested, pooled = self.pool.sizes()
        self._tracer().mark("p2p.wire", height=height, requested=requested,
                            pooled=pooled, peers=len(self.switch.peers),
                            **counters_since(now, last))

    # --- sync loop (reference: blockchain/v0/reactor.go:309-419) -----------

    def start_sync(self) -> None:
        self._running = True
        self._sync_started = time.monotonic()
        self._thread = threading.Thread(target=self._pool_routine,
                                        name="fastsync-pool", daemon=True)
        self._thread.start()

    def switch_to_fast_sync(self, state) -> None:
        """Re-enter fast sync from the given state. Two callers: the
        state-sync bootstrap hand-off (reference: blockchain/v0/reactor.go
        :109 SwitchToFastSync, node.go:991 startStateSync), and the
        consensus stall watchdog handing a stalled node back for catchup —
        so this must be re-entrant: stale speculation is discarded and the
        synced latch re-arms."""
        if self._running:
            return
        self.state = state
        self.initial_state = state
        self.pool.reset(state.last_block_height + 1)
        self._pipeline.discard()
        self._synced.clear()
        self.fast_sync = True
        self.start_sync()

    def on_stop(self) -> None:
        self._running = False

    def wait_until_synced(self, timeout: float) -> bool:
        return self._synced.wait(timeout)

    def _pool_routine(self) -> None:
        try:
            self._pool_loop()
        except Exception as e:  # noqa: BLE001 - fail-stop, never die silent
            if self.logger is not None:
                self.logger.error("fast-sync pool routine crashed", err=e)
            self._running = False

    def _pool_loop(self) -> None:
        last_status = 0.0
        last_switch_check = 0.0
        started_at = time.monotonic()
        while self._running:
            now = time.monotonic()
            if now - last_status > STATUS_UPDATE_INTERVAL_S:
                if self.switch is not None:
                    self.switch.broadcast(BLOCKCHAIN_CHANNEL, msg_status_request())
                last_status = now
            # a request nobody answered is asked of another peer
            for pid in self.pool.expire_requests(now):
                self._stop_peer(pid, "fast-sync request timed out")
            # issue requests
            if self.switch is not None:
                with self.switch._peers_mtx:
                    peers = dict(self.switch.peers)
                for h, pid in self.pool.wanted_requests():
                    p = peers.get(pid)
                    if p is not None:
                        p.try_send(BLOCKCHAIN_CHANNEL, msg_block_request(h))
            # switch to consensus when caught up
            if now - last_switch_check > SWITCH_TO_CONSENSUS_INTERVAL_S:
                last_switch_check = now
                caught_up = self.pool.is_caught_up()
                # The no-peer bailout exists for solo/dev nodes; a node that
                # HAS peers configured (persistent peers or a PEX book that
                # can still produce some) must keep waiting instead of
                # silently skipping sync on a cold start.
                waited_enough = now - started_at > 3.0
                no_peers = self.switch is None or not self.switch.peers
                expects_peers = self.switch is not None and (
                    self.switch._persistent_addrs
                    or any(r.name == "PEX" and not r.book.is_empty()
                           for r in self.switch.reactors.values()
                           if hasattr(r, "book")))
                if caught_up or (waited_enough and no_peers and not expects_peers):
                    self._running = False
                    self._synced.set()
                    if self.consensus_reactor is not None:
                        self.consensus_reactor.switch_to_consensus(self.state)
                    return
            # Drain: process every contiguously-available block before
            # sleeping. The old one-block-per-tick pacing capped sync at
            # 1/TRY_SYNC_INTERVAL_S blocks/s however fast verification ran.
            applied = False
            while self._running and self._try_sync():
                applied = True
            # the next pair is not in the pool: the wire or a peer sets the
            # pace, not the apply
            waiting = (_trace.ENABLED and not applied
                       and not self.pool.is_caught_up())
            with (self._tracer().span("fastsync.pool_wait",
                                      height=self.pool.height)
                  if waiting else _trace.NULL_SPAN):
                time.sleep(TRY_SYNC_INTERVAL_S)

    def _try_sync(self) -> bool:
        """Verify + apply the next block through the depth-K verify-ahead
        pipeline (blockchain/pipeline.py): commit verification for blocks
        h..h+K-1 is dispatched while block h saves/applies, readbacks are
        batched, decisions resolve in height order with serial semantics
        (reference: reactor.go:366 VerifyCommitLight). True when a block
        was applied."""
        return self._pipeline.process_next(self)

    def _punish_invalid(self, height: int, e: Exception) -> None:
        """Punish BOTH senders: the bad LastCommit is carried by the
        second block (reference: blockchain/v0/reactor.go:394-408).
        Scored as well as disconnected (docs/OVERLOAD.md) — a fast-sync
        peer feeding invalid blocks in a redial loop must escalate to a
        ban, not recycle free disconnects."""
        bad = self.pool.redo_request(height)
        bad2 = self.pool.redo_request(height + 1)
        self.last_invalid = (height, e, sorted({bad, bad2} - {None}))
        if self.switch is not None:
            board = getattr(self.switch, "scoreboard", None)
            for pid in sorted({bad, bad2} - {None}):
                if board is not None:
                    board.record(pid, "bad_message")
                self._stop_peer(pid, f"invalid block: {e}")

    def _stop_peer(self, peer_id: str, reason: str) -> None:
        peer = self.switch.peers.get(peer_id) if self.switch is not None else None
        if peer is not None:
            self.peers_stopped += 1
            self.switch.stop_peer_for_error(peer, reason)
