"""Peer misbehavior scoring, timed bans, ingress rate ceilings, and
priority load shedding — the overload-resilience plane (docs/OVERLOAD.md).

The reference's only sanction is StopPeerForError (p2p/switch.go), a
disconnect the peer undoes by redialing. With every hot path funneled into
one shared batched-verify resource, a single flooding or byzantine peer
can saturate the kernel, the drain queues, and the mempool for the whole
node, so this build adds the three layers PBFT-lineage systems (Castro &
Liskov, OSDI'99) and staged event-driven designs (SEDA, SOSP'01) pair with
a shared resource:

* :class:`PeerScoreBoard` — a decaying per-peer misbehavior score fed from
  every place the node previously just disconnected or silently ignored
  (invalid signatures attributed per-lane out of the batched vote-drain
  bitmap, statesync ``reject_senders``, mempool CheckTx-reject floods,
  oversized/unparseable reactor messages, evil handshakes, rate-limit
  violations). Crossing ``disconnect_score`` disconnects; crossing
  ``ban_score`` bans for ``ban_duration_s``, doubling on each re-offense
  up to ``ban_max_duration_s``. Bans refuse both redials and inbound
  accepts (enforced by Switch/Transport).
* :class:`ChannelRateLimiter` — per-peer per-channel token buckets
  (votes/s, txs/s, chunks/s) enforced in MConnection's recv routine;
  over-limit deliveries are scored, not processed.
* :class:`ShedQueue` — a bounded queue that sheds by priority instead of
  blocking producers: votes for the live height survive, stale-height
  gossip drops first. Gossip threads never block on a saturated consumer.

Scores, bans, sheds, and rate-limit hits surface as ``peer_score``,
``peers_banned_total``, ``shed_total{channel}``, and
``rate_limited_total{peer,channel}`` via the node metrics sampler, and as
the ``unsafe_peers`` RPC view.
"""

from __future__ import annotations

import queue as _queue
import threading
import time
from collections import deque
from dataclasses import dataclass

# Offense -> points (docs/OVERLOAD.md scoring table). Points are sized
# against the default thresholds AND against honest steady-state rates:
# with half-life H the equilibrium score of a sustained offense stream is
# points * rate * H/ln2 (~173*points*rate at the default H=120), so an
# offense an HONEST peer can emit continuously during OUR overload (full
# mempool, app-rejected gossip) must carry points small enough that
# honest rates (~10/s) stay under the disconnect threshold while flood
# rates (100s/s) still cross ban in seconds. Protocol violations honest
# peers never emit (invalid signatures, bad framing, evil handshakes)
# carry full-size points: one evil handshake is half a ban, ~13
# invalid-signature votes inside one half-life is a ban.
OFFENSE_POINTS: dict[str, float] = {
    "invalid_signature": 8.0,   # vote-drain bitmap lane / serial VoteError
    "bad_message": 10.0,        # unparseable / unknown channel / reactor raise
    "oversized_message": 10.0,  # recv_message_capacity exceeded
    "evil_handshake": 50.0,     # claimed id != authenticated key
    "statesync_reject": 30.0,   # app reject_senders verdict on a chunk
    "evidence_reject": 6.0,     # gossiped evidence the pool refused to
                                # verify (bogus sigs / wrong chain-id /
                                # expired / contradicting metadata) —
                                # honest peers verified before pooling, so
                                # sustained rejects are a protocol
                                # violation (evidence/reactor.py)
    "checktx_reject": 0.02,     # gossiped tx the app rejected (honest-rate safe)
    "mempool_full": 0.02,       # gossiping into a full mempool (ours, usually)
    "tx_too_large": 8.0,        # gossiped tx over max_tx_bytes
    "rate_limited": 4.0,        # per-channel message ceiling exceeded
}

# Fully-decayed score entries below this are pruned; offense/rate-limit
# books are capped so an attacker minting fresh identities (ed25519 keys
# are free) cannot grow the anti-DoS layer's own memory without bound.
SCORE_PRUNE_EPSILON = 0.01
MAX_TRACKED = 4096

SANCTION_NONE = "none"
SANCTION_DISCONNECT = "disconnect"
SANCTION_BAN = "ban"


@dataclass
class ScoreConfig:
    """Thresholds + decay for one node's scoreboard (config/config.py
    P2PConfig peer_* fields)."""

    halflife_s: float = 120.0         # score decay half-life
    disconnect_score: float = 50.0    # crossing => disconnect sanction
    ban_score: float = 100.0          # crossing => timed ban
    ban_duration_s: float = 30.0      # first ban
    ban_max_duration_s: float = 600.0  # cap for the re-offense backoff

    @staticmethod
    def from_p2p_config(p2p) -> "ScoreConfig":
        return ScoreConfig(
            halflife_s=p2p.peer_score_halflife_s,
            disconnect_score=p2p.peer_disconnect_score,
            ban_score=p2p.peer_ban_score,
            ban_duration_s=p2p.peer_ban_duration_s,
            ban_max_duration_s=p2p.peer_ban_max_duration_s,
        )


class PeerScoreBoard:
    """Per-peer decaying misbehavior scores with escalating sanctions.

    Thread-safe; one instance per Switch (in-process mesh nodes must not
    share one — each node sanctions independently). ``clock`` is
    injectable so ban-lifecycle tests drive simulated time.
    """

    def __init__(self, config: ScoreConfig | None = None,
                 clock=time.monotonic, logger=None):
        self.config = config if config is not None else ScoreConfig()
        self._clock = clock
        self.logger = logger
        self._mtx = threading.Lock()
        self._scores: dict[str, tuple[float, float]] = {}  # id -> (score, t)
        self._bans: dict[str, float] = {}                  # id -> ban_until
        self._ban_counts: dict[str, int] = {}              # lifetime re-offenses
        self._offenses: dict[tuple[str, str], int] = {}    # (id, offense) -> n
        self.bans_total = 0
        self.shed: dict[str, int] = {}                    # channel -> shed msgs
        self.rate_limited: dict[tuple[str, str], int] = {}  # (id, ch) -> n
        # sanction listeners, called OUTSIDE the lock:
        self.on_ban: list = []         # callbacks(peer_id, until_s)
        self.on_disconnect: list = []  # callbacks(peer_id, reason)

    # --- scoring -----------------------------------------------------------

    def _decayed_locked(self, peer_id: str, now: float) -> float:
        entry = self._scores.get(peer_id)
        if entry is None:
            return 0.0
        score, last = entry
        hl = self.config.halflife_s
        if hl > 0 and now > last:
            score *= 0.5 ** ((now - last) / hl)
        return score

    def record(self, peer_id: str, offense: str,
               points: float | None = None) -> str:
        """Score one offense; returns the sanction applied (``none``,
        ``disconnect``, or ``ban``). Unattributed reports (empty peer id)
        are dropped — a message we cannot attribute must not sanction
        anyone. Sanction callbacks fire outside the board lock."""
        if not peer_id:
            return SANCTION_NONE
        pts = points if points is not None else OFFENSE_POINTS.get(offense, 1.0)
        now = self._clock()
        sanction = SANCTION_NONE
        until = 0.0
        with self._mtx:
            key = (peer_id, offense)
            if key not in self._offenses and len(self._offenses) >= MAX_TRACKED:
                self._offenses.pop(next(iter(self._offenses)))
            self._offenses[key] = self._offenses.get(key, 0) + 1
            prev = self._decayed_locked(peer_id, now)
            score = prev + pts
            cfg = self.config
            if cfg.ban_score > 0 and score >= cfg.ban_score:
                until = self._install_ban_locked(peer_id, now, None)
                self._scores.pop(peer_id, None)
                sanction = SANCTION_BAN
            else:
                if (peer_id not in self._scores
                        and len(self._scores) >= MAX_TRACKED):
                    self._prune_scores_locked(now)
                self._scores[peer_id] = (score, now)
                if cfg.disconnect_score > 0 and score >= cfg.disconnect_score:
                    # EVERY offense at/above the threshold disconnects: a
                    # redialing peer pacing its score inside
                    # [disconnect, ban) must not misbehave sanction-free
                    sanction = SANCTION_DISCONNECT
        if sanction == SANCTION_BAN:
            if self.logger is not None:
                self.logger.info("peer banned", peer=peer_id[:12],
                                 offense=offense, until=until)
            for cb in list(self.on_ban):
                try:
                    cb(peer_id, until)
                except Exception:  # noqa: BLE001 - a listener must not block
                    pass
        elif sanction == SANCTION_DISCONNECT:
            for cb in list(self.on_disconnect):
                try:
                    cb(peer_id, f"misbehavior score threshold ({offense})")
                except Exception:  # noqa: BLE001
                    pass
        return sanction

    def _install_ban_locked(self, peer_id: str, now: float,
                            duration_s: float | None) -> float:
        """One escalation schedule for scored AND manual bans: first ban
        lasts ban_duration_s, doubling per prior offense up to the cap.
        The ban books are bounded too — an identity-minting attacker
        earning throwaway bans must not grow them forever (expired
        entries evict first; the re-offense history of the evicted
        oldest identities is the price of boundedness)."""
        n = self._ban_counts.get(peer_id, 0)
        dur = duration_s if duration_s is not None else min(
            self.config.ban_duration_s * (2.0 ** min(n, 16)),
            self.config.ban_max_duration_s)
        if peer_id not in self._bans and len(self._bans) >= MAX_TRACKED:
            # evict expired entries first; with none expired, evict the
            # most recently INSTALLED ban — under identity-minting
            # pressure (the only way the book fills) that is the
            # attacker's own previous throwaway identity, so minting can
            # never lift an older genuine offender's live ban early
            expired = [p for p, t in self._bans.items() if t <= now]
            victim = expired[0] if expired else next(reversed(self._bans))
            del self._bans[victim]
        if (peer_id not in self._ban_counts
                and len(self._ban_counts) >= MAX_TRACKED):
            self._ban_counts.pop(next(iter(self._ban_counts)))
        until = now + dur
        self._bans[peer_id] = until
        self._ban_counts[peer_id] = n + 1
        self.bans_total += 1
        return until

    def _prune_scores_locked(self, now: float) -> None:
        """Drop fully-decayed entries (and, under identity-minting
        pressure, the lowest scores past the cap): the anti-DoS layer
        must not itself grow without bound."""
        for pid in [p for p in self._scores
                    if self._decayed_locked(p, now) < SCORE_PRUNE_EPSILON]:
            del self._scores[pid]
        while len(self._scores) >= MAX_TRACKED:
            lowest = min(self._scores,
                         key=lambda p: self._decayed_locked(p, now))
            del self._scores[lowest]

    def score(self, peer_id: str) -> float:
        with self._mtx:
            return self._decayed_locked(peer_id, self._clock())

    # --- bans --------------------------------------------------------------

    def is_banned(self, peer_id: str) -> bool:
        """True while a ban is in force; expired bans are removed lazily
        (the re-offense count stays, so the NEXT ban backs off)."""
        if not peer_id:
            return False
        now = self._clock()
        with self._mtx:
            until = self._bans.get(peer_id)
            if until is None:
                return False
            if now >= until:
                del self._bans[peer_id]
                return False
            return True

    def ban(self, peer_id: str, duration_s: float | None = None) -> float:
        """Manually ban (operator action / tests); returns ban_until."""
        now = self._clock()
        with self._mtx:
            until = self._install_ban_locked(peer_id, now, duration_s)
        for cb in list(self.on_ban):
            try:
                cb(peer_id, until)
            except Exception:  # noqa: BLE001
                pass
        return until

    def unban(self, peer_id: str) -> None:
        with self._mtx:
            self._bans.pop(peer_id, None)

    # --- overload counters (fed by shed queues / rate limiters) ------------

    def count_shed(self, channel: str, n: int = 1) -> None:
        with self._mtx:
            self.shed[channel] = self.shed.get(channel, 0) + n

    def count_rate_limited(self, peer_id: str, channel: str) -> None:
        with self._mtx:
            key = (peer_id, channel)
            if key not in self.rate_limited and len(self.rate_limited) >= MAX_TRACKED:
                self.rate_limited.pop(next(iter(self.rate_limited)))
            self.rate_limited[key] = self.rate_limited.get(key, 0) + 1

    # --- observability ------------------------------------------------------

    def snapshot(self) -> dict:
        """Monotonic counters + live gauges for the node metrics sampler
        (pumped as deltas, like the faults/nemesis planes)."""
        now = self._clock()
        with self._mtx:
            self._prune_scores_locked(now)
            return {
                "scores": {p: self._decayed_locked(p, now)
                           for p in self._scores},
                "bans_total": self.bans_total,
                "shed": dict(self.shed),
                "rate_limited": dict(self.rate_limited),
            }

    def describe(self) -> dict:
        """JSON-friendly state for the unsafe_peers RPC."""
        now = self._clock()
        with self._mtx:
            return {
                "scores": {p: round(self._decayed_locked(p, now), 3)
                           for p in self._scores},
                "banned": {p: round(until - now, 3)
                           for p, until in self._bans.items() if until > now},
                "ban_counts": dict(self._ban_counts),
                "bans_total": self.bans_total,
                "offenses": {f"{p}:{o}": n
                             for (p, o), n in self._offenses.items()},
                "shed": dict(self.shed),
                "rate_limited": {f"{p}:{ch}": n
                                 for (p, ch), n in self.rate_limited.items()},
                "config": {
                    "halflife_s": self.config.halflife_s,
                    "disconnect_score": self.config.disconnect_score,
                    "ban_score": self.config.ban_score,
                    "ban_duration_s": self.config.ban_duration_s,
                    "ban_max_duration_s": self.config.ban_max_duration_s,
                },
            }


# ---------------------------------------------------------------------------
# Per-channel inbound message ceilings
# ---------------------------------------------------------------------------


def parse_rate_spec(spec: str) -> dict[int, float]:
    """``"0x22:500,0x30:1000"`` -> {0x22: 500.0, 0x30: 1000.0} (channel id
    in any int base, msgs/s; rate <= 0 rejected — an accidental zero would
    silently blackhole a channel)."""
    out: dict[int, float] = {}
    for stmt in spec.split(","):
        stmt = stmt.strip()
        if not stmt:
            continue
        ch, sep, rate = stmt.partition(":")
        if not sep:
            raise ValueError(f"bad rate spec {stmt!r} (want ch:msgs_per_s)")
        r = float(rate)
        if r <= 0:
            raise ValueError(f"bad rate spec {stmt!r} (rate must be > 0)")
        out[int(ch, 0)] = r
    return out


class ChannelRateLimiter:
    """Token bucket per channel id: ``rate`` msgs/s sustained with a
    one-second burst. Channels with no configured rate are unlimited.
    One instance per MConnection, so the ceilings are per-peer."""

    def __init__(self, rates: dict[int, float], clock=time.monotonic):
        self._clock = clock
        self._mtx = threading.Lock()
        # ch -> [rate, burst_cap, tokens, last_refill]; the cap is at
        # least one whole message so fractional rates (e.g. 0.5 chunks/s)
        # accumulate to a deliverable token instead of silently
        # blackholing the channel forever
        self._buckets = {ch: [float(r), max(float(r), 1.0),
                              max(float(r), 1.0), clock()]
                         for ch, r in rates.items() if r > 0}

    def allow(self, ch_id: int) -> bool:
        b = self._buckets.get(ch_id)
        if b is None:
            return True
        now = self._clock()
        with self._mtx:
            rate, cap, tokens, last = b
            tokens = min(cap, tokens + rate * max(0.0, now - last))
            if tokens >= 1.0:
                b[2] = tokens - 1.0
                b[3] = now
                return True
            b[2] = tokens
            b[3] = now
            return False


# ---------------------------------------------------------------------------
# Priority load shedding
# ---------------------------------------------------------------------------

# Gossip message classes, lowest shed-priority first. Control items
# (priority=None) bypass shedding entirely: stop sentinels and sync
# barriers must never be lost to an overload.
PRIO_STALE = 0   # gossip for heights already committed (re-derivable)
PRIO_FUTURE = 1  # gossip ahead of the live height
PRIO_LIVE = 2    # votes/proposal/parts for the height being decided


class ShedQueue:
    """Bounded FIFO that sheds by priority instead of blocking producers
    (the SEDA admission discipline): when full, the oldest entry of the
    lowest priority class is evicted to admit a higher-priority arrival;
    an arrival no more important than everything queued is shed itself.
    FIFO order is preserved for everything admitted, so consumers see
    exactly the arrival-order semantics of queue.Queue minus dropped
    gossip — indistinguishable from p2p message loss, which gossip
    re-delivery already tolerates.

    API-compatible with the queue.Queue surface the consensus receive
    routine uses (put/get/get_nowait/empty, queue.Empty raised).
    """

    def __init__(self, maxsize: int = 0, on_shed=None):
        self.maxsize = maxsize
        self._dq: deque = deque()  # (priority | None, channel, item)
        self._mtx = threading.Lock()
        self._not_empty = threading.Condition(self._mtx)
        # per-priority population: put() decides evict-vs-shed in O(1)
        # in the common full-of-equal-priority flood case; the O(n)
        # victim scan runs only when an eviction will actually succeed
        self._prio_counts: dict[int, int] = {}
        # sheds by (priority class of what was shed, channel)
        self.shed_by_class: dict[tuple[int, str], int] = {}
        self._on_shed = on_shed  # callback(channel) after the lock drops

    @property
    def shed_counts(self) -> dict[str, int]:
        """Sheds by channel, whatever the class."""
        out: dict[str, int] = {}
        with self._mtx:
            for (_prio, channel), n in self.shed_by_class.items():
                out[channel] = out.get(channel, 0) + n
        return out

    def put(self, item, priority: int | None = None,
            channel: str = "ctrl", block: bool = True,
            timeout=None) -> bool:
        """Admit ``item``; returns False when it was shed. ``priority``
        None marks a control item that is always admitted (the queue may
        exceed maxsize by the handful of in-flight sentinels). Never
        blocks regardless of ``block`` — that is the point."""
        shed_channel = None
        admitted = True
        with self._mtx:
            if (priority is not None and self.maxsize > 0
                    and len(self._dq) >= self.maxsize):
                if not any(n > 0 for p, n in self._prio_counts.items()
                           if p < priority):
                    # nothing strictly lower queued: shed the arrival
                    # (O(1) — the common case when a flood has filled the
                    # queue with its own priority class)
                    shed_channel = channel
                    shed_prio = priority
                    admitted = False
                else:
                    # evict the oldest entry of the lowest class present
                    victim_i = None
                    victim_prio = priority
                    for i, (p, _ch, _it) in enumerate(self._dq):
                        if p is not None and p < victim_prio:
                            victim_i = i
                            victim_prio = p
                            if p == PRIO_STALE:
                                break  # nothing sheds earlier than stale
                    shed_prio, shed_channel, _vi = self._dq[victim_i]
                    del self._dq[victim_i]
                    self._prio_counts[shed_prio] -= 1
                by_class = (shed_prio, shed_channel)
                self.shed_by_class[by_class] = \
                    self.shed_by_class.get(by_class, 0) + 1
            if admitted:
                self._dq.append((priority, channel, item))
                if priority is not None:
                    self._prio_counts[priority] = \
                        self._prio_counts.get(priority, 0) + 1
                self._not_empty.notify()
        if shed_channel is not None and self._on_shed is not None:
            try:
                self._on_shed(shed_channel)
            except Exception:  # noqa: BLE001 - metrics must not break the path
                pass
        return admitted

    def get(self, block: bool = True, timeout=None):
        with self._not_empty:
            if not block:
                if not self._dq:
                    raise _queue.Empty
            elif timeout is None:
                while not self._dq:
                    self._not_empty.wait()
            else:
                deadline = time.monotonic() + timeout
                while not self._dq:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise _queue.Empty
                    self._not_empty.wait(remaining)
            prio, _ch, item = self._dq.popleft()
            if prio is not None:
                self._prio_counts[prio] -= 1
            return item

    def get_nowait(self):
        return self.get(block=False)

    def empty(self) -> bool:
        with self._mtx:
            return not self._dq

    def qsize(self) -> int:
        with self._mtx:
            return len(self._dq)
