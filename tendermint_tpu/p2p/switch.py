"""Peer, Transport, Switch, Reactor: the p2p service layer (reference:
p2p/switch.go, p2p/transport.go, p2p/peer.go, p2p/base_reactor.go:15-54).

Transport: TCP listen/dial -> SecretConnection -> NodeInfo handshake.
Peer: one MConnection; reactors receive (ch_id, peer, msg_bytes).
Switch: reactor registry, peer lifecycle, broadcast, dial/accept loops,
reconnect-to-persistent-peers.
"""

from __future__ import annotations

import random
import socket
import threading
import time

from typing import TYPE_CHECKING

from tendermint_tpu.encoding import proto
from tendermint_tpu.utils import faults, peerscore
from tendermint_tpu.p2p.connection import (
    ChannelDescriptor,
    MConnection,
    MConnectionProtocolError,
)
from tendermint_tpu.p2p.key import NodeKey
from tendermint_tpu.p2p.node_info import NodeInfo

if TYPE_CHECKING:
    from tendermint_tpu.p2p.secret_connection import SecretConnection


class P2PError(Exception):
    pass


class Reactor:
    """reference: p2p/base_reactor.go:15-54."""

    def __init__(self, name: str):
        self.name = name
        self.switch: "Switch | None" = None

    def get_channels(self) -> list[ChannelDescriptor]:
        return []

    def add_peer(self, peer: "Peer") -> None:
        pass

    def remove_peer(self, peer: "Peer", reason) -> None:
        pass

    def receive(self, ch_id: int, peer: "Peer", msg_bytes: bytes) -> None:
        pass

    def on_start(self) -> None:
        pass

    def on_stop(self) -> None:
        pass


class Peer:
    """reference: p2p/peer.go:23."""

    def __init__(self, conn: SecretConnection, node_info: NodeInfo,
                 channels: list[ChannelDescriptor], on_receive, on_error,
                 outbound: bool, persistent: bool = False,
                 socket_addr: str = "", send_rate: int = 5_120_000,
                 recv_rate: int = 5_120_000, local_id: str = "",
                 msg_rates: dict[int, float] | None = None,
                 on_rate_limited=None, tracer=None):
        self.node_info = node_info
        self.outbound = outbound
        self.persistent = persistent
        self.socket_addr = socket_addr
        self._data: dict = {}
        self.mconn = MConnection(
            conn, channels,
            on_receive=lambda ch, msg: on_receive(ch, self, msg),
            on_error=lambda err: on_error(self, err),
            send_rate=send_rate, recv_rate=recv_rate,
            local_id=local_id, remote_id=node_info.node_id,
            msg_rates=msg_rates,
            on_rate_limited=(lambda ch: on_rate_limited(self, ch))
            if on_rate_limited is not None else None,
            tracer=tracer,
        )

    @property
    def id(self) -> str:
        return self.node_info.node_id

    def start(self) -> None:
        self.mconn.start()

    def stop(self) -> None:
        self.mconn.stop()

    def send(self, ch_id: int, msg: bytes) -> bool:
        return self.mconn.send(ch_id, msg)

    def try_send(self, ch_id: int, msg: bytes) -> bool:
        return self.mconn.try_send(ch_id, msg)

    def set(self, key: str, value) -> None:
        self._data[key] = value

    def get(self, key: str):
        return self._data.get(key)

    def __repr__(self) -> str:
        return f"Peer{{{self.id[:12]} {'out' if self.outbound else 'in'}}}"


class Transport:
    """MultiplexTransport equivalent (reference: p2p/transport.go)."""

    def __init__(self, node_key: NodeKey, node_info: NodeInfo,
                 handshake_timeout_s: float = 20.0, dial_timeout_s: float = 3.0):
        self.node_key = node_key
        self.node_info = node_info
        self.handshake_timeout_s = handshake_timeout_s
        self.dial_timeout_s = dial_timeout_s
        self._listener: socket.socket | None = None
        # overload-resilience hooks (set by the owning Switch): a banned
        # peer is refused right after the handshake identifies it, on the
        # accept AND dial sides alike; an evil handshake (claimed id not
        # matching the authenticated key) is scored before the teardown
        self.ban_checker = None        # fn(node_id) -> bool
        self.on_evil_handshake = None  # fn(authenticated_node_id)

    def listen(self, addr: str) -> str:
        host, port = _split_addr(addr)
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind((host, port))
        s.listen(64)
        self._listener = s
        actual = s.getsockname()
        self.node_info.listen_addr = f"tcp://{actual[0]}:{actual[1]}"
        return self.node_info.listen_addr

    def accept(self) -> tuple[SecretConnection, NodeInfo, str]:
        if self._listener is None:
            raise P2PError("transport not listening")
        raw, addr = self._listener.accept()
        return self._upgrade(raw, f"{addr[0]}:{addr[1]}")

    def dial(self, addr: str) -> tuple[SecretConnection, NodeInfo, str]:
        # peer-id context: an "id@host:port" addr names the remote, so a
        # nemesis partition can refuse dials across the cut
        faults.fire("p2p.dial", local=self.node_info.node_id,
                    remote=addr.split("@", 1)[0] if "@" in addr else "")
        host, port = _split_addr(addr)
        raw = socket.create_connection((host, port), timeout=self.dial_timeout_s)
        return self._upgrade(raw, f"{host}:{port}")

    def _upgrade(self, raw: socket.socket, addr: str):
        # Deferred: SecretConnection needs the optional `cryptography`
        # package; the switch (backoff logic, registry) must import without
        # it so hosts lacking the dep can still run non-p2p subsystems.
        from tendermint_tpu.p2p.secret_connection import SecretConnection

        raw.settimeout(self.handshake_timeout_s)
        conn = SecretConnection(raw, self.node_key.priv_key)
        # NodeInfo exchange (reference: transport.go handshake)
        conn.write(proto.delimited(self.node_info.marshal()))
        buf = conn.read_msg()
        while True:
            try:
                body, _ = proto.parse_delimited(buf)
                break
            except ValueError:
                buf += conn.read_msg()
        peer_info = NodeInfo.unmarshal(body)
        peer_info.validate_basic()
        # The authenticated ed25519 key must match the claimed node ID.
        derived = conn.remote_pub_key.address().hex()
        if derived != peer_info.node_id:
            if self.on_evil_handshake is not None:
                # score the AUTHENTICATED identity: the claimed one is
                # whatever the liar chose to type
                self.on_evil_handshake(derived)
            raise P2PError(
                f"peer ID mismatch: claimed {peer_info.node_id}, authenticated {derived}"
            )
        if self.ban_checker is not None and self.ban_checker(peer_info.node_id):
            raise P2PError(f"peer {peer_info.node_id[:12]} is banned")
        raw.settimeout(None)
        return conn, peer_info, addr

    def close(self) -> None:
        if self._listener is not None:
            # shutdown wakes a thread blocked in accept(); close() alone
            # leaves it there, and the port open, until one more peer dials
            try:
                self._listener.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                self._listener.close()
            except OSError:
                pass


# Persistent-peer redial backoff (reference: p2p/switch.go:768
# reconnectToPeer): first retry fast, then exponential with jitter so a
# fleet of nodes redialing one restarting peer never synchronizes into a
# dial storm. Capped low enough that a peer coming back is found quickly.
RECONNECT_BASE_S = 0.5
RECONNECT_MAX_S = 10.0
RECONNECT_JITTER = 0.2


def reconnect_backoff_s(attempt: int, rng=random) -> float:
    """Delay before redial number ``attempt`` (0-based: the delay AFTER the
    attempt-th consecutive failure), exponentially grown and jittered.
    The exponent is clamped BEFORE exponentiation: 2.0**1024 overflows a
    float, and a peer down for hours must not kill the reconnect thread."""
    base = min(RECONNECT_BASE_S * (2.0 ** min(attempt, 16)), RECONNECT_MAX_S)
    return base * (1.0 + RECONNECT_JITTER * rng.random())


class Switch:
    """reference: p2p/switch.go:65."""

    def __init__(self, transport: Transport, logger=None,
                 max_inbound: int = 40, max_outbound: int = 10,
                 send_rate: int = 5_120_000, recv_rate: int = 5_120_000,
                 scoreboard: peerscore.PeerScoreBoard | None = None,
                 msg_rates: dict[int, float] | None = None):
        self.send_rate = send_rate
        self.recv_rate = recv_rate
        self.transport = transport
        # Overload-resilience plane (docs/OVERLOAD.md): one scoreboard per
        # switch — in-process mesh nodes must sanction independently. The
        # board decides sanctions; this switch enforces them (disconnect,
        # ban = teardown + dial/accept refusal until expiry).
        self.scoreboard = (scoreboard if scoreboard is not None
                           else peerscore.PeerScoreBoard(logger=logger))
        self.scoreboard.on_ban.append(self._on_peer_banned)
        self.scoreboard.on_disconnect.append(self._on_peer_sanctioned)
        self.msg_rates = dict(msg_rates) if msg_rates else {}
        transport.ban_checker = self.scoreboard.is_banned
        transport.on_evil_handshake = (
            lambda nid: self.scoreboard.record(nid, "evil_handshake"))
        self.reactors: dict[str, Reactor] = {}
        self._channels: list[ChannelDescriptor] = []
        self._reactors_by_ch: dict[int, Reactor] = {}
        self.peers: dict[str, Peer] = {}
        self._peers_mtx = threading.RLock()
        self._running = False
        self._stopped = False   # stop() was called: no peer is added after it
        self.logger = logger
        self.max_inbound = max_inbound
        self.max_outbound = max_outbound
        self._persistent_addrs: list[str] = []
        self._accept_thread: threading.Thread | None = None
        self._reconnect_thread: threading.Thread | None = None
        # flight recorder (utils/trace.py): node wiring installs the node's
        # tracer BEFORE start(); every peer connection built afterwards
        # records its per-channel send/recv events there
        self.tracer = None
        # Redial backoff state, instance-level so kick_reconnect() can wipe
        # it (a nemesis heal must not wait out the clamped max backoff
        # accumulated while the partition blocked every dial).
        self._reconnect_attempts: dict[str, int] = {}
        self._reconnect_next_try: dict[str, float] = {}
        # MConnection.wire_counters() of the peers that are gone, summed:
        # wire_totals() never runs backwards when a peer is stopped
        self._wire_gone: dict = {}

    # --- registry ----------------------------------------------------------

    def add_reactor(self, name: str, reactor: Reactor) -> Reactor:
        for d in reactor.get_channels():
            if d.id in self._reactors_by_ch:
                raise P2PError(f"channel {d.id:#x} already registered")
            self._channels.append(d)
            self._reactors_by_ch[d.id] = reactor
        self.reactors[name] = reactor
        reactor.switch = self
        self.transport.node_info.channels = bytes(sorted(self._reactors_by_ch))
        return reactor

    # --- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        self._running = True
        for r in self.reactors.values():
            r.on_start()
        if self.transport._listener is not None:
            self._accept_thread = threading.Thread(target=self._accept_loop, daemon=True)
            self._accept_thread.start()
        self._reconnect_thread = threading.Thread(target=self._reconnect_loop, daemon=True)
        self._reconnect_thread.start()
        # A healed partition should reconnect promptly, not after the max
        # backoff the cut accumulated (lazy import: nemesis is pure stdlib,
        # but keep the switch importable standalone all the same).
        from tendermint_tpu.utils import nemesis

        nemesis.PLANE.on_heal.append(self.kick_reconnect)

    def stop(self) -> None:
        self._running = False
        from tendermint_tpu.utils import nemesis

        try:
            nemesis.PLANE.on_heal.remove(self.kick_reconnect)
        except ValueError:
            pass
        for r in self.reactors.values():
            r.on_stop()
        with self._peers_mtx:
            self._stopped = True
            peers = list(self.peers.values())
        for p in peers:
            self.stop_peer_for_error(p, "switch stopping")
        self.transport.close()

    # --- dialing / accepting -----------------------------------------------

    def dial_peer(self, addr: str, persistent: bool = False) -> Peer | None:
        node_id = addr.split("@", 1)[0] if "@" in addr else ""
        if node_id and self.scoreboard.is_banned(node_id):
            # refuse BEFORE the socket opens: a banned peer's redial must
            # cost us nothing (the transport-side ban_checker still covers
            # addresses dialed without an id prefix)
            if self.logger:
                self.logger.info("refusing dial to banned peer", addr=addr)
            return None
        try:
            conn, peer_info, sock_addr = self.transport.dial(addr)
            return self._add_peer(conn, peer_info, outbound=True,
                                  persistent=persistent, socket_addr=addr)
        except Exception as e:  # noqa: BLE001
            if self.logger:
                self.logger.info("dial failed", addr=addr, err=e)
            return None

    def add_persistent_peers(self, addrs: list[str]) -> None:
        self._persistent_addrs.extend(a for a in addrs if a)

    def _accept_loop(self) -> None:
        while self._running:
            try:
                conn, peer_info, sock_addr = self.transport.accept()
            except Exception:  # noqa: BLE001
                if not self._running:
                    return
                continue
            n_in = sum(1 for p in self.peers.values() if not p.outbound)
            if n_in >= self.max_inbound:
                conn.close()
                continue
            try:
                self._add_peer(conn, peer_info, outbound=False, socket_addr=sock_addr)
            except Exception:  # noqa: BLE001
                conn.close()

    def kick_reconnect(self) -> None:
        """Forget all redial backoff state so every missing persistent peer
        is retried on the next pass (≤0.25 s). Called on nemesis heal: a
        peer redialed throughout a long partition sits at the clamped max
        backoff, and a healed link must not wait that out."""
        self._reconnect_attempts.clear()
        self._reconnect_next_try.clear()

    def _reconnect_loop(self) -> None:
        """Redial missing persistent peers with exponential backoff +
        jitter; a successful dial (or the peer appearing inbound) resets
        that address's schedule."""
        while self._running:
            try:
                if self._persistent_addrs:
                    self._reconnect_pass(self._reconnect_attempts,
                                         self._reconnect_next_try)
            except Exception as e:  # noqa: BLE001 - the redial thread must
                # survive anything; losing it silently strands every
                # persistent peer for the rest of the process lifetime
                if self.logger:
                    self.logger.error("reconnect pass failed", err=e)
            # nothing to redial -> idle slowly: 50+ in-process switches
            # (the scenario fabric) each waking 4x/s add up on one core
            time.sleep(0.25 if self._persistent_addrs else 1.0)

    def _reconnect_pass(self, attempts: dict[str, int],
                        next_try: dict[str, float]) -> None:
        now = time.monotonic()
        for addr in list(self._persistent_addrs):
            node_id = addr.split("@")[0] if "@" in addr else None
            if node_id and self.scoreboard.is_banned(node_id):
                # don't burn backoff schedule on a banned persistent peer;
                # when the ban expires the address is retried immediately
                attempts.pop(addr, None)
                next_try.pop(addr, None)
                continue
            have = node_id in self.peers if node_id else any(
                p.socket_addr.endswith(addr) for p in self.peers.values()
            )
            if have:
                attempts.pop(addr, None)
                next_try.pop(addr, None)
                continue
            if now < next_try.get(addr, 0.0):
                continue
            if self.dial_peer(addr, persistent=True) is not None:
                # reset the attempt counter on success: the NEXT outage of
                # this link starts its backoff from scratch instead of
                # inheriting the clamped max from the previous one
                attempts.pop(addr, None)
                next_try.pop(addr, None)
            else:
                k = attempts.get(addr, 0)
                attempts[addr] = k + 1
                next_try[addr] = time.monotonic() + reconnect_backoff_s(k)

    def _add_peer(self, conn, peer_info: NodeInfo, outbound: bool,
                  persistent: bool = False, socket_addr: str = "") -> Peer:
        self.transport.node_info.compatible_with(peer_info)
        if peer_info.node_id == self.transport.node_info.node_id:
            conn.close()
            raise P2PError("connected to self")
        if self.scoreboard.is_banned(peer_info.node_id):
            # inbound rejection + the in-process mesh seam: however the
            # connection reached us (accept loop, test socketpair), a
            # banned identity never becomes a Peer
            conn.close()
            raise P2PError(f"peer {peer_info.node_id[:12]} is banned")
        with self._peers_mtx:
            if self._stopped:
                # a dial or an accept that was in flight when stop() ran
                conn.close()
                raise P2PError("switch stopped")
            if peer_info.node_id in self.peers:
                conn.close()
                raise P2PError("duplicate peer")
            peer = Peer(conn, peer_info, self._channels, self._on_receive,
                        self._on_peer_error, outbound, persistent, socket_addr,
                        send_rate=self.send_rate, recv_rate=self.recv_rate,
                        local_id=self.transport.node_info.node_id,
                        msg_rates=self.msg_rates,
                        on_rate_limited=self._on_rate_limited,
                        tracer=self.tracer)
            self.peers[peer.id] = peer
        # Reactors attach their per-peer state (and queue their hello
        # messages) BEFORE the connection starts reading: bytes the remote
        # already sent — its status, its NewRoundStep — must not reach a
        # reactor whose add_peer hasn't run yet, or a peer that never
        # re-announces (parked at a height) stays invisible forever
        # (reference: the InitPeer/AddPeer split of p2p/switch.go:840).
        for r in self.reactors.values():
            r.add_peer(peer)
        peer.start()
        return peer

    # --- peer events -------------------------------------------------------

    def _on_receive(self, ch_id: int, peer: Peer, msg_bytes: bytes) -> None:
        if self.scoreboard.is_banned(peer.id):
            # post-ban traffic never reaches a reactor (the drain must not
            # process a banned peer's in-flight backlog); tear down in case
            # the ban callback raced the delivery
            self.stop_peer_for_error(peer, "peer is banned")
            return
        reactor = self._reactors_by_ch.get(ch_id)
        if reactor is None:
            self.scoreboard.record(peer.id, "bad_message")
            self.stop_peer_for_error(peer, f"unknown channel {ch_id:#x}")
            return
        try:
            reactor.receive(ch_id, peer, msg_bytes)
        except Exception as e:  # noqa: BLE001
            # Codec-shaped failures (ValueError from proto parsing /
            # unmarshal validation) are the PEER's malformed payload:
            # score them so a redial-and-repeat loop escalates to a ban
            # instead of free disconnect cycles. Anything else —
            # KeyError/IndexError included, the classic shapes of a
            # node-local reactor bug on valid input — tears the peer
            # down (the pre-existing contract) without scoring: our own
            # bug must not progressively ban the honest peer set.
            if isinstance(e, ValueError):
                self.scoreboard.record(peer.id, "bad_message")
            self.stop_peer_for_error(peer, e)

    def _on_peer_error(self, peer: Peer, err) -> None:
        if isinstance(err, MConnectionProtocolError):
            # framing/capacity violations (oversized message, bad varint,
            # unknown mconnection channel) are the peer's doing; a plain
            # MConnectionError (socket EOF) is just the network — scoring
            # it would ban honest peers across partition/reconnect churn
            self.scoreboard.record(peer.id, "oversized_message")
        self.stop_peer_for_error(peer, err)

    def _on_rate_limited(self, peer: Peer, ch_id: int) -> None:
        """An over-limit delivery was discarded by the connection's token
        bucket: count + score it (enough of these escalate to a ban)."""
        self.scoreboard.count_rate_limited(peer.id, f"{ch_id:#x}")
        self.scoreboard.record(peer.id, "rate_limited")

    def _on_peer_banned(self, peer_id: str, until: float) -> None:
        self.stop_peer_by_id(peer_id, "banned for misbehavior")

    def _on_peer_sanctioned(self, peer_id: str, reason: str) -> None:
        self.stop_peer_by_id(peer_id, reason)

    def stop_peer_by_id(self, peer_id: str, reason) -> bool:
        """Public stop-by-id for behaviour reporters etc.; returns False when
        the peer is already gone."""
        with self._peers_mtx:
            peer = self.peers.get(peer_id)
        if peer is None:
            return False
        self.stop_peer_for_error(peer, reason)
        return True

    def stop_peer_for_error(self, peer: Peer, reason) -> None:
        """reference: p2p/switch.go StopPeerForError."""
        with self._peers_mtx:
            if self.peers.get(peer.id) is not peer:
                return
            del self.peers[peer.id]
            _sum_counters(self._wire_gone, peer.mconn.wire_counters())
        peer.stop()
        for r in self.reactors.values():
            try:
                r.remove_peer(peer, reason)
            except Exception:  # noqa: BLE001
                pass

    # --- broadcast ---------------------------------------------------------

    def broadcast(self, ch_id: int, msg: bytes) -> None:
        with self._peers_mtx:
            peers = list(self.peers.values())
        for p in peers:
            p.try_send(ch_id, msg)

    def wire_totals(self) -> dict:
        """MConnection.wire_counters() summed over every peer this switch
        has had, live or gone (the ``p2p.wire`` mark reads its deltas)."""
        with self._peers_mtx:
            out: dict = {}
            _sum_counters(out, self._wire_gone)
            for p in self.peers.values():
                _sum_counters(out, p.mconn.wire_counters())
        return out

    def num_peers(self) -> tuple[int, int]:
        with self._peers_mtx:
            out = sum(1 for p in self.peers.values() if p.outbound)
            return out, len(self.peers) - out


def _sum_counters(total: dict, one: dict) -> None:
    """total += one, key by key, a nested table likewise."""
    for key, n in one.items():
        if isinstance(n, dict):
            _sum_counters(total.setdefault(key, {}), n)
        else:
            total[key] = total.get(key, 0) + n


def counters_since(now: dict, last: dict) -> dict:
    """now - last, key by key, a nested table likewise."""
    return {k: (counters_since(v, last.get(k, {})) if isinstance(v, dict)
                else v - last.get(k, 0)) for k, v in now.items()}


def _split_addr(addr: str) -> tuple[str, int]:
    a = addr
    if "://" in a:
        a = a.split("://", 1)[1]
    if "@" in a:
        a = a.split("@", 1)[1]
    host, port = a.rsplit(":", 1)
    return host, int(port)
