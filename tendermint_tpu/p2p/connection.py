"""MConnection: multiplexes priority channels over one SecretConnection
(reference: p2p/conn/connection.go:78, proto/tendermint/p2p/conn.proto).

Wire format: varint-delimited Packet protos over the encrypted stream.
  Packet { oneof sum: PacketPing = 1 | PacketPong = 2 | PacketMsg = 3 }
  PacketMsg { channel_id = 1; eof = 2; data = 3 }
Messages larger than the packet payload size are split across PacketMsgs and
reassembled at eof. Channel scheduling is priority-weighted ratio picking
like the reference's sendRoutine (connection.go:320-420).
"""

from __future__ import annotations

import queue
import struct
import threading
import time
from dataclasses import dataclass, field

from tendermint_tpu.encoding import proto
from tendermint_tpu.utils import faults
from tendermint_tpu.utils.flowrate import Monitor

MAX_PACKET_MSG_PAYLOAD_SIZE = 1024
PING_INTERVAL_S = 20.0
PONG_TIMEOUT_S = 45.0
FLUSH_THROTTLE_S = 0.01
MAX_MSG_SIZE = 10 * 1024 * 1024
# reference: config SendRate/RecvRate default 5120000 B/s (connection.go:
# flow-controlled via libs/flowrate Monitor.Limit)
DEFAULT_SEND_RATE = 5_120_000
DEFAULT_RECV_RATE = 5_120_000


# a channel's row of MConnection.wire_counters()
WIRE_KEYS = ("packets_sent", "msgs_sent", "bytes_sent",
             "packets_recv", "msgs_recv", "bytes_recv")


class MConnectionError(Exception):
    pass


class MConnectionProtocolError(MConnectionError):
    """The PEER violated the wire protocol (oversized packet/message, bad
    framing, unknown channel) — scoreable misbehavior, unlike a plain
    MConnectionError (socket EOF/teardown), which is just the network."""


@dataclass
class ChannelDescriptor:
    """reference: p2p/conn/connection.go:560-600."""

    id: int
    priority: int = 1
    send_queue_capacity: int = 100
    recv_message_capacity: int = 22020096


class _Channel:
    def __init__(self, desc: ChannelDescriptor):
        self.desc = desc
        self.send_queue: queue.Queue = queue.Queue(maxsize=desc.send_queue_capacity)
        self.sending: bytes | None = None
        self.sent_pos = 0
        self.recently_sent = 0
        self.recving = bytearray()
        # what crossed the wire on this channel: [packets, messages, payload
        # bytes], each written by one routine only (plain integers, no clock)
        self.sent = [0, 0, 0]
        self.received = [0, 0, 0]

    def is_send_pending(self) -> bool:
        return self.sending is not None or not self.send_queue.empty()

    def next_packet(self) -> tuple[bytes, bool]:
        if self.sending is None:
            self.sending = self.send_queue.get_nowait()
            self.sent_pos = 0
        chunk = self.sending[self.sent_pos : self.sent_pos + MAX_PACKET_MSG_PAYLOAD_SIZE]
        self.sent_pos += len(chunk)
        eof = self.sent_pos >= len(self.sending)
        if eof:
            self.sending = None
            self.sent_pos = 0
        self.recently_sent += len(chunk)
        sent = self.sent
        sent[0] += 1
        sent[1] += eof
        sent[2] += len(chunk)
        return chunk, eof


class MConnection:
    """on_receive(ch_id, msg_bytes); on_error(err) when the conn dies."""

    def __init__(self, conn, channels: list[ChannelDescriptor], on_receive,
                 on_error=None, send_rate: int = DEFAULT_SEND_RATE,
                 recv_rate: int = DEFAULT_RECV_RATE,
                 local_id: str = "", remote_id: str = "",
                 msg_rates: dict[int, float] | None = None,
                 on_rate_limited=None, tracer=None):
        self._conn = conn
        # peer-id context for the link-scoped fault plane (utils/nemesis.py):
        # which directed link this connection is, so a partition can cut
        # exactly the messages crossing it
        self._local_id = local_id
        self._remote_id = remote_id
        self._channels = {d.id: _Channel(d) for d in channels}
        self._on_receive = on_receive
        self._on_error = on_error
        self._send_event = threading.Event()
        self._running = False
        self._stopped = False  # terminal: stop() or a transport error
        self._send_thread: threading.Thread | None = None
        self._recv_thread: threading.Thread | None = None
        self._last_recv = time.monotonic()
        self._recv_stream = b""
        # flow accounting + throttling (reference: connection.go:78
        # sendMonitor/recvMonitor; Limit() applied in sendSomePacketMsgs)
        self.send_monitor = Monitor()
        self.recv_monitor = Monitor()
        self._send_rate = send_rate
        self._recv_rate = recv_rate
        # Per-peer per-channel inbound message ceilings (msgs/s token
        # buckets, docs/OVERLOAD.md): over-limit deliveries are reported
        # to on_rate_limited(ch_id) — scored by the switch — instead of
        # being processed.
        self._rate_limiter = None
        if msg_rates:
            from tendermint_tpu.utils.peerscore import ChannelRateLimiter

            self._rate_limiter = ChannelRateLimiter(msg_rates)
        self._on_rate_limited = on_rate_limited
        # flight recorder (utils/trace.py): per-channel send/recv events
        # land in the owning node's tracer; None = untraced
        self._tracer = tracer

    def start(self) -> None:
        self._running = True
        self._send_thread = threading.Thread(
            target=self._send_routine, name="mconn-send", daemon=True)
        self._recv_thread = threading.Thread(
            target=self._recv_routine, name="mconn-recv", daemon=True)
        self._send_thread.start()
        self._recv_thread.start()

    def stop(self) -> None:
        self._stopped = True
        self._running = False
        self._send_event.set()
        self._conn.close()

    # --- sending -----------------------------------------------------------

    def send(self, ch_id: int, msg: bytes, block: bool = True) -> bool:
        """Queue a message on a channel (reference: connection.go:250-290).
        Queuing is allowed BEFORE start(): the switch attaches reactors
        (which send their hello messages — status, NewRoundStep) before it
        starts the connection, so no peer can deliver bytes to a reactor
        that hasn't attached its per-peer state yet; the send routine
        drains the queues once start() runs."""
        ch = self._channels.get(ch_id)
        if ch is None or self._stopped:
            return False
        try:
            verdict = faults.link_outcome("p2p.send", self._local_id,
                                          self._remote_id, channel=ch_id)
        except faults.FaultDisconnect as e:
            # documented disconnect semantics: a transport-style teardown
            # (peer removal + reconnect), never an exception into the
            # arbitrary sending thread (gossip loops have no handler)
            self._die(e)
            return False
        if verdict == "drop":
            return True  # loss after send: the caller sees success
        try:
            ch.send_queue.put(msg, block=block, timeout=10 if block else None)
        except queue.Full:
            return False
        tr = self._tracer
        if tr is not None and tr.enabled:
            tr.mark("p2p.send", channel=f"{ch_id:#x}", bytes=len(msg))
        if verdict == "dup":
            try:
                ch.send_queue.put(msg, block=False)
            except queue.Full:
                pass  # duplication is best-effort; the original made it in
        elif verdict == "flood":
            # byzantine amplification (nemesis flood action): seeded
            # corrupted copies ride along with the real message — invalid
            # signatures / unparseable junk the RECEIVER must score away
            from tendermint_tpu.utils import nemesis

            for junk in nemesis.PLANE.flood_payloads(
                    self._local_id, self._remote_id, ch_id, msg):
                try:
                    ch.send_queue.put(junk, block=False)
                except queue.Full:
                    break  # amplification is best-effort
        self._send_event.set()
        return True

    def try_send(self, ch_id: int, msg: bytes) -> bool:
        return self.send(ch_id, msg, block=False)

    def wire_counters(self) -> dict:
        """What this connection moved so far, per direction: packets,
        messages and payload bytes (all channels, and each channel under
        ``channels``), the sealed frames of the connection under it, and the
        seconds the flow-rate limiter slept. Counted with integer adds on
        the routines' own threads; read from outside (the ``p2p.wire``
        mark), so a reading may be a packet behind."""
        out = {"packets_sent": 0, "msgs_sent": 0, "bytes_sent": 0,
               "packets_recv": 0, "msgs_recv": 0, "bytes_recv": 0,
               "frames_sent": getattr(self._conn, "frames_sent", 0),
               "frames_recv": getattr(self._conn, "frames_recv", 0),
               "sealed_bytes_sent": getattr(self._conn, "sealed_bytes_sent", 0),
               "sealed_bytes_recv": getattr(self._conn, "sealed_bytes_recv", 0),
               "send_blocked_s": self.send_monitor.blocked_s,
               "recv_blocked_s": self.recv_monitor.blocked_s,
               "channels": {}}
        for ch_id, ch in self._channels.items():
            row = dict(zip(WIRE_KEYS, (*ch.sent, *ch.received)))
            out["channels"][f"{ch_id:#x}"] = row
            for key, n in row.items():
                out[key] += n
        return out

    def _pick_channel(self) -> _Channel | None:
        """Least ratio of recentlySent/priority (reference:
        connection.go:380-420 sendPacketMsg)."""
        best, least = None, None
        for ch in self._channels.values():
            if not ch.is_send_pending():
                continue
            ratio = ch.recently_sent / ch.desc.priority
            if least is None or ratio < least:
                least = ratio
                best = ch
        return best

    def _send_routine(self) -> None:
        last_ping = time.monotonic()
        try:
            while self._running:
                ch = self._pick_channel()
                if ch is None:
                    if time.monotonic() - last_ping > PING_INTERVAL_S:
                        self._write_packet(proto.Writer().message(1, b"", always=True).out())
                        last_ping = time.monotonic()
                    fired = self._send_event.wait(timeout=0.05)
                    if fired:
                        self._send_event.clear()
                    # decay recentlySent (flowrate stand-in)
                    for c in self._channels.values():
                        c.recently_sent = int(c.recently_sent * 0.8)
                    continue
                # Rate limit before pulling the packet (reference:
                # sendSomePacketMsgs -> sendMonitor.Limit(maxPacketMsgSize,
                # SendRate, true)).
                self.send_monitor.limit(MAX_PACKET_MSG_PAYLOAD_SIZE,
                                        self._send_rate, block=True)
                chunk, eof = ch.next_packet()
                pm = (
                    proto.Writer()
                    .varint(1, ch.desc.id)
                    .bool(2, eof)
                    .bytes(3, chunk)
                    .out()
                )
                packet = proto.Writer().message(3, pm, always=True).out()
                self._write_packet(packet)
                self.send_monitor.update(len(packet))
        except Exception as e:  # noqa: BLE001
            self._die(e)

    def _write_packet(self, packet: bytes) -> None:
        self._conn.write(proto.delimited(packet))

    # --- receiving ---------------------------------------------------------

    def _read_delimited(self) -> bytes:
        # varint length then body, over the stream-oriented secret conn
        ln = 0
        shift = 0
        while True:
            b = self._read_bytes(1)[0]
            ln |= (b & 0x7F) << shift
            if not b & 0x80:
                break
            shift += 7
            if shift > 35:
                raise MConnectionProtocolError("bad packet length varint")
        if ln > MAX_MSG_SIZE:
            raise MConnectionProtocolError(f"packet too big: {ln}")
        return self._read_bytes(ln)

    def _read_bytes(self, n: int) -> bytes:
        while len(self._recv_stream) < n:
            # Rate limit before pulling bytes off the wire, symmetrical to
            # the send side (reference: connection.go recvRoutine ->
            # recvMonitor.Limit(maxMsgPacketTotalSize, RecvRate, true)):
            # a flooding sender backs up into ITS socket buffer instead of
            # monopolizing our reactor threads. Blocking limit() returns
            # at least 1 allowed byte.
            want = self.recv_monitor.limit(65536, self._recv_rate, block=True)
            chunk = self._conn.read(max(want, 1))
            if not chunk:
                raise MConnectionError("connection closed")
            self._recv_stream += chunk
            self.recv_monitor.update(len(chunk))
        out = self._recv_stream[:n]
        self._recv_stream = self._recv_stream[n:]
        return out

    def _recv_routine(self) -> None:
        try:
            while self._running:
                packet = self._read_delimited()
                f = proto.fields(packet)
                if 1 in f:  # ping -> pong
                    self._write_packet(proto.Writer().message(2, b"", always=True).out())
                elif 2 in f:  # pong
                    self._last_recv = time.monotonic()
                elif 3 in f:
                    pf = proto.fields(f[3][-1])
                    ch_id = proto.as_sint64(pf.get(1, [0])[-1])
                    eof = bool(pf.get(2, [0])[-1])
                    data = pf.get(3, [b""])[-1]
                    ch = self._channels.get(ch_id)
                    if ch is None:
                        raise MConnectionProtocolError(f"unknown channel {ch_id:#x}")
                    ch.recving += data
                    got = ch.received
                    got[0] += 1
                    got[1] += eof
                    got[2] += len(data)
                    if len(ch.recving) > ch.desc.recv_message_capacity:
                        raise MConnectionProtocolError("received message exceeds capacity")
                    if eof:
                        msg = bytes(ch.recving)
                        ch.recving = bytearray()
                        # per-channel message ceiling: an over-limit
                        # delivery is scored (via the switch callback),
                        # never processed — the channel's token bucket is
                        # the SEDA admission gate in front of the reactors
                        if (self._rate_limiter is not None
                                and not self._rate_limiter.allow(ch_id)):
                            if self._on_rate_limited is not None:
                                self._on_rate_limited(ch_id)
                            continue
                        # drop skips delivery; dup delivers twice;
                        # disconnect raises into _die, which tears the
                        # peer down like a transport error
                        verdict = faults.link_outcome(
                            "p2p.recv", self._local_id, self._remote_id,
                            channel=ch_id)
                        if verdict != "drop":
                            tr = self._tracer
                            if tr is not None and tr.enabled:
                                # the span times the reactor's receive
                                # handler — where per-message Python cost
                                # (the 100-node wall) actually goes
                                with tr.span("p2p.recv",
                                             channel=f"{ch_id:#x}",
                                             bytes=len(msg)):
                                    self._on_receive(ch_id, msg)
                            else:
                                self._on_receive(ch_id, msg)
                            if verdict == "dup":
                                self._on_receive(ch_id, msg)
                self._last_recv = time.monotonic()
        except Exception as e:  # noqa: BLE001
            self._die(e)

    def _die(self, err: Exception) -> None:
        # gates on the terminal flag, not _running: a fatal fault on a
        # message queued BEFORE start() must still tear the peer down
        if self._stopped:
            return
        self._stopped = True
        self._running = False
        try:
            self._conn.close()
        except Exception:  # noqa: BLE001
            pass
        if self._on_error is not None:
            self._on_error(err)
