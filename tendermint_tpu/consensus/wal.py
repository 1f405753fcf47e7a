"""Consensus write-ahead log (reference: consensus/wal.go:57,75,91,201,231,300).

Frame format mirrors the reference's WALEncoder: crc32 | length | protobuf
TimedWALMessage. The checksum is ``zlib.crc32`` (IEEE), where the reference's
is crc32c (Castagnoli): a log is read back only by the program that wrote it,
and the checksum is NOT changed by the one-pass encoder below (ISSUE 41): the
files it writes are byte for byte the files the per-message writer wrote.
Messages are replayed on restart to recover in-flight consensus state;
EndHeightMessage marks a completed height (fsync'd, the crash-recovery
anchor).

File rotation follows libs/autofile/group.go semantics (size-limited chunks
Head, Head.000, ...), simplified to a single directory of numbered chunks.
"""

from __future__ import annotations

import os
import struct
import threading
import zlib
from dataclasses import dataclass

from tendermint_tpu.encoding import proto
from tendermint_tpu.utils import faults

MAX_MSG_SIZE_BYTES = 1024 * 1024  # reference: consensus/wal.go:32
DEFAULT_HEAD_SIZE_LIMIT = 10 * 1024 * 1024


class WALError(Exception):
    pass


class CorruptedWALError(WALError):
    pass


@dataclass
class TimedWALMessage:
    time_ns: int
    msg: object  # EndHeightMessage | MsgInfo-like | TimeoutInfo-like


@dataclass
class EndHeightMessage:
    height: int


@dataclass
class WALMessageBlob:
    """Opaque consensus message payload: (kind, payload bytes, peer_id)."""

    kind: str
    payload: bytes
    peer_id: str = ""


_HEADER = struct.Struct(">II")


def _frame(time_field: bytes, msg: bytes) -> bytes:
    """crc32 | length | TimedWALMessage{time = 1, msg = 2}: THE definition
    of a frame, for one message or a drain's thousand (its reader is
    ``_valid_frames``). ``msg`` is the encoded WALMessage."""
    body = b"".join((time_field, b"\x12", proto.encode_uvarint(len(msg)), msg))
    if len(body) > MAX_MSG_SIZE_BYTES:
        raise WALError(f"msg is too big: {len(body)} bytes, max: {MAX_MSG_SIZE_BYTES} bytes")
    return _HEADER.pack(zlib.crc32(body), len(body)) + body


def _time_field(time_ns: int) -> bytes:
    return b"\x08" + proto.encode_varint(time_ns) if time_ns else b""


def blob_frames(blobs, time_ns: int) -> list[bytes]:
    """One frame a ``(kind, payload, peer_id)``, in order, all under one
    clock reading: WALMessage{blob = 2 {kind = 1, payload = 2, peer_id = 3}},
    empty fields omitted as ``proto.Writer`` omits them. No Writer: a drain
    holds a thousand of these, and all but a handful share kind and peer."""
    uvarint = proto.encode_uvarint
    time_field = _time_field(time_ns)
    kinds: dict[str, bytes] = {}  # kind -> field 1, encoded
    peers: dict[str, bytes] = {}  # peer id -> field 3, encoded
    frames = []
    for kind, payload, peer_id in blobs:
        kind_field = kinds.get(kind)
        if kind_field is None:
            kind_field = kinds[kind] = proto.Writer().string(1, kind).out()
        peer_field = peers.get(peer_id)
        if peer_field is None:
            peer_field = peers[peer_id] = proto.Writer().string(3, peer_id).out()
        n = len(payload)
        inner = (b"".join((kind_field, b"\x12", uvarint(n), payload, peer_field))
                 if n else kind_field + peer_field)
        frames.append(_frame(time_field,
                             b"".join((b"\x12", uvarint(len(inner)), inner))))
    return frames


def _msg_frames(m, time_ns: int) -> list[bytes]:
    """The frame of one message, as the n = 1 case of the drain's encoder."""
    if isinstance(m, WALMessageBlob):
        return blob_frames(((m.kind, m.payload, m.peer_id),), time_ns)
    if isinstance(m, EndHeightMessage):
        end = proto.Writer().varint(1, m.height).out()
        return [_frame(_time_field(time_ns),
                       proto.Writer().message(1, end, always=True).out())]
    raise WALError(f"unknown WAL message type {type(m)}")


def _decode_msg(buf: bytes):
    f = proto.fields(buf)
    if 1 in f:
        inner = proto.fields(f[1][-1])
        return EndHeightMessage(height=proto.as_sint64(inner.get(1, [0])[-1]))
    if 2 in f:
        inner = proto.fields(f[2][-1])
        return WALMessageBlob(
            kind=inner.get(1, [b""])[-1].decode(),
            payload=inner.get(2, [b""])[-1],
            peer_id=inner.get(3, [b""])[-1].decode() if 3 in inner else "",
        )
    raise CorruptedWALError("empty WAL message")


def _valid_frames(data: bytes):
    """Yield (pos, end, time_ns, msg) for each valid frame of a chunk,
    stopping at the first torn/truncated/corrupt/undecodable frame — the
    ONE definition of frame validity, shared by replay and repair so the
    two can never disagree on where the valid prefix ends."""
    pos = 0
    while pos + 8 <= len(data):
        crc, length = struct.unpack_from(">II", data, pos)
        if length > MAX_MSG_SIZE_BYTES or pos + 8 + length > len(data):
            return
        body = data[pos + 8 : pos + 8 + length]
        if zlib.crc32(body) & 0xFFFFFFFF != crc:
            return
        try:
            f2 = proto.fields(body)
            time_ns = proto.as_sint64(f2.get(1, [0])[-1])
            msg = _decode_msg(f2.get(2, [b""])[-1])
        except (CorruptedWALError, ValueError):
            return
        end = pos + 8 + length
        yield pos, end, time_ns, msg
        pos = end


class WAL:
    """reference: consensus/wal.go BaseWAL."""

    def __init__(self, path: str, head_size_limit: int = DEFAULT_HEAD_SIZE_LIMIT):
        self.dir = path
        self.head_size_limit = head_size_limit
        os.makedirs(self.dir, exist_ok=True)
        self._mtx = threading.Lock()
        self._head: object | None = None
        self._head_index = self._max_index()
        self._repair()
        self._open_head()

    # --- chunk management (autofile group light) ---------------------------

    def _chunk_path(self, index: int) -> str:
        return os.path.join(self.dir, f"wal.{index:06d}")

    def _indexes(self) -> list[int]:
        out = []
        for name in os.listdir(self.dir):
            if name.startswith("wal."):
                try:
                    out.append(int(name[4:]))
                except ValueError:
                    pass
        return sorted(out)

    def _max_index(self) -> int:
        idx = self._indexes()
        return idx[-1] if idx else 0

    def _open_head(self) -> None:
        self._head = open(self._chunk_path(self._head_index), "ab")

    def _repair(self) -> None:
        """Make the on-disk log append-safe again after damage: replay
        stops at the first torn/corrupt frame in ANY chunk, so everything
        from that point on — the damaged chunk's tail, all later chunks,
        and any frame a reopened node would append — is unreachable. On
        open, find the first chunk with a non-clean tail, truncate it to
        its valid prefix, retire every later chunk (messages after a lost
        frame must not replay — ordering across the gap is broken), and
        point appends at the repaired chunk. Damaged originals are kept
        aside as .corrupted.N for forensics (reference:
        consensus/replay.go:73 repairWalFile).

        Crash-safe order: later chunks are retired highest-index-first,
        then the torn chunk is replaced via write-temp + fsync + hard-link
        original aside + atomic rename + directory fsync. At every
        intermediate state the replayable prefix is unchanged (replay
        still stops at the tear), and a re-crash just repeats the repair."""
        torn = None
        for index in self._indexes():
            path = self._chunk_path(index)
            with open(path, "rb") as f:
                data = f.read()
            end = 0
            for _pos, frame_end, _t, _m in _valid_frames(data):
                end = frame_end
            if end < len(data):
                torn = (index, data, end)
                break
        if torn is None:
            return
        index, data, end = torn
        for later in reversed([i for i in self._indexes() if i > index]):
            self._retire(self._chunk_path(later), keep_prefix=None)
        self._retire(self._chunk_path(index), keep_prefix=data[:end])
        self._head_index = index

    def _retire(self, path: str, keep_prefix: bytes | None) -> None:
        """Move `path` aside as .corrupted.N; when keep_prefix is given,
        atomically replace it with that prefix instead of removing it."""
        n = 0
        while os.path.exists(f"{path}.corrupted.{n}"):
            n += 1
        if keep_prefix is None:
            os.replace(path, f"{path}.corrupted.{n}")
        else:
            tmp = path + ".repair.tmp"
            with open(tmp, "wb") as dst:
                dst.write(keep_prefix)
                dst.flush()
                os.fsync(dst.fileno())
            os.link(path, f"{path}.corrupted.{n}")
            os.replace(tmp, path)
        dirfd = os.open(self.dir, os.O_RDONLY)
        try:
            os.fsync(dirfd)
        finally:
            os.close(dirfd)

    def _maybe_rotate(self) -> None:
        if self._head.tell() >= self.head_size_limit:
            self._head.close()
            self._head_index += 1
            self._open_head()

    # --- writes ------------------------------------------------------------

    def write(self, msg, time_ns: int = 0) -> None:
        """Buffered write (fsync only on write_sync; reference:
        consensus/wal.go:166-199)."""
        frames = _msg_frames(msg, time_ns)
        with self._mtx:
            self._write_locked(frames)

    def write_blobs(self, blobs, time_ns: int = 0) -> int:
        """A drain's ``(kind, payload, peer_id)`` messages, buffered, a frame
        each and in order, the bytes ``write`` would have written one by one
        at ``time_ns`` -> the ``write`` calls issued on the file."""
        frames = blob_frames(blobs, time_ns)
        with self._mtx:
            return self._write_locked(frames)

    def write_sync(self, msg, time_ns: int = 0) -> None:
        frames = _msg_frames(msg, time_ns)
        with self._mtx:
            self._write_locked(frames)
            faults.fire("wal.fsync")  # crash here loses the buffered frames
            self._head.flush()
            os.fsync(self._head.fileno())

    def _write_locked(self, frames: list[bytes]) -> int:
        """Hand the frames to the file in one write, and check the head's
        size once, after them: a chunk passes ``head_size_limit`` by one
        drain at most (the reference checks on a ticker, not a write:
        libs/autofile/group.go processTicks). While a fault rule is armed
        the frames go one by one, so that a schedule's hit index counts
        frames. -> write calls issued."""
        if faults.REGISTRY.active:
            for frame in frames:
                # torn/partial rules write a cut prefix of this frame and
                # crash, leaving on disk exactly what a power cut mid-append
                # leaves (the frames before it are already with the file).
                faults.torn_write("wal.write", self._head, frame)
                self._head.write(frame)
            writes = len(frames)
        else:
            self._head.write(b"".join(frames))
            writes = 1
        self._maybe_rotate()
        return writes

    def flush_and_sync(self) -> None:
        with self._mtx:
            faults.fire("wal.fsync")
            self._head.flush()
            os.fsync(self._head.fileno())

    def close(self) -> None:
        with self._mtx:
            if self._head is not None:
                self._head.flush()
                self._head.close()
                self._head = None

    # --- reads -------------------------------------------------------------

    def iter_messages(self, start_index: int | None = None):
        """Yield (TimedWALMessage, (chunk_index, offset)) across chunks,
        stopping at the first corrupt/truncated frame (crash tail)."""
        for index in self._indexes():
            if start_index is not None and index < start_index:
                continue
            path = self._chunk_path(index)
            with open(path, "rb") as f:
                data = f.read()
            end = 0
            for pos, fend, time_ns, msg in _valid_frames(data):
                yield TimedWALMessage(time_ns=time_ns, msg=msg), (index, pos)
                end = fend
            if end < len(data):
                return  # corrupt/torn tail: nothing after it is trustworthy

    def search_for_end_height(self, height: int):
        """Find messages after EndHeightMessage{height} (reference:
        consensus/wal.go:231-290). Returns list of messages after it, or
        None if not found."""
        found = False
        after: list[TimedWALMessage] = []
        for tm, _loc in self.iter_messages():
            if found:
                after.append(tm)
            elif isinstance(tm.msg, EndHeightMessage) and tm.msg.height == height:
                found = True
        return after if found else None
