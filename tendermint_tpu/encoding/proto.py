"""Minimal protobuf wire codec.

Byte-compatible with the gogoproto-generated marshaling the reference uses for
its canonical sign-bytes and wire types (reference: proto/tendermint/types/
canonical.proto, libs/protoio/writer.go). We implement only the wire format —
varint, fixed64/32, length-delimited — plus the delimited (varint length
prefixed) framing `protoio.MarshalDelimited` applies to sign-bytes
(reference: types/vote.go:93, libs/protoio/io.go).

proto3 zero-value omission rules are applied by the callers (message builders
in tendermint_tpu.encoding.canonical and tendermint_tpu.types): scalar fields
equal to zero / empty are omitted; non-nullable embedded messages are always
emitted (gogoproto.nullable=false semantics).
"""

from __future__ import annotations

import struct

# Wire types
WIRE_VARINT = 0
WIRE_FIXED64 = 1
WIRE_BYTES = 2
WIRE_FIXED32 = 5


# Varint pieces, indexed by value: the last byte of a varint (no continuation
# bit), one continuation byte of a 7-bit group, the two of a 14-bit group,
# and the last two bytes of a negative int64 (bits 56..62, then bit 63).
_UV1 = tuple(bytes((k,)) for k in range(1 << 7))
UV7C = tuple(bytes((k | 0x80,)) for k in range(1 << 7))
UV14C = tuple(bytes((k & 0x7F | 0x80, k >> 7 | 0x80)) for k in range(1 << 14))
_NEG_TAIL = tuple(bytes((k | 0x80, 1)) for k in range(1 << 7))


def encode_uvarint(n: int) -> bytes:
    if n < 0x80:  # tags, lengths, small ints
        if n < 0:
            raise ValueError("uvarint cannot be negative")
        return _UV1[n]
    if n < 0x200000:
        if n < 0x4000:
            return UV7C[n & 0x7F] + _UV1[n >> 7]
        return UV14C[n & 0x3FFF] + _UV1[n >> 14]
    if n < 0x800000000:  # a Unix time in seconds, most nanos
        if n < 0x10000000:
            return UV14C[n & 0x3FFF] + UV7C[n >> 14 & 0x7F] + _UV1[n >> 21]
        return UV14C[n & 0x3FFF] + UV14C[n >> 14 & 0x3FFF] + _UV1[n >> 28]
    pairs = []
    while n >= 0x4000:
        pairs.append(UV14C[n & 0x3FFF])
        n >>= 14
    return b"".join(pairs) + encode_uvarint(n)


def encode_varint(n: int) -> bytes:
    """int64 varint: negatives encode as 10-byte two's complement."""
    if n >= 0:
        return encode_uvarint(n)
    if n < -(1 << 63):  # no int64: what the sum makes of it, or the error
        return encode_uvarint(n + (1 << 64))
    # & on a negative int reads its two's complement, so no sum is needed
    return b"".join((UV14C[n & 0x3FFF], UV14C[n >> 14 & 0x3FFF],
                     UV14C[n >> 28 & 0x3FFF], UV14C[n >> 42 & 0x3FFF],
                     _NEG_TAIL[n >> 56 & 0x7F]))


def decode_uvarint(buf: bytes, pos: int = 0) -> tuple[int, int]:
    result = 0
    shift = 0
    while True:
        if pos >= len(buf):
            raise ValueError("truncated varint")
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            if shift >= 63 and result >= 1 << 64:
                raise ValueError("varint overflow")
            return result, pos
        shift += 7
        if shift >= 70:
            raise ValueError("varint too long")


def decode_varint(buf: bytes, pos: int = 0) -> tuple[int, int]:
    v, pos = decode_uvarint(buf, pos)
    if v >= 1 << 63:
        v -= 1 << 64
    return v, pos


_TAG_CACHE: dict[int, bytes] = {}


def tag(field: int, wire: int) -> bytes:
    key = field << 3 | wire
    t = _TAG_CACHE.get(key)
    if t is None:
        t = _TAG_CACHE[key] = encode_uvarint(key)
    return t


class Writer:
    """Append-only protobuf message writer with proto3 omission helpers."""

    __slots__ = ("buf",)

    def __init__(self) -> None:
        self.buf = bytearray()

    # raw appends -----------------------------------------------------------
    def raw(self, b: bytes) -> "Writer":
        self.buf += b
        return self

    # field writers (proto3: zero values omitted) ---------------------------
    def uvarint(self, field: int, v: int) -> "Writer":
        if v:
            self.buf += tag(field, WIRE_VARINT)
            self.buf += encode_uvarint(v)
        return self

    def varint(self, field: int, v: int) -> "Writer":
        if v:
            self.buf += tag(field, WIRE_VARINT)
            self.buf += encode_varint(v)
        return self

    def bool(self, field: int, v: bool) -> "Writer":
        if v:
            self.buf += tag(field, WIRE_VARINT)
            self.buf.append(1)
        return self

    def sfixed64(self, field: int, v: int) -> "Writer":
        if v:
            self.buf += tag(field, WIRE_FIXED64)
            self.buf += struct.pack("<q", v)
        return self

    def fixed64(self, field: int, v: int) -> "Writer":
        if v:
            self.buf += tag(field, WIRE_FIXED64)
            self.buf += struct.pack("<Q", v)
        return self

    def double(self, field: int, v: float) -> "Writer":
        if v != 0.0:
            self.buf += tag(field, WIRE_FIXED64)
            self.buf += struct.pack("<d", v)
        return self

    def bytes(self, field: int, v: bytes) -> "Writer":
        if v:
            self.buf += tag(field, WIRE_BYTES)
            self.buf += encode_uvarint(len(v))
            self.buf += v
        return self

    def string(self, field: int, v: str) -> "Writer":
        return self.bytes(field, v.encode("utf-8"))

    def message(self, field: int, body: bytes, always: bool = False) -> "Writer":
        """Embedded message. `always=True` mirrors gogoproto nullable=false
        (emit even when empty); default proto3 omits empty/absent messages."""
        if body or always:
            self.buf += tag(field, WIRE_BYTES)
            self.buf += encode_uvarint(len(body))
            self.buf += body
        return self

    def packed_varints(self, field: int, vs) -> "Writer":
        if vs:
            body = b"".join(encode_varint(v) for v in vs)
            self.message(field, body)
        return self

    def out(self) -> bytes:
        return bytes(self.buf)


def repeated_messages(tag_byte: bytes, bodies) -> bytes:
    """A repeated embedded-message field in one pass: each body behind the
    field's tag and its length, emitted also when empty (nullable=false).
    For the containers that hold hundreds to thousands of elements, where a
    Writer call per element is most of the encoding's cost."""
    return b"".join([tag_byte + encode_uvarint(len(b)) + b for b in bodies])


def delimited(msg: bytes) -> bytes:
    """Varint length-prefixed framing (reference: libs/protoio — used for
    sign-bytes and all p2p/WAL message framing)."""
    return encode_uvarint(len(msg)) + msg


def parse_delimited(buf: bytes, pos: int = 0) -> tuple[bytes, int]:
    n, pos = decode_uvarint(buf, pos)
    if pos + n > len(buf):
        raise ValueError("truncated delimited message")
    return bytes(buf[pos : pos + n]), pos + n


class Reader:
    """Streaming field reader: yields (field_number, wire_type, value).

    value is int for varint/fixed, bytes for length-delimited.
    """

    __slots__ = ("buf", "pos", "end")

    def __init__(self, buf: bytes, pos: int = 0, end: int | None = None) -> None:
        self.buf = buf
        self.pos = pos
        self.end = len(buf) if end is None else end

    def __iter__(self):
        return self

    def __next__(self):
        if self.pos >= self.end:
            raise StopIteration
        key, self.pos = decode_uvarint(self.buf, self.pos)
        field, wire = key >> 3, key & 7
        if wire == WIRE_VARINT:
            v, self.pos = decode_uvarint(self.buf, self.pos)
        elif wire == WIRE_FIXED64:
            (v,) = struct.unpack_from("<Q", self.buf, self.pos)
            self.pos += 8
        elif wire == WIRE_BYTES:
            n, self.pos = decode_uvarint(self.buf, self.pos)
            if self.pos + n > self.end:
                raise ValueError("truncated bytes field")
            v = bytes(self.buf[self.pos : self.pos + n])
            self.pos += n
        elif wire == WIRE_FIXED32:
            (v,) = struct.unpack_from("<I", self.buf, self.pos)
            self.pos += 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        return field, wire, v


def fields(buf: bytes) -> dict[int, list]:
    """Parse all fields into {field_number: [values...]}."""
    out: dict[int, list] = {}
    for field, _wire, v in Reader(buf):
        out.setdefault(field, []).append(v)
    return out


def as_sint64(v: int) -> int:
    """Reinterpret a decoded uvarint as int64."""
    return v - (1 << 64) if v >= 1 << 63 else v


def as_sfixed64(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v
