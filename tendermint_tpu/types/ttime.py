"""Go-compatible time values for canonical encoding.

The reference signs over google.protobuf.Timestamp (seconds + nanos), with
Go's zero time (0001-01-01T00:00:00Z, seconds = -62135596800) as the zero
value for absent/nil commit signatures. Nanoseconds-since-epoch cannot
represent that, so Time carries (seconds, nanos) directly.

Reference: gogo StdTimeMarshal usage in types/block.go:445-452,
types/canonical.go:13 (RFC3339Nano string form for display).
"""

from __future__ import annotations

import time as _time
from dataclasses import dataclass
from functools import total_ordering

from tendermint_tpu.encoding import proto
from tendermint_tpu.utils import clock as _clock

GO_ZERO_SECONDS = -62135596800  # 0001-01-01T00:00:00Z


@total_ordering
@dataclass(frozen=True)
class Time:
    seconds: int = GO_ZERO_SECONDS
    nanos: int = 0

    @staticmethod
    def zero() -> "Time":
        return Time()

    @staticmethod
    def now() -> "Time":
        # reads through utils/clock so a skewed process (TMTPU_CLOCK_SKEW_S
        # or a nemesis skew action on clock.DEFAULT) timestamps accordingly;
        # per-node components read their own node Clock instead
        return Time.from_unix_ns(_clock.now_ns())

    @staticmethod
    def from_unix_ns(ns: int) -> "Time":
        return Time(ns // 1_000_000_000, ns % 1_000_000_000)

    def is_zero(self) -> bool:
        return self.seconds == GO_ZERO_SECONDS and self.nanos == 0

    def unix_ns(self) -> int:
        return self.seconds * 1_000_000_000 + self.nanos

    def add_ns(self, ns: int) -> "Time":
        return Time.from_unix_ns(self.unix_ns() + ns)

    def __lt__(self, other: "Time") -> bool:
        return (self.seconds, self.nanos) < (other.seconds, other.nanos)

    # --- encoding ----------------------------------------------------------
    def marshal(self) -> bytes:
        """google.protobuf.Timestamp body (field 1 seconds, field 2 nanos)."""
        # no Writer: a commit holds one of these per signature
        seconds, nanos = self.seconds, self.nanos
        out = b"\x08" + proto.encode_varint(seconds) if seconds else b""
        return out + b"\x10" + proto.encode_varint(nanos) if nanos else out

    @staticmethod
    def unmarshal(buf: bytes) -> "Time":
        seconds, nanos = 0, 0
        for field, _w, v in proto.Reader(buf):
            if field == 1:
                seconds = proto.as_sint64(v)
            elif field == 2:
                nanos = proto.as_sint64(v)
        return Time(seconds, nanos)

    def __str__(self) -> str:
        if self.is_zero():
            return "0001-01-01T00:00:00Z"
        frac = f".{self.nanos:09d}".rstrip("0") if self.nanos else ""
        t = _time.gmtime(self.seconds)
        return (
            f"{t.tm_year:04d}-{t.tm_mon:02d}-{t.tm_mday:02d}T"
            f"{t.tm_hour:02d}:{t.tm_min:02d}:{t.tm_sec:02d}{frac}Z"
        )


def canonical_now(override_ns: int | None = None) -> Time:
    """tmtime.Now truncates to the canonical form (UTC, no monotonic part)."""
    if override_ns is not None:
        return Time.from_unix_ns(override_ns)
    return Time.now()
