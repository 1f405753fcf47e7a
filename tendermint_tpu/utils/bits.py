"""BitArray: the vote/part bitmap type (reference: libs/bits/bit_array.go,
proto/tendermint/libs/bits/types.proto).

Backed by a single Python int (arbitrary-precision bitmask), which makes
or/and/sub/is_full O(words) and keeps indexing trivial. Drop-in for the
list[bool] bitmaps it replaces: supports len/index/slice/iter/assignment.
"""

from __future__ import annotations

import random

from tendermint_tpu.encoding import proto


class BitArray:
    __slots__ = ("bits", "_mask")

    def __init__(self, bits: int = 0):
        if bits < 0:
            raise ValueError("negative bit count")
        self.bits = bits
        self._mask = 0

    # --- constructors -------------------------------------------------------

    @staticmethod
    def from_bools(bools) -> "BitArray":
        ba = BitArray(len(bools))
        m = 0
        for i, b in enumerate(bools):
            if b:
                m |= 1 << i
        ba._mask = m
        return ba

    def copy(self) -> "BitArray":
        ba = BitArray(self.bits)
        ba._mask = self._mask
        return ba

    # --- element access (list[bool] compatible) -----------------------------

    def __len__(self) -> int:
        return self.bits

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [bool(self._mask >> k & 1) for k in range(*i.indices(self.bits))]
        if i < 0:
            i += self.bits
        if not 0 <= i < self.bits:
            raise IndexError(i)
        return bool(self._mask >> i & 1)

    def __setitem__(self, i: int, v: bool) -> None:
        if i < 0:
            i += self.bits
        if not 0 <= i < self.bits:
            raise IndexError(i)
        if v:
            self._mask |= 1 << i
        else:
            self._mask &= ~(1 << i)

    def __iter__(self):
        m = self._mask
        for _ in range(self.bits):
            yield bool(m & 1)
            m >>= 1

    def get_index(self, i: int) -> bool:
        return bool(self[i]) if 0 <= i < self.bits else False

    def set_index(self, i: int, v: bool) -> bool:
        if not 0 <= i < self.bits:
            return False
        self[i] = v
        return True

    # --- set ops (reference: bit_array.go Or/And/Sub/Not) -------------------

    def or_(self, other: "BitArray") -> "BitArray":
        ba = BitArray(max(self.bits, other.bits))
        ba._mask = self._mask | other._mask
        return ba

    def and_(self, other: "BitArray") -> "BitArray":
        ba = BitArray(min(self.bits, other.bits))
        ba._mask = self._mask & other._mask & ((1 << ba.bits) - 1)
        return ba

    def sub(self, other: "BitArray") -> "BitArray":
        """Bits set in self but not in other (reference Sub truncates to
        self's length)."""
        ba = BitArray(self.bits)
        ba._mask = self._mask & ~other._mask & ((1 << self.bits) - 1)
        return ba

    def not_(self) -> "BitArray":
        ba = BitArray(self.bits)
        ba._mask = ~self._mask & ((1 << self.bits) - 1)
        return ba

    def update(self, other: "BitArray") -> None:
        """In-place or with another array (reference Update)."""
        self._mask |= other._mask & ((1 << self.bits) - 1)

    # --- queries ------------------------------------------------------------

    def is_empty(self) -> bool:
        return self._mask == 0

    def is_full(self) -> bool:
        return self.bits > 0 and self._mask == (1 << self.bits) - 1

    def sum(self) -> int:
        return bin(self._mask).count("1")

    def pick_random(self, rng: random.Random | None = None) -> tuple[int, bool]:
        """A uniformly random set index (reference PickRandom)."""
        set_bits = [i for i in range(self.bits) if self._mask >> i & 1]
        if not set_bits:
            return 0, False
        return (rng or random).choice(set_bits), True

    # --- wire (proto/tendermint/libs/bits/types.proto) ----------------------

    def marshal(self) -> bytes:
        """bits=1 varint, elems=2 packed uint64 (proto/tendermint/libs/bits)."""
        elems = [(self._mask >> i) & 0xFFFFFFFFFFFFFFFF
                 for i in range(0, self.bits, 64)]
        return proto.Writer().varint(1, self.bits).packed_varints(2, elems).out()

    @staticmethod
    def unmarshal(buf: bytes) -> "BitArray":
        f = proto.fields(buf)
        ba = BitArray(proto.as_sint64(f.get(1, [0])[-1]))
        elems = []
        for raw in f.get(2, []):
            if isinstance(raw, bytes):  # packed
                pos = 0
                while pos < len(raw):
                    v, pos = proto.decode_uvarint(raw, pos)
                    elems.append(v)
            else:
                elems.append(raw)
        m = 0
        for i, elem in enumerate(elems):
            m |= elem << (64 * i)
        # a sender may claim any length: no mask of a length the elements
        # do not reach is built
        ba._mask = m & ((1 << ba.bits) - 1) if ba.bits < m.bit_length() else m
        return ba

    # --- display (reference String: "x" = set, "_" = unset) -----------------

    def __str__(self) -> str:
        return "".join("x" if b else "_" for b in self)

    def __repr__(self) -> str:
        return f"BitArray{{{self}}}"

    def __eq__(self, other) -> bool:
        if isinstance(other, BitArray):
            return self.bits == other.bits and self._mask == other._mask
        if isinstance(other, (list, tuple)):
            return list(self) == list(other)
        return NotImplemented
