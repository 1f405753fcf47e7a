"""Trusted light-block store (reference: light/store/store.go interface,
light/store/db/db.go implementation).

Persists verified LightBlocks keyed by height. Backed by any
tendermint_tpu.store.db.DB (memdb or sqlite), so a light node's trust state
survives restarts.
"""

from __future__ import annotations

import threading

from tendermint_tpu.store.db import DB, prefix_end
from tendermint_tpu.types.light_block import LightBlock


def _key(height: int) -> bytes:
    return b"lb/" + height.to_bytes(8, "big")


class DBStore:
    """reference: light/store/db/db.go:22 (dbs struct)."""

    def __init__(self, db: DB, prefix: str = ""):
        self._db = db
        self._prefix = prefix.encode() if prefix else b""
        self._mtx = threading.Lock()

    def _k(self, height: int) -> bytes:
        return self._prefix + _key(height)

    # --- Store interface (reference: light/store/store.go:12-44) -----------

    def save_light_block(self, lb: LightBlock) -> int:
        """Returns the number of bytes handed to the db."""
        if lb.height <= 0:
            raise ValueError("lightBlock height must be > 0")
        raw = lb.marshal()
        with self._mtx:
            self._db.set(self._k(lb.height), raw)
        return len(raw)

    def delete_light_block(self, height: int) -> None:
        if height <= 0:
            raise ValueError("height must be > 0")
        with self._mtx:
            self._db.delete(self._k(height))

    def light_block(self, height: int) -> LightBlock | None:
        if height <= 0:
            raise ValueError("height must be > 0")
        raw = self._db.get(self._k(height))
        if raw is None:
            return None
        return LightBlock.unmarshal(raw)

    def _range(self) -> tuple[bytes, bytes | None]:
        start = self._prefix + b"lb/"
        return start, prefix_end(start)

    def latest_light_block(self) -> LightBlock | None:
        """Keys are fixed-width big-endian, so DB order == height order:
        the latest block is the last key (reference: light/store/db/db.go:114
        does the same with a reverse iterator)."""
        start, end = self._range()
        for k, v in self._db.reverse_iterator(start, end):
            return LightBlock.unmarshal(v)
        return None

    def first_light_block_height(self) -> int:
        start, end = self._range()
        for k, _ in self._db.iterator(start, end):
            return int.from_bytes(k[len(start):], "big")
        return -1

    def light_block_before(self, height: int) -> LightBlock | None:
        """Largest stored height strictly below `height` (reference:
        light/store/db/db.go:168)."""
        start, _ = self._range()
        for _, v in self._db.reverse_iterator(start, self._k(height)):
            return LightBlock.unmarshal(v)
        return None

    def prune(self, size: int) -> None:
        """Keep at most `size` newest blocks (reference:
        light/store/db/db.go:192)."""
        excess = self.size() - size
        if excess <= 0:
            return
        start, end = self._range()
        doomed = []
        for k, _ in self._db.iterator(start, end):
            if len(doomed) >= excess:
                break
            doomed.append(k)
        with self._mtx:
            for k in doomed:
                self._db.delete(k)

    def size(self) -> int:
        start, end = self._range()
        return sum(1 for _ in self._db.iterator(start, end))
