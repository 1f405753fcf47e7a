"""BlockStore: persists blocks as meta + parts + commits (reference:
store/store.go:93,203,226,248,332).

Layout (one KV row per item, like the reference's calc*Key scheme):
  H:<height>        -> BlockMeta proto
  P:<height>:<idx>  -> Part proto
  C:<height>        -> Commit proto   (LastCommit of height+1)
  SC:<height>       -> Commit proto   (locally seen commit for height)
  BH:<hash>         -> height (decimal)
  blockStore        -> BlockStoreState {base, height}

Every value is written inside the CRC32 integrity envelope
(store/envelope.py) and every read routes through the checked decode: a
flipped bit raises a typed CorruptedStoreError naming the key (and fires
the ``on_corruption`` repair hook) instead of an unhandled proto error or
a silently-served bad block. Pre-envelope rows read compatibly
(docs/DURABILITY.md).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field as dc_field

from tendermint_tpu.encoding import proto
from tendermint_tpu.store import envelope
from tendermint_tpu.store.db import DB, prefix_end
from tendermint_tpu.utils import faults
from tendermint_tpu.utils import trace as _trace
from tendermint_tpu.types.block import Block, Commit, Header
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.part_set import Part, PartSet


@dataclass
class BlockMeta:
    """reference: types/block_meta.go."""

    block_id: BlockID = dc_field(default_factory=BlockID)
    block_size: int = 0
    header: Header = dc_field(default_factory=Header)
    num_txs: int = 0

    def marshal(self) -> bytes:
        return (
            proto.Writer()
            .message(1, self.block_id.marshal(), always=True)
            .varint(2, self.block_size)
            .message(3, self.header.marshal(), always=True)
            .varint(4, self.num_txs)
            .out()
        )

    @staticmethod
    def unmarshal(buf: bytes) -> "BlockMeta":
        f = proto.fields(buf)
        return BlockMeta(
            block_id=BlockID.unmarshal(f.get(1, [b""])[-1]),
            block_size=proto.as_sint64(f.get(2, [0])[-1]),
            header=Header.unmarshal(f.get(3, [b""])[-1]),
            num_txs=proto.as_sint64(f.get(4, [0])[-1]),
        )


def _meta_key(h: int) -> bytes:
    return b"H:%020d" % h


def _part_key(h: int, i: int) -> bytes:
    return b"P:%020d:%08d" % (h, i)


def _commit_key(h: int) -> bytes:
    return b"C:%020d" % h


def _seen_commit_key(h: int) -> bytes:
    return b"SC:%020d" % h


def _hash_key(block_hash: bytes) -> bytes:
    return b"BH:" + block_hash


_STATE_KEY = b"blockStore"


def _block_rows(block: Block, part_set: PartSet) -> list:
    """The meta / BH / part / last-commit rows every block writer lays
    down. save_block and the repair path's rewrite_block share this so a
    repaired height is byte-identical to a freshly saved one — any layout
    change lands in both writers at once."""
    height = block.header.height
    block_id = BlockID(hash=block.hash(), part_set_header=part_set.header())
    meta = BlockMeta(
        block_id=block_id,
        block_size=sum(len(p.bytes_) for p in part_set.parts),
        header=block.header,
        num_txs=len(block.data.txs),
    )
    sets = [(_meta_key(height), envelope.wrap(meta.marshal())),
            (_hash_key(block.hash()), envelope.wrap(str(height).encode()))]
    for i, part in enumerate(part_set.parts):
        sets.append((_part_key(height, i), envelope.wrap(part.marshal())))
    if block.last_commit is not None:
        sets.append((_commit_key(height - 1),
                     envelope.wrap(block.last_commit.marshal())))
    return sets

LOAD_SITE = "store.block.load"


class BlockStore:
    """Thread-safe; mirrors store/store.go semantics including pruning."""

    def __init__(self, db: DB):
        self._db = db
        self._mtx = threading.RLock()
        # repair hook: the node wires this to its StoreRepairer so every
        # detection quarantines + schedules without the caller's help
        self.on_corruption = None
        st = db.get(_STATE_KEY)
        if st is None:
            self.base = 0
            self.height = 0
        else:
            try:
                f = self._decode(_STATE_KEY, st, proto.fields)
                self.base = proto.as_sint64(f.get(1, [0])[-1])
                self.height = proto.as_sint64(f.get(2, [0])[-1])
            except envelope.CorruptedStoreError:
                # the {base, height} row is fully re-derivable from the H:
                # keyspace: self-heal instead of refusing to construct
                self.base, self.height = self._rederive_state()
                envelope.quarantine(db, envelope.CorruptedStoreError(
                    "block", _STATE_KEY, "rederived after corruption", st))
                db.set(_STATE_KEY, envelope.wrap(self._state_bytes()))
                envelope.count_repair("block")

    def _rederive_state(self) -> tuple[int, int]:
        lo = next(self._db.iterator(b"H:", prefix_end(b"H:")), None)
        hi = next(self._db.reverse_iterator(b"H:", prefix_end(b"H:")), None)
        if lo is None or hi is None:
            return 0, 0
        return int(lo[0][2:]), int(hi[0][2:])

    # --- the checked read path --------------------------------------------

    def _load(self, key: bytes, fn):
        """DB get -> fault site -> envelope unwrap -> guarded decode."""
        raw = faults.mutate_value(LOAD_SITE, self._db.get(key))
        if raw is None:
            return None
        return self._decode(key, raw, fn)

    def _decode(self, key: bytes, raw: bytes, fn):
        return envelope.decode(raw, "block", key, fn,
                               on_corruption=self.on_corruption)

    # --- accessors ---------------------------------------------------------

    def size(self) -> int:
        with self._mtx:
            return 0 if self.height == 0 else self.height - self.base + 1

    def load_base_meta(self) -> BlockMeta | None:
        with self._mtx:
            base = self.base
        return self.load_block_meta(base) if base else None

    def load_block_meta(self, height: int) -> BlockMeta | None:
        return self._load(_meta_key(height), BlockMeta.unmarshal)

    def load_block(self, height: int) -> Block | None:
        meta = self.load_block_meta(height)
        if meta is None:
            return None
        parts = []
        for i in range(meta.block_id.part_set_header.total):
            part = self._load(_part_key(height, i), Part.unmarshal)
            if part is None:
                return None
            parts.append(part.bytes_)
        # the joined payload is unframed; the guarded decode still converts
        # any unmarshal blow-up into the typed error naming the height
        return self._decode(_meta_key(height), b"".join(parts),
                            Block.unmarshal)

    def load_block_by_hash(self, block_hash: bytes) -> Block | None:
        h = self._load(_hash_key(block_hash), envelope.decimal_height)
        if h is None:
            return None
        return self.load_block(h)

    def load_block_part(self, height: int, index: int) -> Part | None:
        return self._load(_part_key(height, index), Part.unmarshal)

    def load_block_commit(self, height: int) -> Commit | None:
        """Commit for `height` stored with block height+1 (reference:
        store/store.go:203)."""
        return self._load(_commit_key(height), Commit.unmarshal)

    def load_seen_commit(self, height: int) -> Commit | None:
        return self._load(_seen_commit_key(height), Commit.unmarshal)

    # --- mutation ----------------------------------------------------------

    def save_block(self, block: Block, part_set: PartSet, seen_commit: Commit) -> None:
        """reference: store/store.go:332-383."""
        if block is None:
            raise ValueError("BlockStore can only save a non-nil block")
        height = block.header.height
        tr = _trace.current() if _trace.ENABLED else None
        with (tr.span("store.save_block", height=height) if tr
              else _trace.NULL_SPAN), self._mtx:
            want = self.height + 1
            if self.height > 0 and height != want:
                raise ValueError(f"BlockStore can only save contiguous blocks. Wanted {want}, got {height}")
            if not part_set.is_complete():
                raise ValueError("BlockStore can only save complete block part sets")

            sets = _block_rows(block, part_set)
            sets.append((_seen_commit_key(height),
                         envelope.wrap(seen_commit.marshal())))

            self.height = height
            if self.base == 0:
                self.base = height
            sets.append((_STATE_KEY, envelope.wrap(self._state_bytes())))
            faults.fire("store.block.save")
            self._db.write_batch(sets)
            if tr:
                tr.annotate(bytes=sum(len(v) for _k, v in sets),
                            parts=part_set.count, rows=len(sets))

    def save_seen_commit(self, height: int, seen_commit: Commit) -> None:
        """Standalone seen-commit write for the state-sync bootstrap
        (reference: store/store.go:385 SaveSeenCommit)."""
        with self._mtx:
            self._db.set(_seen_commit_key(height),
                         envelope.wrap(seen_commit.marshal()))

    def rewrite_block(self, block: Block, part_set: PartSet,
                      commit: Commit | None) -> bool:
        """Repair-path write: re-lay every row of an ALREADY-COMMITTED
        height from a verified block (store/repair.py), without the
        contiguity/state bookkeeping of save_block — base/height are
        untouched, the damage was record-level. Returns False without
        writing when the height left the live range while the repair was
        in flight (a concurrent prune_blocks advanced ``base``): rows
        re-laid below base would never be revisited by pruning and leak
        forever."""
        height = block.header.height
        sets = _block_rows(block, part_set)
        if commit is not None:
            # fill only the commit rows the damage took: an intact C: row
            # keeps its original bytes, a lost SC: row is restored from the
            # canonical commit (a different-but-valid +2/3 sig set is fine)
            if self._db.get(_commit_key(height)) is None:
                sets.append((_commit_key(height),
                             envelope.wrap(commit.marshal())))
            if self._db.get(_seen_commit_key(height)) is None:
                sets.append((_seen_commit_key(height),
                             envelope.wrap(commit.marshal())))
        with self._mtx:
            if not (self.base <= height <= self.height):
                return False  # pruned (or rolled back) mid-repair
            self._db.write_batch(sets)
        return True

    def prune_blocks(self, height: int) -> int:
        """Removes blocks below `height`, keeping `height` (reference:
        store/store.go:248-330). Returns number pruned."""
        with self._mtx:
            if height <= 0:
                raise ValueError("height must be greater than 0")
            if height > self.height:
                raise ValueError(f"cannot prune beyond the latest height {self.height}")
            if height < self.base:
                return 0
            pruned = 0
            deletes: list[bytes] = []
            bh_index = None  # built on first corrupt meta, shared by all
            for h in range(self.base, height):
                try:
                    meta = self.load_block_meta(h)
                except envelope.CorruptedStoreError:
                    # a corrupt meta must not wedge pruning OR leak its
                    # height's rows forever: fall back to prefix scans (one
                    # BH: keyspace pass per prune call, not per height —
                    # this all runs under the store mutex)
                    if bh_index is None:
                        bh_index = self._bh_rows_by_height()
                    deletes.extend(self._keys_for_height_scan(h, bh_index))
                    pruned += 1
                    continue
                if meta is None:
                    continue
                deletes.append(_meta_key(h))
                deletes.append(_hash_key(meta.block_id.hash))
                deletes.append(_commit_key(h - 1))
                deletes.append(_seen_commit_key(h))
                for i in range(meta.block_id.part_set_header.total):
                    deletes.append(_part_key(h, i))
                pruned += 1
            self.base = height
            self._db.write_batch([(_STATE_KEY, envelope.wrap(self._state_bytes()))],
                                 deletes)
            return pruned

    def _bh_rows_by_height(self) -> dict[bytes | None, list[bytes]]:
        """One pass over the BH: keyspace: decimal height bytes -> [keys],
        with undecodable rows collected under ``None``."""
        out: dict[bytes | None, list[bytes]] = {}
        for k, v in self._db.iterator(b"BH:", prefix_end(b"BH:")):
            try:
                out.setdefault(envelope.unwrap(v, "block", k), []).append(k)
            except envelope.CorruptedStoreError:
                out.setdefault(None, []).append(k)
        return out

    def _keys_for_height_scan(self, h: int, bh_index: dict) -> list[bytes]:
        """All live rows of one height found by prefix scan (the
        meta-corrupt pruning fallback: part count and block hash are not
        decodable, so enumerate instead of computing). ``bh_index`` is the
        shared :meth:`_bh_rows_by_height` map; undecodable BH rows are
        pruned with the first corrupt height that consults it."""
        keys = [_meta_key(h), _commit_key(h - 1), _seen_commit_key(h)]
        pp = b"P:%020d:" % h
        keys.extend(k for k, _ in self._db.iterator(pp, prefix_end(pp)))
        keys.extend(bh_index.get(str(h).encode(), ()))
        keys.extend(bh_index.pop(None, ()))
        return keys

    def _state_bytes(self) -> bytes:
        return proto.Writer().varint(1, self.base).varint(2, self.height).out()
