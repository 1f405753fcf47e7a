"""Tx + block indexers and the service that feeds them from the EventBus
(reference: state/txindex/kv/kv.go, state/indexer/block/kv/kv.go,
state/txindex/indexer_service.go:19).

Index layout (kv backend):
  txr/<hash>                -> JSON TxResult document (served raw over RPC)
  txe/<key>/<value>/<h>/<i> -> hash  (event postings, incl. tx.height)
  blk/<key>/<value>/<h>     -> height (block events from Begin/EndBlock)

Search is the AND of per-condition posting scans: `=` conditions hit exact
posting prefixes; range/CONTAINS/EXISTS conditions scan the key's postings
and filter values (full reference operator grammar,
libs/pubsub/query/query.go). The reference's psql sink
(state/indexer/sink/psql) is mirrored by state/sql_sink.py (write-only,
any DB-API driver; tested on sqlite3).
"""

from __future__ import annotations

import base64
import contextlib
import json
import threading

from tendermint_tpu.store import envelope
from tendermint_tpu.store.db import DB, prefix_end
from tendermint_tpu.utils import faults
from tendermint_tpu.utils import trace as _trace
from tendermint_tpu.types import events as tmevents
from tendermint_tpu.types.tx import tx_hash


def _esc(s: str) -> str:
    return s.replace("/", "%2F")


LOAD_SITE = "store.txindex.load"


def _checked(db, key: bytes, raw: bytes | None, fn, on_corruption=None):
    """The indexers' checked read path: fault site -> envelope -> guarded
    decode, quarantining on detection. Most index rows are DERIVED data the
    repairer re-creates from the block + ABCI-responses stores (txr/, txe/,
    blkh/); the repaired counter is bumped there, when the reindex actually
    lands — never here at detection time (docs/DURABILITY.md)."""
    raw = faults.mutate_value(LOAD_SITE, raw)
    if raw is None:
        return None
    try:
        return envelope.decode(raw, "txindex", key, fn,
                               on_corruption=on_corruption)
    except envelope.CorruptedStoreError:
        envelope.quarantine(db, envelope.CorruptedStoreError(
            "txindex", key, "quarantined on read", raw))
        raise


def _posting_hash(b: bytes) -> bytes:
    """Strict posting decode: the value IS a 32-byte tx hash. Shape
    validation closes the one envelope blind spot — a bit flip landing in
    the 2-byte magic demotes the row to the legacy path, where an
    identity decode would accept anything (docs/DURABILITY.md)."""
    if len(b) != 32:
        raise ValueError(f"posting value is {len(b)} bytes, want a 32-byte "
                         "tx hash")
    return b


def _height_str(b: bytes) -> int:
    """Strict decimal decode for blk/blkh height rows (same blind-spot
    closure as _posting_hash)."""
    return envelope.decimal_height(b)


class TxIndexer:
    """reference: state/txindex/kv/kv.go:32 TxIndex."""

    def __init__(self, db: DB):
        self._db = db
        self._mtx = threading.Lock()
        self._staged: list | None = None
        self.on_corruption = None
        # (rows, bytes) of the last height_txn's batch, for indexer.height
        self.last_batch = (0, 0)

    @contextlib.contextmanager
    def height_txn(self):
        """Batch one height's tx postings into a single write_batch (the kv
        analogue of SqlEventSink.height_txn): index() calls stage their rows
        while the context is open and the whole height lands in one batch on
        exit — one store write per height instead of one per tx."""
        with self._mtx:
            if self._staged is not None:
                raise RuntimeError("height_txn does not nest")
            self._staged = []
        try:
            yield self
        except Exception:
            with self._mtx:
                self._staged = None
            raise
        else:
            with self._mtx:
                sets, self._staged = self._staged, None
                if sets:
                    self._db.write_batch(sets)
                if _trace.ENABLED:
                    self.last_batch = (len(sets),
                                       sum(len(v) for _k, v in sets))

    def index(self, height: int, idx: int, tx: bytes, result) -> None:
        h = tx_hash(tx)
        doc = {
            "hash": h.hex().upper(),
            "height": str(height),
            "index": idx,
            "tx": base64.b64encode(tx).decode(),
            "tx_result": {
                "code": result.code if result else 0,
                "data": base64.b64encode(result.data if result else b"").decode(),
                "log": result.log if result else "",
                "gas_wanted": str(result.gas_wanted if result else 0),
                "gas_used": str(result.gas_used if result else 0),
                "events": [
                    {"type": e.type, "attributes": [
                        {"key": base64.b64encode(a.key).decode(),
                         "value": base64.b64encode(a.value).decode(),
                         "index": a.index}
                        for a in e.attributes]}
                    for e in (result.events if result else [])
                ],
            },
        }
        sets = [(b"txr/" + h, envelope.wrap(json.dumps(doc).encode()))]
        postings = [("tx.height", str(height))]
        for e in (result.events if result else []):
            for a in e.attributes:
                if not a.index:
                    continue  # only attributes the app marked indexable
                try:
                    postings.append((f"{e.type}.{a.key.decode()}", a.value.decode()))
                except UnicodeDecodeError:
                    continue
        for key, value in postings:
            pk = f"txe/{_esc(key)}/{_esc(value)}/{height}/{idx}".encode()
            sets.append((pk, envelope.wrap(h)))
        with self._mtx:
            if self._staged is not None:
                self._staged.extend(sets)
            else:
                self._db.write_batch(sets)

    def get(self, h: bytes) -> dict | None:
        key = b"txr/" + h
        return _checked(self._db, key, self._db.get(key), json.loads,
                        on_corruption=self.on_corruption)

    def _scan(self, key: str, op: str, value: str | None) -> set[bytes]:
        """Candidate tx hashes for one condition (reference: kv.go:133
        Search + matchRange). `=` hits the exact posting prefix; range /
        CONTAINS / EXISTS conditions scan the key's postings and filter
        the posted values."""
        if op == "=":
            prefix = f"txe/{_esc(key)}/{_esc(value)}/".encode()
            return {h for h in
                    (self._posting(k, v) for k, v in
                     list(self._db.iterator(prefix, prefix_end(prefix))))
                    if h is not None}
        prefix = f"txe/{_esc(key)}/".encode()
        found = set()
        for k, v in list(self._db.iterator(prefix, prefix_end(prefix))):
            posted = k.decode().split("/")[2].replace("%2F", "/")
            if op == "exists" or tmevents.Query._cmp(op, posted, value):
                h = self._posting(k, v)
                if h is not None:
                    found.add(h)
        return found

    def _posting(self, k: bytes, v: bytes) -> bytes | None:
        """One posting row through the checked path; a corrupt posting is
        quarantined and simply drops out of the candidate set."""
        try:
            return _checked(self._db, k, v, _posting_hash,
                            on_corruption=self.on_corruption)
        except envelope.CorruptedStoreError:
            return None

    def search(self, query: str) -> list[dict]:
        """AND of conditions over the event postings; supports the full
        operator grammar (=, <, <=, >, >=, CONTAINS, EXISTS)."""
        q = tmevents.Query(query)
        conditions = [c for c in q.conditions
                      if c[0] != tmevents.EVENT_TYPE_KEY]
        if not conditions:
            return []
        result_hashes: set[bytes] | None = None
        for key, op, value in conditions:
            found = self._scan(key, op, value)
            result_hashes = found if result_hashes is None else (result_hashes & found)
            if not result_hashes:
                return []
        docs = []
        for h in result_hashes:
            try:
                docs.append(self.get(h))
            except envelope.CorruptedStoreError:
                continue  # quarantined; the posting's doc is gone
        docs = [d for d in docs if d is not None]
        docs.sort(key=lambda d: (int(d["height"]), d["index"]))
        return docs


class BlockIndexer:
    """reference: state/indexer/block/kv/kv.go."""

    def __init__(self, db: DB):
        self._db = db
        self._mtx = threading.Lock()
        self.on_corruption = None

    def index(self, height: int, begin_block_events, end_block_events) -> None:
        sets = [(f"blkh/{height}".encode(),
                 envelope.wrap(str(height).encode()))]
        for stage, evs in (("begin_block", begin_block_events),
                           ("end_block", end_block_events)):
            for e in evs or []:
                for a in e.attributes:
                    if not a.index:
                        continue
                    try:
                        key = f"{e.type}.{a.key.decode()}"
                        value = a.value.decode()
                    except UnicodeDecodeError:
                        continue
                    pk = f"blk/{_esc(key)}/{_esc(value)}/{height}".encode()
                    sets.append((pk, envelope.wrap(str(height).encode())))
        with self._mtx:
            self._db.write_batch(sets)

    def has(self, height: int) -> bool:
        return self._db.get(f"blkh/{height}".encode()) is not None

    def _height_row(self, k: bytes, v: bytes) -> int | None:
        try:
            return _checked(self._db, k, v, _height_str,
                            on_corruption=self.on_corruption)
        except envelope.CorruptedStoreError:
            return None

    def search(self, query: str) -> list[int]:
        q = tmevents.Query(query)
        conditions = [c for c in q.conditions
                      if c[0] != tmevents.EVENT_TYPE_KEY]
        if not conditions:
            return []
        heights: set[int] | None = None
        for key, op, value in conditions:
            if key == "block.height":
                if op == "=":
                    found = {int(value)} if self.has(int(value)) else set()
                else:
                    prefix = b"blkh/"
                    found = set()
                    for k, v in list(self._db.iterator(prefix, prefix_end(prefix))):
                        h = self._height_row(k, v)
                        if h is not None and (
                                op == "exists"
                                or tmevents.Query._cmp(op, str(h), value)):
                            found.add(h)
            elif op == "=":
                prefix = f"blk/{_esc(key)}/{_esc(value)}/".encode()
                found = {h for h in
                         (self._height_row(k, v) for k, v in
                          list(self._db.iterator(prefix, prefix_end(prefix))))
                         if h is not None}
            else:
                prefix = f"blk/{_esc(key)}/".encode()
                found = set()
                for k, v in list(self._db.iterator(prefix, prefix_end(prefix))):
                    posted = k.decode().split("/")[2].replace("%2F", "/")
                    if op == "exists" or tmevents.Query._cmp(op, posted, value):
                        h = self._height_row(k, v)
                        if h is not None:
                            found.add(h)
            heights = found if heights is None else (heights & found)
            if not heights:
                return []
        return sorted(heights)


class IndexerService:
    """Subscribes to the EventBus and feeds both indexers (reference:
    state/txindex/indexer_service.go:19)."""

    SUBSCRIBER = "IndexerService"

    def __init__(self, tx_indexer: TxIndexer, block_indexer: BlockIndexer,
                 event_bus, logger=None):
        self.tx_indexer = tx_indexer
        self.block_indexer = block_indexer
        self.event_bus = event_bus
        self.logger = logger
        self._running = False
        self._thread: threading.Thread | None = None
        self._block_sub = None
        # counters: heights and transactions indexed; the most events (tx
        # and header messages) and the most heights (header messages, the
        # one in hand included) that ever waited for this service
        self.heights_indexed = 0
        self.txs_indexed = 0
        self.backlog_max = 0
        self.backlog_heights_max = 0
        self.last_indexed_height = 0
        self._in_hand = 0
        self._indexed_cv = threading.Condition()
        # called after every height indexed (the node points it at its
        # BlockExecutor.backlog_changed)
        self.on_indexed = None

    def backlog_heights(self) -> int:
        """Heights published to this service and not yet indexed: headers
        waiting, and the height in hand. 0 once the drain thread is gone,
        so nobody waits on a service that will never catch up."""
        sub, t = self._block_sub, self._thread
        if sub is None or not self._running or t is None or not t.is_alive():
            return 0
        return len(sub.queue) + self._in_hand

    def wait_indexed(self, height: int, timeout_s: float) -> bool:
        """Block until every height up to ``height`` is in the index (its
        header's and its transactions' rows written). False on timeout, or
        when the service stops first."""
        with self._indexed_cv:
            return self._indexed_cv.wait_for(
                lambda: self.last_indexed_height >= height
                or not self._running, timeout_s
            ) and self.last_indexed_height >= height

    def start(self) -> None:
        self._tx_sub = self.event_bus.subscribe(
            self.SUBSCRIBER, f"{tmevents.EVENT_TYPE_KEY}={tmevents.EVENT_TX}",
            out_capacity=0)
        self._block_sub = self.event_bus.subscribe(
            self.SUBSCRIBER,
            f"{tmevents.EVENT_TYPE_KEY}={tmevents.EVENT_NEW_BLOCK_HEADER}",
            out_capacity=0)
        self._running = True
        self._thread = threading.Thread(target=self._run, name="indexer",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._running = False
        with self._indexed_cv:
            self._indexed_cv.notify_all()
        try:
            self.event_bus.unsubscribe_all(self.SUBSCRIBER)
        except ValueError:
            pass
        # Join the drain thread so no index write is in flight when callers
        # (e.g. Node.stop) go on to close the sink's DB connection.
        if self._thread is not None and self._thread.is_alive():
            self._thread.join(timeout=2.0)

    def _run(self) -> None:
        try:
            self._drain()
        except tmevents.SubscriptionCancelled:
            return  # unsubscribed during stop()
        except Exception as e:  # noqa: BLE001 - indexing is best-effort;
            # a dead drainer must at least say so
            if self.logger:
                self.logger.error("indexer drain crashed", err=e)

    def _drain(self) -> None:
        # Reference ordering (state/txindex/indexer_service.go:59-75): drive
        # off the header subscription; for each header pull exactly num_txs
        # tx events, index the BLOCK first, then its txs — the SQL sink
        # requires the block row to exist before its tx rows.
        while self._running:
            bmsg = self._block_sub.next(timeout=0.1)
            if bmsg is None:
                continue
            self._in_hand = 1
            # what had piled up when the service turned to this height: the
            # header in hand, those behind it, their transactions' messages
            waiting = len(self._block_sub.queue) + 1
            self.backlog_heights_max = max(self.backlog_heights_max, waiting)
            self.backlog_max = max(self.backlog_max,
                                   waiting + len(self._tx_sub.queue))
            try:
                if not self._index_height(bmsg.data):
                    return
            finally:
                self._in_hand = 0
            if self.on_indexed is not None:
                self.on_indexed()

    def _index_height(self, d) -> bool:
        """One height: its header, then its num_txs transactions. False
        when the service stopped before the height was whole."""
        tr = _trace.current() if _trace.ENABLED else None
        with (tr.span("indexer.height", height=d.header.height,
                      txs=d.num_txs) if tr else _trace.NULL_SPAN):
            # Batch the height: every posting of this block (header + its
            # num_txs tx results) lands in ONE indexer transaction when the
            # backend offers a height_txn seam (kv batches the store write,
            # the SQL sink commits once instead of 1 + num_txs times).
            with contextlib.ExitStack() as stack:
                for indexer in (self.block_indexer, self.tx_indexer):
                    hx = getattr(indexer, "height_txn", None)
                    if hx is not None:
                        stack.enter_context(hx())
                try:
                    self.block_indexer.index(
                        d.header.height,
                        d.result_begin_block.events
                        if d.result_begin_block else [],
                        d.result_end_block.events
                        if d.result_end_block else [])
                except Exception as e:  # noqa: BLE001
                    if self.logger:
                        self.logger.error("failed to index block", err=e)
                for _ in range(d.num_txs):
                    msg = None
                    while self._running and msg is None:
                        msg = self._tx_sub.next(timeout=0.1)
                    if msg is None:
                        return False
                    t = msg.data
                    try:
                        self.tx_indexer.index(t.height, t.index, t.tx,
                                              t.result)
                    except Exception as e:  # noqa: BLE001
                        if self.logger:
                            self.logger.error("failed to index tx", err=e)
            # the transaction is written: the height is in the index
            if tr:
                rows, nbytes = getattr(self.tx_indexer, "last_batch", (0, 0))
                tr.annotate(rows=rows, bytes=nbytes)
        with self._indexed_cv:
            self.heights_indexed += 1
            self.txs_indexed += d.num_txs
            self.last_indexed_height = d.header.height
            self._indexed_cv.notify_all()
        return True
