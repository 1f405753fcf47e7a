"""Mempool: v0 FIFO clist semantics + v1 priority ordering (reference:
mempool/v0/clist_mempool.go:203,372,641, mempool/v1/mempool.go,
mempool/cache.go).

One implementation covers both reference versions behind Config.version:
"v0" reaps in insertion order; "v1" reaps by (priority desc, insertion asc)
using the ABCI CheckTx `priority` field. Gossip iteration (iter_txs) is
always insertion-ordered, mirroring the clist walk the reactors do.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field as dc_field

from tendermint_tpu.abci import types as abci
from tendermint_tpu.mempool.ingest import IngestCoalescer
from tendermint_tpu.mempool import ingest as _ingest
from tendermint_tpu.types.tx import tx_key
from tendermint_tpu.utils import faults
from tendermint_tpu.utils import trace as _trace


class MempoolError(Exception):
    pass


class ErrTxInCache(MempoolError):
    def __init__(self):
        super().__init__("tx already exists in cache")


class ErrMempoolIsFull(MempoolError):
    def __init__(self, n, max_n, nbytes, max_bytes):
        super().__init__(
            f"mempool is full: number of txs {n} (max: {max_n}), total txs bytes {nbytes} (max: {max_bytes})"
        )


class ErrTxTooLarge(MempoolError):
    def __init__(self, max_size, size):
        super().__init__(f"Tx too large. Max size is {max_size}, but got {size}")


class ErrPreCheck(MempoolError):
    pass


@dataclass
class MempoolTx:
    tx: bytes
    height: int  # height at which tx entered the pool
    gas_wanted: int = 0
    priority: int = 0
    sender: str = ""
    seq: int = 0
    senders: set = dc_field(default_factory=set)  # peer ids that sent it
    time: float = 0.0  # wall clock at entry (TTL eviction)


class TxCache:
    """LRU dedup cache (reference: mempool/cache.go)."""

    def __init__(self, size: int):
        self.size = size
        self._map: OrderedDict[bytes, None] = OrderedDict()
        self._mtx = threading.Lock()

    def push(self, tx: bytes) -> bool:
        """False if already present."""
        k = tx_key(tx)
        with self._mtx:
            if k in self._map:
                self._map.move_to_end(k)
                return False
            self._map[k] = None
            if len(self._map) > self.size:
                self._map.popitem(last=False)
            return True

    def contains(self, tx: bytes) -> bool:
        """Peek without the LRU bump (the batch pre-filter's dedup probe;
        the authoritative push happens at the replay's serial position)."""
        with self._mtx:
            return tx_key(tx) in self._map

    def remove(self, tx: bytes) -> None:
        with self._mtx:
            self._map.pop(tx_key(tx), None)

    def reset(self) -> None:
        with self._mtx:
            self._map.clear()


class Mempool:
    def __init__(self, app, *, version: str = "v0", max_txs: int = 5000,
                 max_txs_bytes: int = 1024 * 1024 * 1024,
                 cache_size: int = 10000, max_tx_bytes: int = 1024 * 1024,
                 keep_invalid_txs_in_cache: bool = False,
                 recheck: bool = True,
                 ttl_duration_s: float = 0.0, ttl_num_blocks: int = 0):
        self.app = app  # proxy.AppConnMempool-like
        self.version = version
        self.max_txs = max_txs
        self.max_txs_bytes = max_txs_bytes
        self.max_tx_bytes = max_tx_bytes
        self.keep_invalid = keep_invalid_txs_in_cache
        self.recheck = recheck
        # 0 disables each bound (reference: mempool/v1/mempool.go
        # purgeExpiredTxs; config.toml ttl-duration / ttl-num-blocks)
        self.ttl_duration_s = ttl_duration_s
        self.ttl_num_blocks = ttl_num_blocks

        self.cache = TxCache(cache_size)
        self._txs: OrderedDict[bytes, MempoolTx] = OrderedDict()  # key -> tx
        self._txs_bytes = 0
        self._height = 0
        self._seq = 0
        self._mtx = threading.RLock()
        self._notified_available = False
        self._txs_available: threading.Event | None = None
        self.pre_check = None   # fn(tx) -> raises ErrPreCheck
        self.post_check = None  # fn(tx, res) -> raises
        # flight recorder (utils/trace.py): node wiring installs the node's
        # tracer; None = untraced (standalone mempools, tests)
        self.tracer = None
        # the micro-batching front door (mempool/ingest.py): lazy executor,
        # costs nothing until the first ingest_tx/ingest_txs submission
        self._ingest = IngestCoalescer(self)

    # --- Mempool interface (reference: mempool/mempool.go:14-90) -----------

    def size(self) -> int:
        with self._mtx:
            return len(self._txs)

    def size_bytes(self) -> int:
        with self._mtx:
            return self._txs_bytes

    def lock(self) -> None:
        self._mtx.acquire()

    def unlock(self) -> None:
        self._mtx.release()

    def enable_txs_available(self) -> None:
        self._txs_available = threading.Event()

    def txs_available(self) -> threading.Event | None:
        return self._txs_available

    def check_tx(self, tx: bytes, sender_peer: str = "") -> abci.ResponseCheckTx:
        """Synchronous CheckTx (reference: mempool/v0/clist_mempool.go:203).

        INVARIANT: check_tx_batch's phase-2 replay below mirrors this
        decision procedure step for step; any semantic change here MUST be
        mirrored there (the batched path's bit-identical guarantee is
        differentially gated by tests/test_ingest.py and
        __graft_entry__.ingest_stage, which will fail loudly on drift)."""
        if len(tx) > self.max_tx_bytes:
            raise ErrTxTooLarge(self.max_tx_bytes, len(tx))
        if self.pre_check is not None:
            self.pre_check(tx)
        with self._mtx:
            full = (len(self._txs) >= self.max_txs
                    or self._txs_bytes + len(tx) > self.max_txs_bytes)
            if full and self.version != "v1":
                # v0 rejects when full; v1 may evict lower-priority txs
                # AFTER the app has priced the newcomer (see below).
                raise ErrMempoolIsFull(len(self._txs), self.max_txs,
                                       self._txs_bytes, self.max_txs_bytes)
        if not self.cache.push(tx):
            # record extra sender for gossip suppression
            with self._mtx:
                existing = self._txs.get(tx_key(tx))
                if existing is not None and sender_peer:
                    existing.senders.add(sender_peer)
            raise ErrTxInCache()

        tr = self.tracer
        if tr is not None and tr.enabled:
            with tr.span("mempool.check_tx", bytes=len(tx)):
                res = self.app.check_tx(
                    abci.RequestCheckTx(tx=tx, type=abci.CHECK_TX_TYPE_NEW))
        else:
            res = self.app.check_tx(
                abci.RequestCheckTx(tx=tx, type=abci.CHECK_TX_TYPE_NEW))
        if self.post_check is not None:
            try:
                self.post_check(tx, res)
            except Exception:
                # post-check failure = invalid tx (reference resCbFirstTime):
                # it must not stay cached unless keep_invalid says so
                if not self.keep_invalid:
                    self.cache.remove(tx)
                raise
        if res.is_ok():
            with self._mtx:
                self._make_room_locked(tx, res.priority)
                self._seq += 1
                mtx = MempoolTx(tx=tx, height=self._height,
                                gas_wanted=res.gas_wanted, priority=res.priority,
                                sender=res.sender, seq=self._seq,
                                time=time.monotonic())
                if sender_peer:
                    mtx.senders.add(sender_peer)
                self._txs[tx_key(tx)] = mtx
                self._txs_bytes += len(tx)
                self._notify_txs_available()
        else:
            if not self.keep_invalid:
                self.cache.remove(tx)
        return res

    # --- the micro-batched front door (mempool/ingest.py, docs/INGEST.md) --

    def ingest_tx(self, tx: bytes, sender_peer: str = "") -> abci.ResponseCheckTx:
        """The coalesced front door: same returns and same raises as
        check_tx, but concurrent callers (RPC handler threads, gossip recv
        threads) share batched CheckTx dispatches through the ingest
        coalescer. TMTPU_INGEST=0 restores the serial path verbatim."""
        if not _ingest.enabled():
            return self.check_tx(tx, sender_peer)
        p = self._ingest.submit(tx, sender_peer)
        tr = self.tracer
        if tr is not None and tr.enabled:
            t0 = time.monotonic()
            try:
                return p.wait()
            finally:
                tr.record("mempool.ingest_wait", time.monotonic() - t0)
        return p.wait()

    def ingest_txs(self, txs: list[bytes], sender_peer: str = "") -> list:
        """Multi-tx front door (gossip deliveries): per-tx outcomes —
        a ResponseCheckTx where the serial loop would return one, the
        exception instance where it would raise. Never raises itself."""
        if not _ingest.enabled():
            out = []
            for tx in txs:
                try:
                    out.append(self.check_tx(tx, sender_peer))
                except Exception as e:  # noqa: BLE001 - outcome, not error
                    out.append(e)
            return out
        pendings = [self._ingest.submit(tx, sender_peer) for tx in txs]
        for p in pendings:
            p.done.wait()
        return [p.outcome for p in pendings]

    def check_tx_batch(self, txs: list[bytes], senders: list[str] | None = None,
                       tx_type: int = abci.CHECK_TX_TYPE_NEW) -> list:
        """Admit a micro-batch through ONE batched ABCI CheckTx and ONE
        mempool lock acquisition (docs/INGEST.md).

        Returns a per-tx outcome list, order-aligned with ``txs``: a
        ResponseCheckTx where the serial check_tx would return one, the
        exact exception INSTANCE where it would raise. The decision
        procedure IS the serial loop's, replayed in original order under
        the lock — admission verdicts, v1 eviction, priority order, cache
        effects, and per-sender attribution are bit-identical to N serial
        calls; only the app round trip is batched. (A tx the replay later
        rejects as full may have been priced by the app anyway — CheckTx
        is stateless by ABCI contract, as in the reference's async
        mempool.) A failure of the batched dispatch itself — injected
        fault, transport error, a pre-batch remote app — degrades to the
        serial per-tx loop, so every caller still gets the serial path's
        exact outcome."""
        n = len(txs)
        if senders is None:
            senders = [""] * n
        out: list = [None] * n
        # --- phase 1: per-tx pre-verdicts + the app-batch candidate set ----
        # (size/pre_check verdicts are final; the cache probe only decides
        # who rides the batched dispatch — the authoritative push happens
        # at each tx's serial position in the replay below)
        need: list[int] = []
        seen: set[bytes] = set()
        for i, tx in enumerate(txs):
            if len(tx) > self.max_tx_bytes:
                out[i] = ErrTxTooLarge(self.max_tx_bytes, len(tx))
                continue
            if self.pre_check is not None:
                try:
                    self.pre_check(tx)
                except Exception as e:  # noqa: BLE001 - serial raises it
                    out[i] = e
                    continue
            k = tx_key(tx)
            if k in seen or self.cache.contains(tx):
                # expected duplicate: no app call; the replay confirms via
                # the real cache.push (and falls back to a serial app call
                # when the earlier copy was un-cached in the meantime)
                continue
            seen.add(k)
            need.append(i)
        # --- the batched app round trips (outside the mempool lock) --------
        responses: dict[int, object] = {}
        if need:
            batch = [txs[i] for i in need]
            try:
                faults.fire("mempool.ingest")
                tr = self.tracer
                if tr is not None and tr.enabled:
                    with tr.span("mempool.ingest_batch", n=len(batch)):
                        rs = self._batched_app_check(batch, tx_type)
                else:
                    rs = self._batched_app_check(batch, tx_type)
                for i, r in zip(need, rs):
                    responses[i] = r
            except Exception:  # noqa: BLE001 - degrade to the serial loop
                for i in need:
                    try:
                        responses[i] = self.app.check_tx(
                            abci.RequestCheckTx(tx=txs[i], type=tx_type))
                    except Exception as e:  # noqa: BLE001 - per-tx outcome
                        responses[i] = e
        # --- phase 2: serial-order replay under ONE lock acquisition -------
        # INVARIANT: this loop IS check_tx's decision procedure (see its
        # docstring) — keep the two in lockstep; the differential gates
        # (tests/test_ingest.py, __graft_entry__.ingest_stage) fail on drift.
        pushed: set[int] = set()
        i = 0
        while i < n:
            deferred = -1
            with self._mtx:
                while i < n:
                    if out[i] is not None:
                        i += 1
                        continue
                    tx = txs[i]
                    full = (len(self._txs) >= self.max_txs
                            or self._txs_bytes + len(tx) > self.max_txs_bytes)
                    if full and self.version != "v1":
                        # v0 rejects-when-full BEFORE the cache push, so a
                        # retry after commit is not refused as a duplicate
                        out[i] = ErrMempoolIsFull(
                            len(self._txs), self.max_txs,
                            self._txs_bytes, self.max_txs_bytes)
                        i += 1
                        continue
                    if i not in pushed:
                        if not self.cache.push(tx):
                            existing = self._txs.get(tx_key(tx))
                            if existing is not None and senders[i]:
                                existing.senders.add(senders[i])
                            out[i] = ErrTxInCache()
                            i += 1
                            continue
                        pushed.add(i)
                    res = responses.get(i)
                    if res is None:
                        # a duplicate whose earlier copy was un-cached
                        # before the replay reached it: the serial path
                        # would call the app HERE — do so outside the lock
                        deferred = i
                        break
                    if isinstance(res, Exception):
                        # serial semantics: an app blow-up propagates
                        # AFTER the cache push, with the tx left cached
                        out[i] = res
                        i += 1
                        continue
                    if self.post_check is not None:
                        try:
                            self.post_check(tx, res)
                        except Exception as e:  # noqa: BLE001 - verdict
                            if not self.keep_invalid:
                                self.cache.remove(tx)
                            out[i] = e
                            i += 1
                            continue
                    if res.is_ok():
                        try:
                            self._make_room_locked(tx, res.priority)
                        except MempoolError as e:
                            out[i] = e
                            i += 1
                            continue
                        self._seq += 1
                        mtx = MempoolTx(
                            tx=tx, height=self._height,
                            gas_wanted=res.gas_wanted, priority=res.priority,
                            sender=res.sender, seq=self._seq,
                            time=time.monotonic())
                        if senders[i]:
                            mtx.senders.add(senders[i])
                        self._txs[tx_key(tx)] = mtx
                        self._txs_bytes += len(tx)
                        self._notify_txs_available()
                    else:
                        if not self.keep_invalid:
                            self.cache.remove(tx)
                    out[i] = res
                    i += 1
            if deferred >= 0:
                try:
                    responses[deferred] = self.app.check_tx(
                        abci.RequestCheckTx(tx=txs[deferred], type=tx_type))
                except Exception as e:  # noqa: BLE001 - per-tx outcome
                    responses[deferred] = e
        self._observe_batch(n, out)
        return out

    # The ABCI wire caps one message at 100 MiB (abci/wire.py
    # MAX_MSG_SIZE); a front-door batch of max_tx_bytes-sized txs (or a
    # whole-pool recheck) must never be able to exceed it and kill the
    # mempool connection. Chunked well under the cap.
    BATCH_MAX_BYTES = 8 * 1024 * 1024

    def _batched_app_check(self, txs: list[bytes], tx_type: int) -> list:
        """One or more RequestCheckTxBatch round trips, chunked under
        BATCH_MAX_BYTES. Returns responses order-aligned with ``txs``;
        raises (to the caller's serial fallback) on a response-shape
        mismatch or transport failure."""
        out: list = []
        start = 0
        n = len(txs)
        while start < n:
            nbytes = 0
            end = start
            while end < n and (end == start
                               or nbytes + len(txs[end]) <= self.BATCH_MAX_BYTES):
                nbytes += len(txs[end])
                end += 1
            chunk = txs[start:end]
            resp = self.app.check_tx_batch(
                abci.RequestCheckTxBatch(txs=chunk, type=tx_type))
            if len(resp.responses) != len(chunk):
                raise MempoolError(
                    f"CheckTxBatch returned {len(resp.responses)} responses "
                    f"for {len(chunk)} txs")
            out.extend(resp.responses)
            start = end
        return out

    def _observe_batch(self, n: int, out: list) -> None:
        """Pre-seeded ingest metrics (utils/metrics.py, tmlint
        metrics-discipline); counters must never be able to fail a batch."""
        try:
            from tendermint_tpu.utils import metrics as tmmetrics

            m = tmmetrics.GLOBAL_NODE_METRICS
            if m is None:
                return
            m.ingest_batch_size.observe(n)
            ok = sum(1 for o in out
                     if not isinstance(o, Exception) and o.is_ok())
            m.ingest_txs.add(ok, result="ok")
            m.ingest_txs.add(n - ok, result="reject")
        except Exception:  # noqa: BLE001 - observability never blocks txs
            pass

    def _make_room_locked(self, tx: bytes, priority: int) -> None:
        """v1 full-pool admission (reference: mempool/v1/mempool.go:505-577):
        evict strictly-lower-priority txs, lowest first (ties: newest
        first), until the newcomer fits; if the eligible victims can't make
        enough room, reject it — and drop it from the dedup cache so a
        later retry isn't refused as a duplicate."""
        need_count = 1 if len(self._txs) >= self.max_txs else 0
        need_bytes = max(0, self._txs_bytes + len(tx) - self.max_txs_bytes)
        if not need_count and not need_bytes:
            return
        if self.version != "v1":
            # v0 reached here only via a fill-up race between the unlocked
            # pre-check and insertion: reject-when-full, never evict.
            self.cache.remove(tx)
            raise ErrMempoolIsFull(len(self._txs), self.max_txs,
                                   self._txs_bytes, self.max_txs_bytes)
        victims = [m for m in self._txs.values() if m.priority < priority]
        # Feasibility mirrors the reference exactly (mempool/v1/mempool.go
        # canAddTx caller): reject unless the victims' TOTAL size covers the
        # FULL size of the incoming tx — not merely the byte overflow
        # (round-4 advisor finding: the overflow comparison admitted txs in
        # near-full edge cases the reference rejects).
        if not victims or sum(len(v.tx) for v in victims) < len(tx):
            self.cache.remove(tx)
            raise ErrMempoolIsFull(len(self._txs), self.max_txs,
                                   self._txs_bytes, self.max_txs_bytes)
        victims.sort(key=lambda m: (m.priority, -m.seq))
        freed_bytes = freed_count = 0
        for v in victims:
            del self._txs[tx_key(v.tx)]
            self._txs_bytes -= len(v.tx)
            self.cache.remove(v.tx)
            freed_bytes += len(v.tx)
            freed_count += 1
            if freed_bytes >= need_bytes and freed_count >= need_count:
                break

    def reap_max_bytes_max_gas(self, max_bytes: int, max_gas: int) -> list[bytes]:
        """reference: mempool/v0/clist_mempool.go:519-555; v1 orders by
        priority."""
        from tendermint_tpu.encoding.proto import encode_uvarint

        with self._mtx:
            entries = list(self._txs.values())
            if self.version == "v1":
                entries.sort(key=lambda m: (-m.priority, m.seq))
            out = []
            total_bytes = 0
            total_gas = 0
            for m in entries:
                aux = len(m.tx) + len(encode_uvarint(len(m.tx))) + 1
                if max_bytes > -1 and total_bytes + aux > max_bytes:
                    break
                if max_gas > -1 and total_gas + m.gas_wanted > max_gas:
                    break
                total_bytes += aux
                total_gas += m.gas_wanted
                out.append(m.tx)
            return out

    def reap_max_txs(self, n: int) -> list[bytes]:
        with self._mtx:
            entries = list(self._txs.values())
            if self.version == "v1":
                entries.sort(key=lambda m: (-m.priority, m.seq))
            if n < 0:
                n = len(entries)
            return [m.tx for m in entries[:n]]

    def update(self, height: int, txs: list[bytes],
               deliver_tx_responses: list[abci.ResponseDeliverTx] | None = None,
               pre_check=None, post_check=None) -> None:
        """Remove committed txs; recheck the rest (reference:
        mempool/v0/clist_mempool.go:577-639). Caller must hold the lock.
        pre_check/post_check, when given, replace the admission filters —
        they derive from the NEW state (state/tx_filter.py)."""
        # in the ring of whoever applies the block, under its apply.save
        with (_trace.current().span("mempool.update", height=height,
                                    txs=len(txs))
              if _trace.ENABLED else _trace.NULL_SPAN):
            self._update(height, txs, deliver_tx_responses, pre_check,
                         post_check)

    def _update(self, height, txs, deliver_tx_responses, pre_check,
                post_check) -> None:
        if pre_check is not None:
            self.pre_check = pre_check
        if post_check is not None:
            self.post_check = post_check
        self._height = height
        self._notified_available = False
        for i, tx in enumerate(txs):
            ok = deliver_tx_responses is None or deliver_tx_responses[i].is_ok()
            if ok:
                self.cache.push(tx)  # committed: keep in cache to reject re-adds
            elif not self.keep_invalid:
                self.cache.remove(tx)
            k = tx_key(tx)
            m = self._txs.pop(k, None)
            if m is not None:
                self._txs_bytes -= len(m.tx)
        self._purge_expired(height)
        if self.recheck and self._txs:
            self._recheck_txs()
        if self._txs:
            self._notify_txs_available()

    def _purge_expired(self, height: int) -> None:
        """Evict txs past their TTL (reference: mempool/v1/mempool.go
        purgeExpiredTxs): ttl_num_blocks bounds blocks-in-pool,
        ttl_duration_s bounds wall-clock age; either at 0 is disabled.
        Expired txs leave the cache too, so a later resubmission is not
        rejected as a duplicate. Caller must hold the lock."""
        if not self.ttl_num_blocks and not self.ttl_duration_s:
            return
        now = time.monotonic()
        for k in list(self._txs.keys()):
            m = self._txs[k]
            expired = (
                (self.ttl_num_blocks > 0
                 and height - m.height > self.ttl_num_blocks)
                or (self.ttl_duration_s > 0
                    and now - m.time > self.ttl_duration_s))
            if expired:
                del self._txs[k]
                self._txs_bytes -= len(m.tx)
                self.cache.remove(m.tx)

    def _recheck_txs(self) -> None:
        """reference: mempool/v0/clist_mempool.go:641-664; the post-check
        filter applies on recheck too (resCbRecheck -> postCheck), so a
        max_gas tightened by the applied block evicts over-priced txs.

        The app round trips ride the batched CheckTx path (ONE
        RequestCheckTxBatch for the whole pool, docs/INGEST.md); the
        eviction replay below is unchanged, so recheck survivors are
        bit-identical to the serial loop. A batch-dispatch failure (or a
        pre-batch remote app) degrades to the per-tx loop."""
        keys = list(self._txs.keys())
        responses = None
        if len(keys) > 1 and getattr(self.app, "check_tx_batch", None) is not None:
            txs = [self._txs[k].tx for k in keys]
            try:
                faults.fire("mempool.ingest")
                responses = self._batched_app_check(
                    txs, abci.CHECK_TX_TYPE_RECHECK)
            except Exception:  # noqa: BLE001 - serial fallback below
                responses = None
        for idx, k in enumerate(keys):
            m = self._txs[k]
            if responses is not None:
                res = responses[idx]
            else:
                res = self.app.check_tx(abci.RequestCheckTx(
                    tx=m.tx, type=abci.CHECK_TX_TYPE_RECHECK))
            ok = res.is_ok()
            if ok and self.post_check is not None:
                try:
                    self.post_check(m.tx, res)
                except Exception:  # noqa: BLE001 - filter verdict, not error
                    ok = False
            if not ok:
                del self._txs[k]
                self._txs_bytes -= len(m.tx)
                if not self.keep_invalid:
                    self.cache.remove(m.tx)

    def flush(self) -> None:
        with self._mtx:
            self._txs.clear()
            self._txs_bytes = 0
            self.cache.reset()

    def remove_tx_by_key(self, key: bytes) -> None:
        with self._mtx:
            m = self._txs.pop(key, None)
            if m is not None:
                self._txs_bytes -= len(m.tx)
                self.cache.remove(m.tx)

    def iter_txs(self) -> list[MempoolTx]:
        """Insertion-ordered snapshot for gossip (the clist walk)."""
        with self._mtx:
            return list(self._txs.values())

    def _notify_txs_available(self) -> None:
        if self._txs_available is not None and not self._notified_available:
            self._notified_available = True
            self._txs_available.set()
