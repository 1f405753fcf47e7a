"""The ``full-sync`` mix: a full node catching up by fast sync on a chain
whose blocks are full, through a node's own composition.

One caller, closed loop. A **pass** is what a full node, a sentry, an archive
node, an explorer's backend or a restarted validator does when it joins a
chain that carries transactions: ``Node(Config().set_root(home),
default_app("kvstore"), genesis)`` in a new home directory, as the CLI
constructs it (every default: sqlite block, state and index stores, the kv
indexer subscribed through the event bus, mempool, evidence pool, handshake,
the v0 ``BlockchainReactor`` with fast sync on; no persistent peer, not a
validator). Of that node the driver starts the indexer service (the event
bus has nothing to start) and never the switch, the RPC or consensus. The
chain's blocks (``drivers/fullchain.py``) go into ``node.bc_reactor.pool`` as
two peers' deliveries, decoded anew from their bytes for every pass, and
``VerifyAheadPipeline.process_next(node.bc_reactor)`` -- the call
``BlockchainReactor._try_sync`` makes -- runs until every appliable height is
applied. Then the driver waits until the index holds the last height
(``IndexerService.wait_indexed``), **and only then the pass's clock stops**:
``run.passes`` gets whole passes timed from the first ``process_next`` to the
index holding the last height, so ``catchup_blocks_per_s`` reads blocks
verified, executed, saved **and indexed** per second. One decision is one
height applied (its signatures: the light prefix).

Outside the timed part: before a pass ``crypto.batch.forget_keys()`` (a pass
starts as a new process does), the node built and its pool filled; after it
the node stopped (``Node.stop``, ``Node.close_stores``). Its directory, under
``benchmark/.homes/``, is kept until ``check`` has read it back and is then
removed.

``check`` (outside the window, every run, every comparison exact) holds the
warm-up pass and every pass of the window to the configuration's guarantees:
 (a)/(b) the plain reference (``benchmark/reference/block_replay.py``)
     replays the chain's block bytes: its own ``data_hash``, part-set
     headers, kvstore, app hash and ``last_results_hash``; the node ended at
     the last height with the reference's app hash and ``last_results_hash``,
     and a seeded sample of keys reads back from the app;
 (c) the stopped node's files are opened again by new connections: the block
     store and the state store hold the last height and the reference's
     state, every stored header names the reference's three hashes, and at a
     seeded sample of 8 heights the stored parts are the block's bytes cut at
     65,536, part by part;
 (d) the reopened index answers ``get(hash)`` for a seeded sample of 64
     transactions with the right height, index and bytes, and
     ``search("tx.height=H")`` returns every transaction of the sampled
     heights;
 (e) the backlog behind ``apply_block`` stayed within the bound the
     configuration states (the counters' maxima are in the notes of every
     run);
 (f) one pass per corruption on a copy of the chain, rejected where the
     reference rejects it, the heights below applied, saved and indexed, both
     sending peers dropped: a flipped byte inside a transaction of a middle
     height (its bytes miss their header's ``data_hash``, and before that the
     part-set header its commit signed, which is where a syncing node and the
     reference see it first), and a flipped signature bit inside a light
     prefix;
 (g) ``correct.check_decisions`` on the pooled commits, as the other cells
     (breakers, fall-backs, compiles and variables are ``run.py``'s).

**A program without the seams cannot run this cell** and is told so when
this file is loaded, before any data is made (``spec.SpecError``: the harness
refuses, exit 2, within seconds).
"""

from __future__ import annotations

import base64
import hashlib
import os
import shutil
import time
from types import SimpleNamespace

from benchmark.drivers import fullchain
from benchmark.harness import correct, datagen, spans, spec
from benchmark.reference import block_replay

try:
    from tendermint_tpu.crypto.batch import forget_keys
    from tendermint_tpu.state.txindex import IndexerService
except ImportError as e:
    raise spec.SpecError(
        "the full-sync mix needs a program with crypto.batch.forget_keys and "
        "state.txindex.IndexerService; this one lacks one") from e
if not hasattr(IndexerService, "wait_indexed"):
    raise spec.SpecError(
        "the full-sync mix needs a program whose IndexerService has "
        "wait_indexed(height, timeout_s) (a pass's clock stops when the index "
        "holds the last height) and counts its backlog; this one has neither")
if not spans._program_has("store.save_block"):
    raise spec.SpecError(
        "the full-sync mix needs a program that traces what a block's body "
        "causes (fastsync.part_set, store.save_block, indexer.height, ...) "
        "and records what its invalid-block path refused "
        "(BlockchainReactor.last_invalid); this one does not")

HOMES_DIR = os.path.join(spec.BENCH_DIR, ".homes")
PEERS = ("pA", "pB")
SAMPLE_HEIGHTS = 8
SAMPLE_TXS = 64
REFERENCE_SAMPLE = 4
INDEX_TIMEOUT_S = 120.0


# What a pass left behind, read before its node was stopped (a plain
# namespace: spec.py loads this file outside sys.modules, where a dataclass
# cannot be made):
#   home, applied (heights), indexed (the index held the last applied
#   height), t = (t0, last process_next, index caught up), state (the
#   node's after the last apply), app_sample (key -> value the app answered),
#   pipeline (dispatched, discarded, in flight), counters (post-commit and
#   indexer), invalid = None or (height, the exception's type, its index, the
#   peers dropped, its message), scored (peers the scoreboard holds)
PassRecord = SimpleNamespace


class Driver:
    def __init__(self, run, dataset, traffic: dict):
        self.run, self.ds, self.traffic = run, dataset, traffic
        cfg = dict(run.cell.config)
        if run.rehearse:
            cfg["dataset"] = {**cfg["dataset"], **cfg["rehearse"]}
        self.chain = fullchain.load_or_generate(
            run.cell.config_name + ("-rehearse" if run.rehearse else ""),
            dataset, cfg, run.seed)
        self.heights = self.chain.heights
        self.sigs = self.chain.prefix_sigs
        self.max_backlog = cfg.get("max_backlog_heights")
        run.notes["chain"] = {
            **{k: v for k, v in self.chain.meta.items() if k != "config"},
            "heights": self.heights,
            "txs_per_block": self.chain.txs_per_block,
            "tx_bytes": self.chain.tx_bytes,
            "light_prefix_sigs": [min(self.sigs), max(self.sigs)],
            "sigs_a_pass": sum(self.sigs)}
        self.records = []          # PassRecord of every whole pass
        self._homes = 0
        shutil.rmtree(self._home_prefix(), ignore_errors=True)

    # --- one pass ------------------------------------------------------------

    def _home_prefix(self) -> str:
        return os.path.join(HOMES_DIR, f"{self.run.cell.name}-{self.run.seed}"
                            + ("-rehearse" if self.run.rehearse else ""))

    def _node(self, raws):
        """A new default node in a new home, the chain's blocks in its pool
        -> (node, home)."""
        from tendermint_tpu.config.config import Config
        from tendermint_tpu.node.node import Node, default_app
        from tendermint_tpu.types.block import Block

        home = os.path.join(self._home_prefix(), f"pass-{self._homes}")
        self._homes += 1
        os.makedirs(home)
        node = Node(Config().set_root(home), default_app("kvstore"),
                    self.chain.genesis)
        node.indexer_service.start()
        for i, raw in enumerate(raws):
            node.bc_reactor.pool.add_block(PEERS[i % 2], Block.unmarshal(raw))
        return node, home

    def _pass(self, raws, decide, sample=()) -> PassRecord:
        from tendermint_tpu.abci.types import RequestQuery
        from tendermint_tpu.blockchain.pipeline import VerifyAheadPipeline

        node, home = self._node(raws)
        reactor, pipe = node.bc_reactor, VerifyAheadPipeline()
        forget_keys()
        applied = 0
        t0 = time.monotonic()
        while applied < self.heights and decide(
                lambda: pipe.process_next(reactor), self.sigs[applied]):
            applied += 1
        t_sync = time.monotonic()
        indexed = (applied == 0 or node.indexer_service.wait_indexed(
            applied, INDEX_TIMEOUT_S))
        t1 = time.monotonic()
        state, worker, idx = reactor.state, node.block_exec.post_commit, \
            node.indexer_service
        invalid = reactor.last_invalid
        record = PassRecord(
            home=home, applied=applied, indexed=indexed,
            t=(t0, t_sync, t1),
            state={"height": state.last_block_height,
                   "app_hash": state.app_hash,
                   "last_results_hash": state.last_results_hash},
            app_sample={k: node.proxy_app.query.query(
                RequestQuery(data=k)).value for k in sample},
            pipeline={"dispatched": pipe.dispatched,
                      "discarded": pipe.discarded, "in_flight": len(pipe)},
            counters={"post_commit_submitted": worker.submitted,
                      "post_commit_done": worker.done,
                      "post_commit_backlog_max": worker.backlog_max,
                      "backlog_waits": node.block_exec.backlog_waits,
                      "heights_indexed": idx.heights_indexed,
                      "txs_indexed": idx.txs_indexed,
                      "indexer_backlog_max": idx.backlog_max,
                      "indexer_backlog_heights_max": idx.backlog_heights_max},
            invalid=None if invalid is None else (
                invalid[0], type(invalid[1]).__name__,
                getattr(invalid[1], "index", None), list(invalid[2]),
                str(invalid[1])),
            scored=sorted(node.switch.scoreboard.snapshot()["scores"]))
        node.stop()
        node.close_stores()
        return record

    def _key_sample(self, ref) -> list:
        keys = sorted(ref["store"])
        return [keys[datagen.pick(self.run.seed, len(keys), "app-key", j)]
                for j in range(min(SAMPLE_TXS, len(keys)))]

    def warm_up(self) -> None:
        # the reference's replay of the clean chain, outside the window: the
        # passes read a sample of its keys back from the app while the node
        # is up
        t0 = time.monotonic()
        self.verify_at = sorted({
            1 + datagen.pick(self.run.seed, self.heights, "ref-height", j)
            for j in range(REFERENCE_SAMPLE)})
        self.ref = self._reference(self.chain.raws, self.verify_at)
        self.run.notes["reference"] = {
            "heights_verified": len(self.verify_at),
            "signatures_verified": sum(len(self.ref["prefixes"][h])
                                       for h in self.verify_at
                                       if h in self.ref["prefixes"]),
            "keys": len(self.ref["store"]), "seconds": time.monotonic() - t0}
        self.sample = self._key_sample(self.ref)
        for _ in range(self.traffic["warmup_passes"]):
            record = self._pass(self.chain.raws, lambda fn, _sigs: fn(),
                                sample=self.sample)
            if record.applied != self.heights:
                self.run.failures.append(
                    f"warm-up pass applied {record.applied} of "
                    f"{self.heights} heights, rejected {record.invalid}")
            self.records.append(record)

    def measure(self) -> None:
        run = self.run
        run.open_window("process_next")
        while run.elapsed() < run.seconds:
            record = self._pass(self.chain.raws, run.decide,
                                sample=self.sample)
            if record.applied != self.heights or not record.indexed:
                run.failures.append(
                    f"pass applied {record.applied} of {self.heights} "
                    f"heights (indexed: {record.indexed}), rejected "
                    f"{record.invalid}")
                break
            run.passes.append((record.t[0], record.t[2], self.heights))
            self.records.append(record)
        run.close_window()

    # --- correctness -----------------------------------------------------------

    def _reference(self, raws, verify_at):
        ref = block_replay.replay(
            self.ds.chain_id,
            [(v.pub_key.bytes(), v.power) for v in self.chain.genesis.validators],
            raws, [bid.hash for bid in self.chain.block_ids], verify_at)
        ref["raws"] = raws          # the bytes it replayed, for the read-back
        return ref

    def check(self) -> None:
        run, ref = self.run, self.ref
        fail = run.failures.append
        try:
            if (ref["refused"]
                    or ref["applied"] != list(range(1, self.heights + 1))):
                fail(f"the reference refuses the clean chain: "
                     f"{ref['refused']}, {len(ref['applied'])} heights applied")
                return
            if [len(ref["prefixes"][h]) for h in ref["applied"]] != self.sigs:
                fail("the light prefixes of the program's set differ in "
                     "length from those of the reference's")
            window = self.records[len(self.records) - len(run.passes):]
            self._note_window(window)
            # (a)-(e): every whole pass, its files opened again
            for k, record in enumerate(self.records):
                why = self._differs(record, ref, self.heights)
                if why:
                    fail(f"pass {k} (0 is the warm-up): {why}")
            # (f) corrupted chains, refused where the reference refuses them
            for name, (raws, kind, at) in self._corruptions(ref).items():
                bad = self._reference(raws, {at})
                want = bad["refused"]
                record = self._pass(raws, lambda fn, _sigs: fn())
                got = record.invalid
                run.notes.setdefault("rejected", {})[name] = {
                    "reference": want, "program": got,
                    "applied": record.applied}
                shown = {"commit_block_id": ("ValueError", "different block"),
                         "wrong_signature": ("ErrWrongSignature", "")}[kind]
                if (want is None or want[:2] != (at, kind)
                        or got is None or got[0] != want[0]
                        or got[1] != shown[0] or shown[1] not in got[4]
                        or got[2] != want[2]
                        or record.applied != want[0] - 1
                        or got[3] != sorted(PEERS)
                        or record.scored != sorted(PEERS)
                        or bad.get("data_hash_differs",
                                   True) is not True):
                    fail(f"{name}: the reference refuses {want}; the program "
                         f"applied {record.applied} heights, rejected {got}, "
                         f"scored {record.scored}")
                why = self._differs(record, bad, want[0] - 1 if want else 0)
                if why:
                    fail(f"{name}: below the refused height: {why}")
            # (g) the pooled commits, as every cell
            correct.check_decisions(run, self.ds,
                                    [self.ds.vals.verify_commit_light,
                                     self.ds.vals.verify_commit])
        finally:
            shutil.rmtree(self._home_prefix(), ignore_errors=True)

    def _note_window(self, window) -> None:
        """What the readers of ``layer_metrics/full_*`` take from the
        driver: the window's whole passes."""
        if not window:
            return
        self.run.notes["full"] = {
            "passes": len(window),
            "index_lag_s": [r.t[2] - r.t[1] for r in window],
            "sync_s": [r.t[1] - r.t[0] for r in window],
            "backlog_max_heights": max(
                max(r.counters["post_commit_backlog_max"],
                    r.counters["indexer_backlog_heights_max"])
                for r in window),
            "backlog_waits": sum(r.counters["backlog_waits"] for r in window),
            "counters": window[-1].counters,
            "pipeline": {"dispatched": sum(r.pipeline["dispatched"]
                                           for r in window),
                         "discarded": sum(r.pipeline["discarded"]
                                          for r in window)}}

    def _differs(self, record: PassRecord, ref, last: int) -> str | None:
        """A pass that ended at height ``last`` against the reference's
        replay of the same bytes: the node as it was, then its files through
        new connections."""
        if record.applied != last or record.state["height"] != last:
            return (f"applied {record.applied}, state at height "
                    f"{record.state['height']}, wanted {last}")
        if not record.indexed:
            return "the index never held the last applied height"
        if last == 0:
            return None
        want_app, want_results = ref["app_hash"], ref["last_results_hash"]
        if record.state["app_hash"] != want_app:
            return "the app hash differs from the reference's"
        if record.state["last_results_hash"] != want_results:
            return "last_results_hash differs from the reference's"
        for key, value in record.app_sample.items():
            if ref["store"].get(key) != value:
                return f"the app answers another value for key {key!r}"
        c = record.counters
        if (c["post_commit_submitted"] != last or c["post_commit_done"] != last
                or c["heights_indexed"] != last
                or c["txs_indexed"] != sum(ref["txs"][h]
                                           for h in range(1, last + 1))):
            return f"counters {c} for {last} heights"
        if (self.max_backlog is not None
                and max(c["post_commit_backlog_max"],
                        c["indexer_backlog_heights_max"]) > self.max_backlog):
            return (f"the backlog behind apply_block reached "
                    f"{c['post_commit_backlog_max']} tasks and "
                    f"{c['indexer_backlog_heights_max']} headers; the "
                    f"configuration states {self.max_backlog}")
        p = record.pipeline
        if last == self.heights and (
                p["dispatched"] - p["discarded"] != last or p["in_flight"]):
            return f"pipeline {p} for {last} decisions"
        return self._stores_differ(record.home, ref, last)

    def _stores_differ(self, home: str, ref, last: int) -> str | None:
        """Guarantees (c) and (d): the files of a stopped node, through new
        connections."""
        from tendermint_tpu.state.store import StateStore
        from tendermint_tpu.state.txindex import TxIndexer
        from tendermint_tpu.store.block_store import BlockStore
        from tendermint_tpu.store.db import new_db

        seed = self.run.seed
        dbs = [new_db("sqlite", os.path.join(home, "data", name))
               for name in ("blockstore.db", "state.db", "tx_index.db")]
        try:
            blocks, state_store, index = (BlockStore(dbs[0]),
                                          StateStore(dbs[1]), TxIndexer(dbs[2]))
            if blocks.height != last or blocks.base != 1:
                return (f"the reopened block store holds {blocks.base}.."
                        f"{blocks.height}, wanted 1..{last}")
            state = state_store.load()
            if (state.last_block_height != last
                    or state.app_hash != ref["app_hash"]
                    or state.last_results_hash != ref["last_results_hash"]):
                return "the reopened state store's last save is not the " \
                       "reference's state at the last height"
            for h in range(1, last + 1):
                meta = blocks.load_block_meta(h)
                header = meta.header
                if (header.data_hash, header.last_results_hash,
                        header.app_hash) != ref["headers"][h]:
                    return f"stored header {h} names other hashes"
                psh = meta.block_id.part_set_header
                if (psh.total, psh.hash) != ref["part_set_headers"][h]:
                    return f"stored block {h} names another part set"
                if meta.num_txs != ref["txs"][h]:
                    return f"stored block {h} counts {meta.num_txs} txs"
            if len(state_store.load_abci_responses(last).deliver_txs) \
                    != ref["txs"][last]:
                return "the reopened state store lacks the last height's " \
                       "ABCI responses"
            sampled = sorted({1 + datagen.pick(seed, last, "store-height", j)
                              for j in range(SAMPLE_HEIGHTS)} | {last})
            for h in sampled:
                raw = ref["raws"][h - 1]
                for i, chunk in enumerate(block_replay.parts(raw)):
                    part = blocks.load_block_part(h, i)
                    if part is None or part.bytes_ != chunk:
                        return f"stored part {i} of block {h} differs"
                found = index.search(f"tx.height={h}")
                if len(found) != ref["txs"][h]:
                    return (f"search(tx.height={h}) returns {len(found)} of "
                            f"{ref['txs'][h]} transactions")
            if index.search(f"tx.height={last + 1}"):
                return f"the index holds transactions of height {last + 1}"
            for j in range(SAMPLE_TXS):
                h = 1 + datagen.pick(seed, last, "tx-height", j)
                txs = block_replay.parse_body(ref["raws"][h - 1])["txs"]
                i = datagen.pick(seed, len(txs), "tx-index", j)
                doc = index.get(hashlib.sha256(txs[i]).digest())
                if (doc is None or int(doc["height"]) != h
                        or doc["index"] != i
                        or base64.b64decode(doc["tx"]) != txs[i]
                        or doc["tx_result"]["code"] != 0):
                    return f"get(hash) of transaction {i} of block {h}: {doc}"
        finally:
            for db in dbs:
                db.close()
        return None

    def _corruptions(self, ref) -> dict:
        """name -> (the chain's bytes with one block changed, the kind of
        the reference's refusal, the height it refuses)."""
        from tendermint_tpu.types.block import Block, CommitSig

        run, chain = self.run, self.chain
        out = {}
        # one flipped byte inside a transaction of a middle height
        lo, hi = self.heights // 4 + 1, max(self.heights // 4 + 1,
                                            3 * self.heights // 4)
        h = lo + datagen.pick(run.seed, hi - lo + 1, "bad-tx-height")
        txs = block_replay.parse_body(chain.raws[h - 1])["txs"]
        tx = txs[datagen.pick(run.seed, len(txs), "bad-tx")]
        at = chain.raws[h - 1].index(tx) + fullchain.KEY_HEX + 1 + datagen.pick(
            run.seed, len(tx) - fullchain.KEY_HEX - 1, "bad-tx-byte")
        raw = bytearray(chain.raws[h - 1])
        raw[at] ^= 0x01
        raws = list(chain.raws)
        raws[h - 1] = bytes(raw)
        out["flipped byte in a transaction"] = (raws, "commit_block_id", h)
        # one flipped bit inside the light prefix of another middle height
        h = lo + datagen.pick(run.seed, hi - lo + 1, "bad-sig-height")
        block = Block.unmarshal(chain.raws[h])     # carries the commit for h
        prefix = ref["prefixes"][h]
        idx = prefix[datagen.pick(run.seed, len(prefix), "bad-sig")]
        cs = block.last_commit.signatures[idx]
        flipped = bytearray(cs.signature)
        flipped[datagen.pick(run.seed, 63, "bad-byte")] ^= 0x40
        block.last_commit.signatures[idx] = CommitSig(
            cs.block_id_flag, cs.validator_address, cs.timestamp,
            bytes(flipped))
        raws = list(chain.raws)
        raws[h] = block.marshal()
        out["flipped bit in a light prefix"] = (raws, "wrong_signature", h)
        return out
