"""The ``node-sync`` mix: a full node that starts empty and catches up from
two real peers over the wire, through ``Node.start()`` and the
``BlockchainReactor``'s own loop.

One syncing node, closed loop. The **peers** are the program's ``Node``, each
in an OS process of its own (``drivers/nodesync_peer.py``), over a home
directory whose sqlite stores hold the chain (``drivers/fullchain.py``'s 21
blocks and a seen commit for the last, made once a run in set-up), listening
on TCP, answering ``BlockRequest`` from their own ``BlockStore`` through
their own ``BlockchainReactor.receive``. They are processes and not threads
because far ends inside the measured process run under its interpreter lock
(PERF.md, PR 36): a peer's ``load_block``, marshal, 1,050 packets and their
sealing would be charged to the node under test. They are started once,
outside every timed part, in a session of their own, and die with the
benchmark whatever way it exits.

A **pass**: ``crypto.batch.forget_keys()``; a default ``Node(Config()
.set_root(new home), default_app("kvstore"), genesis)`` whose config differs
from ``Config()`` only in the listen addresses (the run's own loopback
address, ``nodesync_peer.loopback_of``, port 0), the two
``persistent_peers`` and the ``testnet`` command's two local-network flags;
**the clock starts at the call of ``Node.start()``**: listen, switch, dial,
``SecretConnection`` handshake, ``NodeInfo``, status exchange,
``bc_reactor.start_sync()``, RPC, indexer. The reactor's ``fastsync-pool``
thread requests the blocks and applies them; nothing here calls
``process_next``. The driver's thread waits on
``IndexerService.wait_indexed(k)`` for k = 1..20, one **decision** a height
applied and indexed (the first holds ``Node.start()``), and **the clock stops
when ``wait_indexed(20)`` returns**; then ``Node.stop()`` and
``Node.close_stores()``. ``run.passes`` gets whole passes, so
``catchup_blocks_per_s`` reads blocks fetched, verified, executed, saved and
indexed per second.

``check`` (outside the window, every run, every comparison exact) holds the
warm-up pass and every pass of the window to the configuration's guarantees:
 (a)/(b) the plain references (``reference/wire_sync.py`` over
     ``reference/block_replay.py``) replay the chain's block bytes; the node
     ended at the last height with the reference's app hash and
     ``last_results_hash``, and a seeded sample of keys read back from the
     app;
 (c)/(d) the stopped node's files are opened again by new connections: last
     height, the reference's state, every header's three hashes and part-set
     header, the index (``get(hash)`` of 64 sampled transactions,
     ``search("tx.height=H")`` at sampled heights);
 (e) the backlog behind ``apply_block`` within the configuration's bound;
 (f) **all** stored heights' parts are the chain's bytes, part by part; what
     the node counted on channel 0x40 (messages, packets, bytes) is every
     block once plus whole status messages, as ``wire_sync.account`` explains
     it, and every sealed frame is 1,044 bytes;
 (g) one pass beside a third peer process that serves a copy of the chain
     with one flipped transaction byte at a middle height: refused at the
     reference's height and kind, the senders stopped and scored, and the
     pass ends at the last height with the reference's app hash through the
     honest peer. The corrupted peer's process is ended with this leg;
 (h) one clean pass from one peer alone (the other stopped for its length),
     then one pass in which that other peer is ``SIGSTOP``ped once the pool
     knows its range and has taken a seeded number of blocks. The driver's
     watcher reads the pool's open requests (``BlockPool.requested``) and
     ``BlockPool.timed_out`` a millisecond apart: what the silent peer was
     asked and left unanswered was given up no sooner than the
     configuration's ``peer_timeout_s`` and no later than a second past it,
     ``timed_out`` counts exactly those requests, the silent peer is the only
     peer the reactor stopped, and the pass ends at the last height, with
     the reference's app hash, within ``peer_timeout_s``, that second and
     twice the one-peer pass of the oldest request the silent peer left
     open;
 (i) ``correct.check_decisions`` on the pooled commits, as every cell
     (breakers, fall-backs, compiles and variables are ``run.py``'s).

**What a verdict may depend on**: the program's answers and the
configuration's stated bounds, never on which of two processes spoke first.
Legs (h) then (g) run in that order and each pass knows only the processes it
names: until PR 48 the corrupted peer of (g) stayed alive through (h), the
honest peer's PEX reactor had learnt its address, the silent pass's node
dialled it a second after it started, and once the silent peer's requests
were given up half of them went to the corrupted peer: the node refused its
block, stopped **both** senders, and got the honest one back only when that
peer dialled in (the node's one redial thread sat in a handshake with the
stopped process): 3 to 13 s, which the old bound (twice a two-peer pass)
held or did not (PERF.md section 6, PR 48). Every duration a leg compares is
taken inside the pass it judges or in a pass made beside it by the same
``check``, on the watcher's clock; everything else is a count.
**The one retry left** is (g)'s: the corrupted height is one the pool asks of
the corrupted peer when it knows both peers or that one alone (it is dialled
first), but a status message is answered by another process, and where the
honest peer's arrives first the whole first window is asked of it and
nothing is refused. That pass shows nothing about the program, so it is
made once more; a program that accepts the flipped byte accepts it twice.

Each failure carries its guarantee's letter (``run.fail("h", ...)``), so the
result line's ``failures.by_check`` says which leg it was.

A peer at height 21 holds the commit for 21, so a node that has applied 20
and switched to consensus is fed block 21 by its peers' consensus reactors,
as on a live network. The clock has stopped by then or stops within the
index's lag; where block 21 was committed before ``Node.stop()`` the files
hold 1..21 and are read as such (block 21's parts are the chain's bytes too).

The driver reaches the program through public names except
``BlockchainReactor._pipeline`` (its dispatch counters, read after a pass).

**A program without the seams cannot run this cell** and is told so when
this file is loaded, before any data is made (``spec.SpecError``: the harness
refuses, exit 2, within seconds).
"""

from __future__ import annotations

import atexit
import base64
import hashlib
import json
import os
import select
import shutil
import signal
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

import numpy as np

from benchmark.drivers import churnchain, fullchain, livechain, nodesync_peer
from benchmark.harness import correct, datagen, spans, spec
from benchmark.reference import block_replay, wire_sync

try:
    from tendermint_tpu.blockchain import reactor as bc
    from tendermint_tpu.crypto.batch import forget_keys
    from tendermint_tpu.p2p.switch import Switch
    from tendermint_tpu.state.txindex import IndexerService
except ImportError as e:
    raise spec.SpecError(
        "the node-sync mix needs a program with crypto.batch.forget_keys, "
        "state.txindex.IndexerService and the v0 BlockchainReactor; this one "
        "lacks one") from e
if not hasattr(IndexerService, "wait_indexed"):
    raise spec.SpecError(
        "the node-sync mix needs a program whose IndexerService has "
        "wait_indexed(height, timeout_s): a pass's clock stops when the index "
        "holds the last height")
if not (hasattr(bc.BlockPool, "expire_requests")
        and hasattr(bc, "REQUEST_TIMEOUT_S")
        and hasattr(Switch, "wire_totals")
        and spans._program_has("p2p.wire")):
    raise spec.SpecError(
        "the node-sync mix needs a program whose block pool gives a request "
        "up after blockchain.reactor.REQUEST_TIMEOUT_S and asks another peer "
        "(BlockPool.expire_requests, .timed_out), whose switch counts what "
        "its connections moved (Switch.wire_totals) and whose sync writes the "
        "p2p.wire mark; this one does not")

HOMES_DIR = os.path.join(spec.BENCH_DIR, ".homes")
PEER_SCRIPT = nodesync_peer.__file__
HOST = nodesync_peer.loopback_of(os.getpid())   # where this run's nodes listen
# hub-150-full's chain cut to its first 21 blocks: a cache file of its own
CHAIN_CACHE = "hub-150-full-p2p"
CHANNEL = f"{bc.BLOCKCHAIN_CHANNEL:#x}"
SAMPLE_HEIGHTS = 8
SAMPLE_TXS = 64
REFERENCE_SAMPLE = 4
STEP_TIMEOUT_S = 120.0          # one height, or a silent peer's timeout, at most
PEER_READY_S = 300.0
LOOP_GRANULARITY_S = 1.0        # a request is given up this long after its
#                                 time-out at the latest (guarantee (h))
WATCH_LAG_S = 0.25              # what the watcher may see an open request
#                                 late by, on a busy host: it reads the pool
#                                 a millisecond apart, from a thread of the
#                                 measured process

# What a pass left behind, read when its clock stopped (a plain namespace:
# spec.py loads this file outside sys.modules, where a dataclass cannot be
# made): home, applied, t = (t0, t1), state, app_sample, pipeline, counters,
# pool, wire (Switch.wire_totals), invalid, scored, last_apply_end,
# peers_cpu_s; status_msgs once _wire_differs has explained the wire; silent
# (what _silence saw, seconds from t[0]) where a peer was silenced
PassRecord = SimpleNamespace


class _Why(str):
    """What of a pass differs from the reference, in words, and under
    ``check`` the letter of the guarantee it falls under."""

    def __new__(cls, check: str, words: str):
        why = super().__new__(cls, words)
        why.check = check
        return why


_LIVE = []       # peer processes to kill when the interpreter exits


def _kill_all() -> None:
    for peer in list(_LIVE):
        peer.kill()


atexit.register(_kill_all)


class PeerProcess:
    """One ``nodesync_peer.py``: the program's Node over ``home``."""

    def __init__(self, home: str):
        self.home = home
        self.log = open(os.path.join(home, "peer.log"), "wb")
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "TM_TPU_SKIP_WARMUP": "1"}
        self.proc = subprocess.Popen(
            [sys.executable, PEER_SCRIPT, home, str(os.getpid()), HOST],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=self.log,
            env=env, cwd=spec.ROOT, start_new_session=True)
        _LIVE.append(self)
        self.addr = self.id = None
        self.range = None

    def wait_ready(self, timeout_s: float = PEER_READY_S) -> None:
        """The peer's one line: Node.start() has returned and it listens."""
        deadline, buf = time.monotonic() + timeout_s, b""
        fd = self.proc.stdout.fileno()
        while not buf.endswith(b"\n"):
            left = deadline - time.monotonic()
            if left <= 0 or self.proc.poll() is not None:
                raise RuntimeError(
                    f"peer over {self.home} did not come up (exit "
                    f"{self.proc.poll()}); see {self.log.name}")
            if select.select([fd], [], [], min(left, 1.0))[0]:
                chunk = os.read(fd, 65536)
                if not chunk:
                    raise RuntimeError(f"peer over {self.home} closed its "
                                       f"output; see {self.log.name}")
                buf += chunk
        said = json.loads(buf)
        self.addr, self.id = said["p2p"], said["id"]
        self.range = (said["base"], said["height"])

    def cpu_s(self) -> float:
        """User and system CPU seconds of the process so far."""
        try:
            with open(f"/proc/{self.proc.pid}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
            return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")
        except (OSError, IndexError, ValueError):
            return 0.0

    def signal(self, sig: int) -> None:
        if self.proc.poll() is None:
            os.kill(self.proc.pid, sig)

    def kill(self) -> None:
        if self in _LIVE:
            _LIVE.remove(self)
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except OSError:
                pass
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass
        for stream in (self.proc.stdin, self.proc.stdout, self.log):
            try:
                stream.close()
            except OSError:
                pass


class Driver:
    def __init__(self, run, dataset, traffic: dict):
        self.run, self.ds, self.traffic = run, dataset, traffic
        cfg = dict(run.cell.config)
        if run.rehearse:
            cfg["dataset"] = {**cfg["dataset"], **cfg["rehearse"]}
        self.cfg = cfg
        self.p2p = cfg["p2p"]
        if not run.rehearse:
            stated = (self.p2p["request_window"], self.p2p["peer_timeout_s"])
            if stated != (bc.REQUEST_WINDOW, bc.REQUEST_TIMEOUT_S):
                raise spec.SpecError(
                    f"the configuration states request_window and "
                    f"peer_timeout_s {stated}; the program has "
                    f"{(bc.REQUEST_WINDOW, bc.REQUEST_TIMEOUT_S)}")
        self.chain = fullchain.load_or_generate(
            CHAIN_CACHE + ("-rehearse" if run.rehearse else ""),
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
        self.records = []          # PassRecord of every whole clean pass
        self.peers = []            # the two honest PeerProcess
        self._homes = 0
        shutil.rmtree(self._home_prefix(), ignore_errors=True)

    # --- the peers -------------------------------------------------------------

    def _home_prefix(self) -> str:
        return os.path.join(HOMES_DIR, f"{self.run.cell.name}-{self.run.seed}"
                            + ("-rehearse" if self.run.rehearse else ""))

    def _tip_commit(self):
        """A commit for the chain's last block, signed by the genesis set
        under the chain's own pattern of absent and nil votes: what makes a
        peer a node at that height (the chain's maker signs the heights
        before it)."""
        from tendermint_tpu.crypto import ed25519
        from tendermint_tpu.types.block import Commit, CommitSig
        from tendermint_tpu.types.vote import (
            BLOCK_ID_FLAG_COMMIT,
            BLOCK_ID_FLAG_NIL,
        )

        ds, seed, d = self.ds, self.run.seed, self.cfg["dataset"]
        h = len(self.chain.raws)
        vals = ds.vals.validators
        powers = np.array([v.voting_power for v in vals], np.int64)
        absent, nil, _again = churnchain._pattern(
            d.get("pattern_seed", seed), powers, ds.off_idx,
            d["absent_share"], d["nil_share"], h)
        commit = Commit(height=h, round=0, block_id=self.chain.block_ids[-1],
                        signatures=[
            CommitSig.new_absent() if absent[i] else CommitSig(
                BLOCK_ID_FLAG_NIL if nil[i] else BLOCK_ID_FLAG_COMMIT,
                v.address, datagen._timestamp(seed, h, i), b"")
            for i, v in enumerate(vals)])
        for i, secret in enumerate(livechain._secret_of(ds, seed)):
            if absent[i]:
                continue
            key = ed25519.gen_priv_key(secret)
            if key.pub_key().bytes() != vals[i].pub_key.bytes():
                raise RuntimeError(f"validator {i}'s key is not its seed's")
            commit.signatures[i].signature = key.sign(
                commit.vote_sign_bytes(ds.chain_id, i))
        return commit

    def _make_home(self, home: str, raws, state_from: str | None = None) -> None:
        """A home directory a node at the chain's last height would have
        left: ``config/genesis.json``, the block store holding ``raws`` (each
        with the commit the next block carries for it, the last with
        ``_tip_commit``), and the state store after the clean chain was
        applied by a ``BlockExecutor`` over it (copied from ``state_from``
        where another home has it already). ``raws`` may be a function of
        the id the peer will have (its node key is made here then): the
        corrupted copy's height depends on it."""
        from tendermint_tpu.abci.kvstore import KVStoreApplication
        from tendermint_tpu.config.config import Config
        from tendermint_tpu.p2p.key import NodeKey
        from tendermint_tpu.state.execution import BlockExecutor
        from tendermint_tpu.state.state import make_genesis_state
        from tendermint_tpu.state.store import StateStore
        from tendermint_tpu.store.block_store import BlockStore
        from tendermint_tpu.store.db import new_db
        from tendermint_tpu.types.block import Block
        from tendermint_tpu.types.part_set import PartSet

        data = os.path.join(home, "data")
        os.makedirs(os.path.join(home, "config"))
        os.makedirs(data)
        self.chain.genesis.save_as(os.path.join(home, "config", "genesis.json"))
        if callable(raws):
            raws = raws(NodeKey.load_or_gen(
                Config().set_root(home).node_key_file()).id())
        blocks = [Block.unmarshal(raw) for raw in raws]
        commits = [b.last_commit for b in blocks[1:]] + [self.tip_commit]
        db = new_db("sqlite", os.path.join(data, "blockstore.db"))
        store = BlockStore(db)
        for raw, block, commit in zip(raws, blocks, commits):
            store.save_block(block, PartSet.from_data(raw), commit)
        db.close()
        if state_from is not None:
            for name in os.listdir(os.path.join(state_from, "data")):
                if name.startswith("state.db"):
                    shutil.copy(os.path.join(state_from, "data", name),
                                os.path.join(data, name))
            return
        db = new_db("sqlite", os.path.join(data, "state.db"))
        block_exec = BlockExecutor(StateStore(db), KVStoreApplication())
        state = make_genesis_state(self.chain.genesis)
        block_exec.store.save(state)
        for block, block_id in zip(blocks, self.chain.block_ids):
            state, _retain = block_exec.apply_block(state, block_id, block)
        block_exec.stop()
        db.close()

    def _start_peers(self, homes: list) -> list:
        peers = [PeerProcess(home) for home in homes]
        for peer in peers:
            peer.wait_ready()
        return peers

    # --- one pass --------------------------------------------------------------

    def _node(self, peers):
        from tendermint_tpu.node.node import Node, default_app

        home = os.path.join(self._home_prefix(), f"pass-{self._homes}")
        self._homes += 1
        os.makedirs(home)
        cfg = nodesync_peer.local_config(
            home, ",".join(p.addr for p in peers), host=HOST)
        p2p = cfg.p2p
        stated = {k: self.p2p[k] for k in (
            "send_rate", "recv_rate", "max_packet_msg_payload_size", "pex",
            "addr_book_strict", "allow_duplicate_ip")}
        if {k: getattr(p2p, k) for k in stated} != stated:
            raise spec.SpecError(f"the configuration states {stated}; the "
                                 f"node would run with other values")
        return Node(cfg, default_app("kvstore"), self.chain.genesis), home

    def _pass(self, peers, decide, sample=(), silence=None,
              frozen=()) -> PassRecord:
        """One pass from ``peers``. ``silence``: (peer, k) stops that peer's
        process once it has reported its range and the pool has taken k
        blocks, and lets it go on when the pass is over. ``frozen``: peer
        processes stopped for the whole pass (a dial to one is accepted by
        the kernel and its handshake never answered, so it joins nothing)."""
        from tendermint_tpu.abci.types import RequestQuery

        node, home = self._node(peers)
        reactor, idx = node.bc_reactor, node.indexer_service
        forget_keys()
        cpu0 = [p.cpu_s() for p in peers]
        spans0 = len(self.run.spans)
        watcher, pass_over, seen = None, threading.Event(), {}
        for peer in frozen:
            peer.signal(signal.SIGSTOP)
        if silence is not None:
            watcher = threading.Thread(
                target=self._silence, args=(reactor, *silence, pass_over, seen),
                name="bench-silencer", daemon=True)
            watcher.start()

        def first():
            node.start()
            return idx.wait_indexed(1, STEP_TIMEOUT_S)

        applied = 0
        t0 = time.monotonic()
        try:
            while applied < self.heights:
                k = applied + 1
                if not decide(first if k == 1 else
                              (lambda: idx.wait_indexed(k, STEP_TIMEOUT_S)),
                              self.sigs[applied]):
                    break
                applied = k
            t1 = time.monotonic()
            state, worker = reactor.state, node.block_exec.post_commit
            pipe, pool = reactor._pipeline, reactor.pool
            invalid = reactor.last_invalid
            record = PassRecord(
                home=home, applied=applied, t=(t0, t1),
                state={"height": state.last_block_height,
                       "app_hash": state.app_hash,
                       "last_results_hash": state.last_results_hash},
                app_sample={key: node.proxy_app.query.query(
                    RequestQuery(data=key)).value for key in sample},
                pipeline={"dispatched": pipe.dispatched,
                          "discarded": pipe.discarded, "in_flight": len(pipe)},
                counters={"post_commit_submitted": worker.submitted,
                          "post_commit_done": worker.done,
                          "post_commit_backlog_max": worker.backlog_max,
                          "backlog_waits": node.block_exec.backlog_waits,
                          "heights_indexed": idx.heights_indexed,
                          "txs_indexed": idx.txs_indexed,
                          "indexer_backlog_max": idx.backlog_max,
                          "indexer_backlog_heights_max":
                              idx.backlog_heights_max},
                pool={"received": pool.received, "timed_out": pool.timed_out,
                      "peers_stopped": reactor.peers_stopped},
                wire=node.switch.wire_totals(),
                invalid=None if invalid is None else (
                    invalid[0], type(invalid[1]).__name__,
                    getattr(invalid[1], "index", None), list(invalid[2]),
                    str(invalid[1])),
                scored=sorted(node.switch.scoreboard.snapshot()["scores"]),
                last_apply_end=self._last_apply_end(spans0))
        finally:
            if watcher is not None:
                pass_over.set()
                watcher.join()
                silence[0].signal(signal.SIGCONT)
            for peer in frozen:
                peer.signal(signal.SIGCONT)
            node.stop()
            node.close_stores()
        record.peers_cpu_s = [p.cpu_s() - c for p, c in zip(peers, cpu0)]
        if silence is not None:
            record.silent = {
                k: v - t0 if k.endswith("_at") and v is not None else v
                for k, v in seen.items()}
        return record

    def _last_apply_end(self, spans0: int) -> float | None:
        """When the pass's last ``fastsync.apply`` ended, on the recorder's
        clock (a traced run's only)."""
        ends = [s["start"] + s["duration_s"] for s in self.run.spans[spans0:]
                if s["name"] == "fastsync.apply"
                and s["tags"].get("height") == self.heights]
        return ends[-1] if ends else None

    def _silence(self, reactor, peer: PeerProcess, k: int,
                 pass_over: threading.Event, seen: dict) -> None:
        """Stop ``peer``'s process once the node's pool knows its range and
        has taken ``k`` blocks: from then on it answers nothing. Then watch,
        a millisecond apart, what the pool holds open at that peer, until it
        gives those requests up (``BlockPool.timed_out`` moves). Into
        ``seen``, on this thread's clock: ``stopped_at``, ``asked_at`` (when
        the oldest request still open at the end was first seen open),
        ``expired_at``, and ``open_at_expiry`` (how many it left open)."""
        pool = reactor.pool
        first_seen = {}      # height -> when it was first seen open at peer
        while not pass_over.wait(0.001):
            now = time.monotonic()
            # the requests first, the counter second: a counter still at
            # nought says the snapshot is from before the requests were
            # given up, whatever happened since
            open_now = [h for h, p in dict(pool.requested).items()
                        if p == peer.id]
            if pool.timed_out:
                seen.update(expired_at=now, open_at_expiry=len(first_seen),
                            asked_at=min(first_seen.values(), default=None))
                return
            # from the pass's start, not from the stop: a request the peer
            # was asked before it fell silent keeps the time it was asked
            first_seen = {h: first_seen.get(h, now) for h in open_now}
            if ("stopped_at" not in seen and peer.id in pool.peers
                    and pool.received >= k):
                peer.signal(signal.SIGSTOP)
                seen.update(stopped_at=now, after_blocks=pool.received)
                self.run.notes.setdefault("silenced", []).append(
                    {"after_blocks": pool.received,
                     "open_requests": len(open_now)})

    def _key_sample(self, ref) -> list:
        keys = sorted(ref["store"])
        return [keys[datagen.pick(self.run.seed, len(keys), "app-key", j)]
                for j in range(min(SAMPLE_TXS, len(keys)))]

    def warm_up(self) -> None:
        # set-up: the reference's replay of the clean chain, the peers' home
        # directories and their processes, then one whole pass
        run = self.run
        t0 = time.monotonic()
        self.verify_at = sorted({
            1 + datagen.pick(run.seed, self.heights, "ref-height", j)
            for j in range(REFERENCE_SAMPLE)})
        self.ref = self._reference(self.chain.raws, self.verify_at)
        run.notes["reference"] = {
            "heights_verified": len(self.verify_at),
            "keys": len(self.ref["store"]),
            "wire": {k: v for k, v in self.ref["wire"].items()
                     if k != "heights"},
            "seconds": time.monotonic() - t0}
        self.sample = self._key_sample(self.ref)
        t0 = time.monotonic()
        self.tip_commit = self._tip_commit()
        homes = [os.path.join(self._home_prefix(), f"peer-{i}")
                 for i in range(self.p2p["serving_peers"])]
        self._make_home(homes[0], self.chain.raws)
        for home in homes[1:]:
            self._make_home(home, self.chain.raws, state_from=homes[0])
        t1 = time.monotonic()
        self.peers = self._start_peers(homes)
        run.notes["peers"] = {
            "homes_s": t1 - t0, "start_s": time.monotonic() - t1,
            "ranges": [p.range for p in self.peers],
            "pids": [p.proc.pid for p in self.peers]}
        for _ in range(self.traffic["warmup_passes"]):
            record = self._pass(self.peers, lambda fn, _sigs: fn(),
                                sample=self.sample)
            if record.applied != self.heights:
                run.fail("b", f"warm-up pass applied {record.applied} of "
                              f"{self.heights} heights, rejected "
                              f"{record.invalid}")
            self.records.append(record)

    def measure(self) -> None:
        run = self.run
        run.open_window("height")
        while run.elapsed() < run.seconds:
            record = self._pass(self.peers, run.decide, sample=self.sample)
            if record.applied != self.heights:
                run.fail("b", f"pass applied {record.applied} of "
                              f"{self.heights} heights, rejected "
                              f"{record.invalid}, pool {record.pool}")
                break
            run.passes.append((record.t[0], record.t[1], self.heights))
            self.records.append(record)
        run.close_window()

    # --- correctness -----------------------------------------------------------

    def _genesis_keys(self) -> list:
        return [(v.pub_key.bytes(), v.power)
                for v in self.chain.genesis.validators]

    def _reference(self, raws, verify_at):
        ref = wire_sync.ends(self.ds.chain_id, self._genesis_keys(), raws,
                             [bid.hash for bid in self.chain.block_ids],
                             verify_at)
        ref["raws"] = raws
        return ref

    def check(self) -> None:
        run, ref = self.run, self.ref
        try:
            if (ref["refused"]
                    or ref["applied"] != list(range(1, self.heights + 1))):
                run.failures.append(
                    f"the reference refuses the clean chain: "
                    f"{ref['refused']}, {len(ref['applied'])} heights applied")
                return
            if [len(ref["prefixes"][h]) for h in ref["applied"]] != self.sigs:
                run.failures.append(
                    "the light prefixes of the program's set differ in "
                    "length from those of the reference's")
            window = self.records[len(self.records) - len(run.passes):]
            self._note_window(window)
            # (a)-(f): every whole clean pass, its files opened again
            for k, record in enumerate(self.records):
                self._hold_clean(f"pass {k} (0 is the warm-up)", record, ref)
            self._check_silent(ref)
            self._check_corrupted(ref)
            # (i) the pooled commits, as every cell
            correct.check_decisions(run, self.ds,
                                    [self.ds.vals.verify_commit_light,
                                     self.ds.vals.verify_commit])
        finally:
            for peer in self.peers:
                peer.kill()
            shutil.rmtree(self._home_prefix(), ignore_errors=True)

    def _hold_clean(self, name: str, record: PassRecord, ref) -> None:
        """Guarantees (a)-(f) on one whole clean pass: the first that does
        not hold, under its letter."""
        why = self._differs(record, ref) or self._wire_differs(record, ref)
        if why:
            self.run.fail(why.check, f"{name}: {why}")

    def _note_window(self, window) -> None:
        """What the readers of ``layer_metrics/full_*`` and ``wire_*`` take
        from the driver: the window's whole passes (``full``: the keys
        ``drivers/fullsync.py`` notes)."""
        if not window:
            return
        lags = [r.t[1] - r.last_apply_end for r in window
                if r.last_apply_end is not None]
        self.run.notes["full"] = {
            "passes": len(window),
            "index_lag_s": lags,
            "pass_s": [r.t[1] - r.t[0] for r in window],
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
        self.run.notes["wire"] = {
            "peers_cpu_s_a_pass": [r.peers_cpu_s for r in window],
            "pool": [r.pool for r in window],
            "totals_last_pass": window[-1].wire}

    def _differs(self, record: PassRecord, ref) -> _Why | None:
        """A pass against the reference's replay of the same bytes: the node
        as it was when the clock stopped, then its files through new
        connections. The first thing that differs, in words, with its
        guarantee's letter (``_Why``)."""
        last = self.heights
        if record.applied != last or record.state["height"] != last:
            return _Why("b", (f"applied {record.applied}, the reactor's state at "
                         f"height {record.state['height']}, wanted {last}"))
        if record.state["app_hash"] != ref["app_hash"]:
            return _Why("b", "the app hash differs from the reference's")
        if record.state["last_results_hash"] != ref["last_results_hash"]:
            return _Why("b", "last_results_hash differs from the reference's")
        for key, value in record.app_sample.items():
            if ref["store"].get(key) != value:
                return _Why("b", f"the app answers another value for key {key!r}")
        c = record.counters
        txs = sum(ref["txs"][h] for h in range(1, last + 1))
        # a node that went on to consensus may have taken the tip's block up
        tip = ref["txs"][last]
        if not (last <= c["post_commit_done"] <= c["post_commit_submitted"]
                <= last + 1 and last <= c["heights_indexed"] <= last + 1
                and c["txs_indexed"] in (txs, txs + tip)):
            return _Why("d", f"counters {c} for {last} heights")
        if (self.max_backlog is not None
                and max(c["post_commit_backlog_max"],
                        c["indexer_backlog_heights_max"]) > self.max_backlog):
            return _Why("e", (f"the backlog behind apply_block reached "
                         f"{c['post_commit_backlog_max']} tasks and "
                         f"{c['indexer_backlog_heights_max']} headers; the "
                         f"configuration states {self.max_backlog}"))
        p = record.pipeline
        if record.invalid is None and record.pool["timed_out"] == 0 and (
                p["dispatched"] - p["discarded"] != last or p["in_flight"]):
            return _Why("a", f"pipeline {p} for {last} decisions")
        return self._files_differ(record.home, ref)

    def _files_differ(self, home: str, ref) -> _Why | None:
        """Guarantees (c), (d) and (f): the files of a stopped node, through
        new connections. Every stored height's parts, not a sample."""
        from tendermint_tpu.state.store import StateStore
        from tendermint_tpu.state.txindex import TxIndexer
        from tendermint_tpu.store.block_store import BlockStore
        from tendermint_tpu.store.db import new_db

        seed, last = self.run.seed, self.heights
        dbs = [new_db("sqlite", os.path.join(home, "data", name))
               for name in ("blockstore.db", "state.db", "tx_index.db")]
        try:
            blocks, state_store, index = (BlockStore(dbs[0]),
                                          StateStore(dbs[1]), TxIndexer(dbs[2]))
            # the tip's block too where consensus committed it before the stop
            top = blocks.height
            if top not in (last, last + 1) or blocks.base != 1:
                return _Why("c", (f"the reopened block store holds {blocks.base}.."
                             f"{top}, wanted 1..{last} (or the tip's block "
                             f"too)"))
            state = state_store.load()
            tip_txs = len(block_replay.parse_body(ref["raws"][last])["txs"])
            if state.last_block_height == last:
                want = (ref["app_hash"], ref["last_results_hash"])
            else:
                want = (block_replay.app_hash(ref["delivered"] + tip_txs),
                        block_replay.results_hash([(0, b"", 0, 0)] * tip_txs))
            if (state.last_block_height not in (last, top)
                    or (state.app_hash, state.last_results_hash) != want):
                return _Why("c", ("the reopened state store's last save is not "
                             "the reference's state at that height"))
            for h in range(1, last + 1):
                meta = blocks.load_block_meta(h)
                header = meta.header
                if (header.data_hash, header.last_results_hash,
                        header.app_hash) != ref["headers"][h]:
                    return _Why("c", f"stored header {h} names other hashes")
                psh = meta.block_id.part_set_header
                if (psh.total, psh.hash) != ref["part_set_headers"][h]:
                    return _Why("c", f"stored block {h} names another part set")
                if meta.num_txs != ref["txs"][h]:
                    return _Why("c", f"stored block {h} counts {meta.num_txs} txs")
            for h in range(1, top + 1):
                for i, chunk in enumerate(block_replay.parts(ref["raws"][h - 1])):
                    part = blocks.load_block_part(h, i)
                    if part is None or part.bytes_ != chunk:
                        return _Why("f", f"stored part {i} of block {h} differs")
            if len(state_store.load_abci_responses(last).deliver_txs) \
                    != ref["txs"][last]:
                return _Why("c", ("the reopened state store lacks the last "
                             "height's ABCI responses"))
            sampled = sorted({1 + datagen.pick(seed, last, "store-height", j)
                              for j in range(SAMPLE_HEIGHTS)} | {last})
            for h in sampled:
                found = index.search(f"tx.height={h}")
                if len(found) != ref["txs"][h]:
                    return _Why("d", (f"search(tx.height={h}) returns {len(found)} "
                                 f"of {ref['txs'][h]} transactions"))
            if index.search(f"tx.height={top + 1}"):
                return _Why("d", (f"the index holds transactions of height "
                             f"{top + 1}"))
            for j in range(SAMPLE_TXS):
                h = 1 + datagen.pick(seed, last, "tx-height", j)
                txs = block_replay.parse_body(ref["raws"][h - 1])["txs"]
                i = datagen.pick(seed, len(txs), "tx-index", j)
                doc = index.get(hashlib.sha256(txs[i]).digest())
                if (doc is None or int(doc["height"]) != h
                        or doc["index"] != i
                        or base64.b64decode(doc["tx"]) != txs[i]
                        or doc["tx_result"]["code"] != 0):
                    return _Why("d", (f"get(hash) of transaction {i} of block {h}: "
                                 f"{doc}"))
        finally:
            for db in dbs:
                db.close()
        return None

    def _wire_differs(self, record: PassRecord, ref) -> _Why | None:
        """Guarantee (f), the wire's half: a clean pass took every block
        once, and what the node counted on 0x40 is that plus whole status
        messages; every sealed frame is 1,044 bytes."""
        wire, want = record.wire, ref["wire"]
        got = wire.get("channels", {}).get(CHANNEL)
        if got is None:
            return _Why("f", f"the switch counted nothing on channel {CHANNEL}")
        if record.pool != {"received": want["msgs"], "timed_out": 0,
                           "peers_stopped": 0}:
            return _Why("f", (f"the pool took {record.pool}; a clean pass takes "
                         f"{want['msgs']} blocks, each once"))
        told = wire_sync.account(
            ref["raws"], {"msgs": got["msgs_recv"],
                          "packets": got["packets_recv"],
                          "bytes": got["bytes_recv"]},
            [tuple(p.range) for p in self.peers],
            self.p2p["max_packet_msg_payload_size"])
        if told is None:
            return _Why("f", (
                f"channel {CHANNEL} received {got}; the reference counts "
                f"{want['msgs']} blocks in {want['packets']} packets and "
                f"{want['bytes']} bytes, and no number of status messages "
                f"explains the rest"))
        record.status_msgs = told
        frame = self.p2p["sealed_frame_bytes"]
        for way in ("sent", "recv"):
            if wire[f"sealed_bytes_{way}"] != frame * wire[f"frames_{way}"]:
                return _Why("f", f"a sealed frame {way} is not {frame} bytes")
        if wire["frames_recv"] < want["frames_least"]:
            return _Why("f", (f"{wire['frames_recv']} frames received; the blocks "
                         f"alone take {want['frames_least']}"))
        return None

    # --- (g): a peer that serves a corrupted block -------------------------------

    def _corrupted_chain(self, bad_id: str, honest_id: str):
        """The chain's bytes with one byte flipped inside a transaction of a
        middle height, and that height: one the pool asks of the corrupted
        peer when both peers are known to it (heights fall on the peers in
        the order of their ids), and beyond the first window of requests
        where the chain is long enough, so that whichever peer reported
        first, the corrupted one is asked for it."""
        run, chain = self.run, self.chain
        lo = self.heights // 4 + 1
        hi = max(lo, 3 * self.heights // 4)
        mine = sorted([bad_id, honest_id]).index(bad_id)
        fit = [h for h in range(lo, hi + 1) if h % 2 == mine]
        fit = [h for h in fit if h > bc.REQUEST_WINDOW] or fit
        h = fit[datagen.pick(run.seed, len(fit), "bad-tx-height")]
        txs = block_replay.parse_body(chain.raws[h - 1])["txs"]
        tx = txs[datagen.pick(run.seed, len(txs), "bad-tx")]
        at = chain.raws[h - 1].index(tx) + fullchain.KEY_HEX + 1 + datagen.pick(
            run.seed, len(tx) - fullchain.KEY_HEX - 1, "bad-tx-byte")
        raw = bytearray(chain.raws[h - 1])
        raw[at] ^= 0x01
        raws = list(chain.raws)
        raws[h - 1] = bytes(raw)
        return raws, h

    def _check_corrupted(self, ref) -> None:
        run = self.run
        honest = self.peers[0]
        home = os.path.join(self._home_prefix(), "peer-corrupted")
        made = {}

        def copy(bad_id: str):
            made["id"] = bad_id
            made["raws"], made["at"] = self._corrupted_chain(bad_id, honest.id)
            return made["raws"]

        self._make_home(home, copy, state_from=honest.home)
        raws, at, bad_id = made["raws"], made["at"], made["id"]
        want = wire_sync.corrupted(
            self.ds.chain_id, self._genesis_keys(), self.chain.raws, raws,
            [bid.hash for bid in self.chain.block_ids])
        bad = self._start_peers([home])[0]
        try:
            if bad.id != bad_id:
                run.fail("g", f"the corrupted peer came up as {bad.id}, not "
                              f"{bad_id}")
                return
            for _attempt in range(2):
                # the corrupted peer first: it is dialled first. The second
                # attempt is for a pass whose pool heard the honest peer's
                # status first and asked it for the whole first window (the
                # module's text): nothing was refused, nothing was shown
                record = self._pass([bad, honest], lambda fn, _sigs: fn(),
                                    sample=self.sample)
                if record.invalid is not None:
                    break
        finally:
            # no later pass may meet this process: the peers' PEX reactors
            # have its address by now and hand it to every node they meet
            bad.kill()
        run.notes.setdefault("rejected", {})["flipped byte in a transaction"] = {
            "reference": want["refused"], "program": record.invalid,
            "applied": record.applied, "scored": record.scored,
            "pool": record.pool, "seconds": record.t[1] - record.t[0]}
        self._hold_corrupted(record, want, at, bad.id, ref)

    def _hold_corrupted(self, record: PassRecord, want: dict, at: int,
                        bad_id: str, ref) -> None:
        """Guarantee (g) on what the pass beside the corrupted peer left
        behind (``want``: how the plain reference reads the corrupted copy)."""
        run, got = self.run, record.invalid
        run.compare("g_refused_height", None if got is None else got[0], at)
        if (want["refused"] is None
                or want["refused"][:2] != (at, "commit_block_id")
                or want["heights"] != [at] or not want["completes"]
                or want["data_hash_differs"] is not True):
            run.fail("g", f"the reference reads the corrupted copy as {want}")
            return
        if (got is None or got[0] != at or got[1] != "ValueError"
                or "different block" not in got[4] or bad_id not in got[3]
                or not set(got[3]) <= set(record.scored)
                or bad_id not in record.scored
                or record.pool["peers_stopped"] < len(got[3])):
            run.fail("g", f"a corrupted block at height {at}: the reference "
                          f"refuses {want['refused']}; the program rejected "
                          f"{got}, scored {record.scored}, pool {record.pool}")
        why = self._differs(record, ref)
        if why:
            run.fail("g", f"the pass beside a corrupted peer did not end as "
                          f"the reference's: ({why.check}) {why}")

    # --- (h): a peer that stops answering ------------------------------------------

    def _check_silent(self, ref) -> None:
        run = self.run
        silent, other = self.peers[1], self.peers[0]
        # the denominator of "the time the remaining blocks take": a clean
        # pass from the peer that will remain, the other stopped throughout
        alone = self._pass([other], lambda fn, _sigs: fn(),
                           sample=self.sample, frozen=[silent])
        self._hold_clean("the pass from one peer alone", alone, ref)
        one_peer_s = alone.t[1] - alone.t[0]
        k = datagen.pick(run.seed, max(1, self.heights // 5), "silent-after")
        # the silent peer first: it is dialled and reports first
        record = self._pass([silent, other], lambda fn, _sigs: fn(),
                            sample=self.sample, silence=(silent, k))
        run.notes["silent_peer"] = {
            "stopped_after_blocks": k, "seconds": record.t[1] - record.t[0],
            "one_peer_s": one_peer_s, "one_peer_pool": alone.pool,
            "watched": record.silent, "pool": record.pool,
            "applied": record.applied}
        self._hold_silent(record, one_peer_s, ref)

    def _hold_silent(self, record: PassRecord, one_peer_s: float, ref) -> None:
        """Guarantee (h) on what the pass beside the silent peer left
        behind."""
        for why in self._silent_verdict(record, one_peer_s):
            self.run.fail("h", f"a peer stopped after "
                               f"{record.silent.get('after_blocks')} blocks: "
                               f"{why}")
        why = self._differs(record, ref)
        if why:
            self.run.fail("h", f"the pass beside a silent peer did not end "
                               f"as the reference's: ({why.check}) {why}")

    def _silent_verdict(self, record: PassRecord, one_peer_s: float) -> list:
        """What of (h) does not hold, in words, read from ``record.pool``,
        ``record.t`` and ``record.silent`` (the watcher's account, seconds
        from the pass's start). Counts where a count says it; the two
        durations run from the oldest request the silent peer left open, on
        one clock, to the moment the pool gave it up and to the pass's end."""
        timeout, compare = self.p2p["peer_timeout_s"], self.run.compare
        pool, seen = record.pool, record.silent
        compare("h_timed_out", pool["timed_out"],
                seen.get("open_at_expiry", ">0"))
        if pool["timed_out"] <= 0 or seen.get("asked_at") is None:
            return [f"no request of the silent peer's timed out ({pool}); "
                    f"the watcher saw {seen}"]
        out = []
        if pool["timed_out"] != seen["open_at_expiry"]:
            out.append(f"BlockPool.timed_out counts {pool['timed_out']}; the "
                       f"peer left {seen['open_at_expiry']} requests open")
        compare("h_peers_stopped", pool["peers_stopped"], 1)
        if pool["peers_stopped"] != 1:
            out.append(f"{pool['peers_stopped']} peers were stopped; one went "
                       f"silent, and the pass knew no third ({pool})")
        waited = seen["expired_at"] - seen["asked_at"]
        limits = (timeout - WATCH_LAG_S, timeout + LOOP_GRANULARITY_S)
        compare("h_given_up_after_s", waited, list(limits))
        if not limits[0] <= waited <= limits[1]:
            out.append(f"its oldest open request was given up after "
                       f"{waited:.2f} s; the configuration's time-out is "
                       f"{timeout} s (held to {limits[0]}..{limits[1]})")
        took = record.t[1] - record.t[0] - seen["asked_at"]
        bound = timeout + LOOP_GRANULARITY_S + 2 * one_peer_s
        compare("h_done_after_s", took, bound)
        if took > bound:
            out.append(f"the pass ended {took:.1f} s after that request; the "
                       f"bound is {bound:.1f} s ({timeout} + "
                       f"{LOOP_GRANULARITY_S} + twice the one-peer pass's "
                       f"{one_peer_s:.2f})")
        return out
