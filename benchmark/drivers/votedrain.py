"""The ``vote-drain`` mix: live consensus as one node of a localnet sees it.

One ``ConsensusState`` (WAL on disk, ``BlockExecutor``, in-process kvstore,
block store, evidence pool, the default tracer) behind its
``ConsensusReactor``, the reactor attached to a ``p2p.Switch`` with three
peers. The peers are real ``Peer`` objects over socketpairs (the seam
``e2e/fabric.link_nodes`` uses): what the node owes them, a ``HasVote`` for
every vote it adds and its ``NewRoundStep``, is encoded, queued on the
connection, packetised and written; the far ends count the bytes and discard
them. The peers announce no round state of their own, so the node's
per-peer gossip routines find nothing to send them and idle (what a node
sends to peers that are behind it is not part of this cell).

**Entry point: ``ConsensusReactor.receive(ch_id, peer, msg_bytes)``**, wire
bytes on the data and vote channels, from one delivering thread, as fast as
``receive`` returns. A **decision is one height**: it starts when the driver
hands the height's proposal to ``receive`` and ends when the node's own
``on_new_round_step`` callback (the one the reactor broadcasts
``NewRoundStep`` from) shows the next height: the block is committed and
applied. No sleep, no poll, no look at the queue.

Per height, in order: the proposal and its block parts from the proposer's
peer; the prevotes; the precommits (``livechain.py`` makes them). Every vote
is delivered three times, once by each peer. The step's votes go in rounds:
in round k each peer first sends its copies of the other two peers' bursts of
round k - 1, then a burst of up to 256 votes it originates (a validator's
vote originates at peer ``slot mod 3``). So some copies fall
into the drain that holds the original and some into a later one. Three
copies of every vote is the upper bound of a 4-node net: a peer stops
sending a vote once the node's ``HasVote`` for it arrives, and the node sends
that only after the flush that verified the vote resolves, by when every
peer has usually sent its copy.

When the node's step callback shows height h + 1 the peers stop sending what
is left of h (a peer learns the node's height from its ``NewRoundStep``); the
driver counts what it dropped. What is already queued stays queued and
becomes late precommits. Each peer's bytes a second are printed; the
reference's ``recv_rate`` (5,120,000 B/s a link) bounds them: a peer that
would pass it waits (``throttled_s`` says how long; 0 when the node is the
slower side).

**A pass** is a new node from genesis in a new WAL directory, for
``live_heights`` heights; before each pass, outside any decision,
``sigcache.reset()``: a pass stands for a node process, and must not find the
triples of the pass before it. The per-key device table stays resident, as it
does across the heights of a static set.

``check`` (outside the window, every run): one extra pass of 2 heights whose
stream carries seeded corruptions (``CORRUPTIONS``), the warm-up pass and
every pass of the window, each compared with the plain reference
(``benchmark/reference/vote_tally.py``) fed the pass's WAL in the order the
WAL holds it: the votes the node counted, in order; the peers it sanctioned;
the conflicts it reported; per height the block it committed and the signer
set of the commit it saw; the app hash; no live-height message shed; every
delivery in the WAL; for the corrupted pass, a fresh node fed the WAL alone
reaches the same height and app hash. The reference verifies the corrupted lanes and a seeded sample
of 32 deliveries a height (all of them in a rehearsal) of the corrupted and
the warm-up pass. Every comparison is exact.

**A program without the drain's spans cannot run this cell** and is told so
when this file is loaded (``spec.SpecError``: exit 2 within seconds).
"""

from __future__ import annotations

import os
import shutil
import socket
import tempfile
import threading
import time

from benchmark.harness import correct, datagen, spans, spec
from benchmark.reference import vote_tally

NEEDS = ("consensus.vote_apply", "consensus.flush_wait", "consensus.wal_write",
         "consensus.vote_serial", "consensus.finalize_commit")
_missing = [n for n in NEEDS if not spans._program_has(n)]
if _missing:
    raise spec.SpecError(
        "the vote-drain mix needs a program whose vote drain names its "
        f"phases; this one has no span {', '.join(_missing)}: its traced "
        "run could not say where a height's time goes")

from benchmark.drivers import livechain  # noqa: E402 - after the refusal

WAL_ROOT = os.path.join(spec.BENCH_DIR, ".wal")
PEERS = 3
DATA_CHANNEL, VOTE_CHANNEL = 0x21, 0x22
REFERENCE_SAMPLE = 32


class _Conn:
    """The SecretConnection surface over one end of a socketpair."""

    def __init__(self, sock):
        self._s = sock

    def write(self, b):
        self._s.sendall(b)

    def read(self, n):
        try:
            return self._s.recv(n)
        except OSError:
            return b""

    def close(self):
        for end in (lambda: self._s.shutdown(socket.SHUT_RDWR), self._s.close):
            try:
                end()
            except OSError:
                pass


class _FarEnd(threading.Thread):
    """A peer's side of the link: counts what reaches it and discards it."""

    def __init__(self, sock):
        super().__init__(daemon=True, name="votedrain-far-end")
        self.sock, self.bytes = sock, 0

    def run(self):
        while True:
            try:
                chunk = self.sock.recv(1 << 16)
            except OSError:
                return
            if not chunk:
                return
            self.bytes += len(chunk)


class Node:
    """One pass's node, wired as node/node.py wires one, and what ``check``
    needs of it afterwards."""

    def __init__(self, chain, wal_dir: str, consensus_config: dict):
        from tendermint_tpu.abci.kvstore import KVStoreApplication
        from tendermint_tpu.config.config import ConsensusConfig
        from tendermint_tpu.consensus.reactor import ConsensusReactor
        from tendermint_tpu.consensus.replay import Handshaker
        from tendermint_tpu.consensus.state_machine import ConsensusState
        from tendermint_tpu.consensus.wal import WAL
        from tendermint_tpu.crypto import ed25519
        from tendermint_tpu.evidence.pool import EvidencePool
        from tendermint_tpu.p2p.key import NodeKey
        from tendermint_tpu.p2p.node_info import NodeInfo
        from tendermint_tpu.p2p.switch import Switch, Transport
        from tendermint_tpu.privval.file_pv import MockPV
        from tendermint_tpu.state.execution import BlockExecutor
        from tendermint_tpu.state.store import StateStore
        from tendermint_tpu.store.block_store import BlockStore
        from tendermint_tpu.store.db import MemDB
        from tendermint_tpu.types import events as tmevents

        self.chain, self.wal_dir = chain, wal_dir
        born_before = set(threading.enumerate())
        self.app = KVStoreApplication()
        self.state_store = StateStore(MemDB())
        self.block_store = BlockStore(MemDB())
        state = livechain.genesis_state(chain)
        self.state_store.save(state)
        state = Handshaker(self.state_store, self.block_store,
                           chain.genesis).handshake(state, self.app)
        self.event_bus = tmevents.EventBus()
        self.evpool = EvidencePool(MemDB(), self.state_store, self.block_store)
        self.conflicts: list = []
        report = self.evpool.report_conflicting_votes

        def report_and_note(a, b):
            self.conflicts.append((b.type, b.height, b.validator_index))
            report(a, b)

        self.evpool.report_conflicting_votes = report_and_note
        self.block_exec = BlockExecutor(
            self.state_store, self.app, evidence_pool=self.evpool,
            event_bus=self.event_bus, block_store=self.block_store)
        self.cs = ConsensusState(
            ConsensusConfig(**consensus_config), state, self.block_exec,
            self.block_store, evidence_pool=self.evpool,
            priv_validator=MockPV(ed25519.gen_priv_key(chain.node_secret)),
            event_bus=self.event_bus, wal=WAL(wal_dir))
        # the votes the node counted, in the order it counted them
        self.counted: list = []
        self.cs.on_vote.append(lambda v: self.counted.append(
            (v.type, v.height, v.validator_index, v.signature)))
        self.height = self.cs.rs.height
        self._stepped = threading.Condition()
        self.cs.on_new_round_step.append(self._on_step)

        key = NodeKey(ed25519.gen_priv_key(datagen.derive(0, "votedrain-node")))
        self.switch = Switch(Transport(key, NodeInfo(
            node_id=key.id(), network=chain.chain_id)))
        self.cs.scoreboard = self.switch.scoreboard
        self.reactor = ConsensusReactor(self.cs)
        # the switch is not started: it has no listener and nobody to redial,
        # and its peers come through the seam below
        self.switch.add_reactor("CONSENSUS", self.reactor)
        self.peers, self.far_ends = [], []
        for p in range(PEERS):
            near, far = socket.socketpair()
            peer_key = NodeKey(ed25519.gen_priv_key(
                datagen.derive(0, "votedrain-peer", p)))
            info = NodeInfo(node_id=peer_key.id(), network=chain.chain_id,
                            channels=self.switch.transport.node_info.channels)
            self.peers.append(self.switch._add_peer(_Conn(near), info,
                                                    outbound=False))
            self.far_ends.append(_FarEnd(far))
            self.far_ends[-1].start()
        self.cs.start()
        # what this node started (consensus, ticker, connections, gossip):
        # stop() waits for them, so that a pass leaves nothing running
        self._threads = [t for t in threading.enumerate()
                         if t not in born_before]

    def _on_step(self, rs) -> None:
        if rs.height != self.height:
            with self._stepped:
                self.height = rs.height
                self._stepped.notify_all()

    def wait_past(self, height: int, timeout: float) -> bool:
        """Block until the node's own step callback has shown a height above
        ``height``: that height is committed and applied."""
        with self._stepped:
            return self._stepped.wait_for(lambda: self.height > height, timeout)

    def stop(self) -> None:
        self.cs.wait_sync(timeout=60.0)     # what is queued is handled (and
        self.cs.stop()                      # in the WAL) before the WAL shuts
        self.switch.stop()
        self.block_exec.stop()
        for t in self._threads + self.far_ends:
            t.join(timeout=5.0)
        for far in self.far_ends:
            far.sock.close()


def _step_deliveries(votes: list, wire: dict, burst: int) -> list:
    """One step's deliveries in order: [(peer, slot, wire bytes)]."""
    mine = [[i for i, v in enumerate(votes) if v is not None and i % PEERS == p]
            for p in range(PEERS)]
    chunks = [[own[k:k + burst] for k in range(0, len(own), burst)]
              for own in mine]
    rounds = max(len(c) for c in chunks)
    out = []
    for k in range(rounds + 1):
        for p in range(PEERS):
            for q in range(PEERS):
                if q != p and 0 < k <= len(chunks[q]):
                    out.extend((p, i, wire[i]) for i in chunks[q][k - 1])
        for p in range(PEERS):
            if k < len(chunks[p]):
                out.extend((p, i, wire[i]) for i in chunks[p][k])
    return out


class Driver:
    def __init__(self, run, dataset, traffic: dict):
        from tendermint_tpu.consensus import reactor

        self.run, self.ds, self.traffic = run, dataset, traffic
        cfg = dict(run.cell.config)
        if run.rehearse:
            cfg["dataset"] = {**cfg["dataset"], **cfg["rehearse"]}
        self.pass_heights = cfg["dataset"]["live_heights"]
        self.consensus_config = cfg["assumed"]["consensus_config"]
        self.recv_rate = traffic["recv_rate_bytes_per_s"]
        # a height that does not commit in this time fails its decision
        self.height_timeout_s = traffic["height_timeout_s"]
        self.chain = livechain.load_or_generate(
            run.cell.config_name + ("-rehearse" if run.rehearse else ""),
            dataset, cfg, run.seed, self.pass_heights)
        run.notes["live_chain"] = {
            k: self.chain.meta.get(k) for k in
            ("cached", "seconds", "redraws", "workers", "ed25519_signer")}
        run.notes["live_chain"].update(rotate=self.chain.rotate,
                                       node_slot=self.chain.node_slot)
        # every message in the reference's wire encoding, once, before any pass
        burst = traffic["rehearse_burst" if run.rehearse else "burst"]
        self.schedule = []
        for hd in self.chain.heights:
            block = [(hd.proposer % PEERS, DATA_CHANNEL,
                      reactor.msg_proposal(hd.proposal))]
            block += [(hd.proposer % PEERS, DATA_CHANNEL,
                       reactor.msg_block_part(hd.height, 0, hd.parts.get_part(i)))
                      for i in range(hd.parts.header().total)]
            steps = []
            for type_ in (livechain.PREVOTE, livechain.PRECOMMIT):
                wire = {i: reactor.msg_vote(v)
                        for i, v in enumerate(hd.votes[type_]) if v is not None}
                steps.append(_step_deliveries(hd.votes[type_], wire, burst))
            self.schedule.append((block, steps))
        distinct = [sum(1 for v in hd.votes[livechain.PRECOMMIT] if v is not None)
                    for hd in self.chain.heights]
        run.notes["stream"] = {
            "heights_a_pass": self.pass_heights, "burst": burst,
            "votes_a_step": [min(distinct), max(distinct)],
            "deliveries_a_height": [
                min(len(b) + sum(len(s) for s in st) for b, st in self.schedule),
                max(len(b) + sum(len(s) for s in st) for b, st in self.schedule)],
            "block_bytes": [min(hd.parts.byte_size for hd in self.chain.heights),
                            max(hd.parts.byte_size for hd in self.chain.heights)]}
        self.warm_records: list = []    # what check needs of each pass
        self.window_records: list = []
        self.delivered = [0, 0]         # deliveries made, dropped (peers stopped)
        self.pass_bytes = [0] * PEERS   # per peer, since the pass began
        self.throttled_s = 0.0
        self.passes_started = 0
        # this driver's own directory: runs and tests may share cell and seed
        os.makedirs(WAL_ROOT, exist_ok=True)
        self.wal_root = tempfile.mkdtemp(
            prefix=f"{run.cell.name}-{run.seed}-", dir=WAL_ROOT)

    # --- one pass ----------------------------------------------------------------

    def _wal_dir(self, name: str) -> str:
        return os.path.join(self.wal_root, name)

    def _new_node(self) -> Node:
        from tendermint_tpu.crypto import sigcache

        sigcache.reset()
        self.passes_started += 1
        return Node(self.chain, self._wal_dir(f"pass-{self.passes_started}"),
                    self.consensus_config)

    def _throttle(self, p: int, t_start: float) -> None:
        """Hold peer p to the link's recv_rate: one second's worth of burst,
        as the connection's flow monitor allows."""
        ahead = (self.pass_bytes[p] / self.recv_rate
                 - (time.monotonic() - t_start) - 1.0)
        if ahead > 0:
            time.sleep(ahead)
            self.throttled_s += ahead

    def _deliver_height(self, node: Node, k: int, t_start: float,
                        edit=None) -> None:
        """Hand height k + 1's messages to the reactor.
        ``edit(k, step, deliveries)`` rewrites a step (the corrupted pass)."""
        receive, peers = node.reactor.receive, node.peers
        height = k + 1
        block, steps = self.schedule[k]
        made = dropped = 0
        sent = self.pass_bytes
        for p, ch, msg in block:
            receive(ch, peers[p], msg)
            sent[p] += len(msg)
            made += 1
        for s, deliveries in enumerate(steps):
            if edit is not None:
                deliveries = edit(k, s, deliveries)
            for n, (p, _slot, msg) in enumerate(deliveries):
                if node.height > height:
                    dropped += len(deliveries) - n
                    break
                if not n % 64:
                    self._throttle(p, t_start)
                receive(VOTE_CHANNEL, peers[p], msg)
                sent[p] += len(msg)
                made += 1
        self.delivered[0] += made
        self.delivered[1] += dropped

    def _height(self, node: Node, k: int, t_start: float, edit) -> bool:
        """One decision: deliver height k + 1, wait for the node's own word
        that it is committed and applied."""
        self._deliver_height(node, k, t_start, edit)
        return node.wait_past(k + 1, self.height_timeout_s)

    def _traced_sigs(self, first_span: int) -> int | None:
        """Signatures the node verified since span ``first_span`` of the run:
        what went to a device program and what the host verifier answered."""
        if not self.run.traced:
            return None
        return sum(s["tags"].get("sigs", 0) for s in self.run.spans[first_span:]
                   if s["name"] in ("prep.launch", "prep.host_verify"))

    def _pass(self, heights: int, measured: bool = False, edit=None,
              keep_going=lambda: True):
        """A new node from genesis fed ``heights`` heights -> its record.
        ``measured``: each height is a decision of the run."""
        decide = self.run.decide if measured else (lambda fn, _sigs: fn())
        node = self._new_node()
        self.pass_bytes = [0] * PEERS
        made0, dropped0 = self.delivered
        t0 = time.monotonic()
        done = 0
        try:
            for k in range(heights):
                if not keep_going():
                    break
                expected = sum(len(s) for s in self.schedule[k][1]) // PEERS
                first_span = len(self.run.spans)
                ok = decide(lambda: self._height(node, k, t0, edit), expected)
                sigs = self._traced_sigs(first_span) if measured else None
                if sigs:
                    self.run.decisions[-1].sigs = sigs
                if not ok:
                    break
                done += 1
        finally:
            t1 = time.monotonic()
            node.stop()
        return {"node": node, "heights": done, "t0": t0, "t1": t1,
                "made": self.delivered[0] - made0,
                "dropped": self.delivered[1] - dropped0,
                "peer_bytes": list(self.pass_bytes)}

    # --- the run -----------------------------------------------------------------

    def warm_up(self) -> None:
        for _ in range(self.traffic["warmup_passes"]):
            self.warm_records.append(
                {**self._pass(self.pass_heights), "name": "warm-up",
                 "sample": True})

    def measure(self) -> None:
        run = self.run
        self.throttled_s = 0.0
        run.open_window("height")
        while run.elapsed() < run.seconds:
            rec = self._pass(self.pass_heights, measured=True,
                             keep_going=lambda: run.elapsed() < run.seconds)
            self.window_records.append(
                {**rec, "name": f"window pass {len(self.window_records) + 1}",
                 "sample": False})
            if rec["heights"] == self.pass_heights:
                run.passes.append((rec["t0"], rec["t1"], rec["heights"]))
            if run.decisions and not run.decisions[-1].ok:
                run.failures.append(
                    f"{self.window_records[-1]['name']}: height {rec['heights'] + 1} "
                    f"was not committed within {self.height_timeout_s:.0f} s")
                break
        run.close_window()
        window_s = run.window[1] - run.window[0] - run.profiler_s
        run.notes["links"] = {
            "peer_bytes_per_s": [
                sum(r["peer_bytes"][p] for r in self.window_records) / window_s
                for p in range(PEERS)],
            "recv_rate_bytes_per_s": self.recv_rate,
            "throttled_s": self.throttled_s,
            "has_vote_bytes_to_peers": [
                sum(r["node"].far_ends[p].bytes for r in self.window_records)
                for p in range(PEERS)]}
        run.notes["recv"] = self._recv_stats(self.window_records)
        run.notes["deliveries"] = {
            "made": sum(r["made"] for r in self.window_records),
            "dropped_at_commit": sum(r["dropped"] for r in self.window_records)}

    @staticmethod
    def _recv_stats(records: list) -> dict:
        """Messages, seconds and bytes the reactors counted on the receiving
        thread, per channel (``ConsensusReactor.recv_stats``; traced runs)."""
        out: dict = {}
        for r in records:
            stats = getattr(r["node"].reactor, "recv_stats", {})
            for ch, (msgs, seconds, nbytes) in stats.items():
                o = out.setdefault(f"{ch:#x}", {"msgs": 0, "seconds": 0.0,
                                                "bytes": 0})
                o["msgs"] += msgs
                o["seconds"] += seconds
                o["bytes"] += nbytes
        return out

    # --- check -------------------------------------------------------------------

    def check(self) -> None:
        run = self.run
        fail = run.failures.append
        plan = Corruptions(self)
        bad = {**self._pass(CHECK_HEIGHTS, edit=plan.edit),
               "name": "corrupted pass", "sample": True, "plan": plan}
        notes = []
        for rec in [bad] + self.warm_records + self.window_records:
            notes.append(self._check_pass(rec, fail))
            shutil.rmtree(rec["node"].wal_dir, ignore_errors=True)
        shutil.rmtree(self.wal_root, ignore_errors=True)
        window = [n for n in notes if n["pass"].startswith("window")]
        run.notes["passes"] = [n for n in notes if n not in window] + [{
            "pass": f"window passes 1-{len(window)}",
            "heights": sum(n["heights"] for n in window),
            "wal_peer_msgs": sum(n["wal"]["peer_msgs"] for n in window),
            "counted": sum(n["verdicts"].get(vote_tally.COUNTED, 0)
                           for n in window),
            "duplicate": sum(n["verdicts"].get(vote_tally.DUPLICATE, 0)
                             for n in window),
            "ignored": sum(n["verdicts"].get(vote_tally.IGNORED, 0)
                           for n in window),
            "dropped_at_commit": sum(n["dropped_at_commit"] for n in window)}]
        run.notes["corrupted_deliveries"] = plan.notes
        sheds = {"live": {}, "stale": {}, "future": {}}
        for rec in self.window_records:
            for cls, by_channel in rec["node"].cs.shed_counts().items():
                for ch, n in by_channel.items():
                    sheds[cls][ch] = sheds[cls].get(ch, 0) + n
        run.notes["shed_in_window"] = sheds
        correct.check_decisions(run, self.ds, [self.ds.vals.verify_commit])

    def _wal_stream(self, rec: dict) -> tuple[list, dict]:
        """The pass's WAL as the plain reference reads it -> (stream, counts)."""
        from tendermint_tpu.consensus.state_machine import (
            BlockPartMessage,
            VoteMessage,
            wal_blob_to_msg,
        )
        from tendermint_tpu.consensus.cstypes import STEP_NEW_HEIGHT
        from tendermint_tpu.consensus.ticker import TimeoutInfo
        from tendermint_tpu.consensus.wal import WAL, WALMessageBlob

        chain_id = self.chain.chain_id
        rehearse = self.run.rehearse
        parts_seen: dict = {}
        stream, counts = [], {"peer_msgs": 0, "own_msgs": 0, "votes": 0}
        wal = WAL(rec["node"].wal_dir)
        try:
            for tm, _at in wal.iter_messages():
                blob = tm.msg
                if not isinstance(blob, WALMessageBlob):
                    continue
                msg = wal_blob_to_msg(blob)
                if isinstance(msg, TimeoutInfo):
                    if msg.step == STEP_NEW_HEIGHT:
                        stream.append({"kind": "timeout", "height": msg.height})
                    continue
                if msg is None:
                    continue
                counts["peer_msgs" if blob.peer_id else "own_msgs"] += 1
                if isinstance(msg, BlockPartMessage):
                    seen = parts_seen.setdefault(msg.height, set())
                    seen.add(msg.part.index)
                    total = self.chain.heights[msg.height - 1].parts.header().total
                    if len(seen) == total:
                        stream.append({"kind": "block", "height": msg.height})
                    continue
                if not isinstance(msg, VoteMessage):
                    continue
                v = msg.vote
                counts["votes"] += 1
                stream.append({
                    "kind": "vote", "peer": blob.peer_id, "type": v.type,
                    "height": v.height, "round": v.round,
                    "block": v.block_id.hash, "index": v.validator_index,
                    "address": v.validator_address, "signature": v.signature,
                    "check": rehearse and rec["sample"], "vote": v})
        finally:
            wal.close()
        # which signatures the reference verifies: every one in a rehearsal;
        # else the corrupted pass's touched slots, the node's own votes, and
        # a seeded sample per height
        if rec["sample"] and not rehearse:
            touched = rec["plan"].slots if "plan" in rec else set()
            by_height: dict = {}
            for n, d in enumerate(stream):
                if d["kind"] != "vote":
                    continue
                if not d["peer"] or (d["type"], d["height"], d["index"]) in touched:
                    d["check"] = True
                else:
                    by_height.setdefault(d["height"], []).append(n)
            for h, at in by_height.items():
                for j in range(min(REFERENCE_SAMPLE, len(at))):
                    n = at.pop(datagen.pick(self.run.seed, len(at),
                                            "drain-sample", rec["name"], h, j))
                    stream[n]["check"] = True
        for d in stream:
            if d["kind"] == "vote":
                v = d.pop("vote")
                d["sign_bytes"] = v.sign_bytes(chain_id) if d["check"] else b""
        return stream, counts

    def _check_pass(self, rec: dict, fail) -> dict:
        node, name, heights = rec["node"], rec["name"], rec["heights"]
        chain = self.chain
        validators = [(v.address, v.pub_key.bytes(), v.voting_power)
                      for v in self.ds.vals.validators]
        stream, counts = self._wal_stream(rec)
        want = vote_tally.tally(validators, stream)
        note = {"pass": name, "heights": heights, "wal": counts,
                "verdicts": {k: want["verdicts"].count(k)
                             for k in sorted(set(want["verdicts"]))},
                "signatures_checked": sum(1 for d in stream if d.get("check"))}
        # (a) the votes counted, in order
        if node.counted != want["counted"]:
            at = next((j for j, (g, w) in enumerate(zip(node.counted,
                                                        want["counted"]))
                       if g != w), min(len(node.counted), len(want["counted"])))
            fail(f"{name}: the node counted {len(node.counted)} votes, the "
                 f"reference {len(want['counted'])}; they part at place {at}")
        # (b) the peers sanctioned
        ids = {peer.id: p for p, peer in enumerate(node.peers)}
        offenses = node.switch.scoreboard.describe()["offenses"]
        got_invalid = {key.split(":")[0]: n for key, n in offenses.items()
                       if key.endswith(":invalid_signature")}
        if got_invalid != want["invalid_by_peer"]:
            fail(f"{name}: sanctioned for invalid signatures "
                 f"{ {ids.get(k, k): n for k, n in got_invalid.items()} }, the "
                 f"reference says "
                 f"{ {ids.get(k, k): n for k, n in want['invalid_by_peer'].items()} }")
        if node.conflicts != want["conflicts"]:
            fail(f"{name}: conflicting votes reported {node.conflicts}, the "
                 f"reference says {want['conflicts']}")
        if "plan" in rec:
            rec["plan"].compare(stream, want, fail)
        # (c) every height committed in round 0 on the proposed block
        if len(want["commits"]) != heights:
            fail(f"{name}: the reference commits {len(want['commits'])} "
                 f"heights, the node {heights}")
        blocks = []
        for c in want["commits"][:heights]:
            hd = chain.heights[c["height"] - 1]
            if c["block"] != hd.block_id.hash:
                fail(f"{name}: the reference commits another block than the "
                     f"proposed one at height {c['height']}")
            block = node.block_store.load_block(c["height"])
            seen = node.block_store.load_seen_commit(c["height"])
            if block is None or seen is None:
                fail(f"{name}: height {c['height']} is not in the block store")
                continue
            if block.hash() != hd.block_id.hash or seen.round != 0:
                fail(f"{name}: height {c['height']} committed another block, "
                     f"or in round {seen.round}")
            signers = [i for i, cs in enumerate(seen.signatures)
                       if not cs.absent()]
            if signers != c["signers"]:
                fail(f"{name}: height {c['height']}: the seen commit has "
                     f"{len(signers)} signers, the reference's {len(c['signers'])}")
            blocks.append({"height": c["height"], "hash": block.hash(),
                           "last_block_hash": block.header.last_block_id.hash,
                           "app_hash": block.header.app_hash,
                           "txs": len(block.data.txs)})
        state = node.state_store.load()
        try:
            reached, app_hash = vote_tally.replay(blocks)
        except ValueError as e:
            fail(f"{name}: {e}")
            reached, app_hash = -1, None
        if heights and (state.last_block_height != heights or reached != heights
                        or state.app_hash != app_hash
                        or app_hash != chain.heights[heights - 1].app_hash):
            fail(f"{name}: the node is at height {state.last_block_height}, "
                 f"the replay at {reached}; app hashes "
                 f"{state.app_hash.hex()} / {app_hash and app_hash.hex()}")
        # (d) every delivery is in the WAL; (e) none of the live height shed
        sheds = node.cs.shed_counts()
        shed = sum(n for by in sheds.values() for n in by.values())
        if counts["peer_msgs"] + shed != rec["made"]:
            fail(f"{name}: {rec['made']} deliveries, {counts['peer_msgs']} in "
                 f"the WAL and {shed} shed")
        if any(sheds["live"].values()):
            fail(f"{name}: messages of the live height were shed: {sheds['live']}")
        note["shed"] = sheds
        note["dropped_at_commit"] = rec["dropped"]
        if "plan" in rec:
            # the corrupted pass only: a replay costs what the pass cost
            self._check_wal_replay(rec, fail)
        return note

    def _check_wal_replay(self, rec: dict, fail) -> None:
        """(d) a fresh machine fed the pass's WAL alone, in its order, through
        the state machine's public inputs: same height, same app hash."""
        from tendermint_tpu.consensus.state_machine import (
            BlockPartMessage,
            ProposalMessage,
            VoteMessage,
            wal_blob_to_msg,
        )
        from tendermint_tpu.consensus.wal import WAL, WALMessageBlob

        first, name = rec["node"], rec["name"]
        fresh = Node(self.chain, self._wal_dir("replay"), self.consensus_config)
        fresh.cs.priv_validator = None      # it signs nothing: the WAL has it
        fresh.cs.priv_validator_pub_key = None
        try:
            wal = WAL(first.wal_dir)
            for tm, _at in wal.iter_messages():
                blob = tm.msg
                msg = (wal_blob_to_msg(blob)
                       if isinstance(blob, WALMessageBlob) else None)
                peer = getattr(blob, "peer_id", "") or "wal"
                if isinstance(msg, VoteMessage):
                    # a vote of a height the pass reached is fed once the
                    # node is there (fed earlier it would be dropped as early)
                    if (msg.vote.height <= rec["heights"] and not fresh.wait_past(
                            msg.vote.height - 1, self.height_timeout_s)):
                        break
                    fresh.cs.add_vote(msg.vote, peer)
                elif isinstance(msg, ProposalMessage):
                    if not fresh.wait_past(msg.proposal.height - 1,
                                           self.height_timeout_s):
                        break
                    fresh.cs.set_proposal(msg.proposal, peer)
                elif isinstance(msg, BlockPartMessage):
                    fresh.cs.add_proposal_block_part(msg.height, msg.round,
                                                     msg.part, peer)
            wal.close()
            fresh.wait_past(rec["heights"], self.height_timeout_s)
        finally:
            fresh.stop()
            shutil.rmtree(fresh.wal_dir, ignore_errors=True)
        a, b = first.state_store.load(), fresh.state_store.load()
        if (a.last_block_height, a.app_hash) != (b.last_block_height, b.app_hash):
            fail(f"{name}: a fresh node fed the WAL reaches height "
                 f"{b.last_block_height}, the pass {a.last_block_height}; app "
                 f"hashes {'equal' if a.app_hash == b.app_hash else 'differ'}")


# --- the corrupted pass ---------------------------------------------------------

CHECK_HEIGHTS = 2


class Corruptions:
    """Seeded edits of the first heights' streams, each with the verdict the
    plain reference has to give the touched delivery:
      - a flipped signature bit in the second copy only: that copy invalid,
        its peer sanctioned, the first copy counted;
      - a flipped bit in the first copy: invalid, the second copy counts;
      - a slot whose address is another validator's: rejected, nobody
        sanctioned; the clean copies after it count;
      - a vote for another block by a validator who already voted, signed
        with its own key: conflict, reported as evidence;
      - a vote of height + 5: ignored.
    Each lands on a validator of its own, in the prevotes of height 1 and in
    the precommits of height 2 (before the slot the commit tips at)."""

    KINDS = ("second_copy_flipped", "first_copy_flipped", "wrong_address",
             "other_block", "future_height")

    def __init__(self, driver: Driver, kinds: tuple = KINDS):
        self.driver, self.notes = driver, []
        self.slots: set = set()             # (type, height, slot) touched
        self.expect: list = []              # (type, height, slot, kind, peer)
        chain, seed = driver.chain, driver.run.seed
        n = driver.ds.vals.size()
        early = n // 3      # copies too are delivered well before +2/3 falls
        self.sites = {}
        for k, step, type_ in ((0, 0, livechain.PREVOTE),
                               (1, 1, livechain.PRECOMMIT)):
            votes = chain.heights[k].votes[type_]
            voted = [i for i, v in enumerate(votes)
                     if v is not None and not v.block_id.is_zero()]
            pool = voted[:max(len(self.KINDS),
                              sum(1 for i in voted if i < early))]
            picks = []
            for j, _kind in enumerate(self.KINDS):
                pool = [i for i in pool if i not in picks]
                picks.append(pool[datagen.pick(seed, len(pool), "drain-bad",
                                               k, j)])
            self.sites[(k, step)] = {kind: slot for kind, slot
                                     in zip(self.KINDS, picks) if kind in kinds}
            for slot in self.sites[(k, step)].values():
                self.slots.add((type_, k + 1, slot))

    def _flip(self, sig: bytes, *path) -> bytes:
        bit = datagen.pick(self.driver.run.seed, 511, "drain-bit", *path)
        out = bytearray(sig)
        out[bit // 8] ^= 1 << (bit % 8)
        return bytes(out)

    def edit(self, k: int, step: int, deliveries: list) -> list:
        import copy

        from tendermint_tpu.consensus import reactor
        from tendermint_tpu.types.block_id import BlockID, PartSetHeader

        site = self.sites.get((k, step))
        if site is None:
            return deliveries
        chain, seed = self.driver.chain, self.driver.run.seed
        type_ = (livechain.PREVOTE, livechain.PRECOMMIT)[step]
        votes = chain.heights[k].votes[type_]
        by_slot = {slot: kind for kind, slot in site.items()}
        seen: dict = {}
        out = []
        for p, slot, msg in deliveries:
            kind = by_slot.get(slot)
            if kind is None:
                out.append((p, slot, msg))
                continue
            nth = seen[slot] = seen.get(slot, 0) + 1
            v = copy.copy(votes[slot])
            if kind == "second_copy_flipped" and nth == 2:
                v.signature = self._flip(v.signature, k, "second")
            elif kind == "first_copy_flipped" and nth == 1:
                v.signature = self._flip(v.signature, k, "first")
            elif kind == "wrong_address" and nth == 1:
                other = (slot + 1) % len(votes)
                v.validator_address = self.driver.ds.vals.validators[other].address
            elif kind == "other_block" and nth == 3:
                # after the honest copies: the validator's second vote, for
                # another block, under its own signature
                v.block_id = BlockID(
                    hash=datagen.derive(seed, "drain-other-block", k),
                    part_set_header=PartSetHeader(
                        total=1, hash=datagen.derive(seed, "drain-other-parts", k)))
                v.signature = self._sign(slot, v)
            elif kind == "future_height" and nth == 2:
                v.height += 5
            else:
                out.append((p, slot, msg))
                continue
            self.expect.append((type_, k + 1, slot, kind, p))
            out.append((p, slot, reactor.msg_vote(v)))
        return out

    def _sign(self, slot: int, vote) -> bytes:
        from benchmark.harness import signing

        ds, seed = self.driver.ds, self.driver.run.seed
        secret = livechain._secret_of(ds, seed)[slot]
        pub = ds.vals.validators[slot].pub_key.bytes()
        return signing.sign_jobs(signing.ED25519, signing.have_openssl(), [
            (secret, pub, vote.sign_bytes(ds.chain_id), b"")])[0]

    WANT = {"second_copy_flipped": vote_tally.INVALID,
            "first_copy_flipped": vote_tally.INVALID,
            "wrong_address": vote_tally.REJECTED,
            "other_block": vote_tally.CONFLICT,
            "future_height": vote_tally.IGNORED}

    def compare(self, stream: list, want: dict, fail) -> None:
        """The reference's verdict on each touched delivery is the one the
        corruption was built for, and the slot's honest copy was counted."""
        votes = [d for d in stream if d["kind"] == "vote"]
        counted = {(t, h, i) for t, h, i, _sig in want["counted"]}
        for type_, height, slot, kind, p in self.expect:
            chain_vote = self.driver.chain.heights[height - 1].votes[type_][slot]
            verdicts = [want["verdicts"][n] for n, d in enumerate(votes)
                        if d["index"] == slot and d["type"] == type_
                        and d["height"] in (height, height + 5)
                        and (d["signature"] != chain_vote.signature
                             or d["height"] != height
                             or d["address"] != chain_vote.validator_address)]
            self.notes.append({"kind": kind, "type": type_, "height": height,
                               "slot": slot, "peer": p, "reference": verdicts})
            if verdicts != [self.WANT[kind]]:
                fail(f"corrupted pass: {kind} at height {height}, slot {slot}: "
                     f"the reference says {verdicts}, not [{self.WANT[kind]}]")
            if (type_, height, slot) not in counted:
                fail(f"corrupted pass: {kind} at height {height}: the honest "
                     f"vote of slot {slot} was not counted")
