"""The ``churn-sync`` mix: a full node catching up by fast sync on a chain
whose validator set changes, on the served apply path.

One caller, closed loop. A **pass** is what a full node, a sentry, an archive
node or a restarted validator does when it joins a live chain from genesis: a
genesis state, a new in-process ``KVStoreApplication``, ``StateStore`` and
``BlockStore`` over ``MemDB``, a ``BlockExecutor`` over them and the v0
``BlockchainReactor(state, block_exec, block_store, fast_sync=True)``. The
chain's blocks (``drivers/churnchain.py``) go into ``reactor.pool`` as two
peers' deliveries, decoded anew from their bytes for every pass (a block that
came off the wire has no hash computed yet), and
``VerifyAheadPipeline.process_next(reactor)`` -- the call
``BlockchainReactor._try_sync`` makes -- runs until every appliable height is
applied, at the default depth, with no variable set. One decision is one
height applied (its signatures: the light prefix under the set in force
there); ``run.passes`` gets whole passes, timed from the first
``process_next`` to the last, so ``catchup_blocks_per_s`` reads blocks
verified, executed and saved per second.

**A pass starts as a new process does**: before each one, outside its timed
part, ``crypto.batch.forget_keys()`` empties the device's key tables, so the
genesis keys and every joiner are built inside the pass as a first sync
builds them (hub-150.fastsync's lesson, PR 25: a second pass that finds the
first one's tables measures the tables).

The set changes only through the chain's own ``val:`` transactions, EndBlock
and ``update_state``; this file never touches a validator set.

``check`` (outside the window, every run, every comparison exact):
 (a) the plain reference (``benchmark/reference/valset_replay.py``) replays
     the chain's block bytes: its own sets by the H+2 rule, its own hashes,
     and the light prefix verified signature by signature at the two heights
     after every change and at ``REFERENCE_SAMPLE`` seeded others;
 (b) the warm-up pass and every pass of the window ended at the last height
     holding the reference's ``validators`` / ``next_validators`` /
     ``last_validators`` (address, key, power, in order), app hash and
     ``last_height_validators_changed``, every stored header naming the
     hashes of the reference's sets;
 (c) in every pass each discarded dispatch was issued again and every
     resolved one applied: ``dispatched - discarded == heights`` with
     nothing left in flight, and a traced run's ``fastsync.discard`` marks
     add up to the counter, one ``reason=valset`` mark per change;
 (d) one pass per corruption on a copy of the chain, rejected at the
     reference's height and slot with ``ErrWrongSignature``, the heights
     below applied and both sending peers dropped: a flipped signature bit
     inside the light prefix of the first height of a new set, and a commit
     for such a height signed by the set before it;
 (e) ``correct.check_decisions`` on the pooled commits, as the other cells.

**A program without the seam or the counters cannot run this cell** and is
told so when this file is loaded, before any data is made
(``spec.SpecError``: the harness refuses, exit 2, within seconds).
"""

from __future__ import annotations

import time

from benchmark.drivers import churnchain
from benchmark.harness import correct, datagen, signing, spans, spec
from benchmark.reference import valset_replay

try:
    from tendermint_tpu.crypto.batch import forget_keys
except ImportError as e:
    raise spec.SpecError(
        "the churn-sync mix needs a program with crypto.batch.forget_keys "
        "(a pass starts with no key resident, as a new node does); this "
        "one has none") from e
if not spans._program_has("fastsync.discard"):
    raise spec.SpecError(
        "the churn-sync mix needs a program that counts and marks the "
        "speculative dispatches it discards (VerifyAheadPipeline.discarded, "
        "fastsync.discard); this one does not")

from tendermint_tpu.blockchain.reactor import BlockchainReactor  # noqa: E402

REFERENCE_SAMPLE = 16
PEERS = ("pA", "pB")


class Reactor(BlockchainReactor):
    """The v0 reactor, remembering what its invalid-block path was told."""

    rejected = None               # (height, exception)
    punished = ()                 # the peers whose blocks were dropped

    def _punish_invalid(self, height, e) -> None:
        before = {peer for _block, peer in self.pool.blocks.values()}
        super()._punish_invalid(height, e)
        self.rejected = (height, e)
        self.punished = sorted(
            before - {peer for _block, peer in self.pool.blocks.values()})


def _triples(vals) -> list:
    return [(v.address, v.pub_key.bytes(), v.voting_power)
            for v in vals.validators]


class Driver:
    def __init__(self, run, dataset, traffic: dict):
        self.run, self.ds, self.traffic = run, dataset, traffic
        cfg = dict(run.cell.config)
        if run.rehearse:
            cfg["dataset"] = {**cfg["dataset"], **cfg["rehearse"]}
        self.chain = churnchain.load_or_generate(
            run.cell.config_name + ("-rehearse" if run.rehearse else ""),
            dataset, cfg, run.seed)
        self.heights = self.chain.heights
        self.sigs = self.chain.prefix_sigs
        run.notes["chain"] = {
            **{k: v for k, v in self.chain.meta.items() if k != "config"},
            "heights": self.heights, "updates": len(self.chain.updates),
            "light_prefix_sigs": [min(self.sigs), max(self.sigs)],
            "sigs_a_pass": sum(self.sigs)}
        self.nodes = []           # (reactor, pipeline) of every whole pass

    # --- one pass ------------------------------------------------------------

    def _node(self, raws):
        """A new node with the chain's blocks in its pool -> (reactor,
        pipeline)."""
        from tendermint_tpu.abci.kvstore import KVStoreApplication
        from tendermint_tpu.blockchain.pipeline import VerifyAheadPipeline
        from tendermint_tpu.state.execution import BlockExecutor
        from tendermint_tpu.state.state import make_genesis_state
        from tendermint_tpu.state.store import StateStore
        from tendermint_tpu.store.block_store import BlockStore
        from tendermint_tpu.store.db import MemDB
        from tendermint_tpu.types.block import Block

        state = make_genesis_state(self.chain.genesis)
        state_store, block_store = StateStore(MemDB()), BlockStore(MemDB())
        state_store.save(state)
        block_exec = BlockExecutor(state_store, KVStoreApplication(),
                                   block_store=block_store)
        reactor = Reactor(state, block_exec, block_store, fast_sync=True)
        for i, raw in enumerate(raws):
            reactor.pool.add_block(PEERS[i % 2], Block.unmarshal(raw))
        return reactor, VerifyAheadPipeline()

    def _pass(self, raws, decide):
        """-> (reactor, pipeline, heights applied, (t0, t1) of the sync)."""
        reactor, pipe = self._node(raws)
        forget_keys()
        applied = 0
        t0 = time.monotonic()
        while applied < self.heights and decide(
                lambda: pipe.process_next(reactor), self.sigs[applied]):
            applied += 1
        t1 = time.monotonic()
        reactor.block_exec.stop()
        return reactor, pipe, applied, (t0, t1)

    def warm_up(self) -> None:
        for _ in range(self.traffic["warmup_passes"]):
            reactor, pipe, applied, _t = self._pass(
                self.chain.raws, lambda fn, _sigs: fn())
            if applied != self.heights:
                self.run.failures.append(
                    f"warm-up pass applied {applied} of {self.heights} "
                    f"heights, rejected {reactor.rejected}")
            self.nodes.append((reactor, pipe))

    def measure(self) -> None:
        run = self.run
        run.open_window("process_next")
        while run.elapsed() < run.seconds:
            reactor, pipe, applied, (t0, t1) = self._pass(self.chain.raws,
                                                          run.decide)
            if applied != self.heights:
                run.failures.append(
                    f"pass applied {applied} of {self.heights} heights, "
                    f"rejected {reactor.rejected}")
                break
            run.passes.append((t0, t1, self.heights))
            self.nodes.append((reactor, pipe))
        run.close_window()

    # --- correctness -----------------------------------------------------------

    def _reference(self, raws, verify_at):
        return valset_replay.replay(
            self.ds.chain_id,
            [(v.pub_key.bytes(), v.power) for v in self.chain.genesis.validators],
            raws, [bid.hash for bid in self.chain.block_ids], verify_at)

    def check(self) -> None:
        run, chain = self.run, self.chain
        fail = run.failures.append
        # (a) the reference's own replay of the chain's bytes
        t0 = time.monotonic()
        changes = [h + valset_replay.DELAY for h in sorted(chain.updates)]
        others = [h for h in range(1, self.heights + 1)
                  if h not in changes and h - 1 not in changes]
        sample = [others.pop(datagen.pick(run.seed, len(others), "ref-height", j))
                  for j in range(min(REFERENCE_SAMPLE, len(others)))]
        verify_at = sorted({h for c in changes for h in (c, c + 1)
                            if h <= self.heights} | set(sample))
        ref = self._reference(chain.raws, verify_at)
        if (ref["refused"] or ref["changes"] != changes
                or ref["applied"] != list(range(1, self.heights + 1))):
            fail(f"the reference refuses the clean chain: {ref['refused']}, "
                 f"{len(ref['applied'])} heights applied, its set changes at "
                 f"{ref['changes']}")
            return
        if [len(ref["prefixes"][h]) for h in ref["applied"]] != self.sigs:
            fail("the light prefixes of the program's sets differ in length "
                 "from those of the reference's")
        run.notes["reference"] = {
            "changes": len(changes), "heights_verified": len(verify_at),
            "signatures_verified": sum(len(ref["prefixes"][h]) for h in verify_at),
            "seconds": time.monotonic() - t0}
        # (b), (c) every whole pass against it
        for k, (reactor, pipe) in enumerate(self.nodes):
            why = self._differs(reactor, pipe, ref)
            if why:
                fail(f"pass {k} (0 is the warm-up): {why}")
        self._check_marks(len(changes))
        # (d) corrupted chains, refused where the reference refuses them
        for name, (raws, at) in self._corruptions(ref, changes).items():
            want = self._reference(raws, {at})["refused"]
            reactor, _pipe, applied, _t = self._pass(
                raws, lambda fn, _sigs: fn())
            got = self._rejection(reactor)
            run.notes.setdefault("rejected", {})[name] = {
                "reference": want, "program": got, "applied": applied}
            if (want is None or want[1] != "wrong_signature"
                    or got != (want[0], "ErrWrongSignature", want[2])
                    or applied != want[0] - 1
                    or list(reactor.punished) != sorted(PEERS)):
                fail(f"{name}: the reference refuses {want}; the program "
                     f"applied {applied} heights, rejected {got}, dropped "
                     f"the blocks of {list(reactor.punished)}")
        # (e) the pooled commits, as every cell
        correct.check_decisions(run, self.ds, [self.ds.vals.verify_commit_light,
                                               self.ds.vals.verify_commit])

    def _differs(self, reactor, pipe, ref) -> str | None:
        state, set_hash = reactor.state, ref["set_hashes"]
        if state.last_block_height != self.heights:
            return f"ended at height {state.last_block_height}"
        for name in ("validators", "next_validators", "last_validators"):
            if _triples(getattr(state, name)) != ref[name]:
                return f"{name} differ from the reference's"
        if state.app_hash != ref["app_hash"]:
            return "the app hash differs from the reference's"
        if (state.last_height_validators_changed
                != ref["last_height_validators_changed"]):
            return (f"last_height_validators_changed "
                    f"{state.last_height_validators_changed}, the reference "
                    f"says {ref['last_height_validators_changed']}")
        if reactor.block_store.height != self.heights:
            return f"the block store holds {reactor.block_store.height} heights"
        for h in range(1, self.heights + 1):
            header = reactor.block_store.load_block_meta(h).header
            if (header.validators_hash != set_hash[h]
                    or header.next_validators_hash != set_hash[h + 1]):
                return f"stored header {h} names other validator hashes"
        if reactor.block_exec.store.load().last_block_height != self.heights:
            return "the state store's last save is not the last height's"
        if pipe.dispatched - pipe.discarded != self.heights or len(pipe):
            return (f"{pipe.dispatched} dispatched, {pipe.discarded} "
                    f"discarded, {len(pipe)} in flight for {self.heights} "
                    f"decisions")
        return None

    def _check_marks(self, changes: int) -> None:
        """A traced window's fastsync.discard marks against the counters."""
        run = self.run
        window = [p for _r, p in self.nodes[len(self.nodes) - len(run.passes):]]
        counted = sum(p.discarded for p in window)
        # what churn_discarded_share reads: the window's whole passes
        run.notes["pipeline"] = {
            "dispatched": sum(p.dispatched for p in window),
            "discarded": counted, "passes": len(window)}
        if not run.traced or not run.passes:
            return
        marks = [s["tags"] for s in run.spans if s["name"] == "fastsync.discard"]
        if (sum(t["entries"] for t in marks) != counted
                or sum(1 for t in marks if t["reason"] == "valset")
                != changes * len(run.passes)
                or any(t["reason"] != "valset" for t in marks)):
            run.failures.append(
                f"{len(marks)} fastsync.discard marks of "
                f"{sum(t['entries'] for t in marks)} entries for {counted} "
                f"discarded dispatches and {changes} changes a pass over "
                f"{len(run.passes)} passes")

    @staticmethod
    def _rejection(reactor):
        if reactor.rejected is None:
            return None
        height, e = reactor.rejected
        return height, type(e).__name__, getattr(e, "index", None)

    def _signed_by(self, validators, commit):
        """The commit signed anew, slot for slot, by another set ([(address,
        key, power)] in order): flags, timestamps and block as they were; the
        off-curve validator, which cannot sign, absent."""
        from tendermint_tpu.types.block import CommitSig

        off_key = self.ds.vals.validators[self.ds.off_idx].pub_key.bytes()
        jobs, slots = [], []
        for i, (addr, key, _power) in enumerate(validators):
            cs = commit.signatures[i]
            if cs.absent() or key == off_key:
                commit.signatures[i] = CommitSig.new_absent()
                continue
            cs.validator_address = addr
            jobs.append((self.chain.secrets[key], key,
                         commit.vote_sign_bytes(self.chain.chain_id, i), b""))
            slots.append(i)
        signed = signing.sign_jobs(signing.ED25519, signing.have_openssl(), jobs)
        for i, sig in zip(slots, signed):
            commit.signatures[i].signature = sig
        return commit

    def _corruptions(self, ref, changes) -> dict:
        """name -> (the chain's bytes with one block's LastCommit replaced,
        the height that commit is for)."""
        from tendermint_tpu.types.block import Block, CommitSig

        run, chain = self.run, self.chain
        out = {}

        def carried(h, commit):
            """The chain with block h + 1 carrying this commit for h."""
            block = Block.unmarshal(chain.raws[h])
            block.last_commit = commit
            raws = list(chain.raws)
            raws[h] = block.marshal()
            return raws

        # one flipped bit inside the light prefix of a new set's first height
        h = changes[datagen.pick(run.seed, len(changes), "bad-change")]
        commit = Block.unmarshal(chain.raws[h]).last_commit
        prefix = ref["prefixes"][h]
        idx = prefix[datagen.pick(run.seed, len(prefix), "bad-sig")]
        cs = commit.signatures[idx]
        flipped = bytearray(cs.signature)
        flipped[datagen.pick(run.seed, 63, "bad-byte")] ^= 0x40
        commit.signatures[idx] = CommitSig(
            cs.block_id_flag, cs.validator_address, cs.timestamp, bytes(flipped))
        out["flipped bit at a new set's first height"] = (carried(h, commit), h)

        # a commit for a new set's first height signed by the set before it:
        # the first change, in seeded order, at which the two sets differ
        # inside the light prefix (else the light verification cannot tell)
        order = list(changes)
        for j in range(len(order)):
            h = order.pop(datagen.pick(run.seed, len(order), "old-set", j))
            commit = self._signed_by(ref["sets"][h - 1],
                                     Block.unmarshal(chain.raws[h]).last_commit)
            raws = carried(h, commit)
            verdict, _prefix = valset_replay.check_commit(
                chain.chain_id, ref["sets"][h],
                valset_replay.parse_block(raws[h])["last_commit"], h,
                chain.block_ids[h - 1].hash, True)
            if verdict is not None and verdict[0] == "wrong_signature":
                out["commit signed by the set before the change"] = (raws, h)
                break
        else:
            run.failures.append("no change of this chain moves a validator "
                                "inside the light prefix")
        return out
