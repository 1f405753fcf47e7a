"""The ``mixed-sync`` mix: BASELINE config 4 on the served path. A default
``Node`` fast-syncs from genesis a chain of 1,000 validators of two key types
(700 ed25519, 300 sr25519) through the v0 ``BlockchainReactor``,
``VerifyAheadPipeline`` and the real ``BlockExecutor``.

The loop is ``drivers/fullsync.py``'s and is **imported**, not copied: this
driver is a subclass of its ``Driver``. A pass is the same (a new default
``Node`` in a new home, the chain's blocks decoded anew into
``node.bc_reactor.pool`` as two peers' deliveries,
``crypto.batch.forget_keys()``, ``process_next`` until every appliable
height is applied, the clock stopped when the index holds the last height),
and so are the warm-up pass, the window, the read-back of a pass's counters
and ``_differs``. What is this file's own:

  - the chain (``drivers/mixedchain.py``): two key types, empty ``Data``, 2%
    absent and 0.2% nil drawn per height, so a block's bytes are its
    1,000-slot LastCommit and every block is verified twice: the +2/3 prefix
    of its commit by the pipeline's ``verify_commit_light`` (667
    signatures), then all of its LastCommit's (~980) by ``verify_commit``
    inside ``apply_block``, through ``BlockExecutor.dispatch_commit_verify``.
    A decision's ``sigs`` counts both;
  - what a pass leaves of the commit->apply seam: the executor's counters
    (handles dispatched, consumed fresh, found stale);
  - the plain reference (``benchmark/reference/mixed_commit.py``): the
    chain replayed from its bytes with ``block_replay.py``'s rules, and on a
    seeded sample of ``reference_heights`` heights of what the passes applied
    both verifications decided signature by signature in pure Python;
  - the read-back of the reopened files for a chain without transactions
    (the index is empty; the state's validator set is held to the
    reference's);
  - the two corrupted chains of guarantee (d), below.

``check`` (outside the window, every run, every comparison exact), by the
configuration's letters:
 (a) every pass dispatched a handle for every height but the first, consumed
     every one fresh and none stale, resolved one light decision a height;
     the reference accepts the chain, its sampled heights signature by
     signature both ways;
 (b)/(c)/(e) ``fullsync.Driver._differs`` on the warm-up pass and every pass
     of the window: app hash, ``last_results_hash``, counters, the backlog's
     bound, then the stopped node's sqlite files through new connections:
     last height, the state and its validator set's hash, every header's
     three hashes and part-set header, the parts of 8 sampled heights;
 (d) one pass per corruption on a copy of the chain.
     *A flipped bit of an ed25519 signature inside the light prefix* of a
     middle height h: refused at h by the light check, as ``fullsync``'s.
     *A flipped bit of an sr25519 signature outside the light prefix of the
     commit for h*: **a flipped bit alone never reaches the full check**,
     in the reference or here: block h+1's bytes then miss the part-set
     header that the commit for h+1 signed, and the light check of h+1
     refuses them first (``hub-150-full.fastsync``'s first corruption). To
     meet ``verify_commit`` the corrupted block has to be a block its
     successors' signers signed: block h+1 carries the corrupted
     LastCommit under a matching ``last_commit_hash`` (so a new block hash),
     and block h+2 carries a commit for *that* block, signed anew by the
     same signers. The light check of h passes (the slot is outside its
     prefix), the light check of h+1 passes (its commit is honest), and the
     full ``verify_commit`` of block h+1's LastCommit refuses the slot, on
     the device route at 1,000 validators. Held: the reference's height,
     kind and slot; the state and the app at h; and what the program does
     besides, written down in the configuration's (d);
 (f) ``correct.check_decisions`` on the pooled commits of both key types
     (breakers, fall-backs, compiles and variables are ``run.py``'s).

**A program without the seam's counters and the ``state.save`` span cannot
run this cell** and is told so when this file is loaded, before any data is
made (``spec.SpecError``: the harness refuses, exit 2, within seconds).
"""

from __future__ import annotations

import os
import shutil
import time

from benchmark.drivers import fullsync, mixedchain
from benchmark.harness import correct, datagen, signing, spans, spec
from benchmark.reference import block_replay, mixed_commit

if not spans._program_has("state.save"):
    raise spec.SpecError(
        "the mixed-sync mix needs a program that counts what became of the "
        "commit->apply seam's handles (BlockExecutor.commit_verify_*), tags "
        "apply.validate with how the LastCommit was answered and traces "
        "StateStore.save (state.save); this one does not")

PEERS = fullsync.PEERS
SAMPLE_HEIGHTS = fullsync.SAMPLE_HEIGHTS


class Driver(fullsync.Driver):
    def __init__(self, run, dataset, traffic: dict):
        # fullsync.Driver.__init__ with mixedchain's chain in fullchain's place
        self.run, self.ds, self.traffic = run, dataset, traffic
        cfg = dict(run.cell.config)
        if run.rehearse:
            cfg["dataset"] = {**cfg["dataset"], **cfg["rehearse"]}
        self.cfg = cfg
        self.chain = mixedchain.load_or_generate(
            run.cell.config_name + ("-rehearse" if run.rehearse else ""),
            dataset, cfg, run.seed)
        self.heights = self.chain.heights
        self.kinds = mixedchain.key_types(self.chain)
        # block k + 1 carries the commit for height k as its LastCommit
        signed = self.chain.sigs.any(axis=2)
        self.full_sigs = [0] + [int(n) for n in signed[:-1].sum(axis=1)]
        self.sigs = [light + full for light, full
                     in zip(self.chain.prefix_sigs, self.full_sigs)]
        self.max_backlog = cfg.get("max_backlog_heights")
        self.reference_heights = cfg["reference_heights"]
        run.notes["chain"] = {
            **{k: v for k, v in self.chain.meta.items() if k != "config"},
            "heights": self.heights,
            "validators": {k: self.kinds.count(k) for k in set(self.kinds)},
            "light_prefix_sigs": [min(self.chain.prefix_sigs),
                                  max(self.chain.prefix_sigs)],
            "last_commit_sigs": [min(self.full_sigs[1:]),
                                 max(self.full_sigs[1:])],
            "sigs_a_pass": sum(self.sigs)}
        self.records = []
        self._homes = 0
        self._built = None         # the node of the pass in hand
        self._verifying = True     # False while set-up replays the chain
        shutil.rmtree(self._home_prefix(), ignore_errors=True)

    # --- one pass ------------------------------------------------------------

    def _node(self, raws):
        self._built, home = super()._node(raws)
        return self._built, home

    def _pass(self, raws, decide, sample=()):
        """``fullsync``'s pass, with what it left of the seam; an exception
        that leaves ``process_next`` ends the pass and is kept."""
        raised = []

        def guarded(fn, sigs):
            def step():
                try:
                    return fn()
                except Exception as e:  # noqa: BLE001 - a refusal, judged below
                    raised.append(e)
                    return False
            return decide(step, sigs)

        record = super()._pass(raws, guarded, sample)
        executor = self._built.block_exec
        record.seam = {"dispatched": executor.commit_verify_dispatched,
                       "fresh": executor.commit_verify_fresh,
                       "stale": executor.commit_verify_stale}
        record.raised = raised[0] if raised else None
        self._built = None
        return record

    def warm_up(self) -> None:
        # the replay of set-up walks the chain and verifies nothing: the
        # pure-Python verification of the sampled heights is check()'s,
        # outside set-up as outside the window
        self._verifying = False
        super().warm_up()
        self._verifying = True
        self.run.notes.pop("reference", None)

    # --- correctness -----------------------------------------------------------

    def _genesis(self) -> list:
        return [(v.pub_key.type, v.pub_key.bytes(), v.power)
                for v in self.chain.genesis.validators]

    def _reference(self, raws, verify_at, hashes=None):
        ref = mixed_commit.replay(
            self.ds.chain_id, self._genesis(), raws,
            hashes or [bid.hash for bid in self.chain.block_ids],
            light_at=verify_at if self._verifying else (),
            full_at=verify_at if self._verifying else ())
        ref["raws"] = raws          # the bytes it replayed, for the read-back
        return ref

    def _letter(self, why: str) -> str:
        """The guarantee a message of ``_differs`` speaks of."""
        if any(word in why for word in ("backlog", "index", "counters")):
            return "e"
        return "c" if ("reopened" in why or "stored" in why) else "b"

    def check(self) -> None:
        run = self.run
        try:
            # (a) the reference, signature by signature at a seeded sample
            t0 = time.monotonic()
            want = min(self.reference_heights, self.heights - 1)
            at, j = set(), 0
            while len(at) < want:     # heights whose commit is checked both ways
                at.add(1 + datagen.pick(run.seed, self.heights - 1,
                                        "ref-height", j))
                j += 1
            at = sorted(at)
            ref = self._reference(self.chain.raws, at)
            run.notes["reference"] = {
                "heights_verified": at,
                "signatures_verified": sum(len(ref["full_slots"][h])
                                           for h in at),
                "seconds": time.monotonic() - t0}
            run.compare("reference_heights", len(at), want)
            if (ref["refused"]
                    or ref["applied"] != list(range(1, self.heights + 1))):
                run.fail("a", f"the reference refuses the clean chain: "
                              f"{ref['refused']} ({ref['refused_by']}), "
                              f"{len(ref['applied'])} heights applied")
                return
            if ([len(ref["prefixes"][h]) for h in ref["applied"]]
                    != self.chain.prefix_sigs
                    or [0] + [len(ref["full_slots"][h])
                              for h in range(1, self.heights)]
                    != self.full_sigs):
                run.fail("a", "the light prefixes or the LastCommits of the "
                              "program's set differ in length from the "
                              "reference's")
            window = self.records[len(self.records) - len(run.passes):]
            self._note_window(window)
            # (a)-(c), (e): every whole pass, its files opened again
            for k, record in enumerate(self.records):
                why = self._differs(record, ref, self.heights)
                if why:
                    run.fail(self._letter(why),
                             f"pass {k} (0 is the warm-up): {why}")
                seam = {"dispatched": self.heights - 1,
                        "fresh": self.heights - 1, "stale": 0}
                if record.seam != seam or record.raised is not None:
                    run.fail("a", f"pass {k}: the commit->apply seam counted "
                                  f"{record.seam}, wanted {seam}; raised "
                                  f"{record.raised!r}")
            # (d) corrupted chains, refused where the reference refuses them
            for name, case in self._corruptions(ref).items():
                self._check_corruption(name, **case)
            # (f) the pooled commits, as every cell
            correct.check_decisions(run, self.ds,
                                    [self.ds.vals.verify_commit_light,
                                     self.ds.vals.verify_commit])
        finally:
            shutil.rmtree(self._home_prefix(), ignore_errors=True)

    def _note_window(self, window) -> None:
        super()._note_window(window)
        if window:
            self.run.notes["mixed"] = {"seam": {
                k: sum(r.seam[k] for r in window)
                for k in ("dispatched", "fresh", "stale")}}

    def _check_corruption(self, name, raws, hashes, by, at, slot) -> None:
        """One pass on a corrupted chain: the reference refuses height ``at``
        by its check ``by`` at ``slot``; the program has to do the same, and
        besides it what the configuration's (d) writes down."""
        run = self.run
        verify_at = {at if by == "light" else at - 1}
        bad = self._reference(raws, verify_at, hashes)
        record = self._pass(raws, lambda fn, _sigs: fn())
        want = (at, "wrong_signature", slot)
        got, raised = record.invalid, record.raised
        run.notes.setdefault("rejected", {})[name] = {
            "reference": [bad["refused"], bad["refused_by"]],
            "program": got, "raised": repr(raised),
            "applied": record.applied, "scored": record.scored}
        ok = bad["refused"] == want and bad["refused_by"] == by
        if by == "light":
            # the pipeline's invalid-block path: both senders dropped, scored
            ok = (ok and raised is None and got is not None
                  and (got[0], got[1], got[2]) == (at, "ErrWrongSignature",
                                                   slot)
                  and got[3] == sorted(PEERS)
                  and record.scored == sorted(PEERS))
        else:
            # where the reference's reactor panics: the error leaves
            # process_next, nobody is dropped, block `at` is already saved
            ok = (ok and got is None and record.scored == []
                  and type(raised).__name__ == "ErrWrongSignature"
                  and getattr(raised, "index", None) == slot)
            bad["block_store_height"] = at
        if not ok or record.applied != at - 1:
            run.fail("d", f"{name}: the reference refuses {bad['refused']} by "
                          f"its {bad['refused_by']} check; the program "
                          f"applied {record.applied} heights, rejected {got}, "
                          f"raised {raised!r}, scored {record.scored}")
        why = self._differs(record, bad, at - 1)
        if why:
            run.fail("d", f"{name}: below the refused height: {why}")

    def _stores_differ(self, home: str, ref, last: int) -> str | None:
        """Guarantee (c) for a chain without transactions: the files of a
        stopped node, through new connections. ``ref["block_store_height"]``
        is where the block store stands when it is ahead of the state (the
        second corruption)."""
        from tendermint_tpu.state.store import StateStore
        from tendermint_tpu.state.txindex import TxIndexer
        from tendermint_tpu.store.block_store import BlockStore
        from tendermint_tpu.store.db import new_db

        stored = ref.get("block_store_height", last)
        dbs = [new_db("sqlite", os.path.join(home, "data", name))
               for name in ("blockstore.db", "state.db", "tx_index.db")]
        try:
            blocks, state_store, index = (BlockStore(dbs[0]),
                                          StateStore(dbs[1]), TxIndexer(dbs[2]))
            if blocks.height != stored or blocks.base != 1:
                return (f"the reopened block store holds {blocks.base}.."
                        f"{blocks.height}, wanted 1..{stored}")
            state = state_store.load()
            if (state.last_block_height != last
                    or state.app_hash != ref["app_hash"]
                    or state.last_results_hash != ref["last_results_hash"]):
                return "the reopened state store's last save is not the " \
                       "reference's state at the last height"
            for name in ("validators", "next_validators", "last_validators"):
                if getattr(state, name).hash() != ref["validators_hash"]:
                    return (f"the reopened state's {name} are not the "
                            f"reference's set")
            for h in range(1, last + 1):
                meta = blocks.load_block_meta(h)
                header = meta.header
                if (header.data_hash, header.last_results_hash,
                        header.app_hash) != ref["headers"][h]:
                    return f"stored header {h} names other hashes"
                psh = meta.block_id.part_set_header
                if (psh.total, psh.hash) != ref["part_set_headers"][h]:
                    return f"stored block {h} names another part set"
                if meta.num_txs != 0:
                    return f"stored block {h} counts {meta.num_txs} txs"
            if state_store.load_abci_responses(last).deliver_txs:
                return "the reopened state store holds responses for " \
                       "transactions the chain does not carry"
            sampled = sorted({1 + datagen.pick(self.run.seed, last,
                                               "store-height", j)
                              for j in range(SAMPLE_HEIGHTS)} | {last, stored})
            for h in sampled:
                for i, chunk in enumerate(block_replay.parts(
                        ref["raws"][h - 1])):
                    part = blocks.load_block_part(h, i)
                    if part is None or part.bytes_ != chunk:
                        return f"stored part {i} of block {h} differs"
                if index.search(f"tx.height={h}"):
                    return f"the index holds transactions of height {h}"
        finally:
            for db in dbs:
                db.close()
        return None

    def _corruptions(self, ref) -> dict:
        """name -> the corrupted chain's bytes and block hashes, and where
        the reference refuses it (``by`` its light or its full check, ``at``
        the height not applied, ``slot``)."""
        from tendermint_tpu.types.block import Block, Commit, CommitSig
        from tendermint_tpu.types.block_id import BlockID
        from tendermint_tpu.types.part_set import PartSet

        run, chain = self.run, self.chain
        hashes = [bid.hash for bid in chain.block_ids]
        lo = self.heights // 4 + 1
        hi = max(lo, min(3 * self.heights // 4, self.heights - 1))

        def flipped(cs, j):
            sig = bytearray(cs.signature)
            sig[datagen.pick(run.seed, 63, "bad-byte", j)] ^= 0x40
            return CommitSig(cs.block_id_flag, cs.validator_address,
                             cs.timestamp, bytes(sig))

        out = {}
        # an ed25519 signature inside the light prefix of a middle height
        h = lo + datagen.pick(run.seed, hi - lo + 1, "bad-sig-height")
        block = Block.unmarshal(chain.raws[h])     # carries the commit for h
        inside = [i for i in ref["prefixes"][h] if self.kinds[i] == "ed25519"]
        slot = inside[datagen.pick(run.seed, len(inside), "bad-sig")]
        block.last_commit.signatures[slot] = flipped(
            block.last_commit.signatures[slot], 0)
        raws = list(chain.raws)
        raws[h] = block.marshal()
        out["flipped ed25519 bit inside a light prefix"] = dict(
            raws=raws, hashes=hashes, by="light", at=h, slot=slot)
        # an sr25519 signature outside the light prefix of the commit for h,
        # in a block h+1 that the signers of h+1 signed as it stands
        def outside_of(height):
            prefix = set(ref["prefixes"][height])
            return [i for i in ref["full_slots"][height]
                    if self.kinds[i] == "sr25519" and i not in prefix]

        middle = list(range(lo, hi + 1))
        start = datagen.pick(run.seed, len(middle), "bad-full-height")
        order = middle[start:] + middle[:start] + [
            x for x in range(1, self.heights) if x not in middle]
        h = next((x for x in order if outside_of(x)), None)
        if h is None:       # a rehearsal's tiny set on a short chain
            raise ValueError("no height of the chain has an sr25519 "
                             "signature outside its light prefix")
        outside = outside_of(h)
        slot = outside[datagen.pick(run.seed, len(outside), "bad-full-sig")]
        forged = Block.unmarshal(chain.raws[h])        # block h+1
        forged.last_commit.signatures[slot] = flipped(
            forged.last_commit.signatures[slot], 1)
        forged.header.last_commit_hash = b""           # filled in by hash()
        forged_hash = forged.hash()
        forged_raw = forged.marshal()
        forged_id = BlockID(hash=forged_hash, part_set_header=PartSet.from_data(
            forged_raw).header())
        carrier = Block.unmarshal(chain.raws[h + 1])   # block h+2
        honest = carrier.last_commit
        resigned = Commit(height=honest.height, round=honest.round,
                          block_id=forged_id, signatures=[
                              CommitSig(cs.block_id_flag, cs.validator_address,
                                        cs.timestamp, cs.signature)
                              for cs in honest.signatures])
        secrets = mixedchain.secrets_of(self.ds, self.cfg, run.seed)
        keys = [v.pub_key.bytes() for v in chain.genesis.validators]
        jobs = {signing.ED25519: [], signing.SR25519: []}
        for i, cs in enumerate(resigned.signatures):
            if not cs.absent():
                kind, secret = secrets[keys[i]]
                jobs[kind].append((i, (
                    secret, keys[i],
                    resigned.vote_sign_bytes(self.ds.chain_id, i),
                    datagen.derive(run.seed, "sr-fork-rng", i))))
        for kind, rows in jobs.items():
            for (i, _job), sig in zip(rows, signing.sign_jobs(
                    kind, signing.have_openssl(), [j for _i, j in rows])):
                resigned.signatures[i].signature = sig
        carrier.last_commit = resigned
        carrier.header.last_block_id = forged_id
        carrier.header.last_commit_hash = b""
        carrier_hash = carrier.hash()
        raws, hashes = list(chain.raws), list(hashes)
        raws[h], raws[h + 1] = forged_raw, carrier.marshal()
        hashes[h], hashes[h + 1] = forged_hash, carrier_hash
        out["flipped sr25519 bit outside a light prefix, in a signed block"] \
            = dict(raws=raws, hashes=hashes, by="full", at=h + 1, slot=slot)
        return out
