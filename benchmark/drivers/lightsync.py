"""The ``light-sync`` mix: a light client catching up, header by header.

One caller, closed loop. A **session** is what a user does who starts a light
client far behind the chain: a new ``light.Client`` in sequential mode with
the configuration's options, trust root = the hash of height 1, over two
in-memory providers (primary and one witness, serving the same chain) and a
fresh in-memory trusted store, then one ``verify_light_block_at_height`` of
the chain's last height. Sessions run back to back for the window. One
session is one decision of the run (its signatures: the light prefixes of the
headers it verified) and one entry of ``run.passes`` with the headers it
verified, so ``catchup_blocks_per_s`` reads light blocks verified and stored
per second over whole sessions, the client's construction included (a user
waits for it) and the two providers' construction excluded (the benchmark's).

**A repeat must not become a hit.** Sessions replay one chain, and the
program keys its device-resident comb tables on a launch's key set
(``ops/ed25519_batch._KS_CACHE``, ``_KS_UNIQ_CACHE``, 8 and 16 entries). The
verify service coalesces the chunks that wait together, so a session is ~10
launches over ~10 distinct unions of prefixes: they fit the cache, and from
the second session on every launch would hit (3.75% misses; my chip run, PR
26) where a client that really starts behind meets each of those unions for
the first time. A session stands for such a client, a process of its own, so
before each one, outside its timed part, the driver empties the two caches
(``_forget_key_sets``): the tables are then built inside the session as a
first sync builds them.

The light blocks are built here from the chained dataset: block k's header
and ``commits[k]``, the commit over that header's hash, with the one static
validator set. ``harness/datagen.py`` is not touched.

``check`` (outside the window, every run, through the same entry point):
 (a) the warm-up session's store ends at the target and every header in it
     hashes as the chain's does, and the plain reference
     (benchmark/reference/light_sync.py) accepts a seeded sample of heights in
     full. The sample, not all 2,000: the reference verifies a signature in
     pure Python in milliseconds, and every header would take minutes;
 (b) one session per corruption on a copy of the chain (``CORRUPTIONS``),
     refused at the reference's height, kind and index, the store holding
     exactly the heights below;
 (c) ``correct.check_decisions`` on the pooled commits, as the other cells;
 (d) no session re-ran a window header by header (``Client.range_fallbacks``).
Every comparison is exact.

**A program without the range path cannot run this cell**, and is told so
when this file is loaded, before any data is made (``spec.SpecError``: the
harness refuses, exit 2, within seconds). Such a program's sequential mode
verifies each header's 42-93 signatures on the host: its sync never reaches
the device, which every cell has to drive, so its untraced run would give a
number and its traced run none ("no device operation in its trace"). Half a
cell is no cell: it is refused whole. The program says that it has the path
by naming the ``light.range`` span (``trace.CANONICAL_SPANS``), as the
readers of ``harness/spans.py`` ask.
"""

from __future__ import annotations

import contextlib
import time

from benchmark.harness import correct, datagen, signing, spans, spec
from benchmark.reference import light_sync

if not spans._program_has("light.range"):
    raise spec.SpecError(
        "the light-sync mix needs a program whose sequential light client "
        "verifies in light.range windows on the device; this one has no such "
        "span, its sync stays on the host and no traced run of it could "
        "show a device operation")

REFERENCE_SAMPLE = 32
# kinds the plain reference names -> how the program says the same thing
KINDS = {
    "wrong_signature": ("ErrInvalidHeader", "wrong signature"),
    "not_enough_power": ("ErrInvalidHeader", "insufficient voting power"),
    "validators_hash_chain": ("LightClientError", "expected old header next validators"),
    "commit_block_id": ("ValueError", "commit signs block"),
}


def _record(lb, chain_id: str) -> dict:
    """A light block as the plain reference reads it."""
    header, commit = lb.signed_header.header, lb.signed_header.commit
    return {
        "height": header.height,
        "time_ns": header.time.unix_ns(),
        "hash": header.hash(),
        "validators_hash": header.validators_hash,
        "next_validators_hash": header.next_validators_hash,
        "commit_height": commit.height,
        "commit_block_hash": commit.block_id.hash,
        "commit_slots": len(commit.signatures),
        "validators": [(v.address, v.pub_key.bytes(), v.voting_power)
                       for v in lb.validator_set.validators],
        "votes": {cs.validator_address: (cs.block_id_flag,
                                         commit.vote_sign_bytes(chain_id, i),
                                         cs.signature)
                  for i, cs in enumerate(commit.signatures) if not cs.absent()},
    }


class Driver:
    def __init__(self, run, dataset, traffic: dict):
        from tendermint_tpu.types.light_block import LightBlock, SignedHeader

        self.run, self.ds, self.traffic = run, dataset, traffic
        self.options = run.cell.config["assumed"]["client"]
        self.chain = {
            b.header.height: LightBlock(SignedHeader(b.header, c), dataset.vals)
            for b, c in zip(dataset.blocks, dataset.commits)}
        self.target = max(self.chain)
        needed = dataset.vals.total_voting_power() * 2 // 3
        self.prefixes = {h: dataset.vals.commit_light_prefix(
            lb.signed_header.commit, needed) for h, lb in self.chain.items()}
        self.sigs = sum(len(p) for h, p in self.prefixes.items() if h > 1)
        run.notes["signer_sets"] = {
            "decisions": len(self.prefixes),
            "distinct": len({tuple(p) for p in self.prefixes.values()})}
        run.notes["session"] = {"headers": self.target - 1, "sigs": self.sigs}
        self.fallbacks = 0
        self.warm = None

    # --- one session -----------------------------------------------------------

    def _now(self, chain: dict):
        from tendermint_tpu.types.ttime import Time

        t = chain[self.target].signed_header.header.time
        return Time(t.seconds + self.options["now_after_target_s"], t.nanos)

    def _providers(self, chain: dict):
        from tendermint_tpu.light import MockProvider

        return (MockProvider(self.ds.chain_id, chain),
                MockProvider(self.ds.chain_id, chain))

    def _session(self, chain: dict, primary, *witnesses):
        """-> (client or None, store, exception or None)."""
        from tendermint_tpu.light import SEQUENTIAL, Client, DBStore, TrustOptions
        from tendermint_tpu.store.db import MemDB

        o = self.options
        store = DBStore(MemDB())
        client = None
        try:
            client = Client(
                self.ds.chain_id,
                TrustOptions(period_s=o["trusting_period_s"], height=1,
                             hash=chain[1].hash()),
                primary, list(witnesses), store, verification_mode=SEQUENTIAL,
                trust_level=tuple(o["trust_level"]),
                max_clock_drift_s=o["max_clock_drift_s"],
                pruning_size=o["pruning_size"])
            client.verify_light_block_at_height(self.target, self._now(chain))
        except Exception as e:  # noqa: BLE001 - a refusal is an answer here
            return client, store, e
        finally:
            # None: the client's own construction refused the trust root
            self.fallbacks += getattr(client, "range_fallbacks", 0)
        return client, store, None

    def _forget_key_sets(self) -> None:
        """Empty the program's key-set caches, as a client process that has
        just started finds them (module docstring). Takes the caches that
        are there: a program that keeps its tables otherwise keeps them."""
        from tendermint_tpu.ops import ed25519_batch

        caches = [c for c in (getattr(ed25519_batch, "_KS_CACHE", None),
                              getattr(ed25519_batch, "_KS_UNIQ_CACHE", None))
                  if c is not None]
        with getattr(ed25519_batch, "_KS_LOCK", None) or contextlib.nullcontext():
            for cache in caches:
                cache.clear()
        self.run.notes["key_set_caches_emptied"] = len(caches)

    def _clean_session(self) -> bool:
        primary, witness = self._providers(self.chain)
        self._forget_key_sets()
        t0 = time.monotonic()

        def session():
            _client, store, err = self._session(self.chain, primary, witness)
            if err is not None:
                raise err
            latest = store.latest_light_block()
            return (latest.height == self.target
                    and latest.hash() == self.chain[self.target].hash())

        ok = self.run.decide(session, self.sigs)
        if ok:
            self.run.passes.append((t0, time.monotonic(), self.target - 1))
        return bool(ok)

    def warm_up(self) -> None:
        for _ in range(self.traffic["warmup_sessions"]):
            self.warm = self._session(self.chain, *self._providers(self.chain))

    def measure(self) -> None:
        run = self.run
        run.open_window("session")
        while run.elapsed() < run.seconds:
            if not self._clean_session():
                break
        run.close_window()

    # --- check -------------------------------------------------------------------

    def check(self) -> None:
        run = self.run
        fail = run.failures.append
        self._check_clean(fail)
        notes = []
        for name, corrupt in CORRUPTIONS:
            notes.append(self._check_corruption(name, corrupt, fail))
        run.notes["corrupted_sessions"] = notes
        if self.fallbacks:
            fail(f"{self.fallbacks} window(s) of a sync were re-run header by "
                 f"header (Client.range_fallbacks)")
        correct.check_decisions(run, self.ds, [self.ds.vals.verify_commit_light])

    def _reference(self, chain: dict, lo: int, hi: int):
        """The plain reference over heights lo..hi after lo - 1."""
        o = self.options
        records = [_record(chain[h], self.ds.chain_id)
                   for h in range(lo - 1, hi + 1)]
        return light_sync.sync(
            records[0], records[1:], int(o["trusting_period_s"] * 1e9),
            self._now(chain).unix_ns(), int(o["max_clock_drift_s"] * 1e9))

    def _check_clean(self, fail) -> None:
        if self.warm is None:
            self.warm = self._session(self.chain, *self._providers(self.chain))
        _client, store, err = self.warm
        if err is not None:
            fail(f"clean session: {type(err).__name__}: {err}")
            return
        latest = store.latest_light_block()
        if latest.height != self.target:
            fail(f"clean session ends at {latest.height}, not {self.target}")
        first = store.first_light_block_height()
        wrong = [h for h in range(first, self.target + 1)
                 if (lb := store.light_block(h)) is None
                 or lb.hash() != self.chain[h].hash()]
        if wrong:
            fail(f"stored headers differ from the chain's at {wrong[:8]}")
        heights = list(range(2, self.target + 1))
        sample = [heights.pop(datagen.pick(self.run.seed, len(heights),
                                           "light-sample", j))
                  for j in range(min(REFERENCE_SAMPLE, len(heights)))]
        refused = [h for h in sample
                   if self._reference(self.chain, h, h) != ([h], None)]
        if refused:
            fail(f"the plain reference refuses clean heights {refused[:8]}")
        self.run.notes["reference_heights"] = len(sample)
        self.run.notes["stored"] = [first, latest.height]

    def _check_corruption(self, name: str, corrupt, fail) -> dict:
        """One session on a copy of the chain with one corruption."""
        chain = dict(self.chain)
        want = corrupt(self, chain)           # {"height", "lane", "span"}
        lo, hi = want["span"]
        accepted, refusal = self._reference(chain, lo, hi)
        _client, store, err = self._session(chain, *self._providers(chain))
        latest = store.latest_light_block()
        stored = store.size()
        note = {"corruption": name, "height": want["height"],
                "lane": want.get("lane"), "reference": refusal,
                "program": None if err is None else type(err).__name__}
        if refusal is None:
            if accepted != list(range(lo, hi + 1)):
                fail(f"{name}: the reference accepted {accepted}")
            if err is not None or latest.height != self.target:
                fail(f"{name}: the reference accepts, the program says "
                     f"{type(err).__name__}: {err}")
            return note
        height, kind, index = refusal
        if err is None:
            fail(f"{name}: accepted; the reference refuses height {height} "
                 f"({kind}, index {index})")
            return note
        if latest.height != height - 1 or stored != height - 1:
            fail(f"{name}: the store holds {stored} headers up to "
                 f"{latest.height}; the reference refuses height {height}")
        type_name, text = KINDS[kind]
        got_index = getattr(getattr(err, "reason", None), "index", None)
        if (type(err).__name__ != type_name or text not in str(err)
                or got_index != index):
            fail(f"{name}: {type(err).__name__}: {err} (index {got_index}); "
                 f"the reference says {kind}, index {index}")
        return note


# --- corruptions: each edits a copy of the chain and says where ----------------


def _height(drv: Driver, *path) -> int:
    """A seeded height with room for a window of headers on either side."""
    return 3 + datagen.pick(drv.run.seed, drv.target - 6, "light-bad", *path)


def _replace_sig(chain: dict, h: int, idx: int, sig: bytes | None) -> None:
    """Slot idx of height h's commit signs ``sig`` instead; None: Absent."""
    from tendermint_tpu.types.block import Commit, CommitSig
    from tendermint_tpu.types.light_block import LightBlock, SignedHeader

    sh = chain[h].signed_header
    sigs = list(sh.commit.signatures)
    cs = sigs[idx]
    sigs[idx] = (CommitSig.new_absent() if sig is None else CommitSig.new_commit(
        cs.block_id_flag, cs.validator_address, cs.timestamp, sig))
    commit = Commit(height=sh.commit.height, round=sh.commit.round,
                    block_id=sh.commit.block_id, signatures=sigs)
    chain[h] = LightBlock(SignedHeader(sh.header, commit),
                          chain[h].validator_set)


def _flip(sig: bytes, seed: int, *path) -> bytes:
    bit = datagen.pick(seed, 511, "light-bit", *path)
    out = bytearray(sig)
    out[bit // 8] ^= 1 << (bit % 8)
    return bytes(out)


def _prefix_lane(drv: Driver, h: int, *path) -> int:
    prefix = drv.prefixes[h]
    return prefix[datagen.pick(drv.run.seed, len(prefix), "light-lane", *path)]


def flipped_bit_in_prefix(drv, chain):
    h = _height(drv, "flip")
    idx = _prefix_lane(drv, h, "flip")
    sig = chain[h].signed_header.commit.signatures[idx].signature
    _replace_sig(chain, h, idx, _flip(sig, drv.run.seed, "in"))
    return {"height": h, "lane": idx, "span": (h - 1, h + 1)}


def s_not_below_l(drv, chain):
    h = _height(drv, "sgel")
    idx = _prefix_lane(drv, h, "sgel")
    sig = chain[h].signed_header.commit.signatures[idx].signature
    _replace_sig(chain, h, idx, sig[:32] + b"\xff" * 32)
    return {"height": h, "lane": idx, "span": (h - 1, h + 1)}


def flipped_bit_outside_prefix(drv, chain):
    """The light rule never consults it: still accepted. (The first height
    from the seeded one on whose commit has a vote for the block behind its
    prefix: a rehearsal's small set may spend every vote on +2/3.)"""
    start = _height(drv, "outside")
    for h in list(range(start, drv.target - 2)) + list(range(3, start)):
        sigs = chain[h].signed_header.commit.signatures
        outside = [i for i in range(drv.prefixes[h][-1] + 1, len(sigs))
                   if sigs[i].for_block()]
        if outside:
            break
    idx = outside[datagen.pick(drv.run.seed, len(outside), "light-lane", "out")]
    _replace_sig(chain, h, idx,
                 _flip(sigs[idx].signature, drv.run.seed, "out"))
    return {"height": h, "lane": idx, "span": (h - 1, h + 1)}


def signers_absent_below_two_thirds(drv, chain):
    """The heaviest signers turned Absent until the votes for the block hold
    no more than 2/3 of the power."""
    h = _height(drv, "short")
    vals = drv.ds.vals
    needed = vals.total_voting_power() * 2 // 3
    sigs = chain[h].signed_header.commit.signatures
    power = sum(vals.validators[i].voting_power
                for i, cs in enumerate(sigs) if cs.for_block())
    gone = []
    for idx in drv.prefixes[h]:
        if power <= needed:
            break
        power -= vals.validators[idx].voting_power
        gone.append(idx)
    for idx in gone:
        _replace_sig(chain, h, idx, None)
    return {"height": h, "lane": gone[-1], "span": (h - 1, h + 1)}


def _resign(drv: Driver, header, old_commit):
    """The commit the same validators would have signed over ``header``:
    each slot that is not Absent signs its own vote again, with the key the
    generator derived for it from the seed."""
    from tendermint_tpu.types.block import Commit, CommitSig
    from tendermint_tpu.types.block_id import BlockID

    ds, seed = drv.ds, drv.run.seed
    key_of = {bytes(pub): k for k, pub in enumerate(ds.pubs)}
    bid = BlockID(hash=header.hash(),
                  part_set_header=old_commit.block_id.part_set_header)
    slots = [CommitSig(cs.block_id_flag, cs.validator_address, cs.timestamp, b"")
             for cs in old_commit.signatures]
    commit = Commit(height=old_commit.height, round=old_commit.round,
                    block_id=bid, signatures=slots)
    live = [i for i, cs in enumerate(slots) if not cs.absent()]
    jobs = []
    for i in live:
        pub = ds.vals.validators[i].pub_key.bytes()
        secret = datagen.derive(seed, "val", signing.ED25519, key_of[pub])
        jobs.append((secret, pub, commit.vote_sign_bytes(ds.chain_id, i), b""))
    for i, sig in zip(live, signing.sign_jobs(signing.ED25519,
                                              signing.have_openssl(), jobs)):
        slots[i].signature = sig
    return commit


def _break_next_validators_hash(drv: Driver, chain: dict, h: int) -> None:
    import copy

    from tendermint_tpu.types.light_block import LightBlock, SignedHeader

    sh = chain[h].signed_header
    header = copy.copy(sh.header)
    header.next_validators_hash = datagen.derive(drv.run.seed, "light-nvh", h)
    header._hash_cache = None
    chain[h] = LightBlock(SignedHeader(header, _resign(drv, header, sh.commit)),
                          chain[h].validator_set)


def broken_next_validators_hash(drv, chain):
    """Header h names another next set, and is signed as such: h verifies,
    h + 1 does not follow from it."""
    h = _height(drv, "nvh")
    _break_next_validators_hash(drv, chain, h)
    return {"height": h + 1, "span": (h - 1, h + 2)}


def commit_for_another_block(drv, chain):
    from tendermint_tpu.types.block import Commit
    from tendermint_tpu.types.block_id import BlockID
    from tendermint_tpu.types.light_block import LightBlock, SignedHeader

    h = _height(drv, "bid")
    sh = chain[h].signed_header
    bid = BlockID(hash=datagen.derive(drv.run.seed, "light-bid", h),
                  part_set_header=sh.commit.block_id.part_set_header)
    commit = Commit(height=sh.commit.height, round=sh.commit.round,
                    block_id=bid, signatures=list(sh.commit.signatures))
    chain[h] = LightBlock(SignedHeader(sh.header, commit),
                          chain[h].validator_set)
    return {"height": h, "span": (h - 1, h + 1)}


def bad_signature_above_a_structural_defect(drv, chain):
    """Two defects in one range: the lower height is the one reported."""
    h = min(_height(drv, "two"), drv.target - 5)
    _break_next_validators_hash(drv, chain, h)
    above = h + 3
    idx = _prefix_lane(drv, above, "two")
    sig = chain[above].signed_header.commit.signatures[idx].signature
    _replace_sig(chain, above, idx, _flip(sig, drv.run.seed, "two"))
    return {"height": h + 1, "lane": idx, "span": (h - 1, above + 1)}


CORRUPTIONS = [
    ("flipped signature bit inside the light prefix", flipped_bit_in_prefix),
    ("S >= L inside the light prefix", s_not_below_l),
    ("flipped signature bit outside the light prefix", flipped_bit_outside_prefix),
    ("prefix signers absent, power falls short", signers_absent_below_two_thirds),
    ("broken next_validators_hash", broken_next_validators_hash),
    ("commit for another block", commit_for_another_block),
    ("bad signature above a structural defect",
     bad_signature_above_a_structural_defect),
]
