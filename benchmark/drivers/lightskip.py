"""The ``light-skip`` mix: a light client catching up in its default mode.

One caller, closed loop. A **session** is what a wallet back end, a relayer
or a state-syncing node does whose light client has been away: a new
``light.Client`` in skipping mode, built as ``cli light`` builds it by
default (trust level 1/3, the configuration's options, trust root = the
hash of height 1) over two in-memory providers (primary and one witness,
serving the same chain) and a fresh ``MemDB`` trusted store, then one
``verify_light_block_at_height`` of the chain's last height. Sessions run
back to back for the window. One session is one decision of the run (its
signatures: what the plain reference's serial loops consult) and one entry
of ``run.passes`` with the headers it advanced (target - 1), so
``catchup_blocks_per_s`` reads headers a second over whole sessions, the
client's construction included and the providers' excluded.

**A session starts as a new process does**: before each one, outside its
timed part, ``crypto.batch.forget_keys()`` empties the device's key tables,
so every key a hop meets is built inside the session, and the table passes
``KeyTable.MAX_ROWS`` inside it as a first sync's would.

The chain is ``drivers/rotatingchain.py``'s: a validator set that rotates,
light blocks for the heights a sync visits.

``check`` (outside the window, every run, every comparison exact):
 (a) the plain reference (``benchmark/reference/light_skipping.py``) syncs
     the signed chain: the attempts, fetched and stored heights it gives
     equal the plan the chain was signed for. Its records state header
     hashes and sign bytes from the benchmark's own encoders
     (``reference/canonical.py``, which the chain was hashed and signed
     with), never the program's. It verifies a seeded sample of
     the signatures in full and believes the generator's own bytes
     elsewhere (``SAMPLE_ONE_IN``): 229,000 signatures at 4-8 ms each in
     pure Python are half an hour; a signature that is not byte for byte
     the generator's is always verified, and so is every signature of a
     corrupted height that the reference consults (up to ~14,000 a run:
     about a minute);
 (b) the warm-up session and every session of the window made the
     reference's attempts in its order (``Client.last_bisection``), hold the
     reference's heights in their stores and returned the target; a seeded
     sample of the warm-up session's stored blocks hash as the chain's do;
 (c) one session per corruption on a copy of the chain (``CORRUPTIONS``):
     refused with the reference's kind, at its height and index, after its
     attempts, the store holding its heights;
 (d) in a traced run no commit check of an accepted hop was answered by the
     host verifier; ``correct.check_decisions`` on the pooled commits and
     the breakers, as the other cells.

**A program without the skipping spans cannot run this cell** and is told
so when this file is loaded, before any data is made (``spec.SpecError``:
the harness refuses, exit 2, within seconds): its bisection is another
sequence of attempts than the reference's, and nothing of it could be read.
"""

from __future__ import annotations

import hashlib
import time

from benchmark.drivers import rotatingchain
from benchmark.harness import correct, datagen, spans, spec
from benchmark.reference import ed25519_ref, light_skipping

try:
    from tendermint_tpu.crypto.batch import forget_keys
except ImportError as e:
    raise spec.SpecError(
        "the light-skip mix needs a program with crypto.batch.forget_keys "
        "(a session starts with no key resident, as a new client does); "
        "this one has none") from e
if not spans._program_has("light.skip.hop"):
    raise spec.SpecError(
        "the light-skip mix needs a program whose skipping light client "
        "records its attempts (light.skip.hop, Client.last_bisection) and "
        "bisects as the reference's verifySkipping does; this one does not")

SAMPLE_ONE_IN = 512       # clean signatures the reference verifies in full
STORED_SAMPLE = 3         # stored blocks read back and hashed
CORRUPT_WITHIN = 3        # corruptions land in the first accepted hops
# the reference's kinds -> (exception type, words of its message)
KINDS = {
    "wrong_signature": ("ErrWrongSignature", "wrong signature"),
    "double_vote": ("ErrDoubleVote", "double vote"),
    "invalid_header.wrong_signature": ("ErrInvalidHeader", "wrong signature"),
    "invalid_header.not_enough_power": ("ErrInvalidHeader",
                                        "insufficient voting power"),
    "trusted_header_expired": ("ErrOldHeaderExpired", "expired"),
    "validators_hash_supplied": ("ValueError", "validators hash"),
}


class Session:
    """One client's sync and what it left. (A plain class: the harness loads
    this file without registering it as a module, which dataclasses need.)"""

    def __init__(self, db, store):
        self.client = None    # None: the construction refused the trust root
        self.db, self.store = db, store
        self.block = None     # what verify_light_block_at_height returned
        self.error: Exception | None = None
        self.attempts: list = []   # Client.last_bisection
        self.t0 = self.t1 = 0.0
        self.held: list | None = None   # stored(), kept when the store goes

    def stored(self) -> list[int]:
        """The heights the trusted store holds (its keys end in the height,
        eight bytes big-endian)."""
        if self.held is None:
            self.held = [int.from_bytes(k[-8:], "big")
                         for k, _v in self.db.iterator()]
        return self.held

    def release(self) -> None:
        """Keep the answers, let the client, its store and its 1.7 MB a
        stored block go."""
        self.stored()
        self.client = self.db = self.store = None


def _record(rot, lb) -> dict:
    """A light block as the plain reference reads it. The header's values,
    its hash and every slot's sign bytes are what the generator's own
    encoders state for that height (``benchmark/reference/canonical.py``);
    from the program's containers come only the values a peer supplies and
    a corruption edits: the commit's height and block hash, each slot's
    address, flag and signature, and the set handed over with the block."""
    commit = lb.signed_header.commit
    header = rot.header_record(lb.signed_header.header.height)
    msgs = rot.sign_bytes[header["height"]]
    return {
        **header,
        "commit_height": commit.height,
        "commit_block_hash": commit.block_id.hash,
        "validators": [(v.address, v.pub_key.bytes(), v.voting_power)
                       for v in lb.validator_set.validators],
        "slots": [None if cs.absent() else
                  (cs.validator_address, cs.block_id_flag, msgs[i], cs.signature)
                  for i, cs in enumerate(commit.signatures)],
    }


class Driver:
    def __init__(self, run, dataset, traffic: dict):
        self.run, self.ds, self.traffic = run, dataset, traffic
        cfg = dict(run.cell.config)
        if run.rehearse:
            cfg["dataset"] = {**cfg["dataset"], **cfg["rehearse"]}
        self.options = cfg["assumed"]["client"]
        self.rot = rotatingchain.load_or_generate(
            run.cell.config_name + ("-rehearse" if run.rehearse else ""),
            cfg, run.seed)
        self.chain_id = self.rot.chain_id
        self.chain = self.rot.blocks
        self.target = self.rot.target
        self.target_hash = self.rot.block_ids[self.target].hash
        attempts, fetched, stored, _refusal = self.rot.plan
        self.plan = ([(f, t, v is None) for f, t, v in attempts], stored)
        self.hops = [(f, t) for f, t, v in attempts if v is None]
        self.sigs = self.rot.meta["plan_sigs"]
        self._generated: set[int] = set()   # hashes of (key, message, signature)
        self._in_full: set = set()      # sign bytes never believed unverified
        self._verified: dict = {}       # (key, message, signature) -> verdict
        self._records: dict = {}
        run.notes["chain"] = {
            **{k: v for k, v in self.rot.meta.items() if k != "config"},
            "heights": self.target, "keys": len(self.rot.pubs),
            "light_blocks": len(self.chain), "fetched": len(fetched)}
        run.notes["session"] = {
            "headers": self.target - 1, "sigs": self.sigs,
            "attempts": len(attempts), "hops": len(self.hops),
            "refused": len(attempts) - len(self.hops)}
        self.sessions: list[Session] = []
        self.warm: Session | None = None

    # --- one session -----------------------------------------------------------

    def _now(self, after_s: float | None = None):
        from tendermint_tpu.types.ttime import Time

        after = self.options["now_after_target_s"] if after_s is None else after_s
        return Time.from_unix_ns(rotatingchain.time_ns(self.target)
                                 + int(after * 1e9))

    def _session(self, chain: dict, now=None) -> Session:
        """A new client as `cli light` builds it by default, one sync."""
        from tendermint_tpu.light import (SKIPPING, Client, DBStore,
                                          MockProvider, TrustOptions)
        from tendermint_tpu.store.db import MemDB

        o = self.options
        primary = MockProvider(self.chain_id, chain)
        witness = MockProvider(self.chain_id, chain)
        db = MemDB()
        out = Session(db, DBStore(db))
        out.t0 = time.monotonic()
        try:
            out.client = Client(
                self.chain_id,
                TrustOptions(period_s=o["trusting_period_s"], height=1,
                             hash=self.rot.block_ids[1].hash),
                primary, [witness], out.store, verification_mode=SKIPPING,
                trust_level=tuple(o["trust_level"]),
                max_clock_drift_s=o["max_clock_drift_s"],
                pruning_size=o["pruning_size"])
            out.block = out.client.verify_light_block_at_height(
                self.target, now or self._now())
        except Exception as e:  # noqa: BLE001 - a refusal is an answer here
            out.error = e
        out.t1 = time.monotonic()
        if out.client is not None:
            out.attempts = list(out.client.last_bisection)
        return out

    def _clean_session(self) -> bool:
        forget_keys()
        box = []

        def session():
            s = self._session(self.chain)
            box.append(s)
            if s.error is not None:
                raise s.error
            return s.block is not None and s.block.hash() == self.target_hash

        ok = self.run.decide(session, self.sigs)
        if ok:
            s = box[0]
            s.release()
            self.sessions.append(s)
            self.run.passes.append((s.t0, s.t1, self.target - 1))
        return bool(ok)

    def warm_up(self) -> None:
        for _ in range(self.traffic["warmup_sessions"]):
            forget_keys()
            self.warm = self._session(self.chain)

    def measure(self) -> None:
        run = self.run
        run.open_window("session")
        while run.elapsed() < run.seconds:
            if not self._clean_session():
                break
        run.close_window()

    # --- the plain reference ---------------------------------------------------

    def _fetch(self, chain: dict):
        """The reference's primary over ``chain``: records made once per
        light block; every signature of the generator's own chain is
        remembered as such."""
        def fetch(height: int) -> dict:
            lb = chain[height]
            # the block is held beside its record, so its id stays its own
            _held, rec = self._records.get(id(lb), (None, None))
            if rec is None:
                rec = _record(self.rot, lb)
                self._records[id(lb)] = (lb, rec)
                if lb is self.chain.get(height):
                    pubs = [v[1] for v in rec["validators"]]
                    self._generated.update(
                        hash((pubs[i], s[2], s[3]))
                        for i, s in enumerate(rec["slots"]) if s is not None)
            return rec
        return fetch

    def _verify_sig(self, pub: bytes, msg: bytes, sig: bytes) -> bool:
        """The generator's own signature over its own message is valid by
        construction (OpenSSL or the benchmark's signer made it): one in
        SAMPLE_ONE_IN of those, drawn by the seed, is verified in full. So
        is every signature that is not the generator's, and every signature
        of a corrupted height (``_in_full``: its sign bytes), once each."""
        if (hash((pub, msg, sig)) in self._generated
                and msg not in self._in_full):
            draw = hashlib.sha256(repr(self.run.seed).encode() + msg).digest()
            if int.from_bytes(draw[:4], "big") % SAMPLE_ONE_IN:
                return True
        ok = self._verified.get((pub, msg, sig))
        if ok is None:
            self.verified_in_full += 1
            ok = self._verified[pub, msg, sig] = ed25519_ref.verify(pub, msg, sig)
        return ok

    def _reference(self, chain: dict, now=None):
        o = self.options
        fetch = self._fetch(chain)
        return light_skipping.sync(
            fetch(1), self.target, fetch, int(o["trusting_period_s"] * 1e9),
            (now or self._now()).unix_ns(), int(o["max_clock_drift_s"] * 1e9),
            tuple(o["trust_level"]), verify_sig=self._verify_sig)

    # --- check -------------------------------------------------------------------

    def check(self) -> None:
        run = self.run
        fail = run.failures.append
        self.verified_in_full = 0
        self._check_clean(fail)
        run.notes["corrupted_sessions"] = [
            self._check_corruption(name, corrupt, fail)
            for name, corrupt in CORRUPTIONS]
        run.notes["reference_signatures_in_full"] = self.verified_in_full
        self._check_routes(fail)
        correct.check_decisions(run, self.ds, [self.ds.vals.verify_commit_light])

    def _check_clean(self, fail) -> None:
        attempts, fetched, stored, refusal = self._reference(self.chain)
        if refusal is not None:
            fail(f"the plain reference refuses the clean chain: {refusal}")
        got = ([(f, t, v is None) for f, t, v in attempts], stored)
        if got != self.plan or sorted({1, *fetched}) != self.rot.visited:
            fail("the plain reference syncs the signed chain otherwise than "
                 "the plan it was signed for")
        if self.warm is None:
            forget_keys()
            self.warm = self._session(self.chain)
        for name, s in [("warm-up", self.warm)] + [
                (f"window session {k}", s) for k, s in enumerate(self.sessions)]:
            if s.error is not None:
                fail(f"{name}: {type(s.error).__name__}: {s.error}")
            elif s.block is None or s.block.hash() != self.target_hash:
                fail(f"{name}: returned another block than the target")
            if (s.attempts, s.stored()) != self.plan:
                at = next((k for k, (a, b) in enumerate(zip(s.attempts, self.plan[0]))
                           if a != b), min(len(s.attempts), len(self.plan[0])))
                fail(f"{name}: {len(s.attempts)} attempts, stored "
                     f"{s.stored()[:6]}..; the reference makes "
                     f"{len(self.plan[0])} and stores {self.plan[1][:6]}..; "
                     f"they part at attempt {at}")
        inner = self.plan[1][1:-1]
        sample = [inner.pop(datagen.pick(self.run.seed, len(inner),
                                         "skip-stored", j))
                  for j in range(min(STORED_SAMPLE, len(inner)))]
        wrong = [h for h in [1, self.target] + sample
                 if (lb := self.warm.store.light_block(h)) is None
                 or lb.hash() != self.rot.block_ids[h].hash]
        if wrong:
            fail(f"stored blocks differ from the chain's at {wrong}")
        self.run.notes["stored"] = self.plan[1]

    def _check_corruption(self, name: str, corrupt, fail) -> dict:
        """One session on a copy of the chain with one corruption."""
        chain = dict(self.chain)
        want = corrupt(self, chain)       # {"height", "lane", "now"}
        now = want.get("now")
        # a corrupted commit: the reference verifies every signature of that
        # height it consults, not a sample
        self._in_full = ({m for m in self.rot.sign_bytes[want["height"]] if m}
                         if "lane" in want else set())
        attempts, _fetched, stored, refusal = self._reference(chain, now)
        forget_keys()
        s = self._session(chain, now)
        note = {"corruption": name, "height": want.get("height"),
                "lane": want.get("lane"), "reference": refusal,
                "program": None if s.error is None else type(s.error).__name__}
        if refusal is None:
            fail(f"{name}: the reference accepts the corrupted chain")
            return note
        height, kind, index = refusal
        if s.error is None:
            fail(f"{name}: accepted; the reference refuses height {height} "
                 f"({kind}, index {index})")
            return note
        # a fetch that is refused is no attempt: the program's list is then
        # one shorter than the reference's heights asked
        if s.attempts != [(f, t, v is None) for f, t, v in attempts]:
            fail(f"{name}: {len(s.attempts)} attempts before the refusal, "
                 f"the reference makes {len(attempts)}")
        if s.stored() != stored:
            fail(f"{name}: the store holds {s.stored()}; the reference "
                 f"{stored}")
        type_name, text = KINDS[kind]
        err = s.error
        got_index = getattr(err, "index",
                            getattr(getattr(err, "reason", None), "index", None))
        refused_at = s.attempts[-1][1] if s.attempts else None
        if kind == "validators_hash_supplied":
            refused_at = height           # refused at the fetch, no attempt
        if (type(err).__name__ != type_name or text not in str(err)
                or got_index != index or refused_at != height):
            fail(f"{name}: {type(err).__name__}: {err} (index {got_index}, "
                 f"height {refused_at}); the reference says {kind}, height "
                 f"{height}, index {index}")
        return note

    def _check_routes(self, fail) -> None:
        """A traced run at the timed size: every commit check of an accepted
        hop is at least a third of the set, so none may be among the batches
        the host verifier answered."""
        run = self.run
        if not run.traced:
            return
        hosted = [s["tags"].get("sigs", 0) for s in run.spans
                  if s["name"] == "prep.host_verify"]
        run.notes["host_verified_batches"] = {
            "count": len(hosted), "largest": max(hosted, default=0)}
        floor = len(self.chain[1].validator_set.validators) // 3
        if not run.rehearse and max(hosted, default=0) > floor:
            fail(f"a batch of {max(hosted)} signatures was answered by the "
                 f"host verifier; an accepted hop's checks are at least "
                 f"{floor}")


# --- corruptions: each edits a copy of the chain and says where ----------------


def _hop(drv: Driver, *path):
    """A seeded accepted hop among the first CORRUPT_WITHIN -> (from, to)."""
    hops = drv.hops[:max(1, min(CORRUPT_WITHIN, len(drv.hops) - 1))]
    return hops[datagen.pick(drv.run.seed, len(hops), "skip-bad", *path)]


def _trusting_prefix(drv: Driver, frm: int, to: int) -> list[int]:
    """The commit slots of ``to`` that the trusting check consults from
    ``frm``: signers found by address in frm's set, up to a third of its
    power."""
    trusted = drv.chain[frm].validator_set
    known = {v.address: v.voting_power for v in trusted.validators}
    num, den = drv.options["trust_level"]
    needed = trusted.total_voting_power() * num // den
    out, tallied = [], 0
    for i, cs in enumerate(drv.chain[to].signed_header.commit.signatures):
        if cs.for_block() and cs.validator_address in known:
            out.append(i)
            tallied += known[cs.validator_address]
            if tallied > needed:
                break
    return out


def _light_prefix(drv: Driver, h: int) -> list[int]:
    vals = drv.chain[h].validator_set
    return vals.commit_light_prefix(drv.chain[h].signed_header.commit,
                                    vals.total_voting_power() * 2 // 3)


def _edit_slot(chain: dict, h: int, idx: int, *, sig: bytes | None = None,
               address: bytes | None = None) -> None:
    from tendermint_tpu.types.block import Commit, CommitSig
    from tendermint_tpu.types.light_block import LightBlock, SignedHeader

    sh = chain[h].signed_header
    sigs = list(sh.commit.signatures)
    cs = sigs[idx]
    sigs[idx] = CommitSig(cs.block_id_flag,
                          cs.validator_address if address is None else address,
                          cs.timestamp, cs.signature if sig is None else sig)
    commit = Commit(height=sh.commit.height, round=sh.commit.round,
                    block_id=sh.commit.block_id, signatures=sigs)
    chain[h] = LightBlock(SignedHeader(sh.header, commit),
                          chain[h].validator_set)


def _flip(sig: bytes, seed: int, *path) -> bytes:
    bit = datagen.pick(seed, 511, "skip-bit", *path)
    out = bytearray(sig)
    out[bit // 8] ^= 1 << (bit % 8)
    return bytes(out)


def bad_signature_in_the_trusting_prefix(drv, chain):
    frm, to = _hop(drv, "trusting")
    prefix = _trusting_prefix(drv, frm, to)
    idx = prefix[datagen.pick(drv.run.seed, len(prefix), "skip-lane", "trusting")]
    sig = chain[to].signed_header.commit.signatures[idx].signature
    _edit_slot(chain, to, idx, sig=_flip(sig, drv.run.seed, "trusting"))
    return {"height": to, "lane": idx}


def bad_signature_in_the_light_prefix_past_it(drv, chain):
    """A slot the light check consults and the trusting check does not: past
    its last slot, or a validator the trusted set does not know. (The first
    accepted hop from the seeded one on that has such a slot.)"""
    start = drv.hops.index(_hop(drv, "light"))
    for frm, to in drv.hops[start:] + drv.hops[:start]:
        trusting = set(_trusting_prefix(drv, frm, to))
        known = {v.address for v in drv.chain[frm].validator_set.validators}
        sigs = chain[to].signed_header.commit.signatures
        free = [i for i in _light_prefix(drv, to) if i not in trusting
                and (i > max(trusting) or sigs[i].validator_address not in known)]
        if free:
            break
    idx = free[datagen.pick(drv.run.seed, len(free), "skip-lane", "light")]
    _edit_slot(chain, to, idx,
               sig=_flip(sigs[idx].signature, drv.run.seed, "light"))
    return {"height": to, "lane": idx}


def signer_listed_twice(drv, chain):
    """A later slot of the trusting prefix states an earlier one's address."""
    frm, to = _hop(drv, "twice")
    prefix = _trusting_prefix(drv, frm, to)
    b = 1 + datagen.pick(drv.run.seed, len(prefix) - 1, "skip-lane", "twice-b")
    a = datagen.pick(drv.run.seed, b, "skip-lane", "twice-a")
    first = chain[to].signed_header.commit.signatures[prefix[a]]
    _edit_slot(chain, to, prefix[b], address=first.validator_address)
    return {"height": to, "lane": prefix[b]}


def pivot_with_another_set(drv, chain):
    """A pivot served with another height's validator set: its header's
    validators_hash is not that set's."""
    from tendermint_tpu.types.light_block import LightBlock

    pivots = [h for h in drv.rot.plan[1] if h != drv.target]
    pivots = pivots[:max(1, min(CORRUPT_WITHIN + 2, len(pivots)))]
    h = pivots[datagen.pick(drv.run.seed, len(pivots), "skip-bad", "set")]
    other = next(q for q in drv.rot.visited
                 if drv.rot.set_hash[q - 1] != drv.rot.set_hash[h - 1])
    chain[h] = LightBlock(chain[h].signed_header, drv.chain[other].validator_set)
    return {"height": h}


def trust_root_past_its_trusting_period(drv, chain):
    """``now`` one second past the period of height 1's header: the first
    attempt is refused before any signature is looked at."""
    root, target = rotatingchain.time_ns(1), rotatingchain.time_ns(drv.target)
    after = (root - target) / 1e9 + drv.options["trusting_period_s"] + 1
    return {"height": drv.target, "now": drv._now(after)}


CORRUPTIONS = [
    ("bad signature inside the trusting prefix",
     bad_signature_in_the_trusting_prefix),
    ("bad signature inside the light prefix past it",
     bad_signature_in_the_light_prefix_past_it),
    ("signer listed twice", signer_listed_twice),
    ("pivot whose validators_hash does not match its set",
     pivot_with_another_set),
    ("trust root past its trusting period", trust_root_past_its_trusting_period),
]
