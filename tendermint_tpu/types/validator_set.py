"""ValidatorSet: ordering, proposer rotation, and the batched commit
verification paths (reference: types/validator_set.go:70,107-180,660-830).

The three Verify* entry points are where the reference burns one serial
ed25519 verify per validator (~70-100us each). Here every signature needed by
the serial decision procedure is queued into one BatchVerifier flush (one TPU
kernel launch), and the reference's *exact* accept/reject + error-attribution
semantics are then replayed over the returned bitmap:

 - VerifyCommit checks ALL signatures (incentivization, see reference comment
   types/validator_set.go:662-666) and fails on the first invalid index;
 - VerifyCommitLight / VerifyCommitLightTrusting stop tallying at +2/3 - in
   the serial code later signatures are NEVER verified, so an invalid
   signature after the threshold does not fail the call. We reproduce that by
   ignoring bitmap entries past the serial stopping point.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.crypto import merkle
from tendermint_tpu.encoding import proto
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.validator import (
    MAX_TOTAL_VOTING_POWER,
    PRIORITY_WINDOW_SIZE_FACTOR,
    Validator,
    clip_int64,
)
from tendermint_tpu.utils import trace as _trace

# Implied validator-set size cap (reference: types/validator_set.go MaxVotesCount)
MAX_VOTES_COUNT = 10000


class ValidatorSetError(Exception):
    pass


class ErrNotEnoughVotingPowerSigned(ValidatorSetError):
    def __init__(self, got: int, needed: int):
        super().__init__(f"invalid commit -- insufficient voting power: got {got}, needed more than {needed}")
        self.got = got
        self.needed = needed


class ErrInvalidCommitSignatures(ValidatorSetError):
    def __init__(self, have: int, want: int):
        super().__init__(f"invalid commit -- wrong set size: {have} vs {want}")


class ErrInvalidCommitHeight(ValidatorSetError):
    def __init__(self, want: int, got: int):
        super().__init__(f"invalid commit -- wrong height: {want} vs {got}")


class ErrWrongSignature(ValidatorSetError):
    def __init__(self, idx: int, sig: bytes):
        super().__init__(f"wrong signature (#{idx}): {sig.hex().upper()}")
        self.index = idx


class ErrDoubleVote(ValidatorSetError):
    """A commit lists one validator of the trusted set twice (reference:
    types/validator_set.go:806, VerifyCommitLightTrusting's seenVals)."""

    def __init__(self, val, first: int, idx: int):
        self.first, self.index = first, idx
        super().__init__(f"double vote from {val} ({first} and {idx})")


class PendingCommitVerify:
    """A dispatched-but-undecided commit verification (the cross-decision
    pipeline handle of verify_commit_async / verify_commit_light_async).

    All host prep and device dispatch happened at creation; ``resolve()``
    performs the (possibly batched-away) readback and replays the EXACT
    serial accept/reject decision procedure, raising precisely what the
    synchronous call would have raised — structural errors captured at
    dispatch time included, so error ordering per decision is unchanged.
    Decision inputs (stopping prefix, voting powers, threshold) are frozen
    at dispatch: a caller that mutates the ValidatorSet afterwards gets the
    dispatch-time decision, the only sane semantics for speculative
    verification (the fast-sync pipeline discards handles whose validator
    set changed before their turn).

    ``pending`` exposes the underlying crypto-layer
    :class:`~tendermint_tpu.crypto.batch.PendingVerify` (None when the
    decision needed no device work) so callers with several decisions in
    flight can batch the readbacks into one device_get
    (crypto_batch.prefetch).

    A decision dispatched with the flight recorder on carries its tracer
    and its decision id (the ``commit.assemble`` root span's id), so the
    wait and the tally land in the same tree whoever resolves it, later."""

    __slots__ = ("pending", "_finalize", "_error", "_tracer", "_decision",
                 "sigs")

    def __init__(self, pending=None, finalize=None, error: Exception | None = None,
                 sigs: int = 0):
        self.pending = pending
        self._finalize = finalize
        self._error = error
        self._tracer = None
        self._decision = 0
        # signatures handed to the verifier, where the entry point says so
        # (the trusting check: its span's `n`)
        self.sigs = sigs

    def resolve(self) -> None:
        """Raises exactly what the synchronous verify would; returns None on
        accept. Idempotent: the bitmap is cached by the crypto layer and the
        decision replay is deterministic."""
        if self._error is not None:
            raise self._error
        tr = self._tracer
        if tr is not None and tr.enabled:
            return self._resolve_traced(tr)
        bitmap: list[bool] = []
        if self.pending is not None:
            _, bitmap = self.pending.resolve()
        self._finalize(bitmap)

    def _resolve_traced(self, tr) -> None:
        did = self._decision
        bitmap: list[bool] = []
        if self.pending is not None:
            with tr.span("commit.wait", decision=did, parent=did):
                _, bitmap = self.pending.resolve()
        with tr.span("commit.tally", decision=did, parent=did):
            self._finalize(bitmap)


class _AddressIndex:
    """Where each address stands in a set's ``validators``. Derived data,
    like the set's hash, and held the same way, except that a set and its
    copies (the same validators in the same order) share one holder, so
    whichever of them is asked first builds the dict for all of them:
    ``State.next_validators`` is never asked for an address, and every
    height's ``validators`` is a copy of it. The dict is assigned once,
    complete, and never changed after: safe under threads that only read."""

    __slots__ = ("positions",)

    def __init__(self):
        self.positions: dict[bytes, int] | None = None


# address indexes built in this process since it started: apply.validate's
# ``index_builds`` tag is the difference of two readings
_index_builds = 0


def index_builds() -> int:
    return _index_builds


class ValidatorSet:
    """Sorted by voting power desc, then address asc. Not thread-safe."""

    def __init__(self, validators: list[Validator] | None = None):
        self.validators: list[Validator] = []
        self.proposer: Validator | None = None
        self._total_voting_power = 0
        if validators is not None:
            self._update_with_change_set(
                [v.copy() for v in validators], allow_deletes=False
            )
            if validators:
                self.increment_proposer_priority(1)

    # --- basic accessors ---------------------------------------------------

    def size(self) -> int:
        return len(self.validators)

    def __len__(self) -> int:
        return len(self.validators)

    def is_nil_or_empty(self) -> bool:
        return len(self.validators) == 0

    def has_address(self, address: bytes) -> bool:
        return self._position(address) >= 0

    def get_by_address(self, address: bytes) -> tuple[int, Validator | None]:
        i = self._position(address)
        return (i, self.validators[i].copy()) if i >= 0 else (-1, None)

    def _address_index(self) -> _AddressIndex:
        """This set's holder: made here when the set has none, or when
        ``validators`` is another list than the one it was made for."""
        held = getattr(self, "_addr_index", None)
        if held is None or held[1] is not self.validators:
            held = self._addr_index = (_AddressIndex(), self.validators)
        return held[0]

    def _positions(self) -> dict[bytes, int]:
        index = self._address_index()
        positions = index.positions
        if positions is None:
            global _index_builds
            positions = {}
            for i, v in enumerate(self.validators):
                positions.setdefault(v.address, i)  # the first, as a scan finds
            index.positions = positions
            _index_builds += 1
        return positions

    def _position(self, address: bytes) -> int:
        """Where the first validator with ``address`` stands, or -1: what a
        scan of ``validators`` answers, in one step. A hit is checked against
        the list, and a miss is believed only while the index is as long as
        the list, so a list appended to, cut or assigned behind the set's
        back gets a new index, of this set alone, and the scan's answer. (A
        validator put in another's place, the length kept, can still be
        missed: the convention of ``hash()``.)"""
        vals = self.validators
        positions = self._positions()
        try:
            i = positions.get(address, -1)
        except TypeError:  # unhashable, a bytearray: compare, as the scan did
            return next((i for i, v in enumerate(vals) if v.address == address), -1)
        if i >= 0:
            if i < len(vals) and vals[i].address == address:
                return i
        elif len(positions) == len(vals):
            return -1
        self._addr_index = None
        return self._positions().get(address, -1)

    def get_by_index(self, index: int) -> tuple[bytes | None, Validator | None]:
        if index < 0 or index >= len(self.validators):
            return None, None
        v = self.validators[index]
        return v.address, v.copy()

    def total_voting_power(self) -> int:
        if self._total_voting_power == 0:
            self._update_total_voting_power()
        return self._total_voting_power

    def _update_total_voting_power(self) -> None:
        s = 0
        for v in self.validators:
            s = clip_int64(s + v.voting_power)
            if s > MAX_TOTAL_VOTING_POWER:
                raise ValidatorSetError(
                    f"total voting power exceeds max {MAX_TOTAL_VOTING_POWER}: {s}"
                )
        self._total_voting_power = s

    def copy(self) -> "ValidatorSet":
        new = ValidatorSet()
        new.validators = [v.copy() for v in self.validators]
        new.proposer = self.proposer
        new._total_voting_power = self._total_voting_power
        # the set hash covers (pubkey, power) only, both copied verbatim
        new._hash_cache = getattr(self, "_hash_cache", None)
        # the same validators in the same order: one address index for both
        new._addr_index = (self._address_index(), new.validators)
        return new

    def validate_basic(self) -> None:
        if self.is_nil_or_empty():
            raise ValidatorSetError("validator set is nil or empty")
        for i, v in enumerate(self.validators):
            try:
                v.validate_basic()
            except ValueError as e:
                raise ValidatorSetError(f"invalid validator #{i}: {e}") from e
        if self.proposer is None:
            raise ValidatorSetError("proposer failed validate basic: nil")
        self.proposer.validate_basic()

    def hash(self) -> bytes:
        """Merkle root over SimpleValidator marshals (reference:
        types/validator_set.go:346-353). Memoized: light-client range sync
        hashes the same set once per header otherwise. The cache survives
        copy() and is invalidated by update_with_change_set; proposer-
        priority rotation does not enter the hash. Direct mutation of a
        validator's power/key bypasses invalidation (same caller convention
        as Header hash caching)."""
        h = getattr(self, "_hash_cache", None)
        if h is None:
            h = merkle.hash_from_byte_slices([v.bytes() for v in self.validators])
            self._hash_cache = h
        return h

    # --- proposer rotation (reference: types/validator_set.go:107-245) -----

    def get_proposer(self) -> Validator | None:
        if not self.validators:
            return None
        if self.proposer is None:
            self.proposer = self._find_proposer()
        return self.proposer.copy()

    def _find_proposer(self) -> Validator:
        proposer = None
        for v in self.validators:
            if proposer is None or v.address != proposer.address:
                proposer = v.compare_proposer_priority(proposer)
        return proposer

    def increment_proposer_priority(self, times: int) -> None:
        if self.is_nil_or_empty():
            raise ValidatorSetError("empty validator set")
        if times <= 0:
            raise ValidatorSetError("cannot call with non-positive times")
        diff_max = PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power()
        self.rescale_priorities(diff_max)
        self._shift_by_avg_proposer_priority()
        proposer = None
        for _ in range(times):
            proposer = self._increment_proposer_priority()
        self.proposer = proposer

    def copy_increment_proposer_priority(self, times: int) -> "ValidatorSet":
        c = self.copy()
        c.increment_proposer_priority(times)
        return c

    def rescale_priorities(self, diff_max: int) -> None:
        if diff_max <= 0:
            return
        diff = self._max_min_priority_diff()
        ratio = (diff + diff_max - 1) // diff_max
        if diff > diff_max:
            for v in self.validators:
                # Go integer division truncates toward zero.
                q = abs(v.proposer_priority) // ratio
                v.proposer_priority = q if v.proposer_priority >= 0 else -q

    def _max_min_priority_diff(self) -> int:
        prios = [v.proposer_priority for v in self.validators]
        return abs(max(prios) - min(prios))

    def _shift_by_avg_proposer_priority(self) -> None:
        n = len(self.validators)
        # Floor-divide like Go big.Int Div (Euclidean for positive divisor).
        total = sum(v.proposer_priority for v in self.validators)
        avg = total // n if total >= 0 else -((-total + n - 1) // n)
        for v in self.validators:
            v.proposer_priority = clip_int64(v.proposer_priority - avg)

    def _increment_proposer_priority(self) -> Validator:
        for v in self.validators:
            v.proposer_priority = clip_int64(v.proposer_priority + v.voting_power)
        mostest = None
        for v in self.validators:
            mostest = v.compare_proposer_priority(mostest)
        mostest.proposer_priority = clip_int64(
            mostest.proposer_priority - self.total_voting_power()
        )
        return mostest

    # --- updates (reference: types/validator_set.go:398-650) ---------------

    def update_with_change_set(self, changes: list[Validator]) -> None:
        self._update_with_change_set([c.copy() for c in changes], allow_deletes=True)

    def _update_with_change_set(self, changes: list[Validator], allow_deletes: bool) -> None:
        if not changes:
            return
        self._hash_cache = None  # membership/power may change
        changes_sorted = sorted(changes, key=lambda v: v.address)
        for a, b in zip(changes_sorted, changes_sorted[1:]):
            if a.address == b.address:
                raise ValidatorSetError(f"duplicate entry {b} in changes")
        updates, removals = [], []
        for c in changes_sorted:
            if c.voting_power < 0:
                raise ValidatorSetError("voting power can't be negative")
            if c.voting_power > MAX_TOTAL_VOTING_POWER:
                raise ValidatorSetError(
                    f"to prevent clipping/overflow, voting power can't be higher than {MAX_TOTAL_VOTING_POWER}"
                )
            if c.voting_power == 0:
                removals.append(c)
            else:
                updates.append(c)
        if removals and not allow_deletes:
            raise ValidatorSetError(f"cannot process validators with voting power 0: {removals}")
        for r in removals:
            if not self.has_address(r.address):
                raise ValidatorSetError(
                    f"failed to find validator {r.address.hex()} to remove"
                )

        # verifyUpdates: check the updated total doesn't overflow.
        delta = 0
        by_addr = {v.address: v for v in self.validators}
        for u in updates:
            prev = by_addr.get(u.address)
            delta += u.voting_power - (prev.voting_power if prev else 0)
        removed_power = sum(
            by_addr[r.address].voting_power for r in removals if r.address in by_addr
        )
        new_total = self.total_voting_power() + delta - removed_power if self.validators else sum(
            u.voting_power for u in updates
        )
        if new_total > MAX_TOTAL_VOTING_POWER:
            raise ValidatorSetError(
                f"total voting power of resulting valset exceeds max {MAX_TOTAL_VOTING_POWER}"
            )

        # computeNewPriorities: new validators start at -1.125 * new total.
        for u in updates:
            prev = by_addr.get(u.address)
            if prev is None:
                u.proposer_priority = -(new_total + (new_total >> 3))
            else:
                u.proposer_priority = prev.proposer_priority

        # apply: merge + delete, re-sort by (power desc, address asc).
        removal_addrs = {r.address for r in removals}
        merged = {v.address: v for v in self.validators}
        for u in updates:
            merged[u.address] = u
        for addr in removal_addrs:
            merged.pop(addr, None)
        self.validators = sorted(
            merged.values(), key=lambda v: (-v.voting_power, v.address)
        )
        # membership and order change here, and nowhere else (the removals
        # above were still looked up in the old list's index)
        self._addr_index = None
        self._total_voting_power = 0
        self._update_total_voting_power()
        if updates or removals:
            # Only rescale/recenter when something changed (updateWithChangeSet
            # tail, reference types/validator_set.go:628-644).
            self.rescale_priorities(PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power())
            self._shift_by_avg_proposer_priority()

    # --- commit verification (the TPU hot path) ----------------------------

    def _commit_structural_error(self, block_id: BlockID, height: int,
                                 commit) -> ValidatorSetError | None:
        """The shared pre-signature checks of every Verify* entry point."""
        if self.size() != len(commit.signatures):
            return ErrInvalidCommitSignatures(self.size(), len(commit.signatures))
        if height != commit.height:
            return ErrInvalidCommitHeight(height, commit.height)
        if block_id != commit.block_id:
            return ValidatorSetError(
                f"invalid commit -- wrong block ID: want {block_id}, got {commit.block_id}"
            )
        return None

    def verify_commit(self, chain_id: str, block_id: BlockID, height: int, commit) -> None:
        """Checks ALL signatures; first bad index wins (reference:
        types/validator_set.go:660-715)."""
        self.verify_commit_async(chain_id, block_id, height, commit).resolve()

    def _assemble_traced(self, tr, mode: str, assemble, *args) -> PendingCommitVerify:
        """Run a decision's dispatch half inside its root span,
        ``commit.assemble``, whose id is the decision id every later span of
        the decision carries (docs/OBSERVABILITY.md)."""
        with tr.span("commit.assemble", decision=True, mode=mode) as did:
            pcv = assemble(*args, tr)
        pcv._tracer, pcv._decision = tr, did
        return pcv

    def add_commit_sigs(self, verifier, chain_id: str, commit,
                        idxs: list[int], val_idxs: list[int], tr=None) -> None:
        """Queue on ``verifier``, in order, the signature in every commit
        slot of ``idxs`` against the key of the validator at the same place
        in ``val_idxs``. The one loop under every Verify* entry point and
        light.range_verify: the sign bytes are assembled once for the commit
        (Commit.sign_bytes_many), and under a tracer that one call is timed
        into the open ``commit.assemble`` span."""
        if tr is None:
            msgs, _ = commit.sign_bytes_many(chain_id, idxs)
        else:
            t0 = time.perf_counter()
            msgs, spliced = commit.sign_bytes_many(chain_id, idxs)
            tr.annotate(sigs=len(idxs), spliced=spliced,
                        sign_bytes_s=time.perf_counter() - t0)
        add, validators, signatures = (
            verifier.add, self.validators, commit.signatures)
        for idx, val_idx, msg in zip(idxs, val_idxs, msgs):
            add(validators[val_idx].pub_key, msg, signatures[idx].signature)

    def verify_commit_async(self, chain_id: str, block_id: BlockID, height: int,
                            commit, force_device: bool = False) -> PendingCommitVerify:
        """Deferred verify_commit: host prep + device dispatch now, the
        serial decision replay (identical errors) on resolve()."""
        if _trace.ENABLED:
            tr = _trace.current()
            if tr.enabled:
                return self._assemble_traced(
                    tr, "full", self._verify_commit_assemble, chain_id,
                    block_id, height, commit, force_device)
        return self._verify_commit_assemble(chain_id, block_id, height, commit,
                                            force_device)

    def _verify_commit_assemble(self, chain_id: str, block_id: BlockID,
                                height: int, commit, force_device: bool,
                                tr=None) -> PendingCommitVerify:
        err = self._commit_structural_error(block_id, height, commit)
        if err is not None:
            return PendingCommitVerify(error=err)
        queued = [idx for idx, cs in enumerate(commit.signatures)
                  if not cs.absent()]
        verifier = crypto_batch.create_batch_verifier()
        self.add_commit_sigs(verifier, chain_id, commit, queued, queued, tr)
        pending = verifier.dispatch(force_device=force_device)
        # Freeze the decision inputs at dispatch time.
        needed = self.total_voting_power() * 2 // 3
        powers = [self.validators[idx].voting_power for idx in queued]
        signatures = list(commit.signatures)

        def finalize(bitmap: list[bool]) -> None:
            ok_by_idx = dict(zip(queued, bitmap))
            tallied = 0
            for idx, power in zip(queued, powers):
                cs = signatures[idx]
                if not ok_by_idx[idx]:
                    raise ErrWrongSignature(idx, cs.signature)
                if cs.for_block():
                    tallied += power
            if tallied <= needed:
                raise ErrNotEnoughVotingPowerSigned(tallied, needed)

        return PendingCommitVerify(pending, finalize)

    def commit_light_prefix(self, commit, needed: int) -> list[int]:
        """Indexes the serial VerifyCommitLight would actually verify: the
        shortest for_block prefix whose power exceeds `needed` (the reference
        stopping rule, types/validator_set.go:740-762). Shared by
        verify_commit_light and light.range_verify so the serial-semantics
        replay can never drift between them."""
        prefix: list[int] = []
        tallied = 0
        for idx, cs in enumerate(commit.signatures):
            if not cs.for_block():
                continue
            prefix.append(idx)
            tallied += self.validators[idx].voting_power
            if tallied > needed:
                break
        return prefix

    def verify_commit_light(self, chain_id: str, block_id: BlockID, height: int, commit) -> None:
        """Stops at +2/3 like the serial code: signatures past the serial
        stopping point are not consulted (reference:
        types/validator_set.go:719-766)."""
        self.verify_commit_light_async(chain_id, block_id, height, commit).resolve()

    def verify_commit_light_async(self, chain_id: str, block_id: BlockID,
                                  height: int, commit,
                                  force_device: bool = False) -> PendingCommitVerify:
        """Deferred verify_commit_light: the fast-sync verify-ahead pipeline
        (blockchain/pipeline.py) dispatches several heights' commits through
        this, overlapping the device round trips with block save/apply, and
        replays each height's serial decision in order on resolve()."""
        if _trace.ENABLED:
            tr = _trace.current()
            if tr.enabled:
                return self._assemble_traced(
                    tr, "light", self._verify_commit_light_assemble, chain_id,
                    block_id, height, commit, force_device)
        return self._verify_commit_light_assemble(chain_id, block_id, height,
                                                  commit, force_device)

    def _verify_commit_light_assemble(self, chain_id: str, block_id: BlockID,
                                      height: int, commit, force_device: bool,
                                      tr=None) -> PendingCommitVerify:
        err = self._commit_structural_error(block_id, height, commit)
        if err is not None:
            return PendingCommitVerify(error=err)
        needed = self.total_voting_power() * 2 // 3
        prefix = self.commit_light_prefix(commit, needed)
        verifier = crypto_batch.create_batch_verifier()
        self.add_commit_sigs(verifier, chain_id, commit, prefix, prefix, tr)
        pending = verifier.dispatch(force_device=force_device)
        powers = [self.validators[idx].voting_power for idx in prefix]
        signatures = list(commit.signatures)

        def finalize(bitmap: list[bool]) -> None:
            tallied = 0
            for idx, power, ok in zip(prefix, powers, bitmap):
                if not ok:
                    raise ErrWrongSignature(idx, signatures[idx].signature)
                tallied += power
                if tallied > needed:
                    return
            raise ErrNotEnoughVotingPowerSigned(tallied, needed)

        return PendingCommitVerify(pending, finalize)

    def verify_commit_light_trusting(self, chain_id: str, commit, trust_level) -> None:
        """trust_level of THIS set must have signed (reference:
        types/validator_set.go:772-830). trust_level: (numerator, denominator).
        Under the flight recorder the whole check (scan, dispatch, wait,
        tally) is one ``light.skip.trusting`` span that names the decision
        below it and the signatures it handed to the verifier."""
        if _trace.ENABLED:
            tr = _trace.current()
            if tr.enabled:
                with tr.span("light.skip.trusting"):
                    pcv = self.verify_commit_light_trusting_async(
                        chain_id, commit, trust_level)
                    tr.annotate(n=pcv.sigs, decision=pcv._decision)
                    try:
                        pcv.resolve()
                    except ValidatorSetError as e:
                        tr.annotate(refused=type(e).__name__)
                        raise
                return
        self.verify_commit_light_trusting_async(chain_id, commit,
                                                trust_level).resolve()

    def verify_commit_light_trusting_async(self, chain_id: str, commit,
                                           trust_level) -> PendingCommitVerify:
        """Deferred verify_commit_light_trusting: scan and dispatch now, the
        serial decision replay (identical errors) on resolve(), as the other
        two commit checks."""
        if _trace.ENABLED:
            tr = _trace.current()
            if tr.enabled:
                return self._assemble_traced(
                    tr, "trusting", self._verify_commit_light_trusting_assemble,
                    chain_id, commit, trust_level)
        return self._verify_commit_light_trusting_assemble(
            chain_id, commit, trust_level)

    def _verify_commit_light_trusting_assemble(self, chain_id: str, commit,
                                               trust_level,
                                               tr=None) -> PendingCommitVerify:
        num, den = trust_level
        if den == 0:
            return PendingCommitVerify(error=ValidatorSetError(
                "trustLevel has zero Denominator"))
        total_mul = self.total_voting_power() * num
        if total_mul > 2**63 - 1:
            return PendingCommitVerify(error=ValidatorSetError(
                "int64 overflow while calculating voting power needed"))
        needed = total_mul // den

        # The serial loop looks a signer up by address in THIS set (another
        # height's), refuses one it has seen, verifies, tallies and stops
        # above `needed`. The scan finds the slots that loop would reach:
        # it ends at the threshold or at a double vote, which the serial
        # code reports only after every signature before it has verified.
        position, validators = self._position, self.validators
        seen: dict[int, int] = {}
        idxs: list[int] = []      # commit slots, in order
        val_idxs: list[int] = []  # the signer's place in this set
        double_vote: ErrDoubleVote | None = None
        tallied_scan = 0
        for idx, cs in enumerate(commit.signatures):
            if not cs.for_block():
                continue
            val_idx = position(cs.validator_address)
            if val_idx < 0:
                continue
            if val_idx in seen:
                double_vote = ErrDoubleVote(validators[val_idx],
                                            seen[val_idx], idx)
                break
            seen[val_idx] = idx
            idxs.append(idx)
            val_idxs.append(val_idx)
            tallied_scan += validators[val_idx].voting_power
            if tallied_scan > needed:
                break

        verifier = crypto_batch.create_batch_verifier()
        self.add_commit_sigs(verifier, chain_id, commit, idxs, val_idxs, tr)
        pending = verifier.dispatch()
        powers = [validators[val_idx].voting_power for val_idx in val_idxs]
        signatures = list(commit.signatures)

        def finalize(bitmap: list[bool]) -> None:
            tallied = 0
            for idx, power, ok in zip(idxs, powers, bitmap):
                if not ok:
                    raise ErrWrongSignature(idx, signatures[idx].signature)
                tallied += power
                if tallied > needed:
                    return
            if double_vote is not None:
                raise double_vote
            raise ErrNotEnoughVotingPowerSigned(tallied, needed)

        return PendingCommitVerify(pending, finalize, sigs=len(idxs))

    # --- wire --------------------------------------------------------------

    def marshal(self) -> bytes:
        out = proto.repeated_messages(  # field 1
            b"\x0a", [v.marshal() for v in self.validators])
        if self.proposer is not None:
            out += proto.repeated_messages(b"\x12", (self.proposer.marshal(),))
        total = self.total_voting_power()
        return out + b"\x18" + proto.encode_varint(total) if total else out

    @staticmethod
    def unmarshal(buf: bytes) -> "ValidatorSet":
        f = proto.fields(buf)
        vs = ValidatorSet()
        vs.validators = [Validator.unmarshal(b) for b in f.get(1, [])]
        if 2 in f:
            vs.proposer = Validator.unmarshal(f[2][-1])
        vs._total_voting_power = 0
        return vs

    def __str__(self) -> str:
        prop = self.proposer.address.hex()[:12] if self.proposer else "nil"
        return f"ValidatorSet{{n={len(self.validators)} proposer={prop}}}"
