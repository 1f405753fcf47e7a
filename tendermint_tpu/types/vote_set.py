"""VoteSet: vote accumulation with 2/3-majority tracking (reference:
types/vote_set.go:78,145-290).

Two verification modes:

* add_vote(vote): the reference's semantics -- one signature verify per call
  (types/vote_set.go:205 -> vote.Verify).
* add_votes(votes): the deferred batched mode the reference lacks (SURVEY.md
  section 7.3): all signatures are verified in ONE BatchVerifier flush (one
  TPU kernel launch), then each vote's side effects (conflict detection,
  maj23 bookkeeping, evidence-triggering errors) are applied in arrival
  order, preserving per-vote error attribution exactly as if add_vote had
  been called serially.
"""

from __future__ import annotations

from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.block import Commit, make_commit
from tendermint_tpu.types.validator_set import ValidatorSet
from tendermint_tpu.utils.bits import BitArray
from tendermint_tpu.types.vote import (
    ErrVoteConflictingVotes,
    ErrVoteInvalidSignature,
    Vote,
    VoteError,
    is_vote_type_valid,
)


class VoteSetError(Exception):
    pass


class _BlockVotes:
    """Votes for one BlockID (reference: types/vote_set.go:560-590)."""

    __slots__ = ("peer_maj23", "bit_array", "votes", "sum")

    def __init__(self, peer_maj23: bool, num_validators: int):
        self.peer_maj23 = peer_maj23
        self.bit_array = BitArray(num_validators)
        self.votes: list[Vote | None] = [None] * num_validators
        self.sum = 0

    def add_verified_vote(self, vote: Vote, voting_power: int) -> None:
        idx = vote.validator_index
        if self.votes[idx] is None:
            self.bit_array[idx] = True
            self.votes[idx] = vote
            self.sum += voting_power

    def get_by_index(self, idx: int) -> Vote | None:
        return self.votes[idx]


class VoteSet:
    def __init__(self, chain_id: str, height: int, round_: int, signed_msg_type: int,
                 val_set: ValidatorSet):
        if height == 0:
            raise VoteSetError("cannot make VoteSet for height == 0, doesn't make sense")
        if not is_vote_type_valid(signed_msg_type):
            raise VoteSetError(f"invalid vote type {signed_msg_type}")
        self.chain_id = chain_id
        self.height = height
        self.round = round_
        self.signed_msg_type = signed_msg_type
        self.val_set = val_set
        self.votes_bit_array = BitArray(val_set.size())
        self.votes: list[Vote | None] = [None] * val_set.size()
        self.sum = 0
        self.maj23: BlockID | None = None
        self.votes_by_block: dict[bytes, _BlockVotes] = {}
        self.peer_maj23s: dict[str, BlockID] = {}

    # --- adding votes ------------------------------------------------------

    def add_vote(self, vote: Vote | None, verified: bool = False) -> bool:
        """Returns True if added (False: duplicate). Raises on invalid
        (reference: types/vote_set.go:145-230).

        verified=True skips the signature check: the caller already verified
        this exact (val_set[index].pub_key, sign_bytes, signature) triple
        through a BatchVerifier flush (the deferred batched mode)."""
        if vote is None:
            raise VoteSetError("nil vote")
        checked = self._precheck(vote, verified)
        if checked is None:
            return False  # exact duplicate
        val = checked
        if not verified and not val.pub_key.verify_signature(
            vote.sign_bytes(self.chain_id), vote.signature
        ):
            raise ErrVoteInvalidSignature(
                f"failed to verify vote with ChainID {self.chain_id} and "
                f"PubKey {val.pub_key.bytes().hex()}: invalid signature"
            )
        added, conflicting = self._apply_verified(vote, val)
        if conflicting is not None:
            err = ErrVoteConflictingVotes(conflicting, vote)
            err.added = added
            raise err
        if not added:
            raise AssertionError("expected to add non-conflicting vote")
        return added

    def add_votes(self, votes: list[Vote]) -> list[tuple[bool, Exception | None]]:
        """Deferred batched mode: one kernel flush for all signatures, then
        in-order application. Result list is parallel to `votes`."""
        from tendermint_tpu.crypto import sigcache

        prechecked: list[tuple[Vote, object] | None] = []
        results: list[tuple[bool, Exception | None]] = [None] * len(votes)  # type: ignore
        dc = sigcache.DrainCache()
        verifier = crypto_batch.create_batch_verifier()
        queued: list[int] = []
        # Gossiped votes at one (height, round, step, block) share identical
        # sign bytes; build each distinct canonical encoding once.
        sb_memo: dict[tuple, bytes] = {}
        for i, vote in enumerate(votes):
            try:
                checked = self._precheck(vote)
            except Exception as e:  # noqa: BLE001 - mirrored per-vote error
                results[i] = (False, e)
                prechecked.append(None)
                continue
            if checked is None:
                results[i] = (False, None)  # duplicate
                prechecked.append(None)
                continue
            prechecked.append((vote, checked))
            sb_key = (vote.height, vote.round, vote.type,
                      vote.block_id.key(), vote.timestamp)
            sb = sb_memo.get(sb_key)
            if sb is None:
                sb = sb_memo[sb_key] = vote.sign_bytes(self.chain_id)
            # A triple already verified in an earlier drain (gossip
            # re-delivery, another round's batch) skips the kernel and goes
            # straight to the accept-replay below.
            if dc.check(i, checked.pub_key.bytes(), sb, vote.signature):
                continue
            verifier.add(checked.pub_key, sb, vote.signature)
            queued.append(i)
        if queued or dc.cached_ok:
            try:
                bitmap = verifier.verify()[1] if queued else []
            except BaseException:
                dc.commit([], [])  # flush metrics deltas; nothing cached
                raise
            ok_by_i = dc.commit(queued, bitmap)
            # queued and the cache hits are each ascending; the merged
            # sorted order is exactly the serial arrival order.
            for i in sorted(ok_by_i):
                vote, val = prechecked[i]  # type: ignore[misc]
                if not ok_by_i[i]:
                    results[i] = (False, ErrVoteInvalidSignature(
                        f"failed to verify vote with ChainID {self.chain_id} and "
                        f"PubKey {val.pub_key.bytes().hex()}: invalid signature"
                    ))
                    continue
                try:
                    # Re-run ONLY the duplicate/conflict check (the rest of
                    # _precheck is state-independent and already passed): an
                    # earlier vote in this same batch may have made this one
                    # a duplicate or a non-deterministic-signature error.
                    existing = self._get_vote(vote.validator_index,
                                              vote.block_id.key())
                    if existing is not None:
                        if existing.signature == vote.signature:
                            results[i] = (False, None)
                        else:
                            results[i] = (False, VoteError(
                                f"existing vote: {existing}; new vote: {vote}: "
                                "non-deterministic signature"))
                        continue
                    added, conflicting = self._apply_verified(vote, val)
                    if conflicting is not None:
                        err = ErrVoteConflictingVotes(conflicting, vote)
                        err.added = added
                        results[i] = (added, err)
                    else:
                        results[i] = (added, None)
                except Exception as e:  # noqa: BLE001
                    results[i] = (False, e)
        return results

    def _precheck(self, vote: Vote, verified: bool = False):
        """Everything add_vote does before the signature check. Returns the
        validator, or None for an exact duplicate."""
        val_index = vote.validator_index
        val_addr = vote.validator_address
        if not vote.block_id.is_zero():
            vote.block_id.validate_basic()
        if val_index < 0:
            raise VoteSetError("index < 0: invalid validator index")
        if not val_addr:
            raise VoteSetError("empty address: invalid validator address")
        if (vote.height != self.height or vote.round != self.round
                or vote.type != self.signed_msg_type):
            raise VoteSetError(
                f"expected {self.height}/{self.round}/{self.signed_msg_type}, "
                f"but got {vote.height}/{vote.round}/{vote.type}: unexpected step"
            )
        addr, val = self.val_set.get_by_index(val_index)
        if val is None:
            raise VoteSetError(
                f"cannot find validator {val_index} in valSet of size {self.val_set.size()}: "
                "invalid validator index"
            )
        if addr != val_addr:
            raise VoteSetError(
                f"vote.ValidatorAddress ({val_addr.hex()}) does not match address "
                f"({addr.hex()}) for vote.ValidatorIndex ({val_index})"
            )
        existing = self._get_vote(val_index, vote.block_id.key())
        if existing is not None:
            if existing.signature == vote.signature:
                return None  # duplicate
            # A second signature over a vote already held. The reference
            # names it non-deterministic without looking at it; a copy a
            # relay corrupted is an invalid signature whether it arrives
            # before the good copy or after, and the batched drain, which
            # verifies before it looks at the set, says so: the serial path
            # has to give the deliverer the same verdict.
            if not verified and not val.pub_key.verify_signature(
                    vote.sign_bytes(self.chain_id), vote.signature):
                raise ErrVoteInvalidSignature(
                    f"failed to verify vote with ChainID {self.chain_id} and "
                    f"PubKey {val.pub_key.bytes().hex()}: invalid signature")
            raise VoteError(
                f"existing vote: {existing}; new vote: {vote}: non-deterministic signature"
            )
        return val

    def _get_vote(self, val_index: int, block_key: bytes) -> Vote | None:
        """reference: types/vote_set.go getVote -- checks the main slot AND
        the per-block tracker (conflicting votes live only in the latter)."""
        existing = self.votes[val_index]
        if existing is not None and existing.block_id.key() == block_key:
            return existing
        bv = self.votes_by_block.get(block_key)
        if bv is not None:
            return bv.get_by_index(val_index)
        return None

    def _apply_verified(self, vote: Vote, val) -> tuple[bool, Vote | None]:
        """addVerifiedVote (reference: types/vote_set.go:234-300): conflict
        handling + maj23 bookkeeping. Returns (added, conflicting)."""
        val_index = vote.validator_index
        voting_power = val.voting_power
        block_key = vote.block_id.key()

        existing = self.votes[val_index]
        conflicting: Vote | None = None
        if existing is not None:
            if existing.block_id == vote.block_id:
                raise AssertionError("addVerifiedVote does not expect duplicate votes")
            conflicting = existing
            # Replace the main-slot vote only if this block already has maj23.
            if self.maj23 is not None and self.maj23.key() == block_key:
                self.votes[val_index] = vote
                self.votes_bit_array[val_index] = True
        else:
            self.votes[val_index] = vote
            self.votes_bit_array[val_index] = True
            self.sum += voting_power

        bv = self.votes_by_block.get(block_key)
        if bv is not None:
            if conflicting is not None and not bv.peer_maj23:
                # Conflict and no peer claims this block is special.
                return False, conflicting
        else:
            if conflicting is not None:
                # Not even tracking this block: forget it.
                return False, conflicting
            bv = _BlockVotes(peer_maj23=False, num_validators=self.val_set.size())
            self.votes_by_block[block_key] = bv

        before = bv.sum
        bv.add_verified_vote(vote, voting_power)
        quorum = self.val_set.total_voting_power() * 2 // 3 + 1
        if before < quorum <= bv.sum and self.maj23 is None:
            self.maj23 = vote.block_id
            # Promote this block's votes into the main tally.
            for i, v in enumerate(bv.votes):
                if v is not None:
                    self.votes[i] = v
        return True, conflicting

    # --- queries (reference: types/vote_set.go:300-520) --------------------

    def get_by_index(self, idx: int) -> Vote | None:
        if idx < 0 or idx >= len(self.votes):
            return None
        return self.votes[idx]

    def get_by_address(self, address: bytes) -> Vote | None:
        idx, _ = self.val_set.get_by_address(address)
        return self.get_by_index(idx) if idx >= 0 else None

    def set_peer_maj23(self, peer_id: str, block_id: BlockID) -> None:
        """reference: types/vote_set.go:300-340."""
        block_key = block_id.key()
        existing = self.peer_maj23s.get(peer_id)
        if existing is not None:
            if existing == block_id:
                return
            raise VoteSetError(
                f"setPeerMaj23: Received conflicting blockID from peer {peer_id}: "
                f"{existing} vs {block_id}"
            )
        self.peer_maj23s[peer_id] = block_id
        bv = self.votes_by_block.get(block_key)
        if bv is not None:
            bv.peer_maj23 = True
        else:
            self.votes_by_block[block_key] = _BlockVotes(
                peer_maj23=True, num_validators=self.val_set.size()
            )

    def bit_array(self) -> BitArray:
        return self.votes_bit_array.copy()

    def bit_array_by_block_id(self, block_id: BlockID) -> BitArray | None:
        bv = self.votes_by_block.get(block_id.key())
        if bv is None:
            return None
        return bv.bit_array.copy()

    def has_two_thirds_majority(self) -> bool:
        return self.maj23 is not None

    def two_thirds_majority(self) -> tuple[BlockID | None, bool]:
        if self.maj23 is not None:
            return self.maj23, True
        return None, False

    def has_two_thirds_any(self) -> bool:
        return self.sum > self.val_set.total_voting_power() * 2 // 3

    def has_one_third_any(self) -> bool:
        return self.sum > self.val_set.total_voting_power() // 3

    def has_all(self) -> bool:
        return self.sum == self.val_set.total_voting_power()

    def size(self) -> int:
        return self.val_set.size()

    def make_commit(self) -> Commit:
        """reference: types/vote_set.go:590-620."""
        if self.signed_msg_type != 2:
            raise VoteSetError("cannot MakeCommit() unless VoteSet.Type is PrecommitType")
        if self.maj23 is None:
            raise VoteSetError("cannot MakeCommit() unless a blockhash has +2/3")
        return make_commit(self.maj23, self.height, self.round, self.votes)

    def __str__(self) -> str:
        n_present = sum(1 for v in self.votes if v is not None)
        return (
            f"VoteSet{{H:{self.height} R:{self.round} T:{self.signed_msg_type} "
            f"{n_present}/{self.size()} sum={self.sum} maj23={self.maj23}}}"
        )
