"""The batched addVote hot loop (BASELINE config 5): gossiped votes drained
and verified in one BatchVerifier flush, with per-vote side effects applied in
arrival order (reference serial path: consensus/state.go:1995 addVote ->
types/vote_set.go:205 vote.Verify, one scalar verify per vote)."""

import time

import pytest

from tendermint_tpu.crypto import ed25519
from tendermint_tpu.types.block_id import BlockID, PartSetHeader
from tendermint_tpu.types.ttime import Time
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.types.validator_set import ValidatorSet
from tendermint_tpu.types.vote import PRECOMMIT_TYPE, PREVOTE_TYPE, Vote, VoteError
from tendermint_tpu.types.vote_set import VoteSet

CHAIN_ID = "batch-chain"
N_VALS = 1024


def _net(n):
    privs = [
        ed25519.gen_priv_key((i + 1).to_bytes(2, "big") * 16) for i in range(n)
    ]
    vals = ValidatorSet(
        [Validator(p.pub_key().address(), p.pub_key(), 10) for p in privs]
    )
    # ValidatorSet orders by (power desc, address asc); realign priv keys.
    by_addr = {p.pub_key().address(): p for p in privs}
    privs = [by_addr[v.address] for v in vals.validators]
    return privs, vals


def _signed_vote(priv, vals, vtype, block_id, i=None):
    addr = priv.pub_key().address()
    idx, _ = vals.get_by_address(addr)
    v = Vote(
        type=vtype, height=1, round=0, block_id=block_id,
        timestamp=Time(1700001000, 0), validator_address=addr,
        validator_index=idx,
    )
    v.signature = priv.sign(v.sign_bytes(CHAIN_ID))
    return v


@pytest.fixture(scope="module")
def big_net():
    return _net(N_VALS)


def test_add_votes_1024_validators_maj23(big_net):
    """1024 prevotes through ONE batched flush; maj23 must be found and every
    vote individually accepted."""
    privs, vals = big_net
    bid = BlockID(hash=b"\x11" * 32,
                  part_set_header=PartSetHeader(total=1, hash=b"\x22" * 32))
    votes = [_signed_vote(p, vals, PREVOTE_TYPE, bid) for p in privs]

    vs = VoteSet(CHAIN_ID, 1, 0, PREVOTE_TYPE, vals)
    t0 = time.monotonic()
    results = vs.add_votes(votes)
    dt = time.monotonic() - t0
    assert all(added for added, err in results), [e for _, e in results if e][:3]
    maj, ok = vs.two_thirds_majority()
    assert ok and maj == bid
    # throughput telemetry (not an assert: CI hosts vary; the serial scalar
    # path at ~2ms/verify would take ~2s for 1024 votes)
    print(f"\nadd_votes: {len(votes)} votes in {dt*1e3:.1f} ms "
          f"({len(votes)/dt:.0f} votes/s)")


def test_add_votes_per_vote_error_attribution(big_net):
    """One corrupted signature in the batch: only that vote errors; order and
    acceptance of the rest are unchanged (the reference's per-vote error
    semantics, types/vote_set.go:209-217)."""
    privs, vals = big_net
    bid = BlockID(hash=b"\x33" * 32,
                  part_set_header=PartSetHeader(total=1, hash=b"\x44" * 32))
    votes = [_signed_vote(p, vals, PREVOTE_TYPE, bid) for p in privs[:200]]
    bad_i = 77
    votes[bad_i].signature = bytes([votes[bad_i].signature[0] ^ 1]) + \
        votes[bad_i].signature[1:]

    vs = VoteSet(CHAIN_ID, 1, 0, PREVOTE_TYPE, vals)
    results = vs.add_votes(votes)
    for i, (added, err) in enumerate(results):
        if i == bad_i:
            assert not added and isinstance(err, VoteError)
        else:
            assert added and err is None, (i, err)


def test_add_votes_duplicate_within_batch(big_net):
    privs, vals = big_net
    bid = BlockID(hash=b"\x55" * 32,
                  part_set_header=PartSetHeader(total=1, hash=b"\x66" * 32))
    v = _signed_vote(privs[0], vals, PREVOTE_TYPE, bid)
    vs = VoteSet(CHAIN_ID, 1, 0, PREVOTE_TYPE, vals)
    results = vs.add_votes([v, v, v])
    assert results[0] == (True, None)
    assert results[1][0] is False and results[1][1] is None  # duplicate
    assert results[2][0] is False and results[2][1] is None


@pytest.mark.parametrize("second, verified, want", [
    ("corrupted", False, "ErrVoteInvalidSignature"),
    ("another_valid", False, "VoteError"),
    ("another_valid", True, "VoteError"),
    ("same", False, None),
], ids=["corrupted_copy_is_an_invalid_signature",
        "a_second_valid_signature_is_non_deterministic",
        "verified_by_the_batch_and_non_deterministic", "exact_copy_is_a_duplicate"])
def test_a_second_signature_over_a_held_vote(big_net, second, verified, want):
    """PR 30: the serial path gives a copy of a vote it already holds the
    verdict the batched drain gives it. A copy whose signature a relay
    corrupted is an invalid signature (the deliverer can be sanctioned), not
    the reference's unexamined "non-deterministic signature"; only a second
    signature that verifies is that."""
    privs, vals = big_net
    bid = BlockID(hash=b"\x55" * 32,
                  part_set_header=PartSetHeader(total=1, hash=b"\x66" * 32))
    first = _signed_vote(privs[0], vals, PREVOTE_TYPE, bid)
    vs = VoteSet(CHAIN_ID, 1, 0, PREVOTE_TYPE, vals)
    assert vs.add_vote(first) is True
    again = first.copy()
    if second == "corrupted":
        again.signature = bytes([first.signature[0] ^ 1]) + first.signature[1:]
    elif second == "another_valid":
        # a signer that does not derive its nonce as RFC 8032 says can sign
        # the same bytes twice: any (R, S) with S = r + H(R, A, M) a verifies
        import hashlib

        from benchmark.reference import ed25519_ref as ref

        a = ref._clamp(hashlib.sha512(privs[0].bytes()[:32]).digest())
        pub = privs[0].pub_key().bytes()
        msg = first.sign_bytes(CHAIN_ID)
        r = 12345
        big_r = ref._compress(ref._scalarmult(r, ref.BASE))
        k = int.from_bytes(hashlib.sha512(big_r + pub + msg).digest(),
                           "little") % ref.L
        again.signature = big_r + ((r + k * a) % ref.L).to_bytes(32, "little")
        assert privs[0].pub_key().verify_signature(msg, again.signature)
    if want is None:
        assert vs.add_vote(again, verified=verified) is False
        return
    with pytest.raises(VoteError) as e:
        vs.add_vote(again, verified=verified)
    assert type(e.value).__name__ == want
    assert ("non-deterministic" in str(e.value)) == (want == "VoteError")


def test_consensus_drain_applies_batch(big_net):
    """The state machine's _handle_vote_batch: a pile of gossiped precommits
    is flushed through one batch verify and applied in order (with one bad
    signature dropped), without touching the scalar per-vote path."""
    privs, vals = big_net
    from tendermint_tpu.consensus import cstypes
    from tendermint_tpu.consensus.state_machine import (
        ConsensusState, MsgInfo, VoteMessage,
    )
    from tendermint_tpu.config.config import test_config
    from tendermint_tpu.state.state import make_genesis_state
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator

    genesis = GenesisDoc(
        chain_id=CHAIN_ID,
        genesis_time=Time(1700001000, 0),
        validators=[GenesisValidator(b"", p.pub_key(), 10) for p in privs[:64]],
    )
    state = make_genesis_state(genesis)
    cs = ConsensusState(test_config().consensus, state, None, None)
    vals64 = cs.rs.votes.val_set

    bid = BlockID(hash=b"\x77" * 32,
                  part_set_header=PartSetHeader(total=1, hash=b"\x88" * 32))
    msgs = []
    # only validators present in the 64-member set can vote here
    members = [p for p in privs if vals64.has_address(p.pub_key().address())]
    assert len(members) == 64
    for p in members:
        v = _signed_vote(p, vals64, PREVOTE_TYPE, bid)
        msgs.append(MsgInfo(VoteMessage(v), "peerX"))
    # corrupt one
    bad = msgs[10].msg.vote
    bad.signature = bytes([bad.signature[0] ^ 1]) + bad.signature[1:]

    cs.rs.step = cstypes.STEP_PREVOTE
    cs._handle_vote_batch(msgs)
    # With the continuous-batching verify service, the flush is genuinely
    # in flight when _handle_vote_batch returns (has_device_output() sees
    # the shared launch) and the drain stashes it; the production loop
    # applies it before any later state transition via _flush_pending_votes
    # — drive that exact step here.
    cs._flush_pending_votes()
    prevotes = cs.rs.votes.prevotes(0)
    assert sum(prevotes.bit_array()) == 63  # all but the corrupted one
    maj, ok = prevotes.two_thirds_majority()
    assert ok and maj == bid
