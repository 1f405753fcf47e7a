"""Types layer: sign-bytes parity vectors + structural invariants.

The golden byte vectors are the reference's own published test vectors
(reference: types/vote_test.go:60-131 TestVoteSignBytesTestVectors), proving
wire-level parity of CanonicalVote sign-bytes with the Go implementation."""

import hashlib
import random

import pytest

import wire_reference as ref
from tendermint_tpu.crypto import ed25519, secp256k1, sr25519
from tendermint_tpu.crypto import keys as keys_mod
from tendermint_tpu.encoding import proto
from tendermint_tpu.types.block import Block, Commit, CommitSig, Data, Header
from tendermint_tpu.types.block_id import BlockID, PartSetHeader
from tendermint_tpu.types.light_block import LightBlock, SignedHeader
from tendermint_tpu.types.part_set import PartSet
from tendermint_tpu.types.proposal import Proposal
from tendermint_tpu.types.ttime import Time
from tendermint_tpu.types.validator import Validator, pubkey_proto_bytes
from tendermint_tpu.types.validator_set import (
    ErrNotEnoughVotingPowerSigned,
    ErrWrongSignature,
    ValidatorSet,
)
from tendermint_tpu.types.vote import (
    BLOCK_ID_FLAG_COMMIT,
    BLOCK_ID_FLAG_NIL,
    PRECOMMIT_TYPE,
    PREVOTE_TYPE,
    ErrVoteConflictingVotes,
    Vote,
)
from tendermint_tpu.types.vote_set import VoteSet


def test_vote_sign_bytes_golden_vectors():
    """reference: types/vote_test.go:60-131."""
    cases = [
        ("", Vote(type=0, height=0, round=0),
         bytes([0xD, 0x2A, 0xB, 0x8, 0x80, 0x92, 0xB8, 0xC3, 0x98, 0xFE, 0xFF, 0xFF, 0xFF, 0x1])),
        ("", Vote(type=PRECOMMIT_TYPE, height=1, round=1),
         bytes([0x21, 0x8, 0x2, 0x11, 1, 0, 0, 0, 0, 0, 0, 0, 0x19, 1, 0, 0, 0, 0, 0, 0, 0,
                0x2A, 0xB, 0x8, 0x80, 0x92, 0xB8, 0xC3, 0x98, 0xFE, 0xFF, 0xFF, 0xFF, 0x1])),
        ("", Vote(type=PREVOTE_TYPE, height=1, round=1),
         bytes([0x21, 0x8, 0x1, 0x11, 1, 0, 0, 0, 0, 0, 0, 0, 0x19, 1, 0, 0, 0, 0, 0, 0, 0,
                0x2A, 0xB, 0x8, 0x80, 0x92, 0xB8, 0xC3, 0x98, 0xFE, 0xFF, 0xFF, 0xFF, 0x1])),
        ("", Vote(type=0, height=1, round=1),
         bytes([0x1F, 0x11, 1, 0, 0, 0, 0, 0, 0, 0, 0x19, 1, 0, 0, 0, 0, 0, 0, 0,
                0x2A, 0xB, 0x8, 0x80, 0x92, 0xB8, 0xC3, 0x98, 0xFE, 0xFF, 0xFF, 0xFF, 0x1])),
        ("test_chain_id", Vote(type=0, height=1, round=1),
         bytes([0x2E, 0x11, 1, 0, 0, 0, 0, 0, 0, 0, 0x19, 1, 0, 0, 0, 0, 0, 0, 0,
                0x2A, 0xB, 0x8, 0x80, 0x92, 0xB8, 0xC3, 0x98, 0xFE, 0xFF, 0xFF, 0xFF, 0x1,
                0x32, 0xD]) + b"test_chain_id"),
    ]
    for i, (chain_id, vote, want) in enumerate(cases):
        got = vote.sign_bytes(chain_id)
        assert got == want, f"case {i}: {got.hex()} != {want.hex()}"


def _mk_validators(n, power=10):
    out = []
    for i in range(n):
        priv = ed25519.gen_priv_key(bytes([i + 1]) * 32)
        out.append((priv, Validator.new(priv.pub_key(), power)))
    return out


def _block_id():
    return BlockID(hash=b"\xaa" * 32,
                   part_set_header=PartSetHeader(total=1, hash=b"\xbb" * 32))


def _mk_commit(chain_id, height, round_, block_id, vals, privs, *, skip=(), nil=(),
               bad_sig=()):
    sigs = []
    for i, (priv, val) in enumerate(zip(privs, vals)):
        if i in skip:
            sigs.append(CommitSig.new_absent())
            continue
        flag = BLOCK_ID_FLAG_NIL if i in nil else BLOCK_ID_FLAG_COMMIT
        ts = Time(1700000000 + i, 500)
        vote = Vote(
            type=PRECOMMIT_TYPE, height=height, round=round_,
            block_id=BlockID() if i in nil else block_id,
            timestamp=ts, validator_address=val.address, validator_index=i,
        )
        sig = priv.sign(vote.sign_bytes(chain_id))
        if i in bad_sig:
            sig = sig[:-1] + bytes([sig[-1] ^ 1])
        sigs.append(CommitSig(flag, val.address, ts, sig))
    return Commit(height=height, round=round_, block_id=block_id, signatures=sigs)


def test_verify_commit_happy_and_sad():
    chain_id = "test-chain"
    pairs = _mk_validators(7)
    privs = [p for p, _ in pairs]
    vals = [v for _, v in pairs]
    vs = ValidatorSet(vals)
    # ValidatorSet sorts by power desc then address: rebuild privs in set order
    order = {v.address: privs[i] for i, (_, v) in enumerate(pairs)}
    sorted_privs = [order[v.address] for v in vs.validators]

    bid = _block_id()
    commit = _mk_commit(chain_id, 5, 2, bid, vs.validators, sorted_privs)
    vs.verify_commit(chain_id, bid, 5, commit)
    vs.verify_commit_light(chain_id, bid, 5, commit)
    vs.verify_commit_light_trusting(chain_id, commit, (1, 3))

    # two absent + one nil still passes (5 of 7 > 2/3... 4.66)
    commit2 = _mk_commit(chain_id, 5, 2, bid, vs.validators, sorted_privs, skip=(0,), nil=(1,))
    vs.verify_commit(chain_id, bid, 5, commit2)

    # bad signature fails VerifyCommit with exact index attribution
    commit3 = _mk_commit(chain_id, 5, 2, bid, vs.validators, sorted_privs, bad_sig=(3,))
    with pytest.raises(ErrWrongSignature) as ei:
        vs.verify_commit(chain_id, bid, 5, commit3)
    assert ei.value.index == 3

    # ...but VerifyCommitLight never looks at index 3 if threshold crossed by 5
    # (7 validators x10 power: need >46, first 5 give 50)
    vs.verify_commit_light(chain_id, bid, 5, _mk_commit(
        chain_id, 5, 2, bid, vs.validators, sorted_privs, bad_sig=(6,)))

    # insufficient power
    commit4 = _mk_commit(chain_id, 5, 2, bid, vs.validators, sorted_privs,
                         skip=(0, 1, 2), nil=(3,))
    with pytest.raises(ErrNotEnoughVotingPowerSigned):
        vs.verify_commit(chain_id, bid, 5, commit4)


def test_vote_set_maj23_and_commit():
    chain_id = "vs-chain"
    pairs = _mk_validators(4)
    vs = ValidatorSet([v for _, v in pairs])
    order = {v.address: p for p, v in pairs}
    sorted_privs = [order[v.address] for v in vs.validators]
    bid = _block_id()

    votes = VoteSet(chain_id, 3, 0, PRECOMMIT_TYPE, vs)
    assert not votes.has_two_thirds_majority()
    for i in range(3):
        v = Vote(type=PRECOMMIT_TYPE, height=3, round=0, block_id=bid,
                 timestamp=Time(1700000100 + i, 0),
                 validator_address=vs.validators[i].address, validator_index=i)
        v.signature = sorted_privs[i].sign(v.sign_bytes(chain_id))
        assert votes.add_vote(v)
    maj, ok = votes.two_thirds_majority()
    assert ok and maj == bid
    commit = votes.make_commit()
    assert commit.signatures[3].absent()
    vs.verify_commit_light(chain_id, bid, 3, commit)

    # duplicate add returns False
    v0 = votes.get_by_index(0)
    assert votes.add_vote(v0) is False


def test_vote_set_conflicting_vote():
    chain_id = "vs-chain"
    pairs = _mk_validators(4)
    vs = ValidatorSet([v for _, v in pairs])
    order = {v.address: p for p, v in pairs}
    sorted_privs = [order[v.address] for v in vs.validators]
    votes = VoteSet(chain_id, 3, 0, PREVOTE_TYPE, vs)

    v1 = Vote(type=PREVOTE_TYPE, height=3, round=0, block_id=_block_id(),
              timestamp=Time(1700000100, 0),
              validator_address=vs.validators[0].address, validator_index=0)
    v1.signature = sorted_privs[0].sign(v1.sign_bytes(chain_id))
    assert votes.add_vote(v1)

    v2 = Vote(type=PREVOTE_TYPE, height=3, round=0, block_id=BlockID(),
              timestamp=Time(1700000101, 0),
              validator_address=vs.validators[0].address, validator_index=0)
    v2.signature = sorted_privs[0].sign(v2.sign_bytes(chain_id))
    with pytest.raises(ErrVoteConflictingVotes) as ei:
        votes.add_vote(v2)
    assert ei.value.vote_a == v1


def test_batched_add_votes_matches_serial():
    chain_id = "batch-chain"
    pairs = _mk_validators(8)
    vs = ValidatorSet([v for _, v in pairs])
    order = {v.address: p for p, v in pairs}
    sorted_privs = [order[v.address] for v in vs.validators]
    bid = _block_id()

    def mk_votes():
        out = []
        for i in range(8):
            v = Vote(type=PREVOTE_TYPE, height=3, round=0, block_id=bid,
                     timestamp=Time(1700000100 + i, 0),
                     validator_address=vs.validators[i].address, validator_index=i)
            v.signature = sorted_privs[i].sign(v.sign_bytes(chain_id))
            if i == 5:  # corrupt one signature
                v.signature = v.signature[:-1] + bytes([v.signature[-1] ^ 1])
            out.append(v)
        return out

    serial = VoteSet(chain_id, 3, 0, PREVOTE_TYPE, vs)
    serial_results = []
    for v in mk_votes():
        try:
            serial_results.append((serial.add_vote(v), None))
        except Exception as e:  # noqa: BLE001
            serial_results.append((False, type(e).__name__))

    batched = VoteSet(chain_id, 3, 0, PREVOTE_TYPE, vs)
    batch_results = [
        (added, type(e).__name__ if e else None)
        for added, e in batched.add_votes(mk_votes())
    ]
    assert serial_results == batch_results
    assert serial.maj23 == batched.maj23
    assert serial.sum == batched.sum


def test_header_hash_changes_with_fields():
    h = Header(chain_id="c", height=3, validators_hash=b"\x01" * 32,
               proposer_address=b"\x02" * 20, time=Time(1700000000, 1))
    base = h.hash()
    assert base is not None and len(base) == 32
    h2 = Header(chain_id="c", height=4, validators_hash=b"\x01" * 32,
                proposer_address=b"\x02" * 20, time=Time(1700000000, 1))
    assert h2.hash() != base
    h3 = Header(chain_id="c", height=3, validators_hash=b"",
                proposer_address=b"\x02" * 20)
    assert h3.hash() is None


def test_part_set_roundtrip():
    data = bytes(range(256)) * 700  # ~180kB -> 3 parts
    ps = PartSet.from_data(data)
    assert ps.header().total == 3
    ps2 = PartSet.from_header(ps.header())
    assert not ps2.is_complete()
    for i in [2, 0, 1]:
        part = ps.get_part(i)
        blob = part.marshal()
        from tendermint_tpu.types.part_set import Part

        assert ps2.add_part(Part.unmarshal(blob))
    assert ps2.is_complete()
    assert ps2.assemble() == data
    # duplicate add -> False
    assert ps2.add_part(ps.get_part(0)) is False


def test_block_roundtrip_and_hash():
    pairs = _mk_validators(4)
    vs = ValidatorSet([v for _, v in pairs])
    bid = _block_id()
    commit = Commit(height=2, round=0, block_id=bid,
                    signatures=[CommitSig.new_absent() for _ in range(4)])
    b = Block(
        header=Header(chain_id="c", height=3, validators_hash=vs.hash(),
                      next_validators_hash=vs.hash(),
                      proposer_address=vs.validators[0].address,
                      time=Time(1700000000, 0)),
        data=Data(txs=[b"tx1", b"tx2"]),
        last_commit=commit,
    )
    h = b.hash()
    assert h is not None
    blob = b.marshal()
    b2 = Block.unmarshal(blob)
    assert b2.hash() == h
    assert b2.data.txs == [b"tx1", b"tx2"]
    assert b2.last_commit.block_id == bid
    b2.validate_basic()


def test_proposal_sign_roundtrip():
    priv = ed25519.gen_priv_key(b"\x07" * 32)
    p = Proposal(height=4, round=2, pol_round=-1, block_id=_block_id(),
                 timestamp=Time(1700000000, 42))
    p.signature = priv.sign(p.sign_bytes("pchain"))
    assert priv.pub_key().verify_signature(p.sign_bytes("pchain"), p.signature)
    p2 = Proposal.unmarshal(p.marshal())
    assert p2 == p


def test_validator_set_proposer_rotation():
    pairs = _mk_validators(3, power=1)
    vs = ValidatorSet([v for _, v in pairs])
    seen = []
    for _ in range(6):
        seen.append(vs.get_proposer().address)
        vs.increment_proposer_priority(1)
    # equal power: perfect round-robin over 3 validators
    assert seen[:3] == seen[3:6]
    assert len(set(seen[:3])) == 3


def test_validator_set_update_and_hash():
    pairs = _mk_validators(3, power=10)
    vs = ValidatorSet([v for _, v in pairs])
    h0 = vs.hash()
    newp = ed25519.gen_priv_key(b"\x99" * 32)
    vs.update_with_change_set([Validator.new(newp.pub_key(), 5)])
    assert vs.size() == 4
    assert vs.hash() != h0
    # new validator got the -1.125*total penalty => should not be proposer now
    assert vs.get_proposer().address != newp.pub_key().address()
    # removal via power 0
    vs.update_with_change_set([Validator.new(newp.pub_key(), 0)])
    assert vs.size() == 3


def test_commit_vote_sign_bytes_template_differential():
    """The templated Commit.vote_sign_bytes must equal building each Vote
    (types/block.py vote_sign_bytes fast path)."""
    from tendermint_tpu.types.block import Commit, CommitSig
    from tendermint_tpu.types.block_id import BlockID, PartSetHeader
    from tendermint_tpu.types.ttime import Time
    from tendermint_tpu.types.vote import (
        BLOCK_ID_FLAG_ABSENT,
        BLOCK_ID_FLAG_COMMIT,
        BLOCK_ID_FLAG_NIL,
    )

    bid = BlockID(hash=b"\x11" * 32,
                  part_set_header=PartSetHeader(total=3, hash=b"\x22" * 32))
    sigs = [
        CommitSig(BLOCK_ID_FLAG_COMMIT, b"\x01" * 20, Time(1700000001, 7), b"s" * 64),
        CommitSig(BLOCK_ID_FLAG_NIL, b"\x02" * 20, Time(1700000002, 0), b"t" * 64),
        CommitSig(BLOCK_ID_FLAG_COMMIT, b"\x03" * 20, Time(0, 0), b"u" * 64),
        CommitSig(BLOCK_ID_FLAG_ABSENT, b"", Time(0, 0), b""),
    ]
    c = Commit(height=300, round=2, block_id=bid, signatures=sigs)
    for chain_id in ("chain-x", "other"):  # second id must drop the template
        for i in range(len(sigs)):
            assert (c.vote_sign_bytes(chain_id, i)
                    == c.get_vote(i).sign_bytes(chain_id)), (chain_id, i)


def _sb_commit(stamps, *, height=300, round_=0, total=1, hash_len=32):
    """A Commit whose slot i carries stamps[i] = (flag, Time), signatures
    unsigned: only the sign bytes are under test."""
    from tendermint_tpu.types.vote import BLOCK_ID_FLAG_ABSENT

    bid = BlockID(hash=b"\x11" * hash_len,
                  part_set_header=PartSetHeader(total=total, hash=b"\x22" * 32))
    sigs = [CommitSig.new_absent() if flag == BLOCK_ID_FLAG_ABSENT
            else CommitSig(flag, bytes([i + 1]) * 20, ts, b"s" * 64)
            for i, (flag, ts) in enumerate(stamps)]
    return Commit(height=height, round=round_, block_id=bid, signatures=sigs)


def _sb_cases():
    from tendermint_tpu.types.ttime import GO_ZERO_SECONDS
    from tendermint_tpu.types.vote import BLOCK_ID_FLAG_ABSENT as A

    C, N = BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL
    t0 = 1_700_000_000
    spread = [(C, Time(t0 + i % 3, 123_456_789 * (i + 1) % 10**9))
              for i in range(9)]
    # id -> (commit, idxs, how many the splice covers)
    cases = {
        "all_commit": (_sb_commit(spread), list(range(9)), 9),
        "commit_nil_mixed": (_sb_commit(
            [(C, Time(t0, 5)), (N, Time(t0 + 1, 6)), (C, Time(t0, 7)),
             (N, Time(t0, 0)), (C, Time(t0 + 2, 8))]), [0, 1, 2, 3, 4], 3),
        "all_nil": (_sb_commit([(N, Time(t0, 5)), (N, Time(t0, 6))]), [0, 1], 0),
        "absent_skipped": (_sb_commit(
            [(C, Time(t0, 5)), (A, Time()), (C, Time(t0, 7)), (A, Time()),
             (N, Time(t0, 9))]), [0, 2, 4], 2),
        "light_prefix_out_of_order": (_sb_commit(spread), [7, 2, 5], 3),
        "round_0": (_sb_commit(spread, round_=0), list(range(9)), 9),
        "round_5": (_sb_commit(spread, round_=5), list(range(9)), 9),
        "part_total_1": (_sb_commit(spread, total=1), list(range(9)), 9),
        "part_total_127": (_sb_commit(spread, total=127), list(range(9)), 9),
        "part_total_128_fallback": (_sb_commit(spread, total=128), list(range(9)), 0),
        "hash_20_bytes_fallback": (_sb_commit(spread, hash_len=20), list(range(9)), 0),
        "height_0_fallback": (_sb_commit(spread, height=0), list(range(9)), 0),
        "empty_idxs": (_sb_commit(spread), [], 0),
        # the precommit among test_vote_sign_bytes_golden_vectors, as a commit
        # slot: nil block id, Go's zero time (checked against the golden
        # bytes themselves in test_sign_bytes_many_golden_vector)
        "golden_precommit_nil": (_sb_commit(
            [(N, Time())], height=1, round_=1), [0], 0),
    }
    for nanos in (0, 1, 127, 128, 16_383, 16_384, 2**21 - 1, 2**21,
                  2**28 - 1, 2**28, 999_999_999,
                  # no valid Timestamp, but Time.unmarshal can hand them over
                  2**30, 2**35 - 1, 2**35, -1):
        cases[f"nanos_{nanos}"] = (_sb_commit(
            [(C, Time(t0, nanos)), (C, Time(t0 + 1, nanos))]), [0, 1], 2)
    for name, seconds in (("0", 0), ("2e31", 2**31), ("2e35", 2**35),
                          ("go_zero", GO_ZERO_SECONDS)):
        cases[f"seconds_{name}"] = (_sb_commit(
            [(C, Time(seconds, 0)), (C, Time(seconds, 999_999_999)),
             (C, Time(seconds, 77))]), [0, 1, 2], 3)
    return cases


_SB_CASES = _sb_cases()


@pytest.mark.parametrize("case", sorted(_SB_CASES))
def test_sign_bytes_many_differential(case):
    """Commit.sign_bytes_many (the per-commit splice of ISSUE 24) equals,
    byte for byte, the three per-vote constructions: vote_sign_bytes, the
    rebuilt Vote's sign_bytes, and the Writer (the layout's source of
    truth); and it says how many it spliced."""
    from tendermint_tpu.types import vote as vmod

    commit, idxs, want_spliced = _SB_CASES[case]
    for chain_id in ("chain-x", "", "c" * 130):  # suffix of 0, 9 and 133 bytes
        msgs, spliced = commit.sign_bytes_many(chain_id, idxs)
        assert spliced == want_spliced
        assert msgs == [commit.vote_sign_bytes(chain_id, i) for i in idxs]
        assert msgs == [commit.get_vote(i).sign_bytes(chain_id) for i in idxs]
        assert msgs == [vmod._canonical_vote_bytes_writer(
            chain_id, PRECOMMIT_TYPE, commit.height, commit.round,
            commit.signatures[i].block_id(commit.block_id),
            commit.signatures[i].timestamp) for i in idxs]


def test_sign_bytes_many_golden_vector():
    """The reference's published precommit vector (types/vote_test.go:60-131,
    case 1 of test_vote_sign_bytes_golden_vectors) through the commit path,
    and the splice's own answer for the shapes of all five: none has a
    template (nil block id), so it declines rather than guess."""
    from tendermint_tpu.types.vote import canonical_vote_bytes_many

    commit, idxs, _ = _SB_CASES["golden_precommit_nil"]
    want = bytes([0x21, 0x8, 0x2, 0x11, 1, 0, 0, 0, 0, 0, 0, 0,
                  0x19, 1, 0, 0, 0, 0, 0, 0, 0, 0x2A, 0xB, 0x8, 0x80, 0x92,
                  0xB8, 0xC3, 0x98, 0xFE, 0xFF, 0xFF, 0xFF, 0x1])
    assert commit.sign_bytes_many("", idxs) == ([want], 0)
    for chain_id, vtype, height, round_ in (
            ("", 0, 0, 0), ("", PRECOMMIT_TYPE, 1, 1), ("", PREVOTE_TYPE, 1, 1),
            ("", 0, 1, 1), ("test_chain_id", 0, 1, 1)):
        assert canonical_vote_bytes_many(
            chain_id, vtype, height, round_, BlockID(), [Time()]) is None


def test_sign_bytes_many_keeps_nothing_between_calls():
    """ISSUE 24: all hoisting lives inside one call. The same Commit object
    verified twice pays (and answers) the same; a timestamp or the block id
    changed between two calls shows in the very next call's bytes, and in
    verify_commit's verdict at that index; the only process-wide state that
    grows is _CV_TEMPLATES, by the commit's one key."""
    from tendermint_tpu.types import vote as vmod

    chain_id = "no-memo-chain"
    pairs = _mk_validators(7)
    vs = ValidatorSet([v for _, v in pairs])
    order = {v.address: p for p, v in pairs}
    privs = [order[v.address] for v in vs.validators]
    bid = _block_id()
    commit = _mk_commit(chain_id, 9, 0, bid, vs.validators, privs)
    idxs = list(range(7))

    vmod._CV_TEMPLATES.clear()
    first = commit.sign_bytes_many(chain_id, idxs)
    assert first == commit.sign_bytes_many(chain_id, idxs) and first[1] == 7
    assert list(vmod._CV_TEMPLATES) == [(chain_id, PRECOMMIT_TYPE, 0, 1)]
    for obj in (commit, commit.signatures[3], commit.signatures[3].timestamp):
        assert set(vars(obj)) == {f for f in obj.__dataclass_fields__}
    vs.verify_commit(chain_id, bid, 9, commit)
    vs.verify_commit(chain_id, bid, 9, commit)

    # one timestamp moves by a nanosecond: new bytes, and the old signature
    # no longer covers them
    old_ts = commit.signatures[3].timestamp
    commit.signatures[3].timestamp = Time(old_ts.seconds, old_ts.nanos + 1)
    msgs, _ = commit.sign_bytes_many(chain_id, idxs)
    assert msgs[3] != first[0][3] and msgs[:3] + msgs[4:] == first[0][:3] + first[0][4:]
    assert msgs[3] == commit.get_vote(3).sign_bytes(chain_id)
    for entry in (vs.verify_commit, vs.verify_commit_light):
        with pytest.raises(ErrWrongSignature) as ei:
            entry(chain_id, bid, 9, commit)
        assert ei.value.index == 3
    with pytest.raises(ErrWrongSignature) as ei:
        vs.verify_commit_light_trusting(chain_id, commit, (2, 3))
    assert ei.value.index == 3
    commit.signatures[3].timestamp = old_ts
    vs.verify_commit(chain_id, bid, 9, commit)

    # the commit's block id changes under the same height: every message
    # changes, and the first signature is the first to fail
    other = BlockID(hash=b"\xcc" * 32,
                    part_set_header=PartSetHeader(total=1, hash=b"\xbb" * 32))
    commit.block_id = other
    msgs, spliced = commit.sign_bytes_many(chain_id, idxs)
    assert spliced == 7 and all(a != b for a, b in zip(msgs, first[0]))
    assert msgs == [commit.get_vote(i).sign_bytes(chain_id) for i in idxs]
    with pytest.raises(ErrWrongSignature) as ei:
        vs.verify_commit(chain_id, other, 9, commit)
    assert ei.value.index == 0
    assert list(vmod._CV_TEMPLATES) == [(chain_id, PRECOMMIT_TYPE, 0, 1)]


def test_canonical_vote_bytes_template_cache_differential():
    """canonical_vote_bytes' template cache must be invisible: byte-equal
    to a fresh construction across types, rounds, nil block ids, many
    timestamps, and cache eviction (types/vote.py)."""
    from tendermint_tpu.encoding import proto
    from tendermint_tpu.types import vote as vmod
    from tendermint_tpu.types.block_id import BlockID, PartSetHeader
    from tendermint_tpu.types.ttime import Time

    def fresh(chain_id, vtype, height, round_, bid, ts):
        w = proto.Writer()
        w.varint(1, vtype)
        w.sfixed64(2, height)
        w.sfixed64(3, round_)
        cbid = vmod.canonical_block_id_bytes(bid)
        if cbid is not None:
            w.message(4, cbid, always=True)
        w.message(5, ts.marshal(), always=True)
        w.string(6, chain_id)
        return proto.delimited(w.out())

    bids = [BlockID(),
            BlockID(hash=b"\x07" * 32,
                    part_set_header=PartSetHeader(total=2, hash=b"\x08" * 32))]
    cases = []
    for h in (1, 77, 300):
        for r in (0, 5):
            for vt in (vmod.PREVOTE_TYPE, vmod.PRECOMMIT_TYPE):
                for bid in bids:
                    for ts in (Time(0, 0), Time(1_700_000_000, 999)):
                        cases.append(("chain-%d" % (h % 2), vt, h, r, bid, ts))
    vmod._CV_TEMPLATES.clear()
    for case in cases * 2:  # second pass hits the cache
        assert vmod.canonical_vote_bytes(*case) == fresh(*case), case
    # force eviction mid-stream and keep verifying
    vmod._CV_TEMPLATES.clear()
    for case in cases:
        assert vmod.canonical_vote_bytes(*case) == fresh(*case)


# --- one-pass wire encoders against the field-by-field reference -------------

_INT64_MAX, _INT64_MIN = 2**63 - 1, -(2**63)
_ADDR, _SIG = bytes(range(20)), bytes(range(64))

_COMMIT_SIGS = {
    "absent": CommitSig.new_absent(),
    "commit": CommitSig(BLOCK_ID_FLAG_COMMIT, _ADDR, Time(1_700_000_000, 123_456_789), _SIG),
    "nil": CommitSig(BLOCK_ID_FLAG_NIL, _ADDR, Time(1_700_000_001, 999_999_999), _SIG),
    "zero-nanos": CommitSig(BLOCK_ID_FLAG_COMMIT, _ADDR, Time(1_700_000_000, 0), _SIG),
    "pre-1970": CommitSig(BLOCK_ID_FLAG_COMMIT, _ADDR, Time(-86_400, 5), _SIG),
    "epoch": CommitSig(BLOCK_ID_FLAG_COMMIT, _ADDR, Time(0, 0), _SIG),
    "epoch-nanos": CommitSig(BLOCK_ID_FLAG_COMMIT, _ADDR, Time(0, 77), _SIG),
    "no-signature": CommitSig(BLOCK_ID_FLAG_COMMIT, _ADDR, Time(1, 1), b""),
    "long-fields": CommitSig(7, b"\x01" * 200, Time(2**40, 2**40), b"\x02" * 20_000),
}


@pytest.mark.parametrize("case", sorted(_COMMIT_SIGS))
def test_commit_sig_marshal_differential(case):
    cs = _COMMIT_SIGS[case]
    assert cs.marshal() == ref.commit_sig(cs)
    assert cs.timestamp.marshal() == ref.time_body(cs.timestamp)
    assert CommitSig.unmarshal(cs.marshal()) == cs


_KEYS = {
    "ed25519": ed25519.PubKey(b"\x11" * 32),
    "secp256k1": secp256k1.PubKey(b"\x02" + b"\x22" * 32),
    "sr25519": sr25519.PubKey(b"\x33" * 32),
    "empty-key": secp256k1.PubKey(b""),  # proto3 omits the oneof's empty bytes
}
_POWERS = {
    "zero": (0, 0),
    "positive": (10_000_000, 123_456_789),
    "negative": (10, -123_456_789),
    "minus-one": (1, -1),
    "extremes": (_INT64_MAX, _INT64_MIN),
    "max-max": (_INT64_MAX, _INT64_MAX),
}


@pytest.mark.parametrize("numbers", sorted(_POWERS))
@pytest.mark.parametrize("key", sorted(_KEYS))
def test_validator_marshal_differential(key, numbers):
    power, priority = _POWERS[numbers]
    v = Validator(_ADDR, _KEYS[key], power, priority)
    assert v.marshal() == ref.validator(v)
    assert pubkey_proto_bytes(v.pub_key) == ref.pubkey(v.pub_key)
    if key != "empty-key":  # "empty PublicKey proto" does not decode
        assert Validator.unmarshal(v.marshal()) == v
    v.address = b""  # proto3 omits it
    assert v.marshal() == ref.validator(v)


def test_unrepresentable_key_type_still_raises():
    class Bls(keys_mod.PubKey):
        type = "bls12-381"

        def address(self):
            return _ADDR

        def bytes(self):
            return b"\x44" * 48

        def verify_signature(self, msg, sig):
            return False

        def equals(self, other):
            return other is self

    for encode in (pubkey_proto_bytes, lambda k: Validator(_ADDR, k, 1).marshal(),
                   lambda k: ValidatorSet([Validator(_ADDR, k, 1)]).marshal()):
        with pytest.raises(ValueError, match="bls12-381 not representable"):
            encode(Bls())


def _wire_commit(n, rng):
    sigs = []
    for i in range(n):
        if i % 7 == 3:
            sigs.append(CommitSig.new_absent())
        else:
            sigs.append(CommitSig(
                BLOCK_ID_FLAG_NIL if i % 11 == 5 else BLOCK_ID_FLAG_COMMIT,
                rng.randbytes(20), Time(1_700_000_000 + i % 3, rng.randrange(10**9)),
                rng.randbytes(64)))
    return Commit(height=1 + n, round=n % 2, block_id=_block_id(), signatures=sigs)


def _wire_set(n, rng):
    """n validators of the three key types; priorities of both signs, as
    increment_proposer_priority leaves them."""
    kinds = (ed25519.PubKey, sr25519.PubKey,
             lambda raw: secp256k1.PubKey(b"\x03" + raw))
    vals = []
    for i in range(n):
        pub = kinds[i % 3](rng.randbytes(32))
        vals.append(Validator(rng.randbytes(20), pub, 1 + rng.randrange(10**7)))
    vs = ValidatorSet(vals)
    if n:
        vs.increment_proposer_priority(1 + n % 5)
    return vs


@pytest.mark.parametrize("n", [0, 1, 150])
def test_commit_marshal_differential(n):
    c = _wire_commit(n, random.Random(29 + n))
    assert c.marshal() == ref.commit(c)
    assert Commit.unmarshal(c.marshal()) == c
    # the same bodies are the Merkle leaves of Commit.hash
    assert [cs.marshal() for cs in c.signatures] == [ref.commit_sig(cs) for cs in c.signatures]


@pytest.mark.parametrize("proposer", ["proposer", "no-proposer"])
@pytest.mark.parametrize("n", [0, 1, 150])
def test_validator_set_marshal_differential(n, proposer):
    vs = _wire_set(n, random.Random(290 + n))
    if proposer == "no-proposer":
        vs.proposer = None
    elif n:
        assert vs.proposer is not None
        assert any(v.proposer_priority < 0 for v in vs.validators) or n == 1
    raw = vs.marshal()
    assert raw == ref.validator_set(vs)
    back = ValidatorSet.unmarshal(raw)
    assert back.validators == vs.validators and back.proposer == vs.proposer
    assert back.marshal() == raw


_VARINTS = [_INT64_MIN, -1, 0, 127, 128, _INT64_MAX]


@pytest.mark.parametrize("n", _VARINTS + ["sample"])
def test_encode_varint_equals_the_loop(n):
    if n == "sample":
        rng = random.Random(2929)
        ns = [rng.getrandbits(rng.randrange(1, 64)) * rng.choice((1, -1))
              for _ in range(5000)]
        ns += [s * (1 << k) + d for k in range(64) for d in (-1, 0, 1) for s in (1, -1)]
        ns = [v for v in ns if _INT64_MIN <= v <= _INT64_MAX]
    else:
        ns = [n]
    for v in ns:
        got = proto.encode_varint(v)
        assert got == ref.loop_varint(v), v
        assert proto.decode_varint(got) == (v, len(got))
        if v >= 0:
            assert proto.encode_uvarint(v) == got
    # what is no int64 reads as it did: 64 bits and over by the same groups,
    # under -2**64 the same error
    for v in (2**64 - 1, 2**64, 2**70 + 5, _INT64_MIN - 1, -(2**64)):
        assert proto.encode_varint(v) == ref.loop_varint(v), v
    with pytest.raises(ValueError):
        proto.encode_varint(-(2**64) - 1)
    with pytest.raises(ValueError):
        proto.encode_uvarint(-1)


def _golden_blocks():
    """A fixed 4-validator light block and block: absent, nil and commit
    slots, zero and non-zero nanos, priorities of both signs, a proposer,
    an empty tx."""
    privs = [ed25519.gen_priv_key(bytes([i + 1]) * 32) for i in range(4)]
    vs = ValidatorSet([Validator.new(p.pub_key(), 10 + 7 * i) for i, p in enumerate(privs)])
    vs.increment_proposer_priority(3)
    by_addr = {p.pub_key().address(): p for p in privs}
    bid = _block_id()
    sigs = []
    for i, val in enumerate(vs.validators):
        if i == 1:
            sigs.append(CommitSig.new_absent())
            continue
        nil = i == 2
        ts = Time(1700000000 + i, 0 if i == 3 else 123456789)
        vote = Vote(type=PRECOMMIT_TYPE, height=7, round=1,
                    block_id=BlockID() if nil else bid, timestamp=ts,
                    validator_address=val.address, validator_index=i)
        sigs.append(CommitSig(BLOCK_ID_FLAG_NIL if nil else BLOCK_ID_FLAG_COMMIT,
                              val.address, ts,
                              by_addr[val.address].sign(vote.sign_bytes("golden"))))
    commit = Commit(height=7, round=1, block_id=bid, signatures=sigs)
    header = Header(chain_id="golden", height=8, time=Time(1700000010, 42),
                    last_block_id=bid, validators_hash=vs.hash(),
                    next_validators_hash=vs.hash(),
                    proposer_address=vs.validators[0].address)
    block = Block(header=header, data=Data(txs=[b"tx1", b"", b"tx3"]), last_commit=commit)
    return LightBlock(SignedHeader(header, commit), vs), block


def test_marshal_golden_vector():
    """SHA-256 of the encodings as commit 06ef5b7 (the parent of the
    one-pass encoders) produced them: the stored and gossiped bytes, and
    with them every part-set hash and BlockID, did not move."""
    lb, block = _golden_blocks()
    assert [v.proposer_priority for v in lb.validator_set.validators] == [-40, 14, -14, 40]
    raw = lb.marshal()
    assert len(raw) == 954
    assert hashlib.sha256(raw).hexdigest() == (
        "9fe316728b1d0f47b6be54a4c2461429bcc480c9a47091d1c440d6de6fd1b68e")
    assert raw == ref.light_block(lb)
    raw = block.marshal()
    assert len(raw) == 615
    assert hashlib.sha256(raw).hexdigest() == (
        "cee0c777ad1e794887c28bb1d3182dc6f002da18ae871721e3a2433267d59284")
    assert LightBlock.unmarshal(lb.marshal()).marshal() == lb.marshal()
    assert Block.unmarshal(raw).marshal() == raw
