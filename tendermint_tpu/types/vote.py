"""Vote and its canonical sign-bytes (reference: types/vote.go:50,93,147,
types/canonical.go:56, proto/tendermint/types/{types,canonical}.proto).

Sign-bytes are the varint-length-delimited marshal of CanonicalVote:
  1 type (varint)   2 height (sfixed64)   3 round (sfixed64)
  4 block_id (nullable: omitted when vote is nil)
  5 timestamp (non-nullable: always emitted)   6 chain_id
Byte-compatibility here is what lets the TPU batch verifier reproduce the
exact signatures the reference network produces.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace

from tendermint_tpu.crypto import keys
from tendermint_tpu.encoding import proto
from tendermint_tpu.types.block_id import BlockID, PartSetHeader
from tendermint_tpu.types.ttime import Time

# SignedMsgType (proto/tendermint/types/types.proto:24-37)
UNKNOWN_TYPE = 0
PREVOTE_TYPE = 1
PRECOMMIT_TYPE = 2
PROPOSAL_TYPE = 32

# BlockIDFlag (proto/tendermint/types/types.proto:13-22)
BLOCK_ID_FLAG_ABSENT = 1
BLOCK_ID_FLAG_COMMIT = 2
BLOCK_ID_FLAG_NIL = 3

def is_vote_type_valid(t: int) -> bool:
    return t in (PREVOTE_TYPE, PRECOMMIT_TYPE)


def canonical_block_id_bytes(bid: BlockID) -> bytes | None:
    """CanonicalBlockID marshal, or None for a zero (nil-vote) BlockID
    (reference: types/canonical.go:18)."""
    if bid.is_zero():
        return None
    return (
        proto.Writer()
        .bytes(1, bid.hash)
        .message(2, bid.part_set_header.marshal(), always=True)
        .out()
    )


_CV_TEMPLATES: dict = {}


def _cv_ends(chain_id: str, vtype: int, height: int, round_: int,
             block_id: BlockID) -> tuple[bytes, bytes] | None:
    """``(prefix, suffix)``: the sign bytes before the timestamp's length
    and after its body, for the ubiquitous shape (32-byte hashes, small
    part total, non-nil block); None where only the Writer knows the
    layout. Given (chain_id, vtype, round, total) the byte layout is fixed
    — height is sfixed64 — so a cached splice template fills in height and
    hashes with one join. The template is SELF-CHECKED against the Writer
    construction when built: layout drift disables the fast path for that
    key rather than ever signing wrong bytes."""
    psh = block_id.part_set_header
    if not (len(block_id.hash) == 32 and len(psh.hash) == 32
            and 0 < psh.total < 128 and 0 < height < 2**63
            and 0 <= round_ < 2**63 and vtype != 0):
        # height 0 is never signed; zero-valued proto fields are omitted by
        # the Writer, so the fixed-layout assumption needs height > 0
        return None
    key = (chain_id, vtype, round_, psh.total)
    tmpl = _CV_TEMPLATES.get(key, False)
    if tmpl is False:
        if len(_CV_TEMPLATES) >= 64:
            _CV_TEMPLATES.clear()
        # layout: head|height8|mid1|bid.hash|mid2|psh.hash|ts|suffix
        psh_inner = 1 + len(proto.encode_uvarint(psh.total)) + 2 + 32
        f4_inner = 2 + 32 + 1 + len(proto.encode_uvarint(psh_inner)) + psh_inner
        head = proto.Writer().varint(1, vtype).out() + b"\x11"
        # round 0 (the common prevote/precommit round) is omitted entirely,
        # like every zero-valued proto field the Writer drops
        round_seg = (b"" if round_ == 0
                     else b"\x19" + round_.to_bytes(8, "little"))
        mid1 = (round_seg
                + b"\x22" + proto.encode_uvarint(f4_inner) + b"\x0a\x20")
        mid2 = (b"\x12" + proto.encode_uvarint(psh_inner)
                + b"\x08" + proto.encode_uvarint(psh.total) + b"\x12\x20")
        suf = proto.Writer().string(6, chain_id).out()
        tmpl = (head, mid1, mid2, suf)
        # self-check: any drift between this splice layout and the Writer
        # path falls back to the Writer permanently for this key
        chk_bid = BlockID(hash=b"\xa7" * 32,
                          part_set_header=PartSetHeader(psh.total, b"\x5c" * 32))
        chk_ts = Time(123456789, 987)
        tsm = chk_ts.marshal()
        fast = proto.delimited(
            head + (54321).to_bytes(8, "little") + mid1 + chk_bid.hash
            + mid2 + chk_bid.part_set_header.hash
            + b"\x2a" + proto.encode_uvarint(len(tsm)) + tsm + suf)
        if fast != _canonical_vote_bytes_writer(
                chain_id, vtype, 54321, round_, chk_bid, chk_ts):
            tmpl = None
        _CV_TEMPLATES[key] = tmpl
    if tmpl is None:
        return None
    head, mid1, mid2, suf = tmpl
    return (head + height.to_bytes(8, "little") + mid1 + block_id.hash
            + mid2 + psh.hash + b"\x2a"), suf


def canonical_vote_bytes(chain_id: str, vtype: int, height: int, round_: int,
                         block_id: BlockID, timestamp: Time) -> bytes:
    """Delimited CanonicalVote marshal = the exact signed payload
    (reference: types/vote.go:93 VoteSignBytes).

    Fast path: where the shape has a splice template (`_cv_ends`), joins
    fill in height, hashes and timestamp instead of a Writer build per
    call. Light-client range sync builds one of these per header; a cache
    keyed on (height, block_id) missed every time there."""
    ends = _cv_ends(chain_id, vtype, height, round_, block_id)
    if ends is None:
        return _canonical_vote_bytes_writer(
            chain_id, vtype, height, round_, block_id, timestamp)
    prefix, suf = ends
    tsm = timestamp.marshal()
    return proto.delimited(
        prefix + proto.encode_uvarint(len(tsm)) + tsm + suf)


def canonical_vote_bytes_many(chain_id: str, vtype: int, height: int,
                              round_: int, block_id: BlockID,
                              timestamps) -> list[bytes] | None:
    """``[canonical_vote_bytes(..., ts) for ts in timestamps]`` for votes
    that differ in nothing but their timestamp (the precommits of one
    commit), byte-identical, with the constant work done once per CALL: of
    the ~120 bytes only the timestamp body and the two length prefixes
    before it differ. None where the shape has no splice template; the
    caller then takes the per-vote path.

    Everything memoised here lives in this call's frame and dies with it.
    A commit's validators sign within a few seconds of each other, so the
    frame up to and including the ``seconds`` field is built once per
    distinct second, for each length the nanos varint can have; per vote
    that leaves the nanos varint, from the tables above, and one join."""
    ends = _cv_ends(chain_id, vtype, height, round_, block_id)
    if ends is None:
        return None
    prefix, suf = ends
    uv, join = proto.encode_uvarint, b"".join
    # the nanos varint from the codec's tables: the two continuation bytes of
    # a 14-bit group, the one of a 7-bit group
    pair, cont = proto.UV14C, proto.UV7C
    # the last nanos byte (no continuation bit) with the suffix behind it
    tails = [uv(last) + suf for last in range(0x80)]
    # all but the timestamp body; its length (at most 22) takes one byte
    fixed = len(prefix) + 1 + len(suf)
    frames: dict = {}
    out = []
    append = out.append
    for ts in timestamps:
        seconds, nanos = ts.seconds, ts.nanos
        by_len = frames.get(seconds)
        if by_len is None:
            sec = proto.Writer().varint(1, seconds).out()
            # by_len[k]: outer length, prefix, timestamp length, seconds
            # field and the nanos tag, for a nanos varint of k bytes
            body = len(sec)
            by_len = frames[seconds] = (
                [uv(fixed + body) + prefix + uv(body) + sec]
                + [uv(fixed + body + 1 + k) + prefix + uv(body + 1 + k)
                   + sec + b"\x10" for k in (1, 2, 3, 4, 5)])
        if 0x10000000 <= nanos < 0x800000000:  # three nanos values in four
            append(join((by_len[5], pair[nanos & 0x3FFF],
                         pair[nanos >> 14 & 0x3FFF], tails[nanos >> 28])))
        elif 0x200000 <= nanos < 0x10000000:
            append(join((by_len[4], pair[nanos & 0x3FFF],
                         cont[nanos >> 14 & 0x7F], tails[nanos >> 21])))
        elif 0 <= nanos < 0x200000:  # 0: zero-valued field, omitted whole
            low = uv(nanos) if nanos else b""
            append(by_len[len(low)] + low + suf)
        else:  # no valid Timestamp; whatever Time.marshal makes of it
            tsm = ts.marshal()
            append(proto.delimited(prefix + uv(len(tsm)) + tsm + suf))
    return out


def _canonical_vote_bytes_writer(chain_id: str, vtype: int, height: int,
                                 round_: int, block_id: BlockID,
                                 timestamp: Time) -> bytes:
    """Plain Writer-based construction (the layout source of truth)."""
    w = proto.Writer()
    w.varint(1, vtype)
    w.sfixed64(2, height)
    w.sfixed64(3, round_)
    cbid = canonical_block_id_bytes(block_id)
    if cbid is not None:
        w.message(4, cbid, always=True)
    pre = w.out()
    suf = proto.Writer().string(6, chain_id).out()
    tsm = timestamp.marshal()
    # field 5 (timestamp), wire type 2: tag 0x2a; always emitted.
    return proto.delimited(pre + b"\x2a" + proto.encode_uvarint(len(tsm))
                           + tsm + suf)


@dataclass
class Vote:
    type: int = UNKNOWN_TYPE
    height: int = 0
    round: int = 0
    block_id: BlockID = field(default_factory=BlockID)
    timestamp: Time = field(default_factory=Time.zero)
    validator_address: bytes = b""
    validator_index: int = 0
    signature: bytes = b""

    def sign_bytes(self, chain_id: str) -> bytes:
        return canonical_vote_bytes(
            chain_id, self.type, self.height, self.round, self.block_id, self.timestamp
        )

    def verify(self, chain_id: str, pub_key: keys.PubKey) -> None:
        """Reference: types/vote.go:147 -- address match then sig verify."""
        if pub_key.address() != self.validator_address:
            raise VoteError("invalid validator address")
        if not pub_key.verify_signature(self.sign_bytes(chain_id), self.signature):
            raise VoteError("invalid signature")

    def validate_basic(self) -> None:
        if not is_vote_type_valid(self.type):
            raise VoteError("invalid Type")
        if self.height < 0:
            raise VoteError("negative Height")
        if self.round < 0:
            raise VoteError("negative Round")
        if not self.block_id.is_zero():
            self.block_id.validate_basic()
            if not self.block_id.is_complete():
                raise VoteError(f"blockID must be either empty or complete, got: {self.block_id}")
        if len(self.validator_address) != keys.ADDRESS_SIZE:
            raise VoteError("expected ValidatorAddress size to be 20 bytes")
        if self.validator_index < 0:
            raise VoteError("negative ValidatorIndex")
        if len(self.signature) == 0:
            raise VoteError("signature is missing")
        if len(self.signature) > MAX_SIGNATURE_SIZE:
            raise VoteError("signature is too big")

    def is_nil(self) -> bool:
        return self.block_id.is_zero()

    def copy(self) -> "Vote":
        return replace(self)

    # --- wire (proto/tendermint/types/types.proto Vote) --------------------
    def marshal(self) -> bytes:
        return (
            proto.Writer()
            .varint(1, self.type)
            .varint(2, self.height)
            .varint(3, self.round)
            .message(4, self.block_id.marshal(), always=True)
            .message(5, self.timestamp.marshal(), always=True)
            .bytes(6, self.validator_address)
            .varint(7, self.validator_index)
            .bytes(8, self.signature)
            .out()
        )

    @staticmethod
    def marshal_many(votes) -> list[bytes]:
        """``[v.marshal() for v in votes]`` in one pass, for the hundreds of
        votes of a drain that share all but five fields: the bytes of fields
        1-4 are built once a ``(type, height, round, block_id)`` by the same
        Writer calls as ``marshal``, which stays the definition
        (tests/test_wal.py pins this to it); the tail follows the Writer's
        proto3 omissions, every length a varint."""
        uvarint, varint = proto.encode_uvarint, proto.encode_varint
        heads: dict = {}
        out = []
        for v in votes:
            bid = v.block_id
            psh = bid.part_set_header
            key = (v.type, v.height, v.round, bid.hash, psh.total, psh.hash)
            head = heads.get(key)
            if head is None:
                head = heads[key] = (
                    proto.Writer()
                    .varint(1, v.type)
                    .varint(2, v.height)
                    .varint(3, v.round)
                    .message(4, bid.marshal(), always=True)
                    .out()
                )
            ts = v.timestamp.marshal()
            parts = [head, b"\x2a", uvarint(len(ts)), ts]
            address, index, sig = v.validator_address, v.validator_index, v.signature
            if address:
                parts += (b"\x32", uvarint(len(address)), address)
            if index:
                parts += (b"\x38", varint(index))
            if sig:
                parts += (b"\x42", uvarint(len(sig)), sig)
            out.append(b"".join(parts))
        return out

    @staticmethod
    def unmarshal(buf: bytes) -> "Vote":
        f = proto.fields(buf)
        return Vote(
            type=f.get(1, [0])[-1],
            height=proto.as_sint64(f.get(2, [0])[-1]),
            round=proto.as_sint64(f.get(3, [0])[-1]),
            block_id=BlockID.unmarshal(f.get(4, [b""])[-1]),
            timestamp=Time.unmarshal(f.get(5, [b""])[-1]),
            validator_address=f.get(6, [b""])[-1],
            validator_index=proto.as_sint64(f.get(7, [0])[-1]),
            signature=f.get(8, [b""])[-1],
        )

    def __str__(self) -> str:
        kind = {PREVOTE_TYPE: "Prevote", PRECOMMIT_TYPE: "Precommit"}.get(self.type, "?")
        tgt = "nil" if self.is_nil() else self.block_id.hash.hex()[:12]
        return (
            f"Vote{{{self.validator_index}:{self.validator_address.hex()[:12]} "
            f"{self.height}/{self.round:02d} {kind} {tgt}}}"
        )


MAX_SIGNATURE_SIZE = 64  # largest among ed25519/sr25519/secp256k1 (reference: types/vote.go)


class VoteError(Exception):
    pass


class ErrVoteConflictingVotes(VoteError):
    """Same validator signed two different votes for the same H/R/T
    (reference: types/vote_set.go:84, the evidence trigger)."""

    def __init__(self, vote_a: Vote, vote_b: Vote):
        super().__init__(f"conflicting votes: {vote_a} vs {vote_b}")
        self.vote_a = vote_a
        self.vote_b = vote_b


class ErrVoteNonDeterministicSignature(VoteError):
    pass


class ErrVoteInvalidSignature(VoteError):
    """Signature verification failed — the one vote error whose blame is
    unambiguous: votes are gossip-relayed, but a relay corrupting a vote
    is as culpable as a forger, so the peer misbehavior scoreboard
    (utils/peerscore.py) scores the delivering peer on this type."""
