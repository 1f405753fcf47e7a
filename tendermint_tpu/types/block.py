"""Block, Header, Data, Commit, CommitSig (reference: types/block.go:43,325,
575-787, proto/tendermint/types/types.proto).

Header.Hash is the Merkle root over the 14 proto-encoded fields in declaration
order (reference: types/block.go:440-476); scalar fields are wrapped in the
gogo well-known wrapper types first (cdcEncode, types/encoding_helper.go:11).
"""

from __future__ import annotations

from dataclasses import dataclass, field as dc_field

from tendermint_tpu.crypto import merkle, tmhash
from tendermint_tpu.encoding import proto
from tendermint_tpu.types import tx as tx_mod
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.ttime import Time
from tendermint_tpu.utils import trace as _trace
from tendermint_tpu.types.vote import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BLOCK_ID_FLAG_NIL,
    PRECOMMIT_TYPE,
    Vote,
    canonical_vote_bytes,
    canonical_vote_bytes_many,
)

MAX_HEADER_BYTES = 626  # reference: types/block.go MaxHeaderBytes
BLOCK_PROTOCOL = 11  # reference: version/version.go:21


def cdc_encode_string(v: str) -> bytes:
    return cdc_encode_bytes(v.encode("utf-8")) if v else b""


def cdc_encode_int64(v: int) -> bytes:
    if not v:
        return b""
    return b"\x08" + proto.encode_varint(v)  # field 1, wire varint


def cdc_encode_bytes(v: bytes) -> bytes:
    if not v:
        return b""
    if len(v) < 0x80:  # field 1, wire bytes, single-byte length
        return b"\x0a" + bytes((len(v),)) + v
    return proto.Writer().bytes(1, v).out()


@dataclass(frozen=True)
class Consensus:
    """Version pair (reference: proto/tendermint/version/types.proto)."""

    block: int = BLOCK_PROTOCOL
    app: int = 0

    def marshal(self) -> bytes:
        return proto.Writer().uvarint(1, self.block).uvarint(2, self.app).out()

    @staticmethod
    def unmarshal(buf: bytes) -> "Consensus":
        f = proto.fields(buf)
        return Consensus(block=f.get(1, [0])[-1], app=f.get(2, [0])[-1])


@dataclass
class Header:
    version: Consensus = dc_field(default_factory=Consensus)
    chain_id: str = ""
    height: int = 0
    time: Time = dc_field(default_factory=Time.zero)
    last_block_id: BlockID = dc_field(default_factory=BlockID)
    last_commit_hash: bytes = b""
    data_hash: bytes = b""
    validators_hash: bytes = b""
    next_validators_hash: bytes = b""
    consensus_hash: bytes = b""
    app_hash: bytes = b""
    last_results_hash: bytes = b""
    evidence_hash: bytes = b""
    proposer_address: bytes = b""
    # Set only by precompute_header_hashes on finished headers.
    _hash_cache: bytes | None = dc_field(
        default=None, repr=False, compare=False)

    def __setattr__(self, name, value):
        # Invalidate the cached root on ANY later field mutation: a stale
        # hash() after mutation would silently corrupt block ids (round-4
        # advisor finding; previously safe only by caller convention).
        if name != "_hash_cache" and self.__dict__.get("_hash_cache") is not None:
            self.__dict__["_hash_cache"] = None
        object.__setattr__(self, name, value)

    def hash_fields(self) -> list[bytes]:
        """The 14 merkle leaves of the header hash
        (reference: types/block.go:440-476)."""
        return [
            self.version.marshal(),
            cdc_encode_string(self.chain_id),
            cdc_encode_int64(self.height),
            self.time.marshal(),
            self.last_block_id.marshal(),
            cdc_encode_bytes(self.last_commit_hash),
            cdc_encode_bytes(self.data_hash),
            cdc_encode_bytes(self.validators_hash),
            cdc_encode_bytes(self.next_validators_hash),
            cdc_encode_bytes(self.consensus_hash),
            cdc_encode_bytes(self.app_hash),
            cdc_encode_bytes(self.last_results_hash),
            cdc_encode_bytes(self.evidence_hash),
            cdc_encode_bytes(self.proposer_address),
        ]

    def hash(self) -> bytes | None:
        """reference: types/block.go:440-476. None when ValidatorsHash is
        unset (header not yet complete). Headers may be filled in
        incrementally, so the hash is NOT cached here — batch paths that
        hold finished headers use precompute_header_hashes."""
        if not self.validators_hash:
            return None
        if self._hash_cache is not None:
            return self._hash_cache
        return merkle.hash_from_byte_slices(self.hash_fields())

    def validate_basic(self) -> None:
        if len(self.chain_id) > 50:
            raise ValueError("chainID is too long")
        if self.height < 0:
            raise ValueError("negative Header.Height")
        if self.height == 0:
            raise ValueError("zero Header.Height")
        self.last_block_id.validate_basic()
        for name in ("last_commit_hash", "data_hash", "evidence_hash",
                     "validators_hash", "next_validators_hash",
                     "consensus_hash", "last_results_hash"):
            h = getattr(self, name)
            if h and len(h) != tmhash.SIZE:
                raise ValueError(f"wrong {name}")
        if len(self.proposer_address) != 20:
            raise ValueError("invalid ProposerAddress length")

    def marshal(self) -> bytes:
        return (
            proto.Writer()
            .message(1, self.version.marshal(), always=True)
            .string(2, self.chain_id)
            .varint(3, self.height)
            .message(4, self.time.marshal(), always=True)
            .message(5, self.last_block_id.marshal(), always=True)
            .bytes(6, self.last_commit_hash)
            .bytes(7, self.data_hash)
            .bytes(8, self.validators_hash)
            .bytes(9, self.next_validators_hash)
            .bytes(10, self.consensus_hash)
            .bytes(11, self.app_hash)
            .bytes(12, self.last_results_hash)
            .bytes(13, self.evidence_hash)
            .bytes(14, self.proposer_address)
            .out()
        )

    @staticmethod
    def unmarshal(buf: bytes) -> "Header":
        f = proto.fields(buf)
        return Header(
            version=Consensus.unmarshal(f.get(1, [b""])[-1]),
            chain_id=f.get(2, [b""])[-1].decode("utf-8"),
            height=proto.as_sint64(f.get(3, [0])[-1]),
            time=Time.unmarshal(f.get(4, [b""])[-1]),
            last_block_id=BlockID.unmarshal(f.get(5, [b""])[-1]),
            last_commit_hash=f.get(6, [b""])[-1],
            data_hash=f.get(7, [b""])[-1],
            validators_hash=f.get(8, [b""])[-1],
            next_validators_hash=f.get(9, [b""])[-1],
            consensus_hash=f.get(10, [b""])[-1],
            app_hash=f.get(11, [b""])[-1],
            last_results_hash=f.get(12, [b""])[-1],
            evidence_hash=f.get(13, [b""])[-1],
            proposer_address=f.get(14, [b""])[-1],
        )


def precompute_header_hashes(headers: list[Header]) -> None:
    """Hash a whole header chain as one same-arity merkle forest
    (crypto/merkle hash_trees_fixed: O(log 14) C-batched sha256 calls
    instead of 27 hashlib calls per header) and fill each header's hash
    cache. Only finished headers (validators_hash set) are cached; call
    this on received chains, never on headers still being built."""
    done = [h for h in headers
            if h.validators_hash and h._hash_cache is None]
    if not done:
        return
    roots = merkle.hash_trees_fixed([h.hash_fields() for h in done])
    for h, root in zip(done, roots):
        h._hash_cache = root


@dataclass
class CommitSig:
    """One validator's slot in a Commit (reference: types/block.go:575-680)."""

    block_id_flag: int = BLOCK_ID_FLAG_ABSENT
    validator_address: bytes = b""
    timestamp: Time = dc_field(default_factory=Time.zero)
    signature: bytes = b""

    @staticmethod
    def new_absent() -> "CommitSig":
        return CommitSig()

    @staticmethod
    def new_commit(block_id_flag: int, validator_address: bytes,
                   timestamp: Time, signature: bytes) -> "CommitSig":
        return CommitSig(block_id_flag, validator_address, timestamp, signature)

    def absent(self) -> bool:
        return self.block_id_flag == BLOCK_ID_FLAG_ABSENT

    def for_block(self) -> bool:
        return self.block_id_flag == BLOCK_ID_FLAG_COMMIT

    def block_id(self, commit_block_id: BlockID) -> BlockID:
        """reference: types/block.go:652-665."""
        if self.block_id_flag == BLOCK_ID_FLAG_COMMIT:
            return commit_block_id
        return BlockID()

    def validate_basic(self) -> None:
        if self.block_id_flag not in (BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL):
            raise ValueError(f"unknown BlockIDFlag: {self.block_id_flag}")
        if self.absent():
            if self.validator_address:
                raise ValueError("validator address is present")
            if not self.timestamp.is_zero():
                raise ValueError("time is present")
            if self.signature:
                raise ValueError("signature is present")
        else:
            if len(self.validator_address) != 20:
                raise ValueError("expected ValidatorAddress size to be 20 bytes")
            if not self.signature:
                raise ValueError("signature is missing")
            if len(self.signature) > 64:
                raise ValueError("signature is too big")

    def marshal(self) -> bytes:
        # no Writer: a commit holds one of these per validator. proto3 omits
        # the zero flag and the empty address and signature; the timestamp
        # is nullable=false, emitted always.
        uv = proto.encode_uvarint
        flag, address = self.block_id_flag, self.validator_address
        stamp, signature = self.timestamp.marshal(), self.signature
        return (
            (b"\x08" + proto.encode_varint(flag) if flag else b"")
            + (b"\x12" + uv(len(address)) + address if address else b"")
            + b"\x1a" + uv(len(stamp)) + stamp
            + (b"\x22" + uv(len(signature)) + signature if signature else b"")
        )

    @staticmethod
    def unmarshal(buf: bytes) -> "CommitSig":
        f = proto.fields(buf)
        return CommitSig(
            block_id_flag=f.get(1, [0])[-1],
            validator_address=f.get(2, [b""])[-1],
            timestamp=Time.unmarshal(f.get(3, [b""])[-1]),
            signature=f.get(4, [b""])[-1],
        )


@dataclass
class Commit:
    height: int = 0
    round: int = 0
    block_id: BlockID = dc_field(default_factory=BlockID)
    signatures: list[CommitSig] = dc_field(default_factory=list)

    def get_vote(self, val_idx: int) -> Vote:
        """Reconstruct the precommit Vote for validator slot val_idx
        (reference: types/block.go:784-806)."""
        cs = self.signatures[val_idx]
        return Vote(
            type=PRECOMMIT_TYPE,
            height=self.height,
            round=self.round,
            block_id=cs.block_id(self.block_id),
            timestamp=cs.timestamp,
            validator_address=cs.validator_address,
            validator_index=val_idx,
            signature=cs.signature,
        )

    def vote_sign_bytes(self, chain_id: str, val_idx: int) -> bytes:
        """Canonical sign bytes for the precommit in slot val_idx —
        equivalent to get_vote(val_idx).sign_bytes(chain_id) (differential-
        tested): the per-vote source of truth. A loop over a commit's slots
        takes sign_bytes_many instead."""
        cs = self.signatures[val_idx]
        return canonical_vote_bytes(chain_id, PRECOMMIT_TYPE, self.height,
                                    self.round, cs.block_id(self.block_id),
                                    cs.timestamp)

    def sign_bytes_many(self, chain_id: str, idxs) -> tuple[list[bytes], int]:
        """``[self.vote_sign_bytes(chain_id, i) for i in idxs]`` with the
        work that is constant across a commit done once per call, and how
        many of them that covered (the ``spliced`` tag of
        ``commit.assemble``). The votes for the block differ in nothing but
        their timestamps, so canonical_vote_bytes_many splices those into
        one prefix; votes for nil (a zero BlockID has no fixed layout) and
        a commit whose shape has no template take the per-index path.
        Nothing is kept between calls: a pool of commits verified over and
        over pays the same every time."""
        sigs = self.signatures
        for_block = [sigs[i].timestamp for i in idxs
                     if sigs[i].block_id_flag == BLOCK_ID_FLAG_COMMIT]
        msgs = canonical_vote_bytes_many(
            chain_id, PRECOMMIT_TYPE, self.height, self.round, self.block_id,
            for_block) if for_block else None
        if msgs is None:
            return [self.vote_sign_bytes(chain_id, i) for i in idxs], 0
        if len(msgs) == len(idxs):
            return msgs, len(msgs)
        spliced = iter(msgs)
        return [next(spliced) if sigs[i].block_id_flag == BLOCK_ID_FLAG_COMMIT
                else self.vote_sign_bytes(chain_id, i) for i in idxs], len(msgs)

    def size(self) -> int:
        return len(self.signatures)

    def is_commit(self) -> bool:
        return len(self.signatures) != 0

    def validate_basic(self) -> None:
        if self.height < 0:
            raise ValueError("negative Height")
        if self.round < 0:
            raise ValueError("negative Round")
        if self.height >= 1:
            if self.block_id.is_zero():
                raise ValueError("commit cannot be for nil block")
            if not self.signatures:
                raise ValueError("no signatures in commit")
            for i, cs in enumerate(self.signatures):
                try:
                    cs.validate_basic()
                except ValueError as e:
                    raise ValueError(f"wrong CommitSig #{i}: {e}") from e

    def hash(self) -> bytes:
        """reference: types/block.go:894-911."""
        return merkle.hash_from_byte_slices([cs.marshal() for cs in self.signatures])

    def bit_array(self) -> list[bool]:
        return [not cs.absent() for cs in self.signatures]

    def marshal(self) -> bytes:
        return (
            proto.Writer()
            .varint(1, self.height)
            .varint(2, self.round)
            .message(3, self.block_id.marshal(), always=True)
            .out()
        ) + proto.repeated_messages(  # field 4
            b"\x22", [cs.marshal() for cs in self.signatures])

    @staticmethod
    def unmarshal(buf: bytes) -> "Commit":
        f = proto.fields(buf)
        return Commit(
            height=proto.as_sint64(f.get(1, [0])[-1]),
            round=proto.as_sint64(f.get(2, [0])[-1]),
            block_id=BlockID.unmarshal(f.get(3, [b""])[-1]),
            signatures=[CommitSig.unmarshal(b) for b in f.get(4, [])],
        )


@dataclass
class Data:
    txs: list[bytes] = dc_field(default_factory=list)

    def hash(self) -> bytes:
        if _trace.ENABLED and self.txs:
            with _trace.current().span("block.data_hash", txs=len(self.txs)):
                return tx_mod.txs_hash(self.txs)
        return tx_mod.txs_hash(self.txs)

    def marshal(self) -> bytes:
        w = proto.Writer()
        for t in self.txs:
            w.bytes(1, t) if t else w.message(1, b"", always=True)
        return w.out()

    @staticmethod
    def unmarshal(buf: bytes) -> "Data":
        f = proto.fields(buf)
        return Data(txs=list(f.get(1, [])))


@dataclass
class Block:
    header: Header = dc_field(default_factory=Header)
    data: Data = dc_field(default_factory=Data)
    evidence: list = dc_field(default_factory=list)
    last_commit: Commit | None = None

    def hash(self) -> bytes | None:
        """Header hash, with LastCommitHash filled (reference:
        types/block.go:123-141 fillHeader + Hash)."""
        if self.last_commit is None and self.header.height > 1:
            return None
        self.fill_header()
        return self.header.hash()

    def fill_header(self) -> None:
        if not self.header.last_commit_hash and self.last_commit is not None:
            self.header.last_commit_hash = self.last_commit.hash()
        if not self.header.data_hash:
            self.header.data_hash = self.data.hash()
        if not self.header.evidence_hash:
            self.header.evidence_hash = evidence_hash(self.evidence)

    def validate_basic(self) -> None:
        self.header.validate_basic()
        if self.last_commit is None and self.header.height > 1:
            raise ValueError("nil LastCommit")
        if self.last_commit is not None:
            self.last_commit.validate_basic()
            if self.header.last_commit_hash != self.last_commit.hash():
                raise ValueError("wrong Header.LastCommitHash")
        if self.header.data_hash != self.data.hash():
            raise ValueError("wrong Header.DataHash")
        if self.header.evidence_hash != evidence_hash(self.evidence):
            raise ValueError("wrong Header.EvidenceHash")

    def hashes_to(self, h: bytes) -> bool:
        return bool(h) and self.hash() == h

    def marshal(self) -> bytes:
        w = (
            proto.Writer()
            .message(1, self.header.marshal(), always=True)
            .message(2, self.data.marshal(), always=True)
            .message(3, evidence_list_marshal(self.evidence), always=True)
        )
        if self.last_commit is not None:
            w.message(4, self.last_commit.marshal())
        return w.out()

    @staticmethod
    def unmarshal(buf: bytes) -> "Block":
        from tendermint_tpu.types import evidence as ev_mod

        f = proto.fields(buf)
        evs = []
        if 3 in f:
            ef = proto.fields(f[3][-1])
            evs = [ev_mod.evidence_unmarshal(b) for b in ef.get(1, [])]
        lc = Commit.unmarshal(f[4][-1]) if 4 in f else None
        return Block(
            header=Header.unmarshal(f.get(1, [b""])[-1]),
            data=Data.unmarshal(f.get(2, [b""])[-1]),
            evidence=evs,
            last_commit=lc,
        )


def evidence_hash(evidence: list) -> bytes:
    """EvidenceData hash = merkle over evidence proto marshals (reference:
    types/evidence.go EvidenceData/evidence list Hash)."""
    return merkle.hash_from_byte_slices([ev.bytes() for ev in evidence])


def evidence_list_marshal(evidence: list) -> bytes:
    w = proto.Writer()
    for ev in evidence:
        w.message(1, ev.bytes(), always=True)
    return w.out()


def make_commit(block_id: BlockID, height: int, round_: int, votes) -> Commit:
    """Build a Commit from a VoteSet's ordered vote slots (reference:
    types/vote_set.go:612-636 MakeCommit + types/vote.go:62 CommitSig): a
    vote for a block OTHER than the maj23 block is excluded (absent), not
    marked nil -- its signature signs a different BlockID."""
    sigs = []
    for v in votes:
        if v is None:
            sigs.append(CommitSig.new_absent())
            continue
        flag = BLOCK_ID_FLAG_NIL if v.block_id.is_zero() else BLOCK_ID_FLAG_COMMIT
        if flag == BLOCK_ID_FLAG_COMMIT and v.block_id != block_id:
            sigs.append(CommitSig.new_absent())
            continue
        sigs.append(CommitSig(flag, v.validator_address, v.timestamp, v.signature))
    return Commit(height=height, round=round_, block_id=block_id, signatures=sigs)
