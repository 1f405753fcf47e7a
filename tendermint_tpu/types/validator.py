"""Validator (reference: types/validator.go,
proto/tendermint/types/validator.proto)."""

from __future__ import annotations

from dataclasses import dataclass

from tendermint_tpu.crypto import keys
from tendermint_tpu.encoding import proto

# Matches types/validator_set.go:MaxTotalVotingPower = MaxInt64 / 8
MAX_TOTAL_VOTING_POWER = (2**63 - 1) // 8
PRIORITY_WINDOW_SIZE_FACTOR = 2

_INT64_MAX = 2**63 - 1
_INT64_MIN = -(2**63)


def clip_int64(v: int) -> int:
    return max(_INT64_MIN, min(_INT64_MAX, v))


# the PublicKey oneof's tags: fields 1, 2, 3, wire type bytes
_PUBKEY_TAG = {"ed25519": b"\x0a", "secp256k1": b"\x12", "sr25519": b"\x1a"}


def pubkey_proto_bytes(pub: keys.PubKey) -> bytes:
    """tendermint.crypto.PublicKey oneof marshal (reference:
    crypto/encoding/codec.go PubKeyToProto; keys.proto fields: ed25519=1,
    secp256k1=2).

    EXTENSION: sr25519 = 3. The v0.34 reference ships an sr25519 key type
    but cannot proto-encode it (codec.go:35-38 errors), so sr25519
    validators can't exist in a reference validator set at all; field 3 is
    the convention forks that do support it use. Wire compatibility for
    ed25519/secp256k1 chains is unaffected."""
    tag = _PUBKEY_TAG.get(pub.type)
    if tag is None:
        raise ValueError(f"key type {pub.type} not representable in PublicKey proto")
    raw = pub.bytes()
    return tag + proto.encode_uvarint(len(raw)) + raw if raw else b""


def pubkey_from_proto_bytes(buf: bytes) -> keys.PubKey:
    f = proto.fields(buf)
    if 1 in f:
        return keys.pubkey_from_type_bytes("ed25519", f[1][-1])
    if 2 in f:
        return keys.pubkey_from_type_bytes("secp256k1", f[2][-1])
    if 3 in f:
        return keys.pubkey_from_type_bytes("sr25519", f[3][-1])
    raise ValueError("empty PublicKey proto")


@dataclass
class Validator:
    address: bytes
    pub_key: keys.PubKey
    voting_power: int
    proposer_priority: int = 0

    @staticmethod
    def new(pub_key: keys.PubKey, voting_power: int) -> "Validator":
        return Validator(
            address=pub_key.address(), pub_key=pub_key,
            voting_power=voting_power, proposer_priority=0,
        )

    def copy(self) -> "Validator":
        # direct ctor: dataclasses.replace costs ~5x more and sits on the
        # per-vote hot path (ValidatorSet.get_by_index returns copies)
        return Validator(self.address, self.pub_key, self.voting_power,
                         self.proposer_priority)

    def validate_basic(self) -> None:
        if self.pub_key is None:
            raise ValueError("validator does not have a public key")
        if self.voting_power < 0:
            raise ValueError("validator has negative voting power")
        if len(self.address) != keys.ADDRESS_SIZE:
            raise ValueError("validator address is the wrong size")

    def compare_proposer_priority(self, other: "Validator | None") -> "Validator":
        """Higher priority wins; ties broken by lower address (reference:
        types/validator.go:60-82)."""
        if other is None:
            return self
        if self.proposer_priority > other.proposer_priority:
            return self
        if self.proposer_priority < other.proposer_priority:
            return other
        if self.address < other.address:
            return self
        if self.address > other.address:
            return other
        raise AssertionError("cannot compare identical validators")

    def bytes(self) -> bytes:
        """SimpleValidator proto marshal -- the validator-set hash leaf
        (reference: types/validator.go:117-131)."""
        return (
            proto.Writer()
            .message(1, pubkey_proto_bytes(self.pub_key))
            .varint(2, self.voting_power)
            .out()
        )

    # full Validator proto (validator.proto)
    def marshal(self) -> bytes:
        # no Writer: a set holds one of these per validator. proto3 omits
        # the zero scalars; the key is nullable=false, emitted always.
        uv = proto.encode_uvarint
        address, power = self.address, self.voting_power
        priority = self.proposer_priority
        key = pubkey_proto_bytes(self.pub_key)
        return (
            (b"\x0a" + uv(len(address)) + address if address else b"")
            + b"\x12" + uv(len(key)) + key
            + (b"\x18" + proto.encode_varint(power) if power else b"")
            + (b"\x20" + proto.encode_varint(priority) if priority else b"")
        )

    @staticmethod
    def unmarshal(buf: bytes) -> "Validator":
        f = proto.fields(buf)
        return Validator(
            address=f.get(1, [b""])[-1],
            pub_key=pubkey_from_proto_bytes(f.get(2, [b""])[-1]),
            voting_power=proto.as_sint64(f.get(3, [0])[-1]),
            proposer_priority=proto.as_sint64(f.get(4, [0])[-1]),
        )

    def __str__(self) -> str:
        return f"Validator{{{self.address.hex()[:12]} VP:{self.voting_power} A:{self.proposer_priority}}}"
