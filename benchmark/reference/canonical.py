"""The canonical encodings a light client's checks rest on, over plain
values: a header's hash and the bytes a precommit signs. Nothing of the
program is imported.

Written from the reference's proto files and types (v0.34), as recalled on
a machine without its tree or a network, so each is cited by name:

  - ``types/block.go`` ``Header.Hash``: the RFC 6962 root
    (``light_sync.merkle_root``) over fourteen leaves in the header's field
    order: ``version.Consensus{block=1, app=2}``, then each field wrapped
    as ``cdcEncode`` wraps it (gogotypes ``StringValue``, ``Int64Value``,
    ``BytesValue``: the value under field 1, and **no bytes at all** for an
    empty one), except the time (``google.protobuf.Timestamp{seconds=1,
    nanos=2}``) and the last block id (``BlockID{hash=1,
    part_set_header=2}``, the part-set header written even when empty),
    which go in as their own messages;
  - ``types/vote.go`` ``VoteSignBytes``: ``valset_replay.vote_sign_bytes``
    (the length-delimited ``CanonicalVote``), given here the block id and
    each slot's timestamp as the messages above.

proto3 writes no field whose value is zero or empty. A chain generator
(``drivers/rotatingchain.py``) signs **these** bytes and chains **these**
hashes, so a program whose encoder or hasher differs refuses the chain,
and the plain reference is never fed what the program computed.
"""

from __future__ import annotations

from benchmark.reference import valset_replay
from benchmark.reference.light_sync import _varint, merkle_root

BLOCK_PROTOCOL = 11          # version/version.go BlockProtocol of v0.34


def _uint(field: int, n: int) -> bytes:
    return bytes([field << 3]) + _varint(n) if n else b""


def _bytes(field: int, body: bytes) -> bytes:
    return bytes([field << 3 | 2]) + _varint(len(body)) + body if body else b""


def _message(field: int, body: bytes) -> bytes:
    """A non-nullable embedded message: written even when empty."""
    return bytes([field << 3 | 2]) + _varint(len(body)) + body


def timestamp(seconds: int, nanos: int) -> bytes:
    return _uint(1, seconds) + _uint(2, nanos)


def block_id(hash_: bytes, parts_total: int, parts_hash: bytes) -> bytes:
    return _bytes(1, hash_) + _message(
        2, _uint(1, parts_total) + _bytes(2, parts_hash))


def header_hash(*, chain_id: str, height: int, seconds: int, nanos: int,
                last_block_id: bytes, validators_hash: bytes,
                next_validators_hash: bytes, proposer_address: bytes,
                last_commit_hash: bytes = b"", data_hash: bytes = b"",
                consensus_hash: bytes = b"", app_hash: bytes = b"",
                last_results_hash: bytes = b"", evidence_hash: bytes = b"",
                version_block: int = BLOCK_PROTOCOL, version_app: int = 0) -> bytes:
    """Header.Hash; ``last_block_id`` is ``block_id(...)`` of the header
    before (of three empty values for the first header)."""
    return merkle_root([
        _uint(1, version_block) + _uint(2, version_app),
        _bytes(1, chain_id.encode()),
        _uint(1, height),
        timestamp(seconds, nanos),
        last_block_id,
        _bytes(1, last_commit_hash),
        _bytes(1, data_hash),
        _bytes(1, validators_hash),
        _bytes(1, next_validators_hash),
        _bytes(1, consensus_hash),
        _bytes(1, app_hash),
        _bytes(1, last_results_hash),
        _bytes(1, evidence_hash),
        _bytes(1, proposer_address),
    ])


def commit_sign_bytes(chain_id: str, height: int, round_: int,
                      block_id_bytes: bytes, slots: list) -> list:
    """The sign bytes of every slot of a commit. ``slots``: None for Absent,
    else (flag, seconds, nanos) of the slot's own timestamp -> [None |
    bytes], slot by slot."""
    commit = {"height": height, "round": round_, "block_id": block_id_bytes,
              "slots": [None if s is None else
                        {"flag": s[0], "timestamp": timestamp(s[1], s[2])}
                        for s in slots]}
    return [None if s is None else
            valset_replay.vote_sign_bytes(chain_id, commit, i)
            for i, s in enumerate(commit["slots"])]
