"""A chain whose validator set changes, replayed from its block bytes: the
reference's rule for who the validators of a height are, written straight
from spec/abci/apps.md "Updating the Validator Set", state/execution.go
updateState and types/validator_set.go UpdateWithChangeSet, over plain
Python: nothing of the program is imported.

  - a transaction ``val:<base64 ed25519 key>!<power>`` in block H is a
    validator update (abci/example/kvstore/persistent_kvstore.go); the
    updates of block H are applied to the set of H+1 and give the set of
    **H+2** (``DELAY``); power 0 removes a validator, a key the set does not
    hold joins it, a key it holds takes the new power; the set is kept by
    voting power descending, then address ascending;
  - header H names ``validators_hash`` = the hash of the set of H and
    ``next_validators_hash`` = the hash of the set of H+1 (RFC 6962 tree over
    SimpleValidator encodings, ``light_sync.validators_hash``);
  - the commit for H, carried by block H+1 as its LastCommit, is for H and
    for block H's hash, has one slot per validator of the set of H, and its
    +2/3 prefix by voting power (``light_prefix.py``) verifies, one signature
    at a time (``ed25519_ref.py``), over the canonical vote's sign bytes,
    which this file assembles from the commit's own bytes;
  - the application is the reference's kvstore: its app hash after a block is
    the count of transactions delivered so far, eight bytes big-endian, and
    header H+1 names it.

What is taken as given: each block's own hash (the Merkle root of its
header's fields in their canonical encodings, the program's type, which
every cell shares). Proposer priorities are outside this file: no hash
covers them.

A signature of pure Python takes milliseconds, so ``replay`` verifies the
prefixes of the heights it is told to (``verify_at``) and walks the others
structurally: hashes, sizes, the tally of the prefix.
"""

from __future__ import annotations

import base64
import hashlib
import struct

from benchmark.reference import ed25519_ref, light_prefix, light_sync

DELAY = 2                     # updates of block H are in force at H + DELAY
VAL_TX_PREFIX = b"val:"
ABSENT, COMMIT, NIL = 1, 2, 3
PRECOMMIT = 2


class ChangeSetError(ValueError):
    """A set of updates UpdateWithChangeSet refuses."""


# --- protobuf, as much as a block needs --------------------------------------


def _uvarint(buf: bytes, pos: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf: bytes):
    """(field number, value) of one message: an int for a varint or a fixed
    field, bytes for a length-delimited one."""
    pos = 0
    while pos < len(buf):
        key, pos = _uvarint(buf, pos)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _uvarint(buf, pos)
        elif wire == 1:
            value, pos = struct.unpack_from("<q", buf, pos)[0], pos + 8
        elif wire == 2:
            n, pos = _uvarint(buf, pos)
            value, pos = buf[pos:pos + n], pos + n
        elif wire == 5:
            value, pos = struct.unpack_from("<i", buf, pos)[0], pos + 4
        else:
            raise ValueError(f"wire type {wire}")
        yield field, value


def _one(buf: bytes, field: int, default=b""):
    out = default
    for f, v in _fields(buf):
        if f == field:
            out = v
    return out


def _int64(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def parse_block(raw: bytes) -> dict:
    """Block{header=1, data=2, evidence=3, last_commit=4} -> what the rule
    reads: the header's height, validator hashes and app hash, the
    transactions, and the LastCommit with its slots."""
    header = _one(raw, 1)
    block = {
        "height": _int64(_one(header, 3, 0)),
        "validators_hash": _one(header, 8),
        "next_validators_hash": _one(header, 9),
        "app_hash": _one(header, 11),
        "txs": [v for f, v in _fields(_one(raw, 2)) if f == 1],
        "last_commit": None,
    }
    commit = _one(raw, 4, None)
    if commit is not None:
        block_id = _one(commit, 3)
        block["last_commit"] = {
            "height": _int64(_one(commit, 1, 0)),
            "round": _int64(_one(commit, 2, 0)),
            "block_id": block_id,           # BlockID's bytes, as signed
            "block_hash": _one(block_id, 1),
            "slots": [{"flag": _one(s, 1, 0), "address": _one(s, 2),
                       "timestamp": _one(s, 3), "signature": _one(s, 4)}
                      for f, s in _fields(commit) if f == 4],
        }
    return block


def _len_prefixed(tag: bytes, body: bytes) -> bytes:
    return tag + light_sync._varint(len(body)) + body


def vote_sign_bytes(chain_id: str, commit: dict, slot: int) -> bytes:
    """types/vote.go VoteSignBytes of the precommit in this slot: the
    length-delimited CanonicalVote{type=1, height=2 sfixed64, round=3
    sfixed64, block_id=4, timestamp=5, chain_id=6}. CanonicalBlockID has
    BlockID's fields under BlockID's numbers, and the timestamp is the
    slot's own, so both go in as the commit holds them; a vote for nil has
    no block id; proto3 leaves a zero round out."""
    s = commit["slots"][slot]
    body = b"\x08" + bytes([PRECOMMIT])
    body += b"\x11" + struct.pack("<q", commit["height"])
    if commit["round"]:
        body += b"\x19" + struct.pack("<q", commit["round"])
    if s["flag"] == COMMIT:
        body += _len_prefixed(b"\x22", commit["block_id"])
    body += _len_prefixed(b"\x2a", s["timestamp"])
    body += _len_prefixed(b"\x32", chain_id.encode())
    return light_sync._varint(len(body)) + body


# --- the validator set ---------------------------------------------------------


def address(pub: bytes) -> bytes:
    """crypto/ed25519 Address: SHA-256 of the key, the first 20 bytes."""
    return hashlib.sha256(pub).digest()[:20]


def ordered(validators) -> list[tuple[bytes, bytes, int]]:
    """[(address, key, power)] by power descending, then address."""
    return sorted(validators, key=lambda v: (-v[2], v[0]))


def parse_val_tx(tx: bytes) -> tuple[bytes, int] | None:
    """``val:<base64 key>!<power>`` -> (key, power); None for any other
    transaction (persistent_kvstore.go isValidatorTx / execValidatorTx)."""
    if not tx.startswith(VAL_TX_PREFIX):
        return None
    key, _, power = tx[len(VAL_TX_PREFIX):].partition(b"!")
    return base64.b64decode(key), int(power)


def apply_updates(validators, updates: list[tuple[bytes, int]]):
    """UpdateWithChangeSet over [(address, key, power)] -> the new set, in
    order. Refused, as the reference refuses them: two updates of one key, a
    negative power, the removal of a key the set does not hold, an empty
    set."""
    by_addr = {a: (a, k, p) for a, k, p in validators}
    seen = set()
    for key, power in updates:
        addr = address(key)
        if addr in seen:
            raise ChangeSetError(f"duplicate entry {addr.hex()} in changes")
        seen.add(addr)
        if power < 0:
            raise ChangeSetError("voting power can't be negative")
        if power == 0 and addr not in by_addr:
            raise ChangeSetError(f"failed to find validator {addr.hex()} "
                                 f"to remove")
    for key, power in updates:
        addr = address(key)
        if power == 0:
            del by_addr[addr]
        else:
            by_addr[addr] = (addr, key, power)
    if not by_addr:
        raise ChangeSetError("applying the validator changes would result "
                             "in empty set")
    return ordered(by_addr.values())


# --- the replay ------------------------------------------------------------------


def check_commit(chain_id: str, validators, commit: dict, height: int,
                 block_hash: bytes, verify: bool):
    """VerifyCommitLight of the commit for ``height`` under the set in force
    there -> (verdict, prefix): verdict is None or (kind, index), index the
    slot of a signature that does not verify; prefix the slots consulted."""
    if len(commit["slots"]) != len(validators):
        return ("commit_size", None), []
    if commit["height"] != height:
        return ("commit_height", None), []
    if commit["block_hash"] != block_hash:
        return ("commit_block_id", None), []
    # a commit's slot i is the validator at place i of the set, whoever the
    # slot says it is: the reference looks the key up by index
    flags = {validators[i][0]: s["flag"]
             for i, s in enumerate(commit["slots"]) if s["flag"] != ABSENT}
    place = {a: i for i, (a, _k, _p) in enumerate(validators)}
    prefix = [place[a] for a in light_prefix.light_prefix(
        [(a, p) for a, _k, p in validators], flags)]
    needed = sum(p for _a, _k, p in validators) * 2 // 3
    tallied = 0
    for i in prefix:
        if verify and not ed25519_ref.verify(
                validators[i][1], vote_sign_bytes(chain_id, commit, i),
                commit["slots"][i]["signature"]):
            return ("wrong_signature", i), prefix
        tallied += validators[i][2]
    if tallied <= needed:
        return ("not_enough_power", None), prefix
    return None, prefix


def replay(chain_id: str, genesis, raws: list[bytes], hashes: list[bytes],
           verify_at=(), delay: int = DELAY) -> dict:
    """Apply blocks 1..N-1 of ``raws`` (block N only carries the commit for
    N-1), each on the commit the next block carries for it.

    ``genesis``: [(key, power)]; ``hashes[k]``: the hash of ``raws[k]``;
    ``verify_at``: the heights whose light prefix is verified signature by
    signature. -> ``applied`` (heights, in order), ``refused`` (None, or
    (height, kind, index) of the first height refused; nothing above it is
    looked at), and what a node holds after the last applied height:
    ``validators`` / ``next_validators`` / ``last_validators`` as [(address,
    key, power)], ``app_hash``, ``last_height_validators_changed``,
    ``prefixes`` {height: the slots of its light prefix}, ``changes``
    (heights at which another set than the one before came into force),
    ``sets`` {height: the set in force}, ``set_hashes`` {height: its hash}."""
    blocks = [parse_block(r) for r in raws]
    first = ordered((address(k), k, p) for k, p in genesis)
    sets = {h: first for h in range(1, delay + 1)}     # height -> set in force
    out = {"applied": [], "refused": None, "prefixes": {}, "changes": [],
           "last_height_validators_changed": 1}
    delivered = 0
    app_hash = b""
    verify_at = set(verify_at)

    def refuse(height, kind, index=None):
        out["refused"] = (height, kind, index)

    hashed = {}     # a set that stands for many heights is one list: once

    def hash_at(height):
        validators = sets[height]
        if id(validators) not in hashed:
            hashed[id(validators)] = light_sync.validators_hash(validators)
        return hashed[id(validators)]

    for k in range(len(blocks) - 1):
        block, carrier = blocks[k], blocks[k + 1]
        h = k + 1
        sets.setdefault(h + 1, sets[h])     # only a delay below 2 leaves it open
        if block["height"] != h:
            refuse(h, "height")
            break
        if block["validators_hash"] != hash_at(h):
            refuse(h, "validators_hash")
            break
        if block["next_validators_hash"] != hash_at(h + 1):
            refuse(h, "next_validators_hash")
            break
        if block["app_hash"] != app_hash:
            refuse(h, "app_hash")
            break
        if carrier["last_commit"] is None:
            refuse(h, "no_commit")
            break
        verdict, prefix = check_commit(chain_id, sets[h],
                                       carrier["last_commit"], h, hashes[k],
                                       h in verify_at)
        if verdict is not None:
            refuse(h, *verdict)
            break
        out["prefixes"][h] = prefix
        # EndBlock of h: in force at h + delay, on top of the set before it
        updates = [u for u in map(parse_val_tx, block["txs"]) if u is not None]
        before = sets[h + delay - 1]
        sets[h + delay] = apply_updates(before, updates) if updates else before
        if updates:
            out["last_height_validators_changed"] = h + delay
        delivered += len(block["txs"])
        app_hash = struct.pack(">Q", delivered)
        out["applied"].append(h)
    last = out["applied"][-1] if out["applied"] else 0
    out["changes"] = [h for h in range(2, last + 1) if sets[h] != sets[h - 1]]
    out.update(validators=sets[last + 1],
               next_validators=sets.get(last + 2, sets[last + 1]),
               last_validators=sets[last] if last else [], app_hash=app_hash,
               sets=sets, set_hashes={h: hash_at(h) for h in sets})
    return out
