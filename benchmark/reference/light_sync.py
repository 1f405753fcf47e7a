"""The reference light client's sequential rule, written straight from
light/verifier.go:93-135 (VerifyAdjacent) and types/validator_set.go
VerifyCommitLight, over plain records: nothing of the program is imported.

For each header in height order: adjacency, the trusting period of the header
it follows, a time after that header's and within the clock drift, a
``validators_hash`` equal to the hash of the set that was supplied with it
and to the ``next_validators_hash`` of the header it follows, a commit for
this header (height and block hash), then the +2/3 prefix by voting power
(``light_prefix.py``) verified one signature at a time (``ed25519_ref.py``).

What a record states and this file takes as given: a header's own hash and
each vote's sign bytes. The canonical encodings behind them are the
program's types, which every cell shares; what is compared here is the rule
that decides which headers a light client trusts. The hash of a validator
set is computed here (RFC 6962 tree over SimpleValidator encodings), because
"the set that came with the header is the set the header names" is part of
that rule.

A record is a dict:
  height, time_ns, hash, validators_hash, next_validators_hash   the header
  commit_height, commit_block_hash, commit_slots                  its commit
  validators: [(address, ed25519 public key, voting power)]       supplied set
  votes: {address: (flag, sign bytes, signature)}                 slots not Absent
"""

from __future__ import annotations

import hashlib

from benchmark.reference import ed25519_ref, light_prefix

ACCEPTED = None


def _varint(n: int) -> bytes:
    out = bytearray()
    while n >= 0x80:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def simple_validator(pub: bytes, power: int) -> bytes:
    """SimpleValidator{pub_key: PublicKey{ed25519}, voting_power}
    (reference: types/validator.go:117-131)."""
    key = b"\x0a" + _varint(len(pub)) + pub
    return b"\x0a" + _varint(len(key)) + key + b"\x10" + _varint(power)


def merkle_root(leaves: list[bytes]) -> bytes:
    """crypto/merkle/tree.go HashFromByteSlices (RFC 6962: leaf prefix 0,
    inner prefix 1, split at the largest power of two below n)."""
    n = len(leaves)
    if n == 0:
        return hashlib.sha256(b"").digest()
    if n == 1:
        return hashlib.sha256(b"\x00" + leaves[0]).digest()
    k = 1 << ((n - 1).bit_length() - 1)
    return hashlib.sha256(b"\x01" + merkle_root(leaves[:k])
                          + merkle_root(leaves[k:])).digest()


def validators_hash(validators: list[tuple[bytes, bytes, int]]) -> bytes:
    """types/validator_set.go Hash: the set in its canonical order (voting
    power descending, then address)."""
    ordered = sorted(validators, key=lambda v: (-v[2], v[0]))
    return merkle_root([simple_validator(pub, power)
                        for _addr, pub, power in ordered])


def check_header(trusted: dict, new: dict, trusting_period_ns: int,
                 now_ns: int, max_clock_drift_ns: int):
    """One VerifyAdjacent -> ACCEPTED, or (kind, index): ``kind`` names the
    check that refused, ``index`` the validator's place in the set's
    canonical order for a bad signature, else None."""
    if new["height"] != trusted["height"] + 1:
        return "not_adjacent", None
    if trusted["time_ns"] + trusting_period_ns <= now_ns:
        return "trusted_header_expired", None
    # verifyNewHeaderAndVals: the commit is for this header ...
    if new["commit_height"] != new["height"]:
        return "commit_height", None
    if new["commit_block_hash"] != new["hash"]:
        return "commit_block_id", None
    if new["time_ns"] <= trusted["time_ns"]:
        return "time_not_after_trusted", None
    if new["time_ns"] >= now_ns + max_clock_drift_ns:
        return "time_from_future", None
    if new["validators_hash"] != validators_hash(new["validators"]):
        return "validators_hash_supplied", None
    if new["validators_hash"] != trusted["next_validators_hash"]:
        return "validators_hash_chain", None
    # VerifyCommitLight
    if new["commit_slots"] != len(new["validators"]):
        return "commit_size", None
    ordered = sorted(new["validators"], key=lambda v: (-v[2], v[0]))
    place = {addr: i for i, (addr, _pub, _power) in enumerate(ordered)}
    keys = {addr: pub for addr, pub, _power in ordered}
    needed = sum(power for _a, _p, power in ordered) * 2 // 3
    prefix = light_prefix.light_prefix(
        [(addr, power) for addr, _pub, power in ordered],
        {addr: flag for addr, (flag, _msg, _sig) in new["votes"].items()})
    tallied = 0
    for addr in prefix:
        _flag, msg, sig = new["votes"][addr]
        if not ed25519_ref.verify(keys[addr], msg, sig):
            return "wrong_signature", place[addr]
        tallied += ordered[place[addr]][2]
    if tallied <= needed:
        return "not_enough_power", None
    return ACCEPTED


def sync(trusted: dict, headers: list[dict], trusting_period_ns: int,
         now_ns: int, max_clock_drift_ns: int):
    """verifySequential (light/client.go:613) -> (heights accepted, refusal):
    refusal is None, or (height, kind, index) of the first header refused;
    nothing above it is looked at."""
    accepted = []
    for new in headers:
        verdict = check_header(trusted, new, trusting_period_ns, now_ns,
                               max_clock_drift_ns)
        if verdict is not ACCEPTED:
            return accepted, (new["height"],) + verdict
        accepted.append(new["height"])
        trusted = new
    return accepted, None
