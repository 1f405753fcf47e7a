"""A whole commit of two key types, decided both ways, and the replay of a
static-set chain that carries such commits: written from the reference's
types/validator_set.go (VerifyCommit, VerifyCommitLight), types/validator.go
(SimpleValidator) and state/validation.go over plain Python. Nothing of the
program is imported.

  - ``decide(..., full=False)`` is VerifyCommitLight: the size, height and
    block id of the commit, then the +2/3 prefix by voting power
    (``light_prefix.py``: Absent and Nil slots skipped, the walk stops at the
    validator that brings the tally above 2/3), one signature at a time;
  - ``decide(..., full=True)`` is VerifyCommit, what ``validate_block`` calls
    on a block's LastCommit: the same three checks, then **every** slot that
    is not Absent in slot order, a vote for nil verified over the nil vote's
    sign bytes and left out of the tally, no early exit, and only then the
    tally against 2/3;
  - a signature is verified by its validator's key type: ``ed25519_ref.py``
    or ``sr25519_ref.py`` (schnorrkel over ristretto255, the signing context
    ``substrate``), over ``valset_replay.vote_sign_bytes`` of the commit's
    own bytes;
  - the verdict is None (accept) or (kind, slot): ``wrong_signature`` names
    the first slot, in the order the rule walks, whose signature does not
    verify.

``replay`` is ``block_replay.replay``'s walk for a set that never changes and
holds two key types: the body's rules are ``block_replay.py``'s own functions
as they stand (``data_hash``, the part set, the kvstore, ``app_hash``,
``last_results_hash``); what differs is the set's hash, whose SimpleValidator
names the key's type (``valset_replay`` knows ed25519 alone), and that a
height's LastCommit can be decided in full as well as its light prefix.
A signature of pure Python takes 3-8 ms, so ``replay`` verifies at the
heights it is told to and walks the others structurally (sizes, hashes,
flags, the tally).
"""

from __future__ import annotations

from benchmark.reference import (
    block_replay,
    ed25519_ref,
    light_prefix,
    sr25519_ref,
    valset_replay,
)
from benchmark.reference.light_sync import _varint, merkle_root
from benchmark.reference.valset_replay import ABSENT, COMMIT

VERIFY = {"ed25519": ed25519_ref.verify, "sr25519": sr25519_ref.verify}
# tendermint.crypto.PublicKey's oneof: the field a key type is written under
# (sr25519 = 3 is the program's extension: v0.34's codec refuses the type)
PUBLIC_KEY_FIELD = {"ed25519": 1, "sr25519": 3}


def simple_validator(kind: str, pub: bytes, power: int) -> bytes:
    """SimpleValidator{pub_key: PublicKey{<kind>}, voting_power}."""
    key = bytes([PUBLIC_KEY_FIELD[kind] << 3 | 2]) + _varint(len(pub)) + pub
    return b"\x0a" + _varint(len(key)) + key + b"\x10" + _varint(power)


def ordered(validators) -> list:
    """[(address, kind, key, power)] by power descending, then address."""
    return sorted(validators, key=lambda v: (-v[3], v[0]))


def validators_hash(validators) -> bytes:
    return merkle_root([simple_validator(kind, pub, power)
                        for _addr, kind, pub, power in ordered(validators)])


def decide(chain_id: str, validators, commit: dict, height: int,
           block_hash: bytes, full: bool, verify: bool = True,
           verified: set | None = None):
    """``validators``: [(address, kind, key, power)] in the set's order;
    ``commit``: ``valset_replay.parse_block(...)["last_commit"]``. -> (verdict,
    slots): verdict None or (kind, slot), slots the slots whose signatures
    the rule consults (all of them when it accepts). ``verify`` False walks
    the rule without verifying a signature. ``verified``: the slots of this
    same commit that an earlier call found good; they are not verified
    again, and the slots this call finds good are added (a chain's commit is
    decided twice: its prefix, then all of it)."""
    if len(commit["slots"]) != len(validators):
        return ("commit_size", None), []
    if commit["height"] != height:
        return ("commit_height", None), []
    if commit["block_hash"] != block_hash:
        return ("commit_block_id", None), []
    if full:
        slots = [i for i, s in enumerate(commit["slots"])
                 if s["flag"] != ABSENT]
    else:
        # a commit's slot i is the validator at place i of the set, whoever
        # the slot says it is: the reference looks the key up by index
        flags = {validators[i][0]: s["flag"]
                 for i, s in enumerate(commit["slots"]) if s["flag"] != ABSENT}
        place = {v[0]: i for i, v in enumerate(validators)}
        slots = [place[a] for a in light_prefix.light_prefix(
            [(v[0], v[3]) for v in validators], flags)]
    needed = sum(v[3] for v in validators) * 2 // 3
    tallied = 0
    for i in slots:
        _addr, kind, key, power = validators[i]
        if verify and (verified is None or i not in verified):
            if not VERIFY[kind](
                    key, valset_replay.vote_sign_bytes(chain_id, commit, i),
                    commit["slots"][i]["signature"]):
                return ("wrong_signature", i), slots
            if verified is not None:
                verified.add(i)
        if commit["slots"][i]["flag"] == COMMIT:
            tallied += power
    if tallied <= needed:
        return ("not_enough_power", None), slots
    return None, slots


def replay(chain_id: str, genesis, raws: list[bytes], hashes: list[bytes],
           light_at=(), full_at=()) -> dict:
    """Apply blocks 1..N-1 of ``raws`` (block N only carries the commit for
    N-1) as a syncing node does: height H on the light prefix of the commit
    block H+1 carries for it, then, inside the apply, block H's own
    LastCommit (the commit for H-1) in full. ``genesis``: [(kind, key,
    power)]; ``hashes[k]``: the hash of ``raws[k]``; ``light_at`` / ``full_at``:
    the heights whose commit is verified signature by signature, the one the
    light way, the other in full (the commit **for** that height, which block
    height + 1 carries).

    -> what ``block_replay.replay`` returns (``applied``, ``refused``,
    ``store``, ``app_hash``, ``last_results_hash``, ``headers``,
    ``part_set_headers``, ``txs``, ``prefixes``, ``validators``), and
    ``validators_hash``, ``full_slots`` {height: the slots its full check
    consults}. ``refused`` is (height, kind, slot): the height that is not
    applied; ``refused_by`` says which check it was (``light``: the commit for
    that height; ``full``: its LastCommit, so the slot is one of the commit
    for height - 1)."""
    blocks = [valset_replay.parse_block(r) for r in raws]
    validators = ordered((valset_replay.address(key), kind, key, power)
                         for kind, key, power in genesis)
    set_hash = validators_hash(validators)
    light_at, full_at = set(light_at), set(full_at)
    good = {}       # commit's height -> slots found good: verified once
    out = {"applied": [], "refused": None, "refused_by": None, "store": {},
           "delivered": 0, "app_hash": b"", "last_results_hash": b"",
           "headers": {}, "part_set_headers": {}, "txs": {}, "prefixes": {},
           "full_slots": {}, "validators": validators,
           "validators_hash": set_hash}

    def refuse(height, by, kind, slot=None):
        out["refused"], out["refused_by"] = (height, kind, slot), by

    for k, raw in enumerate(raws[:-1]):
        h = k + 1
        block, carrier = blocks[k], blocks[k + 1]
        body = block_replay.parse_body(raw)
        part_set = block_replay.part_set_header(raw)
        # the light check of the commit for h, as the pipeline makes it
        if block_replay.signed_part_set_header(raws[k + 1]) != part_set:
            refuse(h, "light", "commit_block_id")
            break
        if carrier["last_commit"] is None:
            refuse(h, "light", "no_commit")
            break
        verdict, prefix = decide(chain_id, validators, carrier["last_commit"],
                                 h, hashes[k], full=False, verify=h in light_at,
                                 verified=good.setdefault(h, set()))
        if verdict is not None:
            refuse(h, "light", *verdict)
            break
        out["prefixes"][h] = prefix
        # validate_block: the header against the state, then the LastCommit
        want = (block_replay.data_hash(body["txs"]), out["last_results_hash"],
                out["app_hash"])
        for name, mine in zip(("data_hash", "last_results_hash", "app_hash"),
                              want):
            if body[name] != mine:
                refuse(h, "header", name)
                break
        if out["refused"] is None and body["height"] != h:
            refuse(h, "header", "height")
        if out["refused"] is None and (
                block["validators_hash"] != set_hash
                or block["next_validators_hash"] != set_hash):
            refuse(h, "header", "validators_hash")
        if out["refused"] is None and h > 1:
            verdict, slots = decide(chain_id, validators, block["last_commit"],
                                    h - 1, hashes[k - 1], full=True,
                                    verify=h - 1 in full_at,
                                    verified=good.setdefault(h - 1, set()))
            if verdict is not None:
                refuse(h, "full", *verdict)
            out["full_slots"][h - 1] = slots
        if out["refused"] is not None:
            break
        results = [block_replay.deliver(out["store"], tx)
                   for tx in body["txs"]]
        out["delivered"] += len(results)
        out["app_hash"] = block_replay.app_hash(out["delivered"])
        out["last_results_hash"] = block_replay.results_hash(results)
        out["headers"][h] = want
        out["part_set_headers"][h] = part_set
        out["txs"][h] = len(results)
        out["applied"].append(h)
    return out
