"""The reference's vote tally over a delivery stream, written straight from
types/vote_set.go (addVote, addVerifiedVote), consensus/types/height_vote_set.go
and the late-precommit rule of consensus/state.go addVote, over plain records:
nothing of the program is imported.

One delivery at a time, in the order given: index and address against the
validator set, the copy check, the signature (``ed25519_ref.py``, pure
Python), the conflict check, the power tally per block, the delivery at which
more than 2/3 of the power has precommitted one block, and the commit a node
has seen at that moment. Every height is decided in round 0; a vote of
another round is outside what this tally models and raises.

What a record states and this file takes as given: each vote's sign bytes
(the canonical encoding is the program's type, which every cell shares), a
block's own hash, and the two things about the node's clock that no stream
of votes determines: that the block of a height arrived whole before its
votes, and the place in the stream at which the node's NewHeight timeout
fired (a precommit of the height just committed is counted into the last
commit while the node waits in NewHeight and ignored once the next round
has begun; consensus/state.go:1998-2020).

Where this tally is stricter than the Go text: for a second signature over a
vote already held, vote_set.go returns ErrVoteNonDeterministicSignature
without looking at the signature. Here the signature is verified first: a
copy that a relay corrupted is ``invalid`` whether it arrives before the good
copy or after it, so that the peer who delivered it can be sanctioned. Only a
second signature that verifies is ``rejected`` as non-deterministic.

A stream item is a dict with ``kind``:
  vote     peer, type (1 prevote, 2 precommit), height, round, block (the
           block hash voted for, b"" for nil), index, address, sign_bytes,
           signature, check (verify the signature here; False: the generator
           left the lane alone, it is valid by construction)
  block    height: the proposal's block is whole from here on
  timeout  height: the node's NewHeight timeout of that height was handled
"""

from __future__ import annotations

import struct

from benchmark.reference import ed25519_ref

PREVOTE, PRECOMMIT = 1, 2
NIL = b""

COUNTED = "counted"          # added to the tally
DUPLICATE = "duplicate"      # a copy of a vote already held: no error
INVALID = "invalid"          # the signature does not verify: deliverer sanctioned
CONFLICT = "conflict"        # a second vote for another block: evidence, not added
REJECTED = "rejected"        # index, address or a second valid signature: an error
IGNORED = "ignored"          # not of the live height, nor a late precommit in time


class _VoteSet:
    """types/vote_set.go VoteSet for one (height, round 0, type)."""

    def __init__(self, validators: list, height: int, type_: int):
        self.validators = validators        # [(address, public key, power)]
        self.height, self.type = height, type_
        self.quorum = sum(p for _a, _k, p in validators) * 2 // 3 + 1
        self.votes: list = [None] * len(validators)   # main slot: (block, sig)
        self.by_block: dict = {}            # block -> {"sigs": {index: sig}, "sum"}
        self.maj23 = None

    def _existing(self, index: int, block: bytes):
        main = self.votes[index]
        if main is not None and main[0] == block:
            return main[1]
        tracked = self.by_block.get(block)
        return None if tracked is None else tracked["sigs"].get(index)

    def add(self, d: dict) -> str:
        index, address = d["index"], d["address"]
        if index < 0 or not address:
            return REJECTED
        if d["round"] != 0:
            raise ValueError("a vote of another round than 0: outside this tally")
        if not index < len(self.validators):
            return REJECTED
        val_address, pub, power = self.validators[index]
        if val_address != address:
            return REJECTED
        block, sig = d["block"], d["signature"]
        held = self._existing(index, block)
        if held is not None and held == sig:
            return DUPLICATE
        if d["check"] and not ed25519_ref.verify(pub, d["sign_bytes"], sig):
            return INVALID
        if held is not None:
            return REJECTED             # a second signature, and it verifies
        # addVerifiedVote
        main = self.votes[index]
        if main is not None:
            # a vote for another block: no peer has claimed a majority for
            # any block in this traffic, so it is never added
            return CONFLICT
        self.votes[index] = (block, sig)
        tracked = self.by_block.setdefault(block, {"sigs": {}, "sum": 0})
        tracked["sigs"][index] = sig
        tracked["sum"] += power
        if self.maj23 is None and tracked["sum"] >= self.quorum:
            self.maj23 = block
        return COUNTED

    def signers(self) -> list[int]:
        """Slots of the commit MakeCommit builds now: the votes for the
        majority block and for nil."""
        return [i for i, v in enumerate(self.votes)
                if v is not None and v[0] in (self.maj23, NIL)]


def tally(validators: list, stream: list, first_height: int = 1) -> dict:
    """-> {"verdicts": one per vote item, in order;
           "counted": [(type, height, index, signature)] in counting order;
           "invalid_by_peer": {peer: n}; "conflicts": [(type, height, index)];
           "commits": [{"height", "block", "tipped_at" (vote item number),
                        "signers"}]}"""
    height, in_new_height = first_height, True
    sets: dict = {}
    last_commit = None
    whole: set = set()
    out = {"verdicts": [], "counted": [], "invalid_by_peer": {},
           "conflicts": [], "commits": []}
    n = -1
    for item in stream:
        kind = item["kind"]
        if kind == "block":
            whole.add(item["height"])
            continue
        if kind == "timeout":
            if item["height"] == height:
                in_new_height = False
            continue
        n += 1
        d = item
        vote_set = None
        if d["height"] + 1 == height and d["type"] == PRECOMMIT:
            if in_new_height and last_commit is not None:
                vote_set = last_commit
        elif d["height"] == height:
            key = (height, d["type"])
            if key not in sets:
                sets[key] = _VoteSet(validators, height, d["type"])
            vote_set = sets[key]
        verdict = IGNORED if vote_set is None else vote_set.add(d)
        out["verdicts"].append(verdict)
        if verdict == COUNTED:
            out["counted"].append((d["type"], d["height"], d["index"],
                                   d["signature"]))
        elif verdict == INVALID:
            by = out["invalid_by_peer"]
            by[d["peer"]] = by.get(d["peer"], 0) + 1
        elif verdict == CONFLICT:
            out["conflicts"].append((d["type"], d["height"], d["index"]))
        if (verdict == COUNTED and d["type"] == PRECOMMIT
                and d["height"] == height and vote_set.maj23 not in (None, NIL)):
            if height not in whole:
                raise ValueError(f"height {height}: +2/3 precommits before the "
                                 f"block was whole: outside this tally")
            out["commits"].append({"height": height, "block": vote_set.maj23,
                                   "tipped_at": n,
                                   "signers": vote_set.signers()})
            last_commit = vote_set
            height += 1
            in_new_height = True
    return out


def kvstore_app_hash(txs_so_far: int) -> bytes:
    """abci/example/kvstore: the app hash is the number of transactions
    delivered so far, eight bytes big-endian."""
    return struct.pack(">Q", txs_so_far)


def replay(blocks: list) -> tuple[int, bytes]:
    """One block at a time, in height order, from genesis. A block record:
    height, hash, last_block_hash, app_hash (the header's: the state after
    the block before it), txs (count). -> (height reached, app hash after
    it); raises ValueError where the chain does not link."""
    height, last_hash, txs = 0, b"", 0
    app_hash = b""                      # before the first commit: genesis's
    for b in blocks:
        if b["height"] != height + 1:
            raise ValueError(f"block {b['height']} after height {height}")
        if b["last_block_hash"] != last_hash:
            raise ValueError(f"block {b['height']} does not follow {height}")
        if b["app_hash"] != app_hash:
            raise ValueError(f"block {b['height']} carries another app hash "
                             f"than the replay has after {height}")
        txs += b["txs"]
        app_hash = kvstore_app_hash(txs)
        height, last_hash = b["height"], b["hash"]
    return height, app_hash
