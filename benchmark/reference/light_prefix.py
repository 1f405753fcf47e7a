"""Which signatures VerifyCommitLight consults (reference:
types/validator_set.go, VerifyCommitLight): validators by voting power
descending, then by address; a slot flagged Absent or Nil is skipped; the walk
stops at the first validator that brings the tally above 2/3 of the set's
total power. Plain Python over addresses, powers and flags: nothing of the
program is imported."""

from __future__ import annotations

BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL = 1, 2, 3


def light_prefix(validators: list[tuple[bytes, int]],
                 flags: dict[bytes, int]) -> list[bytes]:
    """``validators``: (address, voting power) of the whole set, any order.
    ``flags``: address -> BlockIDFlag of every slot of the commit that is not
    Absent. -> the addresses whose signatures are verified, in order."""
    needed = sum(power for _addr, power in validators) * 2 // 3
    prefix: list[bytes] = []
    tallied = 0
    for addr, power in sorted(validators, key=lambda v: (-v[1], v[0])):
        if flags.get(addr) != BLOCK_ID_FLAG_COMMIT:
            continue
        prefix.append(addr)
        tallied += power
        if tallied > needed:
            break
    return prefix
