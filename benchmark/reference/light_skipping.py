"""The reference light client's skipping rule over plain records: nothing of
the program is imported.

Written from three pieces of the reference (v0.34), as recalled on a machine
without its tree or a network, so each is cited by function and not quoted:

  - ``verifySkipping`` (light/client.go:706): a cache of fetched blocks, the
    target first; an attempt the trusted set cannot vouch for
    (``ErrNewValSetCantBeTrusted``) moves one place down the cache, and at
    its end a pivot is fetched ``verifySkippingNumerator / Denominator`` =
    9/16 of the way from the verified block to the last one refused; an
    attempt that verifies makes its block the verified one, cuts the cache
    above it and starts again from the target;
  - ``VerifyNonAdjacent`` (light/verifier.go:32-90) and, for a hop of one
    height, ``VerifyAdjacent`` (:93-135), with ``verifyNewHeaderAndVals``;
  - ``VerifyCommitLightTrusting`` (types/validator_set.go:772-830): a signer
    is looked up **by address in the trusted set**, one seen twice is a
    double vote, each found signer's signature is verified in the commit's
    order, and the loop stops above ``trust_level`` of the **trusted** set's
    power; and ``VerifyCommitLight`` (:719-766) by place in the new set
    (``light_prefix.py``).

Departures from the Go text, each deliberate:
  1. A light block is a record (below): a header's own hash and each vote's
     sign bytes are stated, not recomputed here. Who states them matters:
     this cell's generator (``drivers/rotatingchain.py``) hashes its headers
     and signs its votes with ``reference/canonical.py``, the benchmark's
     own encoders, and its records carry those bytes, so nothing the
     program computed reaches this file. The hash of a validator set **is**
     computed here (``light_sync.validators_hash``).
  2. ``ValidateBasic`` of a fetched block is the three checks a record can
     fail: the commit's height, the commit's block hash, the supplied set's
     hash. It runs where ``lightBlockFromPrimary`` runs it: at the fetch.
  3. No provider is replaced: a fetch that fails ends the sync.
  4. What is stored: every block a hop verified, then the target. The
     recalled text saves the target (``updateTrustedLightBlock``) and hands
     the verified blocks to the detector as ``trace``; this benchmark's
     guarantee is "a header is stored only if it verified", which both
     satisfy, and the program under test stores the trace.
  5. ``verify`` is a parameter (default: ``ed25519_ref.verify``, one
     signature at a time). A caller with 160,000 signatures a sync passes a
     verifier that believes the generator's own bytes outside a seeded
     sample, as ``harness/correct.py`` does for the commits of other cells.
  6. Errors are kinds, not Go's error strings: ``(kind, index)``, ``index``
     the commit slot of a bad signature or of the second vote of a pair. A
     kind that Go wraps in ``ErrInvalidHeader`` (verifyNewHeaderAndVals,
     VerifyCommitLight) starts with ``invalid_header.``; the trusting
     check's own errors come back bare, as Go returns them.

A record is ``light_sync.py``'s dict, with the commit's slots in order:
  height, time_ns, hash, validators_hash, next_validators_hash   the header
  commit_height, commit_block_hash                                its commit
  validators: [(address, ed25519 public key, voting power)]       supplied set
  slots: [None | (address, flag, sign bytes, signature)]          None: Absent
"""

from __future__ import annotations

from benchmark.reference import ed25519_ref, light_prefix
from benchmark.reference.light_sync import validators_hash

NUMERATOR, DENOMINATOR = 9, 16          # verifySkippingNumerator / Denominator
CANT_BE_TRUSTED = "cant_be_trusted"     # ErrNewValSetCantBeTrusted: bisect
INVALID_HEADER = "invalid_header."      # ErrInvalidHeader{...}: what the new
#                                         header or its own set's check refuses
COMMIT = light_prefix.BLOCK_ID_FLAG_COMMIT


def _ordered(validators):
    """The set in its canonical order: power descending, then address."""
    return sorted(validators, key=lambda v: (-v[2], v[0]))


def validate_basic(lb: dict):
    """LightBlock.ValidateBasic as far as a record can fail it -> kind or
    None."""
    if lb["commit_height"] != lb["height"]:
        return "commit_height"
    if lb["commit_block_hash"] != lb["hash"]:
        return "commit_block_id"
    if lb["validators_hash"] != validators_hash(lb["validators"]):
        return "validators_hash_supplied"
    return None


def verify_commit_light_trusting(trusted_vals, slots, trust_level, verify):
    """VerifyCommitLightTrusting -> None (accepted) or (kind, index)."""
    num, den = trust_level
    ordered = _ordered(trusted_vals)
    place = {}
    for i, (addr, _pub, _power) in enumerate(ordered):
        place.setdefault(addr, i)       # GetByAddress: the first that matches
    needed = sum(power for _a, _p, power in ordered) * num // den
    seen: dict[int, int] = {}
    tallied = 0
    for idx, slot in enumerate(slots):
        if slot is None or slot[1] != COMMIT:
            continue
        addr, _flag, msg, sig = slot
        val_idx = place.get(addr)
        if val_idx is None:
            continue
        if val_idx in seen:
            return "double_vote", idx
        seen[val_idx] = idx
        _addr, pub, power = ordered[val_idx]
        if not verify(pub, msg, sig):
            return "wrong_signature", idx
        tallied += power
        if tallied > needed:
            return None
    return CANT_BE_TRUSTED, None


def verify_commit_light(vals, slots, verify):
    """VerifyCommitLight by place in ``vals`` -> None or (kind, index)."""
    ordered = _ordered(vals)
    if len(slots) != len(ordered):
        return "commit_size", None
    # a slot's place decides whose key checks it, whatever address it states
    flags = {ordered[i][0]: slot[1]
             for i, slot in enumerate(slots) if slot is not None}
    place = {addr: i for i, (addr, _pub, _power) in enumerate(ordered)}
    needed = sum(power for _a, _p, power in ordered) * 2 // 3
    tallied = 0
    for addr in light_prefix.light_prefix(
            [(a, power) for a, _pub, power in ordered], flags):
        idx = place[addr]
        _a, pub, power = ordered[idx]
        _claimed, _flag, msg, sig = slots[idx]
        if not verify(pub, msg, sig):
            return "wrong_signature", idx
        tallied += power
    if tallied <= needed:
        return "not_enough_power", None
    return None


def _new_header_and_vals(trusted: dict, new: dict, now_ns: int, drift_ns: int):
    """verifyNewHeaderAndVals -> kind or None."""
    bad = validate_basic(new)           # untrustedHeader.ValidateBasic and
    if bad in ("commit_height", "commit_block_id"):   # the supplied set, last
        return bad
    if new["height"] <= trusted["height"]:
        return "height_not_above_trusted"
    if new["time_ns"] <= trusted["time_ns"]:
        return "time_not_after_trusted"
    if new["time_ns"] >= now_ns + drift_ns:
        return "time_from_future"
    return bad


def verify(trusted: dict, new: dict, trusting_period_ns: int, now_ns: int,
           drift_ns: int, trust_level, verify_sig):
    """light.Verify: VerifyAdjacent for the next height, VerifyNonAdjacent
    otherwise -> None or (kind, index)."""
    adjacent = new["height"] == trusted["height"] + 1
    if trusted["time_ns"] + trusting_period_ns <= now_ns:
        return "trusted_header_expired", None
    bad = _new_header_and_vals(trusted, new, now_ns, drift_ns)
    if bad is not None:
        return INVALID_HEADER + bad, None
    if adjacent:
        if new["validators_hash"] != trusted["next_validators_hash"]:
            return "validators_hash_chain", None
    else:
        refused = verify_commit_light_trusting(
            trusted["validators"], new["slots"], trust_level, verify_sig)
        if refused is not None:
            return refused
    refused = verify_commit_light(new["validators"], new["slots"], verify_sig)
    return refused and (INVALID_HEADER + refused[0], refused[1])


def sync(trusted: dict, target: int, fetch, trusting_period_ns: int,
         now_ns: int, drift_ns: int, trust_level=(1, 3),
         verify_sig=ed25519_ref.verify):
    """VerifyLightBlockAtHeight(target) from ``trusted`` in skipping mode.
    ``fetch(height) -> record`` is the primary.

    -> (attempts, fetched, stored, refusal)
      attempts  [(from, to, None | (kind, index))] in order; None: verified
      fetched   the heights asked of the primary, in order
      stored    the heights a client holds afterwards, ``trusted`` first
      refusal   None, or (height, kind, index) of what ended the sync
    """
    attempts, fetched, stored = [], [], [trusted["height"]]

    def fetch_checked(height: int):
        fetched.append(height)
        lb = fetch(height)
        return lb, validate_basic(lb)

    new, bad = fetch_checked(target)
    if bad is not None:
        return attempts, fetched, stored, (target, bad, None)
    cache, depth, verified = [new], 0, trusted
    while True:
        candidate = cache[depth]
        verdict = verify(verified, candidate, trusting_period_ns, now_ns,
                         drift_ns, trust_level, verify_sig)
        attempts.append((verified["height"], candidate["height"], verdict))
        if verdict is None:
            stored.append(candidate["height"])
            if depth == 0:
                return attempts, fetched, stored, None
            verified, cache, depth = candidate, cache[:depth], 0
        elif verdict[0] == CANT_BE_TRUSTED:
            if depth == len(cache) - 1:
                pivot = verified["height"] + (
                    (candidate["height"] - verified["height"])
                    * NUMERATOR // DENOMINATOR)
                inter, bad = fetch_checked(pivot)
                if bad is not None:
                    return attempts, fetched, stored, (pivot, bad, None)
                cache.append(inter)
            depth += 1
        else:
            return attempts, fetched, stored, (candidate["height"],) + verdict
