"""Pure light-client verification functions (reference: light/verifier.go).

Core semantics preserved exactly:
 - VerifyAdjacent (light/verifier.go:93): trust chained through
   NextValidatorsHash equality + 2/3 of the new set signing.
 - VerifyNonAdjacent (light/verifier.go:32): trustLevel (default 1/3) of the
   TRUSTED set must have signed the new header, then 2/3 of the new set.
 - VerifyBackwards (light/verifier.go:218): hash-linked reverse walk.

TPU angle: both commit checks funnel into the batched BatchVerifier used by
ValidatorSet.verify_commit_light / verify_commit_light_trusting, so one
header verification is at most two kernel flushes. A sequential client does
not call verify_adjacent header by header: light.range_verify runs
check_adjacent (everything below but the signatures) per header and verifies
the signatures of a whole window of headers in a few wide launches, with
this file's verdict. verify_adjacent stays as the rule that path is held to.
"""

from __future__ import annotations

from tendermint_tpu.types.light_block import SignedHeader
from tendermint_tpu.types.ttime import Time
from tendermint_tpu.types.validator_set import (
    ErrNotEnoughVotingPowerSigned,
    ValidatorSet,
)
from tendermint_tpu.utils import trace as _trace

# New header can be trusted if at least one correct validator signed it
# (reference: light/verifier.go:16 DefaultTrustLevel).
DEFAULT_TRUST_LEVEL = (1, 3)


class LightClientError(Exception):
    pass


class ErrOldHeaderExpired(LightClientError):
    def __init__(self, at: Time, now: Time):
        self.at, self.now = at, now
        super().__init__(f"old header has expired at {at} (now: {now})")


class ErrInvalidHeader(LightClientError):
    def __init__(self, reason):
        self.reason = reason
        super().__init__(f"invalid header: {reason}")


class ErrNewValSetCantBeTrusted(LightClientError):
    def __init__(self, reason):
        self.reason = reason
        super().__init__(
            f"can't trust new val set: {reason}"
        )


def validate_trust_level(lvl: tuple[int, int]) -> None:
    """trustLevel must be within [1/3, 1] (reference: light/verifier.go:196)."""
    num, den = lvl
    if num * 3 < den or num > den or den == 0:
        raise LightClientError(f"trustLevel must be within [1/3, 1], given {num}/{den}")


def header_expired(h: SignedHeader, trusting_period_s: float, now: Time) -> bool:
    """reference: light/verifier.go:206-210."""
    expiration_ns = h.header.time.unix_ns() + int(trusting_period_s * 1e9)
    return expiration_ns <= now.unix_ns()


def _verify_new_header_and_vals(untrusted_header: SignedHeader,
                                untrusted_vals: ValidatorSet,
                                trusted_header: SignedHeader,
                                now: Time, max_clock_drift_s: float) -> None:
    """reference: light/verifier.go:153-193."""
    try:
        untrusted_header.validate_basic(trusted_header.header.chain_id)
    except ValueError as e:
        raise ErrInvalidHeader(f"untrustedHeader.ValidateBasic failed: {e}") from e
    if untrusted_header.height <= trusted_header.height:
        raise ErrInvalidHeader(
            f"expected new header height {untrusted_header.height} to be greater "
            f"than one of old header {trusted_header.height}"
        )
    if untrusted_header.header.time.unix_ns() <= trusted_header.header.time.unix_ns():
        raise ErrInvalidHeader(
            f"expected new header time {untrusted_header.header.time} to be "
            f"after old header time {trusted_header.header.time}"
        )
    if untrusted_header.header.time.unix_ns() >= now.unix_ns() + int(max_clock_drift_s * 1e9):
        raise ErrInvalidHeader(
            f"new header has a time from the future {untrusted_header.header.time} "
            f"(now: {now}; max clock drift: {max_clock_drift_s}s)"
        )
    vh = untrusted_vals.hash()
    if untrusted_header.header.validators_hash != vh:
        raise ErrInvalidHeader(
            f"expected new header validators ({untrusted_header.header.validators_hash.hex()}) "
            f"to match those that were supplied ({vh.hex()}) at height "
            f"{untrusted_header.height}"
        )


def check_adjacent(trusted_header: SignedHeader,
                   untrusted_header: SignedHeader,
                   untrusted_vals: ValidatorSet,
                   trusting_period_s: float, now: Time,
                   max_clock_drift_s: float) -> None:
    """Everything VerifyAdjacent checks before it looks at a signature
    (reference: light/verifier.go:93-126), in its order. Shared by
    verify_adjacent and light.range_verify, so the two cannot drift."""
    if untrusted_header.height != trusted_header.height + 1:
        raise LightClientError("headers must be adjacent in height")
    if header_expired(trusted_header, trusting_period_s, now):
        raise ErrOldHeaderExpired(
            Time.from_unix_ns(trusted_header.header.time.unix_ns()
                              + int(trusting_period_s * 1e9)), now)
    _verify_new_header_and_vals(untrusted_header, untrusted_vals,
                                trusted_header, now, max_clock_drift_s)
    if untrusted_header.header.validators_hash != trusted_header.header.next_validators_hash:
        raise LightClientError(
            f"expected old header next validators "
            f"({trusted_header.header.next_validators_hash.hex()}) to match those "
            f"from new header ({untrusted_header.header.validators_hash.hex()})"
        )


def verify_adjacent(trusted_header: SignedHeader,
                    untrusted_header: SignedHeader,
                    untrusted_vals: ValidatorSet,
                    trusting_period_s: float, now: Time,
                    max_clock_drift_s: float) -> None:
    """reference: light/verifier.go:93-135 VerifyAdjacent."""
    check_adjacent(trusted_header, untrusted_header, untrusted_vals,
                   trusting_period_s, now, max_clock_drift_s)
    try:
        untrusted_vals.verify_commit_light(
            trusted_header.header.chain_id, untrusted_header.commit.block_id,
            untrusted_header.height, untrusted_header.commit)
    except Exception as e:  # noqa: BLE001 - wrap like the reference
        raise ErrInvalidHeader(e) from e


def verify_non_adjacent(trusted_header: SignedHeader, trusted_vals: ValidatorSet,
                        untrusted_header: SignedHeader,
                        untrusted_vals: ValidatorSet,
                        trusting_period_s: float, now: Time,
                        max_clock_drift_s: float,
                        trust_level: tuple[int, int] = DEFAULT_TRUST_LEVEL) -> None:
    """reference: light/verifier.go:32-90 VerifyNonAdjacent."""
    if untrusted_header.height == trusted_header.height + 1:
        raise LightClientError("headers must be non adjacent in height")
    if header_expired(trusted_header, trusting_period_s, now):
        raise ErrOldHeaderExpired(
            Time.from_unix_ns(trusted_header.header.time.unix_ns()
                              + int(trusting_period_s * 1e9)), now)
    _verify_new_header_and_vals(untrusted_header, untrusted_vals,
                                trusted_header, now, max_clock_drift_s)
    # trustLevel (default 1/3) of the trusted validators must have signed.
    try:
        trusted_vals.verify_commit_light_trusting(
            trusted_header.header.chain_id, untrusted_header.commit, trust_level)
    except ErrNotEnoughVotingPowerSigned as e:
        raise ErrNewValSetCantBeTrusted(e) from e
    # 2/3 of the new validators must have signed. Kept last: untrustedVals
    # can be made large to DOS the light client (reference comment :69-72).
    # The two checks are two dependent decisions, one after the other
    # (light.skip.trusting, then light.skip.light).
    try:
        with (_trace.current().span("light.skip.light")
              if _trace.ENABLED else _trace.NULL_SPAN):
            untrusted_vals.verify_commit_light(
                trusted_header.header.chain_id,
                untrusted_header.commit.block_id,
                untrusted_header.height, untrusted_header.commit)
    except Exception as e:  # noqa: BLE001
        raise ErrInvalidHeader(e) from e


def verify(trusted_header: SignedHeader, trusted_vals: ValidatorSet,
           untrusted_header: SignedHeader, untrusted_vals: ValidatorSet,
           trusting_period_s: float, now: Time, max_clock_drift_s: float,
           trust_level: tuple[int, int] = DEFAULT_TRUST_LEVEL) -> None:
    """reference: light/verifier.go:137-151 Verify."""
    if untrusted_header.height != trusted_header.height + 1:
        verify_non_adjacent(trusted_header, trusted_vals, untrusted_header,
                            untrusted_vals, trusting_period_s, now,
                            max_clock_drift_s, trust_level)
    else:
        verify_adjacent(trusted_header, untrusted_header, untrusted_vals,
                        trusting_period_s, now, max_clock_drift_s)


def verify_backwards(untrusted_header, trusted_header) -> None:
    """Headers, not SignedHeaders (reference: light/verifier.go:218-244)."""
    try:
        untrusted_header.validate_basic()
    except ValueError as e:
        raise ErrInvalidHeader(e) from e
    if untrusted_header.chain_id != trusted_header.chain_id:
        raise ErrInvalidHeader("header belongs to another chain")
    if untrusted_header.time.unix_ns() >= trusted_header.time.unix_ns():
        raise ErrInvalidHeader(
            f"expected older header time {untrusted_header.time} to be before "
            f"new header time {trusted_header.time}"
        )
    if trusted_header.last_block_id.hash != untrusted_header.hash():
        raise ErrInvalidHeader(
            f"older header hash {untrusted_header.hash().hex()} does not match "
            f"trusted header's last block {trusted_header.last_block_id.hash.hex()}"
        )
