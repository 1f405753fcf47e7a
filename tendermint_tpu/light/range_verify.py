"""Batched sequential header verification: BASELINE config 3, and the path
``light.Client`` takes in sequential mode.

The reference light client verifies a header chain one header at a time, each
`VerifyAdjacent` paying a serial loop of ed25519 verifies
(light/verifier.go:93 -> types/validator_set.go:719). On a TPU that is the
wrong shape: a hub-size commit's light prefix is 42-93 signatures, under the
host crossover, so a per-header client never reaches the device, while the
signatures of a whole catch-up are known as soon as its headers are fetched.

`verify_window` takes one bounded window of adjacent headers (the client
fetches at most ``window_slots()`` commit slots at a time, so memory follows
the window and not the range) and

 1. assembles every header's light prefix and queues the signatures on batch
    verifiers that leave as soon as they hold one kernel chunk
    (``ed25519_pallas.CHUNK`` lanes, counted in real prefix lengths), so the
    device works on chunk k while the host assembles chunk k+1;
 2. walks the chain linkage (`verifier.check_adjacent`: adjacency, trusting
    period, times, validator hashes) while the last chunks are in flight;
 3. dispatch by dispatch, in height order: waits for its bitmap, replays each
    of its headers' serial tally over its slice, and saves those that
    verified, while the device works on the dispatches behind it.

The verdict and the side effects are the per-header loop's
(`verify_adjacent` for each header in turn, saving it when it passed): the
FIRST failing height decides, whether its defect is structural or a
signature, and at that height the structural checks come first, as they do in
`verify_adjacent`; the exception is the one `verify_adjacent` raises; the
headers below it are saved and nothing at or above it. Device work on headers
above a failing height is speculative and thrown away. A window whose
signatures number fewer than `host_crossover()` is one flush on the host
route, as a single commit of that size would be.
"""

from __future__ import annotations

from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.light import verifier as lv
from tendermint_tpu.types.light_block import LightBlock
from tendermint_tpu.types.ttime import Time
from tendermint_tpu.types.validator_set import (
    ErrNotEnoughVotingPowerSigned,
    ErrWrongSignature,
)
from tendermint_tpu.utils import trace as _trace

# A window is as many light blocks as hold this many kernel chunks of commit
# slots: enough launches in flight to keep the device busy behind the host,
# few enough that a 10,000-validator chain holds six light blocks at a time.
WINDOW_CHUNKS = 16


def _chunk_lanes() -> int:
    from tendermint_tpu.ops import ed25519_pallas

    return ed25519_pallas.CHUNK


def window_slots() -> int:
    """Commit slots (validators x headers) one window may hold."""
    return WINDOW_CHUNKS * _chunk_lanes()


def tracer():
    """The thread's flight recorder when it is on, else None."""
    if _trace.ENABLED:
        tr = _trace.current()
        if tr.enabled:
            return tr
    return None


def span(tr, name: str, **tags):
    return _trace.NULL_SPAN if tr is None else tr.span(name, **tags)


def _invalid_header(cause: Exception) -> lv.ErrInvalidHeader:
    """What `verify_adjacent` makes of a commit its validator set refuses."""
    err = lv.ErrInvalidHeader(cause)
    err.__cause__ = cause
    return err


def _plan(chain: list[LightBlock]):
    """-> ([(light block, prefix, needed)], stop): each header's light
    prefix, up to the first header whose commit does not fit its validator
    set (``stop`` = (its place in the chain, the loop's exception))."""
    plan = []
    for i, lb in enumerate(chain):
        sh, vals = lb.signed_header, lb.validator_set
        commit = sh.commit
        bad = vals._commit_structural_error(commit.block_id, sh.height, commit)
        if bad is not None:
            return plan, (i, _invalid_header(bad))
        needed = vals.total_voting_power() * 2 // 3
        plan.append((lb, vals.commit_light_prefix(commit, needed), needed))
    return plan, None


def _dispatch(plan) -> list:
    """Queue every planned prefix and dispatch by the kernel's chunk.
    -> [(first planned header, one past the last, PendingVerify)]."""
    from tendermint_tpu.ops import ed25519_batch

    sigs = sum(len(prefix) for _lb, prefix, _needed in plan)
    use_device = sigs >= ed25519_batch.host_crossover()
    # Every chunk of a device-sized window is pinned to the device, the
    # sub-crossover tail too: on the host it would burn the caller's CPU
    # while a device flight is free. Each dispatch lands on the verify
    # service (crypto/verify_service.py), whose executor owns host prep,
    # the launch and the one batched readback.
    lanes = _chunk_lanes() if use_device else sigs
    pending = []
    verifier = crypto_batch.create_batch_verifier()
    first = 0
    for j, (lb, prefix, _needed) in enumerate(plan):
        if len(verifier) and len(verifier) + len(prefix) > lanes:
            pending.append((first, j, verifier.dispatch(force_device=use_device)))
            verifier = crypto_batch.create_batch_verifier()
            first = j
        sh = lb.signed_header
        lb.validator_set.add_commit_sigs(verifier, sh.header.chain_id,
                                         sh.commit, prefix, prefix)
    if first < len(plan):
        # left even when it holds nothing: a header without one vote for
        # its block still gets its tally, and falls short there
        pending.append((first, len(plan),
                        verifier.dispatch(force_device=use_device)))
    return pending


def _walk(trusted: LightBlock, chain: list[LightBlock], upto: int,
          trusting_period_s: float, now: Time, max_clock_drift_s: float):
    """The linkage walk over chain[:upto] -> (place, exception) of the first
    header `check_adjacent` refuses, or None."""
    prev = trusted
    for i in range(upto):
        lb = chain[i]
        try:
            lv.check_adjacent(prev.signed_header, lb.signed_header,
                              lb.validator_set, trusting_period_s, now,
                              max_clock_drift_s)
        except lv.LightClientError as e:
            return i, e
        prev = lb
    return None


def _tally(lb: LightBlock, prefix: list[int], needed: int, bits) -> Exception | None:
    """One header's serial decision over its slice of the bitmap: what
    `ValidatorSet.verify_commit_light` raises, or None."""
    vals, signatures = lb.validator_set.validators, lb.signed_header.commit.signatures
    tallied = 0
    for idx, ok in zip(prefix, bits):
        if not ok:
            return ErrWrongSignature(idx, signatures[idx].signature)
        tallied += vals[idx].voting_power
        if tallied > needed:
            return None
    return ErrNotEnoughVotingPowerSigned(tallied, needed)


def _replay(plan, first: int, end: int, bitmap):
    """The tally of planned headers first..end-1, in height order, over the
    bitmap of the dispatch that carried them -> (place, exception) of the
    first refused header, or None."""
    pos = 0
    for j in range(first, end):
        lb, prefix, needed = plan[j]
        bad = _tally(lb, prefix, needed, bitmap[pos:pos + len(prefix)])
        if bad is not None:
            return j, _invalid_header(bad)
        pos += len(prefix)
    return None


def verify_window(trusted: LightBlock, chain: list[LightBlock],
                  trusting_period_s: float, now: Time,
                  max_clock_drift_s: float = 10.0, save=None,
                  tr=None) -> tuple[int, Exception | None]:
    """Verify `chain` (ascending, adjacent heights) against `trusted`.

    -> (n, refusal): chain[:n] verified and each went to ``save`` in height
    order (it returns the bytes it wrote, or None for a header it left out:
    the ``light.store`` span's ``bytes`` and ``blocks``); ``refusal`` is
    None when n == len(chain), else the exception `verify_adjacent` raises
    for chain[n] after chain[n - 1]. Anything this function *raises* is a
    failure of the machinery, not a verdict."""
    if not chain:
        return 0, None
    # Hash every header in the window as one batched merkle forest before
    # the walk asks for them (types/block.py precompute_header_hashes).
    from tendermint_tpu.types.block import precompute_header_hashes

    precompute_header_hashes(
        [lb.signed_header.header for lb in chain
         if lb.signed_header and lb.signed_header.header])
    with span(tr, "light.range", decision=True, headers=len(chain)):
        with span(tr, "light.assemble"):
            plan, stop = _plan(chain)
            pending = _dispatch(plan)
        with span(tr, "light.structure"):
            # the header that stopped the plan is walked too: its own
            # structural checks come before its commit's
            upto = len(chain) if stop is None else stop[0] + 1
            stop = _walk(trusted, chain, upto, trusting_period_s, now,
                         max_clock_drift_s) or stop
        upto = len(chain) if stop is None else stop[0]
        # Dispatch by dispatch, in height order: wait for its bitmap, tally
        # its headers, save them. The store write is the slowest thing the
        # host does here, and it runs while the device works on the later
        # dispatches; those above a refused header are never waited for.
        for first, end, pv in pending:
            if first >= upto:
                break
            end = min(end, upto)
            with span(tr, "light.wait"):
                _all_ok, bitmap = pv.resolve()
            with span(tr, "light.replay"):
                refused = _replay(plan, first, end, bitmap)
            if refused is not None:
                stop, upto = refused, refused[0]
                end = upto
            with span(tr, "light.store"):
                if save is not None:
                    sizes = [save(lb) for lb in chain[first:end]]
                    if tr is not None:
                        sizes = [n for n in sizes if n is not None]
                        tr.annotate(blocks=len(sizes), bytes=sum(sizes))
        if tr is not None:
            tr.annotate(sigs=sum(len(p) for _lb, p, _n in plan),
                        chunks=len(pending), verified=upto)
    return upto, None if stop is None else stop[1]


def verify_header_range(trusted: LightBlock, chain: list[LightBlock],
                        trusting_period_s: float, now: Time,
                        max_clock_drift_s: float = 10.0,
                        store=None) -> None:
    """`verify_window` for a caller that holds the whole range: raises what
    the per-header loop would, after saving into `store` (when given) the
    headers below the one it refused."""
    _n, refusal = verify_window(
        trusted, chain, trusting_period_s, now, max_clock_drift_s,
        save=None if store is None else store.save_light_block, tr=tracer())
    if refusal is not None:
        raise refusal
