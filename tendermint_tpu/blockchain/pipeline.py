"""Verify-ahead: the cross-decision commit-verify pipeline for fast sync.

Every verify decision pays one host<->device round trip (the sync floor)
whatever its size. The serial fast-sync loop (blockchain/reactor.py
`_try_sync`, v1.py `try_process_block`) pays it once per block, serialized
with block save/apply.

This module lifts the chunk-level pipelining of ops/ed25519_pallas
(dispatch_items_pipelined, _start_host_copy) to DECISION granularity:

  * up to depth-K blocks' commit verifications are dispatched
    (`ValidatorSet.verify_commit_light_async`) while block h is being
    saved/applied;
  * readbacks of every in-flight decision are batched into ONE
    `jax.device_get` (crypto_batch.prefetch), so K decisions pay one sync
    floor instead of K;
  * decisions RESOLVE strictly in height order, and each resolve replays
    the exact serial accept/reject procedure — accept/reject and error
    attribution are byte-identical to the serial loop.

Failure semantics (identical to the serial path): a failed resolve at
height h discards ALL speculative in-flight work, redoes the requests for
h and h+1, and punishes the two sending peers — exactly what the serial
loop does at the same height with the same pool contents. Speculation is
also discarded whenever dispatch-time inputs went stale: the pool's blocks
at the entry's heights changed (peer churn, redo), or the validator set
hash changed after an apply (validator-set updates mid-sync). Discarded
work is re-dispatched against current reality, so the DECISIONS can never
drift from serial — only wasted device cycles are at stake.

Fault sites are preserved inside the pipeline: each speculative dispatch
still passes through `faults.fire("ops.ed25519.device")` (and the sr25519
twin) inside ops dispatch_batch, behind the circuit breaker
(ops/breaker.py) — an injected or real device failure degrades that
dispatch to the host path within the same call and the pipeline's
decisions are unchanged.

`TM_TPU_VERIFY_AHEAD` sets the depth (default 4; 1 = serial behavior,
one decision dispatched and resolved at a time). See docs/PIPELINE.md.

Device-bound speculative dispatches also ride the continuous-batching
verify service (crypto/verify_service.py): the depth-K burst issued by `_fill`
coalesces into shared kernel launches with whatever else is verifying
concurrently (the consensus drain, light range chunks, other fabric
nodes), and the service's executor owns the batched readback — `prefetch`
below then simply waits on the already-coalesced results instead of
issuing its own fetch.
"""

from __future__ import annotations

import os
import threading
from collections import deque
from dataclasses import dataclass

from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.part_set import PartSet
from tendermint_tpu.types.validator_set import PendingCommitVerify
from tendermint_tpu.utils import trace as _trace

DEFAULT_DEPTH = 4
# a traced sync marks what every thread got this often (fastsync.thread_cpu)
CENSUS_EVERY = 10


def verify_ahead_depth() -> int:
    """How many blocks' commit verifications may be in flight while earlier
    blocks save/apply. TM_TPU_VERIFY_AHEAD overrides; read per call so tests
    and operators can flip it without restarting the sync."""
    v = os.environ.get("TM_TPU_VERIFY_AHEAD")
    if not v:
        return DEFAULT_DEPTH
    try:
        return max(1, int(v))
    except ValueError:
        return DEFAULT_DEPTH


@dataclass
class _Entry:
    """One speculative decision: block `first` at `height`, verified by
    `second`'s LastCommit, dispatched against the validator set whose hash
    was `vals_hash`."""

    height: int
    first: object
    second: object
    first_parts: object
    first_id: BlockID
    pending: PendingCommitVerify
    vals_hash: bytes


class VerifyAheadPipeline:
    """Bounded depth-K speculative commit-verify queue over a BlockPool.

    The reactor surface it drives (shared by v0 and v1): `.pool`, `.state`
    (read AND reassigned after apply), `.block_store`, `.block_exec`, and
    `._punish_invalid(height, exc)` implementing the reactor's existing
    invalid-block path (redo h and h+1, punish both senders)."""

    def __init__(self) -> None:
        self._entries: deque[_Entry] = deque()
        # speculative dispatches issued, and those of them thrown away
        # unresolved: dispatched - discarded - len(self) decisions resolved
        self.dispatched = 0
        self.discarded = 0
        # heights applied, and the census of threads a traced sync reads
        # every CENSUS_EVERY of them (None while tracing is off)
        self.applied = 0
        self._census = None
        self._census_from = 0

    def __len__(self) -> int:
        return len(self._entries)

    def discard(self, reason: str = "pool") -> None:
        """Drop all speculative in-flight work. Already-issued device work
        is simply never fetched. `reason` names why, on the
        fastsync.discard mark: "valset" (the validator set changed under
        the entries), "pool" (the pool's blocks changed, or the sync was
        re-armed), "error" (the head's commit was invalid)."""
        if self._entries:
            self.discarded += len(self._entries)
            if _trace.ENABLED:
                _trace.current().mark("fastsync.discard",
                                      entries=len(self._entries),
                                      reason=reason,
                                      height=self._entries[0].height)
        self._entries.clear()

    # --- dispatch ----------------------------------------------------------

    def _force_device(self, reactor) -> bool:
        """Pin speculative dispatches to the device kernel when pipelining
        on a real accelerator. The calibrated host crossover
        (ops/ed25519_batch.host_crossover) prices a FULL sync floor into
        every flush — right for one synchronous decision, wrong here: the
        pipeline's whole point is hiding that floor behind K decisions of
        host work (copy_to_host_async starts the D2H at dispatch), after
        which the kernel's marginal us/sig beats the host C verifier for
        any kernel-sized batch. On a CPU backend the "device" is this same
        host — no round trip to hide, kernel never pays off — and small
        commits (tests, dev nets) stay on the adaptive host/scalar path."""
        depth = verify_ahead_depth()
        if depth <= 1 or os.environ.get("TM_TPU_DISABLE_BATCH") == "1":
            return False
        try:
            import jax

            from tendermint_tpu.ops import ed25519_batch
        except Exception:  # noqa: BLE001 - no jax, no kernels to pin
            return False
        if jax.default_backend() == "cpu":
            return False
        est_per = (2 * reactor.state.validators.size()) // 3 + 1
        return est_per >= ed25519_batch.MIN_BUCKET

    def _dispatch_entry(self, reactor, height: int) -> _Entry | None:
        pool = reactor.pool
        first = pool.peek_block(height)
        second = pool.peek_block(height + 1)
        if first is None or second is None:
            return None
        state = reactor.state
        if _trace.ENABLED:
            tr = _trace.current()
            with tr.span("fastsync.part_set", height=height):
                first_parts = PartSet.from_data(first.marshal())
                tr.annotate(bytes=first_parts.byte_size,
                            parts=first_parts.count)
        else:
            first_parts = PartSet.from_data(first.marshal())
        first_id = BlockID(hash=first.hash(), part_set_header=first_parts.header())
        try:
            # same pre-checks, in the same order, as the serial loop
            if second.last_commit is None:
                raise ValueError("second block has no LastCommit")
            if second.last_commit.block_id != first_id:
                raise ValueError("second block's LastCommit is for a different block")
            tr = _trace.current()
            if tr.enabled:
                # the dispatch span's height is inherited by the crypto
                # layer's host_prep/queue/readback phases (utils/trace.py)
                with tr.span("fastsync.dispatch", height=height):
                    pending = state.validators.verify_commit_light_async(
                        state.chain_id, first_id, first.header.height,
                        second.last_commit,
                        force_device=self._force_device(reactor))
            else:
                pending = state.validators.verify_commit_light_async(
                    state.chain_id, first_id, first.header.height,
                    second.last_commit,
                    force_device=self._force_device(reactor))
        except Exception as e:  # noqa: BLE001 - decided at resolve time, in order
            pending = PendingCommitVerify(error=e)
        return _Entry(height=height, first=first, second=second,
                      first_parts=first_parts, first_id=first_id,
                      pending=pending, vals_hash=state.validators.hash())

    def _fill(self, reactor) -> None:
        depth = verify_ahead_depth()
        pool = reactor.pool
        want = pool.height + len(self._entries)
        while len(self._entries) < depth:
            e = self._dispatch_entry(reactor, want)
            if e is None:
                return
            self._entries.append(e)
            self.dispatched += 1
            want += 1

    # --- the one step both reactors call -----------------------------------

    def process_next(self, reactor) -> bool:
        """Verify + apply the next contiguous block through the pipeline.
        Returns True when a block was applied (call again to drain), False
        when the next block isn't ready or its commit was invalid (peers
        already punished, exactly as the serial path)."""
        tracer = getattr(reactor, "tracer", None)
        if tracer is not None and tracer.enabled:
            # spans from this step (speculative dispatches, the batched
            # readback, the apply) land in the syncing node's recorder
            with tracer.activate():
                return self._process_next(reactor)
        return self._process_next(reactor)

    def _mark_census(self, reactor, height: int | None) -> None:
        """A traced sync's census of the process's threads: one
        fastsync.thread_cpu mark every CENSUS_EVERY heights applied, counted
        from the baseline the pipeline's first traced step read (``height``
        None). Once in ten heights and never per transaction: the thread
        clock is a system call a thread. A reactor with connections writes
        what they moved meanwhile beside it (p2p.wire)."""
        got = self._census.read()
        if got is not None and height is not None:
            _trace.current().mark(
                "fastsync.thread_cpu", height=height,
                sync_thread=threading.current_thread().name, **got)
        mark_wire = getattr(reactor, "mark_wire", None)
        if mark_wire is not None:
            mark_wire(height)

    def _process_next(self, reactor) -> bool:
        pool = reactor.pool
        if _trace.ENABLED and self._census is None:
            self._census = _trace.ThreadCensus()
            self._mark_census(reactor, None)    # the baseline: writes no mark
            self._census_from = self.applied
        for _ in range(2):
            self._fill(reactor)
            if not self._entries:
                return False
            head = self._entries[0]
            # Re-validate dispatch-time inputs against current reality; the
            # serial loop peeks at process time, so stale speculation must
            # be re-dispatched, never resolved.
            first, second = pool.peek_two_blocks()
            if (head.height != pool.height
                    or first is not head.first or second is not head.second):
                self.discard("pool")
                continue
            if head.vals_hash != reactor.state.validators.hash():
                self.discard("valset")
                continue
            break
        else:
            return False

        # Batch the readbacks of every in-flight decision into ONE
        # device_get: K floors -> 1. Entries already resolved (or
        # host-resolved) are untouched; later resolves are then instant.
        head = self._entries.popleft()
        try:
            # the head's wait, as the syncing node feels it: the batched
            # fetch of everything in flight, then the head's own replay
            with (_trace.current().span("fastsync.head_wait",
                                        height=head.height)
                  if _trace.ENABLED else _trace.NULL_SPAN):
                if head.pending.pending is not None and head.pending.pending.has_device_output():
                    crypto_batch.prefetch(
                        [e.pending.pending for e in [head, *self._entries]
                         if e.pending.pending is not None])
                head.pending.resolve()
        except Exception as e:  # noqa: BLE001 - the serial invalid-block path
            self.discard("error")
            reactor._punish_invalid(head.height, e)
            return False
        pool.pop_request()
        # Commit→apply overlap (docs/EXECUTION.md), both directions:
        # (a) with h popped, h+1 is the new pool head — top the
        #     speculative window up NOW so h+1's commit verification is
        #     in flight on-device while h saves/applies below (validator
        #     churn in this apply is caught by the next iteration's
        #     stale-input check and re-dispatched);
        # (b) dispatch h's own LastCommit re-verification (apply_block's
        #     internal validate) so it rides under the block-store save.
        self._fill(reactor)
        # duck-typed executors (headless replay / test stubs) don't
        # speculate and keep their plain apply_block signature
        dispatch = getattr(reactor.block_exec, "dispatch_commit_verify", None)
        commit_pending = dispatch(reactor.state, head.first) if dispatch else None
        with _trace.current().span("fastsync.apply", height=head.height):
            reactor.block_store.save_block(head.first, head.first_parts,
                                           head.second.last_commit)
            if dispatch is not None:
                reactor.state, _ = reactor.block_exec.apply_block(
                    reactor.state, head.first_id, head.first,
                    commit_pending=commit_pending)
            else:
                reactor.state, _ = reactor.block_exec.apply_block(
                    reactor.state, head.first_id, head.first)
        self.applied += 1
        if (self._census is not None and _trace.ENABLED
                and (self.applied - self._census_from) % CENSUS_EVERY == 0):
            self._mark_census(reactor, head.height)
        return True
