"""BlockExecutor: validates blocks, drives the ABCI app, applies validator
updates (reference: state/execution.go:94,117,131,211,259,403).

This module also owns the batched execution plane (docs/EXECUTION.md):
`deliver_block_txs` is the ONE deliver engine every DeliverTx loop in the
tree goes through (block apply, handshake replay, bench, entry gates), so
the batched and serial paths cannot drift; `PostCommitWorker` moves event
publish off the apply critical path; `dispatch_commit_verify` is the
commit→apply overlap seam that lets a block's LastCommit verification ride
the device while host-side work (store save, WAL fsync) proceeds.
"""

from __future__ import annotations

import os
import queue
import threading
from dataclasses import dataclass, replace

from tendermint_tpu.abci import types as abci
from tendermint_tpu.crypto import keys as crypto_keys
from tendermint_tpu.state.state import State
from tendermint_tpu.state.store import ABCIResponses, StateStore
from tendermint_tpu.state.validation import validate_block
from tendermint_tpu.types.block import Block
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.params import ConsensusParams
from tendermint_tpu.types.ttime import Time
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.types.validator_set import ValidatorSet, index_builds
from tendermint_tpu.utils import faults
from tendermint_tpu.utils import trace as _trace


class BlockExecutionError(Exception):
    pass


# --- the batched deliver engine (docs/EXECUTION.md) -------------------------


def deliver_enabled() -> bool:
    """`TMTPU_DELIVER=0` restores the serial per-tx DeliverTx loop. Read
    per call so tests and the chain_throughput bench flip it live."""
    return os.environ.get("TMTPU_DELIVER") != "0"


def deliver_max_batch(default: int = 1024) -> int:
    """Tx cap per batched DeliverTx round trip (`TMTPU_DELIVER_MAX_BATCH`):
    bounds one wire message's size and the app's worst-case batched call."""
    try:
        v = int(os.environ.get("TMTPU_DELIVER_MAX_BATCH", default))
    except ValueError:
        return default
    return max(1, v)


def deliver_block_txs(app, txs) -> list[abci.ResponseDeliverTx]:
    """Execute a block's txs against the app: one ABCI round trip per
    `deliver_max_batch()`-sized chunk (wire extension fields 21/22), with
    per-tx responses order-aligned and bit-identical to the serial loop's.

    Degradation to the serial loop happens ONLY when provably no app code
    ran for the chunk: the `abci.deliver_batch` fault site fires BEFORE
    dispatch, apps without the batch method never get called, and the
    transports fall back only on structural probe / UNIMPLEMENTED
    evidence. A genuine app or transport error during a real batch
    PROPAGATES — the chunk's prefix has already mutated app state, which
    is exactly the serial loop's failure shape, and a silent redo would
    double-apply it.
    """
    txs = list(txs)
    if not txs:
        return []
    batch_fn = getattr(app, "deliver_tx_batch", None)
    if batch_fn is None or not deliver_enabled():
        return [app.deliver_tx(abci.RequestDeliverTx(tx=tx)) for tx in txs]
    out: list[abci.ResponseDeliverTx] = []
    cap = deliver_max_batch()
    with _trace.current().span("abci.deliver_txs", n=len(txs)):
        for start in range(0, len(txs), cap):
            chunk = txs[start:start + cap]
            try:
                faults.fire("abci.deliver_batch")
            except Exception:  # noqa: BLE001 - injected pre-dispatch: no
                # app code has run for this chunk, so the serial loop is
                # safe (cannot double-apply)
                out.extend(app.deliver_tx(abci.RequestDeliverTx(tx=tx))
                           for tx in chunk)
                continue
            with _trace.current().span("abci.deliver_batch", n=len(chunk)):
                rs = batch_fn(abci.RequestDeliverTxBatch(txs=chunk)).responses
            if len(rs) != len(chunk):
                raise BlockExecutionError(
                    f"batched DeliverTx returned {len(rs)} responses "
                    f"for {len(chunk)} txs")
            _observe_deliver_batch(len(chunk))
            out.extend(rs)
    return out


def _observe_deliver_batch(n: int) -> None:
    from tendermint_tpu.utils import metrics as tmmetrics

    m = tmmetrics.GLOBAL_NODE_METRICS
    if m is None:
        return
    try:
        m.deliver_batch_size.observe(float(n))
    except Exception:  # noqa: BLE001 - observability never fails the apply
        pass


def _observe_invalid_txs(n: int) -> None:
    from tendermint_tpu.utils import metrics as tmmetrics

    m = tmmetrics.GLOBAL_NODE_METRICS
    if m is None or n == 0:
        return
    try:
        m.abci_deliver_tx_invalid_total.add(float(n))
    except Exception:  # noqa: BLE001 - observability never fails the apply
        pass


# --- post-commit worker (docs/EXECUTION.md) ---------------------------------


# How many heights may be behind apply_block -- post-commit tasks not yet
# run, plus headers published and not yet indexed -- before the next apply
# waits for them (docs/EXECUTION.md). The reference publishes inside
# ApplyBlock and its indexer's subscription is unbuffered, so a slow indexer
# holds the caller back there too; without a bound every queued height keeps
# its block and its responses alive. A constant, not a knob.
MAX_BACKLOG_HEIGHTS = 2
# a waiter looks again this often whatever it was told: a follower that
# died, or was stopped, then cannot hold an apply for good
_BACKLOG_POLL_S = 0.05


class PostCommitWorker:
    """Single FIFO daemon thread for post-commit work (event publish →
    tx index, RPC subscribers) so `apply_block` returns as soon as state
    is durably saved. One queue, one thread: work for height h runs
    before work for h+1, the ordering subscribers rely on. Crash-shielded:
    a failing task is dropped and later heights still publish.

    Counters: ``submitted`` and ``done`` tasks, and ``backlog_max``, the most
    that were ever waiting or running at once. ``on_done`` is called after
    every task (the executor's backlog gate listens there)."""

    _STOP = object()

    def __init__(self, logger=None, on_done=None):
        self._logger = logger
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        self._thread: threading.Thread | None = None
        self._mtx = threading.Lock()
        self._on_done = on_done
        self.submitted = 0
        self.done = 0
        self.backlog_max = 0

    def backlog(self) -> int:
        """Tasks submitted and not yet finished."""
        return self.submitted - self.done

    def submit(self, fn) -> None:
        with self._mtx:
            t = self._thread
            if t is None or not t.is_alive():
                t = threading.Thread(target=self._run, name="post-commit",
                                     daemon=True)
                self._thread = t
                t.start()
            self.submitted += 1
            self.backlog_max = max(self.backlog_max, self.backlog())
        self._q.put(self._counted(fn))

    def _counted(self, fn):
        def task():
            try:
                fn()
            finally:
                with self._mtx:
                    self.done += 1
                if self._on_done is not None:
                    self._on_done()
        return task

    def flush(self, timeout_s: float = 10.0) -> bool:
        """Block until everything submitted so far has run (tests,
        Node.stop). Returns False on timeout."""
        with self._mtx:
            t = self._thread
        if t is None or not t.is_alive():
            return True
        done = threading.Event()
        self._q.put(done.set)
        return done.wait(timeout_s)

    def stop(self, timeout_s: float = 5.0) -> None:
        with self._mtx:
            t = self._thread
            self._thread = None
        if t is None or not t.is_alive():
            return
        self._q.put(self._STOP)
        t.join(timeout_s)

    def _run(self) -> None:
        try:
            while True:
                fn = self._q.get()
                if fn is PostCommitWorker._STOP:
                    return
                try:
                    fn()
                except Exception:  # noqa: BLE001 - post-commit work must
                    # never kill the worker; later heights still publish
                    if self._logger is not None:
                        try:
                            self._logger.error("post-commit task failed")
                        except Exception:  # noqa: BLE001
                            pass
        except Exception:  # noqa: BLE001 - crash shield (docs/LINT.md)
            pass


# --- commit→apply overlap seam (docs/EXECUTION.md) --------------------------


@dataclass
class SpeculativeCommitVerify:
    """A block's LastCommit verification dispatched on-device ahead of the
    apply, plus the dispatch-time inputs that make it safe to consume:
    the handle is used only if height / last_block_id / validator-set
    hash still match at resolve time, otherwise it is silently discarded
    and the apply falls back to the synchronous verify (the PIPELINE.md
    stale-input discipline)."""

    pending: object  # types.validator_set.PendingCommitVerify
    height: int
    last_block_id: BlockID
    vals_hash: bytes

    def fresh_for(self, state: State, block: Block):
        """The inner pending handle iff dispatch-time inputs still hold."""
        if (self.height == block.header.height
                and self.last_block_id == state.last_block_id
                and self.vals_hash == state.last_validators.hash()):
            return self.pending
        return None


def validator_updates_from_abci(updates: list[abci.ValidatorUpdate]) -> list[Validator]:
    """reference: types/protobuf.go PB2TM.ValidatorUpdates."""
    out = []
    for vu in updates:
        pub = crypto_keys.pubkey_from_type_bytes(vu.pub_key_type, vu.pub_key_bytes)
        out.append(Validator.new(pub, vu.power))
    return out


def validate_validator_updates(updates: list[abci.ValidatorUpdate],
                               params: ConsensusParams) -> None:
    """reference: state/execution.go:379-401."""
    for vu in updates:
        if vu.power < 0:
            raise BlockExecutionError(f"voting power can't be negative {vu}")
        if vu.power == 0:
            continue
        if vu.pub_key_type not in params.validator.pub_key_types:
            raise BlockExecutionError(
                f"validator {vu} is using pubkey {vu.pub_key_type}, which is unsupported for consensus"
            )


class BlockExecutor:
    """reference: state/execution.go:34-92."""

    def __init__(self, state_store: StateStore, app, mempool=None, evidence_pool=None,
                 event_bus=None, block_store=None, logger=None, metrics=None):
        self.store = state_store
        self.app = app  # proxy.AppConnConsensus-like (direct Application ok)
        self.mempool = mempool
        self.evidence_pool = evidence_pool
        self.event_bus = event_bus
        self.block_store = block_store
        self.logger = logger
        self.metrics = metrics
        # the backlog gate (MAX_BACKLOG_HEIGHTS): whoever shortens the
        # backlog notifies this condition
        self._backlog_cv = threading.Condition()
        self._follower = None
        self.backlog_waits = 0
        # the commit->apply seam: handles dispatch_commit_verify gave out,
        # and of those validate_block was handed, the ones whose inputs
        # still held (resolved) and the ones found stale (verified anew)
        self.commit_verify_dispatched = 0
        self.commit_verify_fresh = 0
        self.commit_verify_stale = 0
        # lazy: no thread until the first post-commit submission
        self._post_commit = PostCommitWorker(logger,
                                             on_done=self.backlog_changed)

    # --- proposal creation (reference: state/execution.go:94-129) ----------

    def create_proposal_block(self, height: int, state: State, last_commit,
                              proposer_address: bytes,
                              block_time: Time | None = None) -> Block:
        max_bytes = state.consensus_params.block.max_bytes
        max_gas = state.consensus_params.block.max_gas
        evidence = []
        ev_size = 0
        if self.evidence_pool is not None:
            evidence, ev_size = self.evidence_pool.pending_evidence(
                state.consensus_params.evidence.max_bytes
            )
        max_data = max_data_bytes(max_bytes, ev_size, state.validators.size())
        txs = self.mempool.reap_max_bytes_max_gas(max_data, max_gas) if self.mempool else []
        return state.make_block(height, txs, last_commit, evidence, proposer_address,
                                block_time)

    def validate_block(self, state: State, block: Block,
                       commit_pending: SpeculativeCommitVerify | None = None,
                       tr=None) -> None:
        inner = None
        if commit_pending is not None:
            inner = commit_pending.fresh_for(state, block)
            if inner is None:
                self.commit_verify_stale += 1
            else:
                self.commit_verify_fresh += 1
        validate_block(state, block, self.block_store, commit_pending=inner,
                       tr=tr)
        if self.evidence_pool is not None:
            self.evidence_pool.check_evidence(state, block.evidence)

    def dispatch_commit_verify(self, state: State,
                               block: Block) -> SpeculativeCommitVerify | None:
        """Dispatch `block.last_commit`'s verification on-device NOW and
        return a stale-guarded handle that `validate_block`/`apply_block`
        resolve later — the commit→apply overlap seam: the device round
        trip rides under host-side work (structural checks, store save,
        WAL fsync) instead of serializing with it. `resolve()` replays the
        exact serial accept/reject decision and is idempotent, so passing
        one handle through both the pre-save validate and the apply costs
        one verification total. Returns None when there is nothing to
        verify (the initial block)."""
        if block.header.height == state.initial_height:
            return None
        pending = state.last_validators.verify_commit_async(
            state.chain_id, state.last_block_id,
            block.header.height - 1, block.last_commit)
        self.commit_verify_dispatched += 1
        return SpeculativeCommitVerify(
            pending=pending, height=block.header.height,
            last_block_id=state.last_block_id,
            vals_hash=state.last_validators.hash())

    # --- the backlog behind apply_block (docs/EXECUTION.md) -----------------

    @property
    def post_commit(self) -> PostCommitWorker:
        """The worker, for its counters (submitted, done, backlog_max)."""
        return self._post_commit

    def follow_backlog(self, heights_waiting) -> None:
        """Count the consumer of the post-commit events into the backlog:
        ``heights_waiting()`` says how many published heights it has not
        finished (the node's IndexerService.backlog_heights). The consumer
        calls ``backlog_changed`` whenever that number falls."""
        self._follower = heights_waiting

    def backlog_heights(self) -> int:
        """Heights applied whose post-commit work is not finished: tasks
        the worker still holds, and what the follower still holds."""
        follower = self._follower
        return self._post_commit.backlog() + (follower() if follower else 0)

    def backlog_changed(self) -> None:
        with self._backlog_cv:
            self._backlog_cv.notify_all()

    def _await_backlog(self, tr) -> None:
        """Hold the next apply while MAX_BACKLOG_HEIGHTS heights or more are
        still behind it."""
        bound = MAX_BACKLOG_HEIGHTS
        if self.backlog_heights() < bound:
            return
        self.backlog_waits += 1
        with (tr.span("apply.backlog_wait", backlog=self.backlog_heights(),
                      bound=bound) if tr else _trace.NULL_SPAN):
            with self._backlog_cv:
                while self.backlog_heights() >= bound:
                    self._backlog_cv.wait(_BACKLOG_POLL_S)

    def flush_post_commit(self, timeout_s: float = 10.0) -> bool:
        """Wait for all queued post-commit work (event publish) to run."""
        return self._post_commit.flush(timeout_s)

    def stop(self) -> None:
        self._post_commit.stop()

    # --- applying a decided block (reference: state/execution.go:131-209) --

    def apply_block(self, state: State, block_id: BlockID, block: Block,
                    commit_pending: SpeculativeCommitVerify | None = None,
                    ) -> tuple[State, int]:
        import time as _t

        from tendermint_tpu.utils import metrics as tmmetrics

        _started = _t.monotonic()
        # the four phases of an apply, as spans of the active tracer
        # (docs/OBSERVABILITY.md); one attribute load each while tracing is off
        tr = _trace.current() if _trace.ENABLED else None
        if self.event_bus is not None:
            self._await_backlog(tr)
        with tr.span("apply.validate") if tr else _trace.NULL_SPAN:
            builds = index_builds()
            self.validate_block(state, block, commit_pending=commit_pending,
                                tr=tr)
            if tr:
                tr.annotate(index_builds=index_builds() - builds)

        with tr.span("apply.exec") if tr else _trace.NULL_SPAN:
            abci_responses = self._exec_block_on_app(state, block)
            self.store.save_abci_responses(block.header.height, abci_responses)

        with tr.span("apply.update_state") if tr else _trace.NULL_SPAN:
            end = abci_responses.end_block
            validate_validator_updates(end.validator_updates, state.consensus_params)
            validator_updates = validator_updates_from_abci(end.validator_updates)
            new_state = update_state(state, block_id, block, abci_responses,
                                     validator_updates)
            if tr and validator_updates:
                joined = sum(1 for v in validator_updates if v.voting_power
                             and not state.next_validators.has_address(v.address))
                tr.annotate(updates=len(validator_updates), joined=joined,
                            left=sum(1 for v in validator_updates
                                     if not v.voting_power))

        with tr.span("apply.save") if tr else _trace.NULL_SPAN:
            # Lock mempool, commit app state, update mempool (reference:
            # state/execution.go:211-257).
            app_hash, retain_height = self._commit(new_state, block, abci_responses)
            if self.evidence_pool is not None:
                self.evidence_pool.update(new_state, block.evidence)

            new_state = replace(new_state, app_hash=app_hash)
            self.store.save(new_state)

        # Post-commit work is off the critical path: apply_block returns
        # as soon as state is durably saved; the single FIFO worker keeps
        # height h's events ahead of h+1's for every subscriber.
        if self.event_bus is not None:
            self._post_commit.submit(
                lambda: self._fire_events(block, block_id, abci_responses,
                                          validator_updates))
        if tmmetrics.GLOBAL_NODE_METRICS is not None:
            tmmetrics.GLOBAL_NODE_METRICS.block_processing_time.observe(
                _t.monotonic() - _started)
        return new_state, retain_height

    def _exec_block_on_app(self, state: State, block: Block) -> ABCIResponses:
        """BeginBlock / DeliverTx* / EndBlock (reference:
        state/execution.go:259-377)."""
        commit_info = get_begin_block_validator_info(block, self.store, state.initial_height)
        byz_vals = []
        for ev in block.evidence:
            byz_vals.extend(abci_evidence(ev, state))

        begin_res = self.app.begin_block(abci.RequestBeginBlock(
            hash=block.hash() or b"",
            header=block.header,
            last_commit_info=commit_info,
            byzantine_validators=byz_vals,
        ))
        deliver_txs = deliver_block_txs(self.app, block.data.txs)
        _observe_invalid_txs(sum(1 for r in deliver_txs if not r.is_ok()))
        end_res = self.app.end_block(abci.RequestEndBlock(height=block.header.height))
        return ABCIResponses(deliver_txs=deliver_txs, end_block=end_res, begin_block=begin_res)

    def _commit(self, state: State, block: Block, abci_responses: ABCIResponses):
        """reference: state/execution.go:211-257: flush mempool, app Commit,
        mempool Update (with admission filters rebuilt from the new state)."""
        if self.mempool is not None:
            self.mempool.lock()
        try:
            res = self.app.commit()
            if self.mempool is not None:
                from tendermint_tpu.state.tx_filter import (
                    tx_post_check,
                    tx_pre_check,
                )

                self.mempool.update(
                    block.header.height, block.data.txs, abci_responses.deliver_txs,
                    pre_check=tx_pre_check(state),
                    post_check=tx_post_check(state),
                )
        finally:
            if self.mempool is not None:
                self.mempool.unlock()
        return res.data, res.retain_height

    def _fire_events(self, block: Block, block_id: BlockID,
                     abci_responses: ABCIResponses, validator_updates) -> None:
        """reference: state/execution.go:471-552."""
        if self.event_bus is None:
            return
        from tendermint_tpu.types import events

        tr = _trace.current()
        with tr.span("apply.post_commit", height=block.header.height,
                     txs=len(block.data.txs),
                     events=2 + len(block.evidence) + len(block.data.txs)
                     + (1 if validator_updates else 0)):
            with tr.span("events.publish_block"):
                tr.annotate(events=self._publish_events(
                    block, block_id, abci_responses, validator_updates,
                    events))

    def _publish_events(self, block, block_id, abci_responses,
                        validator_updates, events) -> int:
        """-> messages matched and queued to a subscription (an event bus
        that does not count them reads as none)."""
        bus = self.event_bus
        queued = bus.publish_event_new_block(
            events.EventDataNewBlock(block=block, block_id=block_id,
                                     result_begin_block=abci_responses.begin_block,
                                     result_end_block=abci_responses.end_block)) or 0
        queued += bus.publish_event_new_block_header(
            events.EventDataNewBlockHeader(header=block.header,
                                           num_txs=len(block.data.txs),
                                           result_begin_block=abci_responses.begin_block,
                                           result_end_block=abci_responses.end_block)) or 0
        for ev in block.evidence:
            queued += bus.publish_event_new_evidence(
                events.EventDataNewEvidence(evidence=ev, height=block.header.height)) or 0
        for i, tx in enumerate(block.data.txs):
            queued += bus.publish_event_tx(events.EventDataTx(
                height=block.header.height, tx=tx, index=i,
                result=abci_responses.deliver_txs[i])) or 0
        if validator_updates:
            queued += bus.publish_event_validator_set_updates(
                events.EventDataValidatorSetUpdates(validator_updates=validator_updates)) or 0
        return queued


def update_state(state: State, block_id: BlockID, block: Block,
                 abci_responses: ABCIResponses, validator_updates) -> State:
    """reference: state/execution.go:403-469."""
    n_val_set = state.next_validators.copy()
    last_height_vals_changed = state.last_height_validators_changed
    if validator_updates:
        n_val_set.update_with_change_set(validator_updates)
        last_height_vals_changed = block.header.height + 1 + 1

    n_val_set.increment_proposer_priority(1)

    next_params = state.consensus_params
    last_height_params_changed = state.last_height_consensus_params_changed
    if abci_responses.end_block is not None and abci_responses.end_block.consensus_param_updates is not None:
        next_params = abci_responses.end_block.consensus_param_updates
        next_params.validate_basic()
        last_height_params_changed = block.header.height + 1

    from tendermint_tpu.abci.types import results_hash

    return State(
        version=state.version,
        chain_id=state.chain_id,
        initial_height=state.initial_height,
        last_block_height=block.header.height,
        last_block_id=block_id,
        last_block_time=block.header.time,
        next_validators=n_val_set,
        validators=state.next_validators.copy(),
        last_validators=state.validators.copy(),
        last_height_validators_changed=last_height_vals_changed,
        consensus_params=next_params,
        last_height_consensus_params_changed=last_height_params_changed,
        last_results_hash=results_hash(abci_responses.deliver_txs),
        app_hash=b"",  # set after Commit
    )


def get_begin_block_validator_info(block: Block, store: StateStore,
                                   initial_height: int) -> abci.LastCommitInfo:
    """reference: state/execution.go:307-352."""
    vote_infos = []
    if block.header.height > initial_height:
        last_val_set = store.load_validators(block.header.height - 1)
        commit_size = block.last_commit.size()
        vals_size = last_val_set.size()
        if commit_size != vals_size:
            raise BlockExecutionError(
                f"commit size ({commit_size}) doesn't match valset length ({vals_size}) "
                f"at height {block.header.height}"
            )
        for i, val in enumerate(last_val_set.validators):
            cs = block.last_commit.signatures[i]
            vote_infos.append(abci.VoteInfo(
                validator=abci.ABCIValidator(address=val.address, power=val.voting_power),
                signed_last_block=not cs.absent(),
            ))
    round_ = block.last_commit.round if block.last_commit else 0
    return abci.LastCommitInfo(round=round_, votes=vote_infos)


def abci_evidence(ev, state: State) -> list[abci.ABCIEvidence]:
    """types.Evidence.ABCI() equivalents (reference: types/evidence.go:76,203)."""
    from tendermint_tpu.types.evidence import DuplicateVoteEvidence, LightClientAttackEvidence

    if isinstance(ev, DuplicateVoteEvidence):
        return [abci.ABCIEvidence(
            type=abci.EVIDENCE_TYPE_DUPLICATE_VOTE,
            validator=abci.ABCIValidator(address=ev.vote_a.validator_address,
                                         power=ev.validator_power),
            height=ev.vote_a.height,
            time_seconds=ev.timestamp.seconds,
            time_nanos=ev.timestamp.nanos,
            total_voting_power=ev.total_voting_power,
        )]
    if isinstance(ev, LightClientAttackEvidence):
        out = []
        for v in ev.byzantine_validators:
            out.append(abci.ABCIEvidence(
                type=abci.EVIDENCE_TYPE_LIGHT_CLIENT_ATTACK,
                validator=abci.ABCIValidator(address=v.address, power=v.voting_power),
                height=ev.height(),
                time_seconds=ev.timestamp.seconds,
                time_nanos=ev.timestamp.nanos,
                total_voting_power=ev.total_voting_power,
            ))
        return out
    return []


def max_data_bytes(max_bytes: int, evidence_bytes: int, num_vals: int) -> int:
    """reference: types/block.go MaxDataBytes."""
    MAX_OVERHEAD_FOR_BLOCK = 11
    MAX_HEADER_BYTES = 626
    MAX_COMMIT_OVERHEAD = 94
    MAX_COMMIT_SIG_BYTES = 109
    max_data = (max_bytes - MAX_OVERHEAD_FOR_BLOCK - MAX_HEADER_BYTES
                - MAX_COMMIT_OVERHEAD - num_vals * MAX_COMMIT_SIG_BYTES
                - evidence_bytes)
    if max_data < 0:
        raise BlockExecutionError(
            f"negative MaxDataBytes. Block.MaxBytes={max_bytes} is too small to accommodate header&lastCommit&evidence"
        )
    return max_data
