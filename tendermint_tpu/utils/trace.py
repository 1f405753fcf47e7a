"""Consensus flight recorder: per-node causal span tracing (SURVEY aux #36).

The reference exposes pprof + Prometheus step histograms; a TPU build also
needs to ATTRIBUTE a verify decision's wall time around the host<->device
round trip: of a decision's wall time, how much was host prep, queue wait, device
compute, readback, and bitmap replay — and WHERE in the block lifecycle a
stalled node last made progress.

Three layers:

 - :class:`Tracer` — an instance-scoped bounded ring of :class:`Span`
   records. One per Node (``node.tracer``): the old module-global ring
   interleaved spans from all 50 fabric nodes of an in-process mesh.
   Spans are CAUSAL: nested ``span()`` regions on one thread link
   parent/child ids, and a ``height=`` tag set by an enclosing span is
   inherited by its children (``current_height``), so the deferred verify
   phases dispatched inside a vote-drain span land on the right height.
   A ``decision=`` tag is inherited the same way: the entry point of a
   commit decision opens its root span with ``decision=True`` (the tag
   becomes the span's own id), handles that outlive the call carry the id
   and the causing span across threads (``current_decision`` /
   ``current_span``), and a span or record on another thread names both
   (``decision=``, ``parent=``), so one decision is one tree
   (docs/OBSERVABILITY.md). While jax is imported every span also enters
   a ``jax.profiler.TraceAnnotation`` of its name, so a profiler session
   shows the program's spans on the profiler's own clock. Every span names
   the thread that wrote it and a ``span()`` carries that thread's CPU
   seconds beside its wall seconds: under one interpreter lock a span's
   wall time holds every other thread's turn too, its CPU time only its
   own. :class:`ThreadCensus` is the same reading taken from outside, of
   every live thread at once.
 - the module-level functions: ``span()/mark()/record()`` delegate to the
   thread's ACTIVE tracer (``Tracer.activate()``), falling back to the
   process :data:`DEFAULT` tracer; ``dump()/summarize()/enable()`` always
   address DEFAULT (the pre-flight-recorder API surface — draining a
   node's ring goes through ``node.tracer``/``unsafe_trace``). Hot call
   sites guard on the module attribute :data:`ENABLED` (true while ANY
   tracer is enabled), so the disabled path costs one attribute load
   (tests/test_trace.py gates this).
 - consumers: ``Tracer.timeline(height)`` assembles the structured
   per-height block lifecycle (docs/OBSERVABILITY.md schema; served by the
   ``unsafe_timeline`` RPC route), ``last_phase()`` feeds the soak
   auditor's stall annotations, and spans named in :data:`MIRRORED_SPANS`
   are mirrored into the pre-seeded ``trace_phase_seconds`` histogram.

Beside the per-node rings there is :data:`STARTUP`, a small ring that is
always on and that only cold paths write to (key decompression, table
builds, jit tracing and compiling, the crossover calibration): what a
process spent before its first decision, whether or not tracing is on.

Knobs: ``TMTPU_TRACE=1`` enables every node's tracer at construction;
``TMTPU_TRACE_CAP`` sets the per-tracer ring size (default 4096).
"""

from __future__ import annotations

import contextlib
import itertools
import os
import sys
import threading
import time
from dataclasses import dataclass

DEFAULT_CAP = 4096

# ---------------------------------------------------------------------------
# Canonical span table (tmlint rule `trace-span-discipline`): every span
# name used by trace.span()/mark()/record() in production code must be a
# key here AND documented in docs/OBSERVABILITY.md — ad-hoc span strings
# drift from the doc and break timeline/dashboard consumers.
# ---------------------------------------------------------------------------

CANONICAL_SPANS = {
    # consensus block lifecycle (marks; once per committed single-round
    # height — the `unsafe_timeline` LIFECYCLE set, in causal order)
    "consensus.proposal": "proposal accepted onto the round state",
    "consensus.block_parts": "proposal part-set completed (block assembled)",
    "consensus.precommit": "entered the precommit step",
    "consensus.commit": "entered commit (+2/3 precommits on a block)",
    "consensus.store_save": "block + seen commit persisted (span)",
    "consensus.abci_apply": "ABCI BeginBlock..Commit of the decided block (span)",
    # consensus timing
    "consensus.step": "time spent in the round step just left",
    "consensus.vote_drain": "batched peer-vote drain: the previous flush's "
                            "wait and apply, then build + dispatch (tags "
                            "votes, queued, cache_hits, in_drain_copies, "
                            "skipped)",
    "consensus.wal_write": "a drain's votes written to the WAL, buffered, "
                           "before any is verified (tags msgs, bytes, "
                           "writes: write calls on the file, 1 a drain, "
                           "msgs while a fault rule is armed)",
    "consensus.flush_wait": "the consensus thread blocked on a vote flush's "
                            "bitmap (tag sigs)",
    "consensus.vote_apply": "a drain's votes through addVote in arrival "
                            "order, then the announcement of those added "
                            "(tags votes, added, duplicates, invalid, errors)",
    "consensus.vote_serial": "votes the batch did not verify, in the serial "
                             "path (tags why = single / late / early / "
                             "precheck, votes; one record per drain and why)",
    "consensus.finalize_commit": "validate + save + apply of a decided "
                                 "block (parent of store_save, abci_apply)",
    "consensus.announce": "the peers told which votes were added since the "
                          "mark before (mark, one per flush that sent "
                          "anything; tags votes, has_votes, bit_arrays = "
                          "messages offered to each peer, bytes = their "
                          "length)",
    "consensus.recv": "what ConsensusReactor.receive took since the mark "
                      "before, once a height (mark; tags msgs, seconds, "
                      "bytes, threads = who called it, cpu_s = the CPU "
                      "seconds those threads got since, vote_memo_hits / "
                      "_misses / _full / _size = vote messages answered by "
                      "the reactor's memo, decoded, not kept at its bound, "
                      "and entries held)",
    "consensus.thread_cpu": "CPU seconds of every live thread since the mark "
                            "before, once a height (mark; tags wall_s, "
                            "process_s, rest_s, lost, threads = name -> s)",
    # deferred verify pipeline phases (crypto/batch.py; the sync-floor
    # attribution ROADMAP item 1 needs)
    "verify.host_prep": "host prep + kernel dispatch (ops dispatch_batch)",
    "verify.queue": "dispatch()->resolve() queue wait of a PendingVerify",
    "verify.readback": "blocking D2H fetch (crypto/batch._device_get)",
    "verify.replay": "bitmap fetch -> serial accept/reject replay",
    "verify.shard_dispatch": "a batch spread over the local devices: Pallas "
                             "chunks placed one a chip (ops/ed25519_pallas."
                             "dispatch_chunks; tags kind, n, chunks, devices)",
    "verify.wake": "executor's done.set() -> the waiting caller runs again",
    # one commit decision, entry point to tally (types/validator_set.py);
    # commit.assemble is the decision's root and its span id the decision id
    "commit.assemble": "structural check, the commit's sign bytes (once), add "
                       "per signature, verifier.dispatch (root span of a decision)",
    "commit.wait": "PendingCommitVerify.resolve waiting for the bitmap",
    "commit.tally": "serial accept/reject replay over the bitmap",
    # below ops dispatch_batch (ops/ed25519_batch, sr25519_batch,
    # ed25519_pallas)
    "prep.keyset": "pubkey join, keys mapped to rows of the per-key device "
                   "table, the build of keys it does not hold",
    "prep.scalars": "per-signature hash (SHA-512 / merlin in C), mod L, windows",
    "prep.launch": "host time to enqueue one device program (route, real "
                   "signatures, launched lanes, the device it was placed on)",
    "prep.host_verify": "the C / scalar host verifier answered the batch",
    # the start-up ring (STARTUP): cold paths, recorded with tracing off too
    "startup.key_decode": "Python decompression of keys the table did not hold",
    "startup.table_build": "device build of those keys' comb tables, tile by "
                           "tile, until the last is ready (tags keys, rows "
                           "= the tile rows built for them, launches = the "
                           "tiles, program = pallas | jnp)",
    "startup.jit_trace": "jax traced a function and lowered it to MLIR",
    "startup.jit_compile": "backend compile, or its load from the cache",
    "startup.cache_load": "persistent compile-cache retrieval (inside "
                          "startup.jit_compile)",
    "startup.calibrate": "host/device crossover calibration",
    "startup.warm_kernel": "crypto.batch.warmup compiling and running one "
                           "key type's verify kernel at a warm bucket size "
                           "(tags kind, sigs)",
    # fast-sync verify-ahead (blockchain/pipeline.py)
    "fastsync.dispatch": "speculative commit-verify dispatch for one height",
    "fastsync.head_wait": "the head block's wait: batched prefetch + resolve",
    "fastsync.apply": "block save + ABCI apply of a fast-synced height",
    "fastsync.discard": "speculative dispatches thrown away unresolved "
                        "(mark; tags entries, reason = valset / pool / "
                        "error, height of the first)",
    "fastsync.part_set": "a pooled block marshalled and cut into parts "
                         "before its dispatch (span; tags bytes, parts)",
    "fastsync.thread_cpu": "CPU seconds of every live thread since the mark "
                           "before, every 10 heights a pipeline applied "
                           "(mark; tags wall_s, process_s, rest_s, lost, "
                           "threads = name -> s, sync_thread = who called "
                           "process_next)",
    # tx front door + gossip plane
    "mempool.check_tx": "ABCI CheckTx round trip of one tx",
    "mempool.ingest_batch": "one batched ABCI CheckTxBatch dispatch of the "
                            "ingest front door (span; n= txs)",
    "mempool.ingest_coalesce": "ingest coalescer shared-batch marker "
                               "(requests= txs per batch)",
    "mempool.ingest_wait": "submit->resolve wait of one tx through the "
                           "ingest coalescer",
    "blockchain.recv_block": "a BlockResponse through BlockchainReactor."
                             "receive: envelope parse, Block.unmarshal, the "
                             "pool's add_block (span; tags bytes, height, "
                             "peer)",
    "fastsync.pool_wait": "the sync loop's sleep when the next pair of "
                          "blocks was not in the pool and the pool is not "
                          "caught up: the wire or a peer sets the pace "
                          "(span; tag height)",
    "fastsync.first_block": "start_sync to the first block in the pool "
                            "(mark; tags height, seconds)",
    "p2p.wire": "what the switch's connections moved since the mark "
                "before, summed over its peers, and the pool's two sizes "
                "(mark beside fastsync.thread_cpu; tags packets_*, msgs_*, "
                "bytes_*, frames_*, sealed_bytes_*, *_blocked_s, channels, "
                "requested, pooled, peers)",
    "p2p.send": "message queued to a peer channel (mark)",
    "p2p.recv": "message delivered to a reactor (span over on_receive)",
    # batched execution plane (state/execution.py, docs/EXECUTION.md)
    "abci.deliver_txs": "all DeliverTx work of one block through the "
                        "shared deliver engine (span; n= txs)",
    "abci.deliver_batch": "one batched ABCI DeliverTxBatch chunk dispatch "
                          "(span; n= txs)",
    "apply.post_commit": "post-commit event publish of one height on the "
                         "async worker (span; tags height, txs, events = "
                         "messages published)",
    "apply.backlog_wait": "apply_block held until the heights still behind "
                          "it (post-commit tasks, headers waiting for the "
                          "indexer) fell under the bound (span; tags "
                          "backlog, bound)",
    "events.publish_block": "one height's messages through the event bus, "
                            "on the publishing thread (span; tags height, "
                            "events = messages matched and queued)",
    "indexer.height": "one height through the indexer service: its header "
                      "and its transactions in one indexer transaction "
                      "(span; tags height, txs, rows, bytes)",
    "mempool.update": "Mempool.update of one committed block (span; tags "
                      "height, txs)",
    "block.data_hash": "the Merkle root of a block's transactions "
                       "(Data.hash; span; tag txs)",
    "state.save_responses": "a height's ABCI responses marshalled and saved "
                            "(span; tags height, txs, bytes)",
    # the four phases of BlockExecutor.apply_block, in order
    "apply.validate": "validate_block: header against state, LastCommit's "
                      "full verify_commit (or its resolve), block time (span; "
                      "tags median_s = the weighted median alone, "
                      "index_builds = ValidatorSet address indexes built "
                      "meanwhile, last_commit = pending (a handle dispatched "
                      "ahead was resolved) / sync (verify_commit ran here) / "
                      "none (the initial block), last_commit_s = the seconds "
                      "that resolve or verify took, sigs = the commit's "
                      "slots that are not Absent)",
    "apply.exec": "BeginBlock, DeliverTx*, EndBlock on the app and the "
                  "ABCI responses' save (span)",
    "apply.update_state": "validator updates checked and decoded, "
                          "update_state (span; tags updates, joined, left "
                          "where EndBlock changed the set)",
    "apply.save": "app Commit, mempool and evidence update, the state "
                  "store's save (span)",
    "state.save": "StateStore.save: the validator and parameter history "
                  "rows and the whole State marshalled and written (span; "
                  "tags height, bytes = the State's encoding, validators = "
                  "the size of its current set)",
    # self-healing storage plane (store/scrub.py, store/repair.py)
    "store.save_block": "BlockStore.save_block: meta, parts, commits and "
                        "the store's state in one batch (span; tags height, "
                        "bytes, parts, rows)",
    "store.scrub": "one integrity-scrub pass over a node's stores (span)",
    "store.repair": "peer re-fetch + batch-verified rewrite of one damaged "
                    "height (span; height= tag)",
    # light client (light/client.py, light/range_verify.py, docs/LIGHT.md)
    "light.sync": "one verify_light_block_at_height, fetch of the target to "
                  "the trusted-store update (span; mode=, from=, to= tags)",
    "light.fetch": "light blocks from the primary, validate_basic included: "
                   "one window of a sequential sync, or the target (span; "
                   "from=, headers= tags)",
    "light.range": "one window of a sequential sync through "
                   "range_verify.verify_window, the decision root of its "
                   "launches (span; headers=, sigs=, chunks=, verified= "
                   "tags; fallback=1 on a per-header re-run)",
    "light.assemble": "a window's light prefixes, sign bytes, add per "
                      "signature, one dispatch per kernel chunk (span)",
    "light.structure": "a window's linkage walk, verifier.check_adjacent "
                       "per header (span)",
    "light.wait": "blocked on the bitmap of one of a window's dispatches, "
                  "taken in height order (span)",
    "light.replay": "the serial tally of each header of one dispatch over "
                    "its slice of the bitmap (span)",
    "light.store": "trusted-store writes of one dispatch's verified "
                   "headers; tags blocks, bytes (span)",
    # skipping mode (light/client.py _verify_skipping, light/verifier.py)
    "light.skip.hop": "one attempt of a bisection, lv.verify of a cached "
                      "block from the verified one (span; from=, to=, "
                      "depth=, accepted= tags)",
    "light.skip.trusting": "a whole verify_commit_light_trusting: scan by "
                           "address in the trusted set, dispatch, wait, "
                           "tally (span; n= signatures handed to the "
                           "verifier, decision= its commit.assemble, "
                           "refused= on a refusal)",
    "light.skip.light": "the hop's verify_commit_light on the new set, "
                        "after the trusting check passed (span)",
    "light.skip.fetch": "a pivot from the source, validate_basic included "
                        "(span; height= tag)",
    # light-client serving gateway (light/gateway.py, docs/LIGHT.md)
    "light.gateway.serve": "one client query through the gateway: cache "
                           "lookup, coalesced verification, answer or "
                           "typed refusal (span; height= tag)",
    "light.gateway.fetch": "one provider fetch attempt, including retries "
                           "(span; provider= tag)",
    "light.gateway.hedge": "hedged secondary fired after the primary "
                           "exceeded the latency budget (mark)",
}

# Spans mirrored into the pre-seeded `trace_phase_seconds{phase=}`
# histogram (utils/metrics.py NodeMetrics). Bounded label universe by
# construction — this tuple IS the label set.
MIRRORED_SPANS = (
    "verify.host_prep", "verify.queue", "verify.readback", "verify.replay",
    "verify.shard_dispatch", "consensus.vote_drain", "consensus.store_save",
    "consensus.abci_apply", "mempool.check_tx", "mempool.ingest_batch",
    "mempool.ingest_wait", "abci.deliver_txs", "abci.deliver_batch",
    "apply.post_commit",
)
_MIRROR_SET = frozenset(MIRRORED_SPANS)

# The deterministic per-committed-height lifecycle marks, in causal order
# (a healthy single-round height emits each exactly once; the timeline's
# causal_ok verdict checks first-occurrence order against this).
LIFECYCLE = (
    "consensus.proposal", "consensus.block_parts", "consensus.precommit",
    "consensus.commit", "consensus.store_save", "consensus.abci_apply",
)


def trace_cap(default: int = DEFAULT_CAP) -> int:
    """Per-tracer ring capacity; TMTPU_TRACE_CAP overrides."""
    v = os.environ.get("TMTPU_TRACE_CAP")
    try:
        return max(16, int(v)) if v else default
    except ValueError:
        return default


def trace_enabled_from_env() -> bool:
    """TMTPU_TRACE=1: nodes enable their tracer at construction."""
    return os.environ.get("TMTPU_TRACE") == "1"


@dataclass
class Span:
    name: str
    start: float        # time.monotonic() at entry
    duration_s: float
    tags: dict
    span_id: int = 0
    parent_id: int = 0  # 0 = root (no enclosing span on that thread)
    thread: str = ""    # name of the thread that wrote it
    # time.thread_time() over the region: seconds the thread was on a core.
    # None on a mark and on a record() that was given none.
    cpu_s: float | None = None

    def as_dict(self) -> dict:
        return {"name": self.name, "start": self.start,
                "duration_s": self.duration_s, "span_id": self.span_id,
                "parent_id": self.parent_id, "thread": self.thread,
                "cpu_s": self.cpu_s, "tags": dict(self.tags)}


# ANY tracer enabled — THE one-attribute-load guard hot call sites check
# before building a span. Maintained by Tracer.enable()/disable().
ENABLED = False
_enabled_count = 0
_state_mtx = threading.Lock()

# thread-local active tracer (Tracer.activate()); current() falls back to
# the process DEFAULT so the module-level API keeps its old semantics
_tl = threading.local()


class _NullSpan:
    """What a guarded site enters while tracing is off
    (``trace.current().span(...) if trace.ENABLED else trace.NULL_SPAN``):
    one shared object, so the disabled path allocates nothing."""

    __slots__ = ()

    def __enter__(self):
        return 0

    def __exit__(self, *_exc):
        return False


NULL_SPAN = _NullSpan()

_ANNOTATION = None  # jax.profiler.TraceAnnotation, once jax is imported


def _bridge(name: str, decision):
    """Enter a profiler annotation of the span's name when jax is already
    imported (never import it from here: the recorder serves jax-free
    processes too). Outside a profiler session this is a TraceMe that
    records nothing; inside one the program's spans sit on the profiler's
    clock beside the device's programs."""
    global _ANNOTATION
    ann = _ANNOTATION
    if ann is None:
        prof = getattr(sys.modules.get("jax"), "profiler", None)
        ann = _ANNOTATION = getattr(prof, "TraceAnnotation", None)
        if ann is None:
            return None
    try:
        entered = ann(name, decision=decision) if decision else ann(name)
        entered.__enter__()
    except Exception:  # noqa: BLE001 - the bridge never costs a span
        return None
    return entered


class Tracer:
    """One bounded span ring + causality bookkeeping. Thread-safe: spans
    may complete on any thread; parent/height/decision context is
    per-thread. ``cold=True`` makes an always-on ring that never raises
    the module guard :data:`ENABLED` (the start-up ring)."""

    def __init__(self, name: str = "", cap: int | None = None,
                 enabled: bool = False, cold: bool = False):
        self.name = name
        self.enabled = cold
        self.cap = cap if cap is not None else trace_cap()
        from collections import deque

        self._spans: "deque[Span]" = deque(maxlen=self.cap)
        self._mtx = threading.Lock()
        self._seq = itertools.count(1)
        self._ctx = threading.local()  # per-thread parent/height stacks
        self._cold = cold
        if enabled:
            self.enable()

    # --- enable/disable (keeps the module ENABLED guard honest) ------------

    def enable(self) -> None:
        global ENABLED, _enabled_count
        with _state_mtx:
            if not self.enabled:
                self.enabled = True
                _enabled_count += 1
                ENABLED = True

    def disable(self) -> None:
        global ENABLED, _enabled_count
        with _state_mtx:
            if self.enabled and not self._cold:
                self.enabled = False
                _enabled_count -= 1
                ENABLED = _enabled_count > 0

    # --- thread-local activation -------------------------------------------

    @contextlib.contextmanager
    def activate(self):
        """Make this tracer the thread's `current()` target, so library
        layers (crypto/batch, ops/ed25519_pallas) record into the node
        whose work they are doing without constructor plumbing."""
        prev = getattr(_tl, "tracer", None)
        _tl.tracer = self
        try:
            yield self
        finally:
            _tl.tracer = prev

    # --- recording ----------------------------------------------------------

    def _stacks(self):
        c = self._ctx
        if not hasattr(c, "parents"):
            c.parents = []
            c.heights = []
            c.decisions = []
            c.open = []      # the tag dicts of the open spans (annotate)
            c.thread = threading.current_thread().name
        return c

    def current_height(self):
        """Innermost height= tag of the enclosing span stack, or None."""
        c = self._stacks()
        return c.heights[-1] if c.heights else None

    def current_decision(self) -> int:
        """The decision id the enclosing spans of this thread work for, or
        0: what a handle captures at dispatch to carry across threads."""
        c = self._stacks()
        return c.decisions[-1] if c.decisions else 0

    def current_span(self) -> int:
        """Id of the innermost open span on this thread (the cause of
        whatever is dispatched now), or 0."""
        c = self._stacks()
        return c.parents[-1] if c.parents else 0

    def _inherit(self, c, tags: dict):
        """Fill height= and decision= from the enclosing spans."""
        if "height" not in tags and c.heights:
            tags["height"] = c.heights[-1]
        if "decision" not in tags and c.decisions:
            tags["decision"] = c.decisions[-1]

    @contextlib.contextmanager
    def span(self, name: str, *, parent: int | None = None, **tags):
        """Timed causal region. Children started on this thread inside the
        region get this span as parent and inherit its height and decision
        tags. ``decision=True`` makes this span a decision's root: the tag
        becomes its own id. ``parent=`` names the causing span when it is
        not the enclosing one (work done on another thread). The thread's
        CPU clock is read inside the wall clock's two readings, so
        ``cpu_s <= duration_s``: the difference is time the thread was off
        the core (the interpreter lock, a mutex, a socket, the device)."""
        if not self.enabled:
            yield 0
            return
        c = self._stacks()
        sid = next(self._seq)
        if tags.get("decision") is True:
            tags["decision"] = sid
        self._inherit(c, tags)
        h = tags.get("height")
        d = tags.get("decision")
        if parent is None:
            parent = c.parents[-1] if c.parents else 0
        c.parents.append(sid)
        c.open.append(tags)
        if h is not None:
            c.heights.append(h)
        if d is not None:
            c.decisions.append(d)
        ann = None if self._cold else _bridge(name, d)
        t0 = time.monotonic()
        c0 = time.thread_time()
        try:
            yield sid
        finally:
            cpu = time.thread_time() - c0
            dur = time.monotonic() - t0
            if ann is not None:
                ann.__exit__(None, None, None)
            c.parents.pop()
            c.open.pop()
            if h is not None:
                c.heights.pop()
            if d is not None:
                c.decisions.pop()
            self._append(Span(name, t0, dur, tags, sid, parent, c.thread,
                              cpu))

    def annotate(self, **tags) -> None:
        """Add tags to the innermost open span of this thread: what is only
        known once the work is done (a count, a cache verdict)."""
        if self.enabled:
            c = self._stacks()
            if c.open:
                c.open[-1].update(tags)

    def mark(self, name: str, **tags) -> None:
        """Zero-duration lifecycle event."""
        if not self.enabled:
            return
        c = self._stacks()
        self._inherit(c, tags)
        parent = c.parents[-1] if c.parents else 0
        self._append(Span(name, time.monotonic(), 0.0, tags,
                          next(self._seq), parent, c.thread))

    def record(self, name: str, duration_s: float, *,
               start: float | None = None, parent: int | None = None,
               cpu_s: float | None = None, **tags) -> None:
        """An externally-timed span (e.g. a queue wait measured between
        two events). ``start`` is the ``time.monotonic()`` reading taken
        when the work began; without it the start is back-dated from now,
        which is right only when the record is written the moment the work
        ends. ``parent`` names the causing span when the record is written
        on another thread than the one that caused it. ``cpu_s`` is the
        writer's own ``time.thread_time()`` over the work where it has one;
        a wait between two events on two threads has none."""
        if not self.enabled:
            return
        c = self._stacks()
        self._inherit(c, tags)
        if parent is None:
            parent = c.parents[-1] if c.parents else 0
        if start is None:
            start = time.monotonic() - duration_s
        self._append(Span(name, start, duration_s, tags, next(self._seq),
                          parent, c.thread, cpu_s))

    def _append(self, s: Span) -> None:
        with self._mtx:
            self._spans.append(s)
        if s.name in _MIRROR_SET or s.name == "consensus.step":
            # metric mirror OUTSIDE the ring lock (lock-held-call
            # discipline); lazy import breaks the metrics<->trace cycle
            from tendermint_tpu.utils import metrics as tmmetrics

            m = tmmetrics.GLOBAL_NODE_METRICS
            if m is None:
                return
            if s.name == "consensus.step":
                # the per-step histogram the reference ships
                # (consensus/metrics.go StepDuration); step tag = step name
                m.step_duration.observe(s.duration_s,
                                        step=str(s.tags.get("step", "")))
            else:
                m.trace_phase_seconds.observe(s.duration_s, phase=s.name)

    # --- draining ------------------------------------------------------------

    def dump(self, clear: bool = False) -> list[Span]:
        with self._mtx:
            out = list(self._spans)
            if clear:
                self._spans.clear()
        return out

    def clear(self) -> None:
        with self._mtx:
            self._spans.clear()

    # deliberately NO __len__: an empty ring must not make the tracer
    # falsy (`tracer or DEFAULT` fallbacks would silently misroute spans)
    def size(self) -> int:
        with self._mtx:
            return len(self._spans)

    def summarize(self) -> dict[str, dict]:
        """name -> {count, total_s, cpu_s, max_s} aggregation (``cpu_s``
        over the spans that carry one)."""
        agg: dict[str, dict] = {}
        for s in self.dump():
            a = agg.setdefault(s.name, {"count": 0, "total_s": 0.0,
                                        "cpu_s": 0.0, "max_s": 0.0})
            a["count"] += 1
            a["total_s"] += s.duration_s
            a["cpu_s"] += s.cpu_s or 0.0
            a["max_s"] = max(a["max_s"], s.duration_s)
        return agg

    def last_phase(self) -> dict | None:
        """The most recently COMPLETED span — what a stalled node was last
        able to finish (the soak auditor's stall annotation)."""
        with self._mtx:
            if not self._spans:
                return None
            s = self._spans[-1]
        return {"name": s.name, "height": s.tags.get("height"),
                "round": s.tags.get("round"),
                "age_s": max(0.0, time.monotonic() - (s.start + s.duration_s))}

    def timeline(self, height: int) -> dict:
        """The structured per-height lifecycle (docs/OBSERVABILITY.md):
        every span tagged with this height, start-ordered, plus the
        LIFECYCLE mark census and a causal-order verdict."""
        spans = [s for s in self.dump() if s.tags.get("height") == height]
        spans.sort(key=lambda s: (s.start, s.span_id))
        counts: dict[str, int] = {}
        first_start: dict[str, float] = {}
        phases: dict[str, dict] = {}
        for s in spans:
            counts[s.name] = counts.get(s.name, 0) + 1
            first_start.setdefault(s.name, s.start)
            p = phases.setdefault(s.name, {"count": 0, "total_s": 0.0,
                                           "cpu_s": 0.0})
            p["count"] += 1
            p["total_s"] += s.duration_s
            p["cpu_s"] += s.cpu_s or 0.0
        present = [n for n in LIFECYCLE if n in counts]
        starts = [first_start[n] for n in present]
        causal_ok = all(a <= b for a, b in zip(starts, starts[1:]))
        return {
            "node": self.name,
            "height": height,
            "spans": [s.as_dict() for s in spans],
            "lifecycle": {n: counts.get(n, 0) for n in LIFECYCLE},
            "lifecycle_complete": len(present) == len(LIFECYCLE),
            "causal_ok": causal_ok,
            "phases": phases,
        }

    def describe(self) -> dict:
        return {"name": self.name, "enabled": self.enabled, "cap": self.cap,
                "spans": self.size()}


def handle_tags(height, decision: int) -> dict:
    """height= / decision= for the spans of a handle that captured both at
    dispatch (PendingVerify, the verify service's request), where known."""
    tags = {} if height is None else {"height": height}
    if decision:
        tags["decision"] = decision
    return tags


# --- the census of threads ---------------------------------------------------
# A span says what its own thread got. Which thread has the interpreter the
# rest of the time is read from outside: every live thread's CPU clock, and
# the process's.

_THREAD_CLOCKS: bool | None = None  # can this platform read them? (lazy)


def _thread_clock_id(native_id: int) -> int:
    """Linux's CPU-time clock of the thread with this kernel id: the id
    ``pthread_getcpuclockid`` computes, made from ``Thread.native_id`` so that
    no ``pthread_t`` of a thread that may just have exited is followed. A
    thread that is gone makes ``clock_gettime`` raise OSError."""
    return ((~native_id) << 3) | 6


def _has_thread_clocks() -> bool:
    """True where the clock id above is the platform's own for the calling
    thread and reads like ``time.thread_time()``; checked once."""
    global _THREAD_CLOCKS
    if _THREAD_CLOCKS is None:
        try:
            mine = _thread_clock_id(threading.get_native_id())
            _THREAD_CLOCKS = (
                mine == time.pthread_getcpuclockid(threading.get_ident())
                and abs(time.clock_gettime(mine) - time.thread_time()) < 0.05)
        except (AttributeError, OSError):
            _THREAD_CLOCKS = False
    return _THREAD_CLOCKS


def thread_cpu_times() -> dict | None:
    """{Thread: CPU seconds it has had} over the live threads Python knows,
    or None where the platform has no such clock."""
    if not _has_thread_clocks():
        return None
    out = {}
    for t in threading.enumerate():
        if t.native_id is None:
            continue
        try:
            out[t] = time.clock_gettime(_thread_clock_id(t.native_id))
        except OSError:  # exited between the listing and the reading
            continue
    return out


def _by_name(seconds: dict) -> dict[str, float]:
    """{Thread: s} -> {name: s}, threads of one name summed."""
    out: dict[str, float] = {}
    for t, s in seconds.items():
        out[t.name] = out.get(t.name, 0.0) + s
    return out


def thread_cpu_table() -> dict | None:
    """The process's CPU seconds so far and each live thread's, by name (the
    ``threads`` key of the unsafe_trace answer; needs no tracer)."""
    now = thread_cpu_times()
    if now is None:
        return None
    return {"process_s": time.process_time(), "threads": _by_name(now)}


class ThreadCensus:
    """What each thread got between two readings: the tags of a
    ``consensus.thread_cpu`` or ``fastsync.thread_cpu`` mark."""

    def __init__(self):
        self._last = None  # (monotonic, process_time, {Thread: cpu seconds})

    def read(self) -> dict | None:
        """CPU seconds since the reading before -> {wall_s, process_s,
        threads: name -> s, rest_s, lost}. A thread born since counts its
        whole reading. One that died since is lost with what it got since
        (``lost`` counts them); that, and the threads Python never sees (the
        runtime's, the compiler's), is ``rest_s``: process_s less the threads'
        sum. None on the first reading, which only sets the baseline, and
        where the platform has no such clock."""
        now = thread_cpu_times()
        if now is None:
            return None
        at, process = time.monotonic(), time.process_time()
        last, self._last = self._last, (at, process, now)
        if last is None:
            return None
        at0, process0, before = last
        got = _by_name({t: s - before.get(t, 0.0) for t, s in now.items()})
        return {"wall_s": at - at0, "process_s": process - process0,
                "threads": got,
                "rest_s": process - process0 - sum(got.values()),
                "lost": sum(1 for t in before if t not in now)}


# The process-default tracer: the module-level API's fallback target, and
# what standalone harnesses (bench, tests) use without building a Node.
DEFAULT = Tracer(name="default")

# The start-up ring: always on, written by cold paths only (a key-set miss,
# a jit trace or compile, the calibration), so a process can say where its
# warm-up went without TMTPU_TRACE. It never raises ENABLED: the hot sites'
# guard stays false. Served by the unsafe_trace route (`startup`), summed in
# one log line when crypto.batch.warmup ends.
STARTUP_CAP = 2048
STARTUP = Tracer(name="startup", cap=STARTUP_CAP, cold=True)


def current() -> Tracer:
    """The thread's active tracer (Tracer.activate()), else DEFAULT."""
    t = getattr(_tl, "tracer", None)
    return DEFAULT if t is None else t


# --- module-level delegates (the pre-flight-recorder API surface) -----------


def enable() -> None:
    DEFAULT.enable()


def disable() -> None:
    DEFAULT.disable()


def enabled() -> bool:
    return DEFAULT.enabled


def span(name: str, **tags):
    return current().span(name, **tags)


def mark(name: str, **tags) -> None:
    current().mark(name, **tags)


def record(name: str, duration_s: float, **kw) -> None:
    current().record(name, duration_s, **kw)


def dump(clear: bool = False) -> list[Span]:
    return DEFAULT.dump(clear=clear)


def summarize() -> dict[str, dict]:
    return DEFAULT.summarize()
