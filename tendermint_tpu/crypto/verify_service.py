"""Continuous-batching verify service: ONE device-owning executor for all
signature-verification traffic (ROADMAP item 1).

Every device verify pays a fixed host<->device round trip (the sync floor)
once per DECISION, whatever the kernel's speed. Verify-ahead
(blockchain/pipeline.py) and the batched readback
(crypto/batch.prefetch) only amortize that floor across decisions ONE
CALLER already has in flight; nothing shares it across CALLERS. A 50-node
fabric, a consensus drain racing a fast-sync burst, or light range chunks
each pay their own floor.

This module applies the inference-serving fix — continuous batching — to
the verify plane:

 * every kernel-worthy ``BatchVerifier.dispatch()`` (the consensus vote
   drain, fast-sync verify-ahead, light ``range_verify``, statesync via the
   light client — the whole registry in crypto/batch.py) submits its items
   to one process-wide :class:`VerifyService` and gets back a
   ``ServicePending`` with unchanged PendingVerify semantics;
 * a dedicated executor thread COALESCES requests arriving within a short
   window (``TMTPU_VERIFY_WINDOW_US``) into one shared kernel launch per
   key type — N concurrent dispatches pay ONE sync floor;
 * generations are DOUBLE-BUFFERED: while generation k's kernel computes
   and its D2H copy flies (copy_to_host_async starts at dispatch), the
   executor host-preps and dispatches generation k+1, and only then blocks
   on k's readback;
 * the launch goes through the SAME ``ops.*.dispatch_batch`` the callers
   used directly — the route (ops/ed25519_batch.route_batch, asked again
   on the COALESCED size: crossover, multi-device sharding), the
   ``ops.*.device`` fault sites, and the circuit breaker all apply
   unchanged, so bitmaps are byte-identical and a device failure
   mid-coalesce degrades to the host fallback with every waiter resolved
   exactly once;
 * validator keys' comb tables stay device-resident across heights and
   across interleavings in the per-key table of ops/ed25519_batch
   (KeyTable, a row a key): a coalesced launch's novel pubkey interleaving
   pays only the O(n) key -> row mapping, and builds nothing;
 * the single blocking readback point is :func:`_readback` (audited by the
   tmlint ``device-sync-choke-point`` rule, and routed through
   crypto/batch._device_get so the perf-gate fetch spy still counts it);
 * queue/launch/readback/replay spans are recorded on the DISPATCHING
   node's tracer (each request captures utils/trace.current() at submit,
   with the decision id and the span that caused it), so flight-recorder
   phase attribution stays per-node-accurate and every span of a commit
   decision, on the caller's thread or this one, is one tree. The executor
   works INSIDE real spans of the first request's tracer, so the ops
   layer's prep.* spans nest under verify.host_prep; other nodes' tracers
   that share a launch get a recorded copy.

Knobs (docs/CONFIG.md): ``TMTPU_VERIFY_SERVICE=0`` restores direct
per-caller dispatch; ``TMTPU_VERIFY_WINDOW_US`` sets the coalescing window
(default 150). A shared launch holds at most ``MAX_BATCH`` items.
"""

from __future__ import annotations

import contextlib
import importlib
import os
import queue
import threading
import time as _time

from tendermint_tpu.crypto import batch as _batch
from tendermint_tpu.utils import trace as _trace

_OPS_MODULES = {
    "ed25519": "tendermint_tpu.ops.ed25519_batch",
    "sr25519": "tendermint_tpu.ops.sr25519_batch",
}


def enabled() -> bool:
    """False only when the operator opted out (TMTPU_VERIFY_SERVICE=0;
    read per dispatch so tests and the concurrent_verify bench can flip it
    without restarting)."""
    return os.environ.get("TMTPU_VERIFY_SERVICE") != "0"


def force_all() -> bool:
    """TMTPU_VERIFY_SERVICE=1: route EVERY kernel-worthy dispatch through
    the service, including sub-crossover host batches (tests, the graft
    stage, and the concurrent_verify bench use this to make coalescing
    deterministic)."""
    return os.environ.get("TMTPU_VERIFY_SERVICE") == "1"


def window_us(default: int = 150) -> int:
    """Coalescing window: how long the executor waits for more dispatches
    after the first before launching. Latency cost for a lone caller; the
    price of sharing the floor for concurrent ones. TMTPU_VERIFY_WINDOW_US
    overrides."""
    v = os.environ.get("TMTPU_VERIFY_WINDOW_US")
    try:
        return max(0, int(v)) if v else default
    except ValueError:
        return default


# Item cap per shared launch: bounds the worst-case host-prep latency and
# the device memory of one generation.
MAX_BATCH = 65536


def _readback(tree):
    """THE service's single blocking D2H point (tmlint
    device-sync-choke-point audited site). Routed through
    crypto/batch._device_get so every blocking fetch in the process still
    funnels through one instrumented choke (and the perf-gate fetch spy
    counts the service's readbacks too)."""
    return _batch._device_get(tree)


def _safe_record(req, name: str, duration_s: float, start: float,
                 **tags) -> None:
    """Flight-recorder writes from the executor must never be able to
    strand a generation's waiters: a tracer/metric-mirror failure is
    swallowed (the span is lost, the verification is not). `start` is when
    the work began; `req` gives the tracer, the causing span, the decision
    and the height captured on the submitting thread."""
    try:
        req.tracer.record(name, duration_s, start=start,
                          parent=req.parent or None, **req.tags(), **tags)
    except Exception:  # noqa: BLE001 - observability never blocks resolution
        pass


@contextlib.contextmanager
def _safe_span(req, name: str, **tags):
    """The executor's work inside a REAL span of `req`'s tracer (activated
    on this thread, so the ops layer's spans nest under it), or nothing
    when no request of the launch is traced. Same promise as _safe_record:
    the tracer's own failures are swallowed, the body's are not."""
    stack = None
    if req is not None:
        stack = contextlib.ExitStack()
        try:
            stack.enter_context(req.tracer.activate())
            stack.enter_context(req.tracer.span(
                name, parent=req.parent or None, **req.tags(), **tags))
        except Exception:  # noqa: BLE001 - the span is lost, the work is not
            stack.close()
            stack = None
    try:
        yield
    finally:
        if stack is not None:
            try:
                stack.close()
            except Exception:  # noqa: BLE001
                pass


class _Request:
    """One caller's dispatch: items of one key type, a completion event the
    waiter's ServicePending blocks on, and the flight-recorder context
    captured on the submitting thread."""

    __slots__ = ("kind", "items", "force_device", "done", "result", "error",
                 "tracer", "t_submit", "t_done", "height", "decision",
                 "parent")

    def __init__(self, kind, items, force_device):
        self.kind = kind
        self.items = items
        self.force_device = force_device
        self.done = threading.Event()
        self.result: tuple[bool, list[bool]] | None = None
        self.error: BaseException | None = None
        self.tracer = None
        self.t_submit = 0.0
        self.t_done = 0.0     # stamped just before done.set() (verify.wake)
        self.height = None
        self.decision = 0     # id of the commit decision this serves, or 0
        self.parent = 0       # the span that submitted it

    def tags(self) -> dict:
        """height= / decision= for this request's spans, where known."""
        return _trace.handle_tags(self.height, self.decision)


class VerifyService:
    """The device-owning executor. One per process (see :func:`get`)."""

    def __init__(self) -> None:
        self._q: "queue.Queue[_Request]" = queue.Queue()
        self._thread: threading.Thread | None = None
        self._thread_mtx = threading.Lock()
        # observability counters (read by bench.py concurrent_verify and
        # the service tests; plain ints — the GIL makes += atomic enough
        # for monitoring)
        self.launches = 0            # shared kernel/host launches issued
        self.requests = 0            # dispatches submitted
        self.coalesced_items = 0     # items across all launches
        self.max_coalesced = 0       # most requests sharing one generation
        self.fallbacks = 0           # generations resolved via scalar floor

    # --- submission (any thread) -------------------------------------------

    def submit(self, kind: str, items, force_device: bool = False):
        """Queue one verify request; returns the caller's ServicePending.
        Never blocks beyond the queue put."""
        req = _Request(kind, items, force_device)
        if _trace.ENABLED:
            tr = _trace.current()
            if tr.enabled:
                req.tracer = tr
                req.height = tr.current_height()
                req.decision = tr.current_decision()
                req.parent = tr.current_span()
        req.t_submit = _time.monotonic()
        self.requests += 1
        self._ensure_thread()
        self._q.put(req)
        return _batch.ServicePending(req)

    def _ensure_thread(self) -> None:
        t = self._thread
        if t is not None and t.is_alive():
            return
        with self._thread_mtx:
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._run, name="verify-service", daemon=True)
                self._thread.start()

    # --- executor loop ------------------------------------------------------

    def _run(self) -> None:
        gen = None  # the in-flight (dispatched, unfetched) generation
        while True:
            try:
                if gen is None:
                    first = self._q.get()
                    gen = self._dispatch(self._collect(first))
                # Double-buffer: while generation k computes (its D2H copy
                # started at dispatch), host-prep and dispatch k+1; only
                # then block on k's readback.
                try:
                    nxt = self._q.get_nowait()
                except queue.Empty:
                    self._complete(gen)
                    gen = None
                    continue
                gen2 = self._dispatch(self._collect(nxt))
                self._complete(gen)
                gen = gen2
            except Exception as e:  # noqa: BLE001 - executor must never die
                # Anything that slipped past the per-generation fallbacks
                # (dispatch/complete/launch resolve their own requests on
                # failure). The in-flight generation's waiters MUST still
                # resolve — a stranded done-event is a silent node stall.
                if gen is not None:
                    for (_kind, mod, greqs, _items, _dev, _finish) in gen:
                        try:
                            self._resolve_scalar(mod, greqs)
                        except Exception:  # noqa: BLE001 - last resort
                            self._resolve_error(greqs, e)
                    gen = None
                continue

    def _collect(self, first: _Request) -> list[_Request]:
        """The continuous-batching step: drain requests arriving within the
        coalescing window (or already queued) into one generation, bounded
        by MAX_BATCH items."""
        reqs = [first]
        n = len(first.items)
        deadline = _time.monotonic() + window_us() / 1e6
        while n < MAX_BATCH:
            remaining = deadline - _time.monotonic()
            try:
                r = (self._q.get(timeout=remaining) if remaining > 0
                     else self._q.get_nowait())
            except queue.Empty:
                break
            reqs.append(r)
            n += len(r.items)
        return reqs

    def _dispatch(self, reqs: list[_Request]):
        """Group a generation by key type and issue one shared
        ops.dispatch_batch per kind (host prep + device dispatch, nothing
        fetched). Returns the in-flight generation for _complete()."""
        t0 = _time.monotonic()
        for r in reqs:
            if r.tracer is not None:
                _safe_record(r, "verify.queue", t0 - r.t_submit, r.t_submit)
        groups: dict[str, list[_Request]] = {}
        for r in reqs:
            groups.setdefault(r.kind, []).append(r)
        gen = []
        for kind, greqs in groups.items():
            gen.append(self._launch(kind, greqs))
        return [g for g in gen if g is not None]

    def _launch(self, kind: str, greqs: list[_Request]):
        items = [it for r in greqs for it in r.items]
        force = any(r.force_device for r in greqs)
        try:
            mod = importlib.import_module(_OPS_MODULES[kind])
        except Exception as e:  # noqa: BLE001 - unknown kind / import failure
            self._resolve_error(greqs, e)
            return None
        lead, others, tags = self._traced(greqs)
        tags.update(kind=kind, sigs=len(items))
        t0 = _time.monotonic()
        try:
            # Same entry the callers used directly: crossover routing,
            # sharding on the COALESCED size, ops.*.device fault site, and
            # the circuit breaker (a dispatch-time device failure already
            # comes back as the host fallback's (None, finish)).
            with _safe_span(lead, "verify.host_prep", **tags):
                dev, finish = mod.dispatch_batch(items, force_device=force)
        except Exception:  # noqa: BLE001 - belt and braces under the breaker
            self._resolve_scalar(mod, greqs)
            return None
        prep_s = _time.monotonic() - t0
        self.launches += 1
        self.coalesced_items += len(items)
        self.max_coalesced = max(self.max_coalesced, len(greqs))
        for r in others:
            _safe_record(r, "verify.host_prep", prep_s, t0, **tags)
        return (kind, mod, greqs, items, dev, finish)

    def _complete(self, gen) -> None:
        """Readback + per-request replay of one in-flight generation: ONE
        blocking fetch per kind, then slice each request's bitmap and set
        its completion event. Fetch-time device failures degrade through
        the kind's breaker to the host fallback; every waiter resolves
        exactly once on every path."""
        for kind, mod, greqs, items, dev, finish in gen:
            lead, others, tags = self._traced(greqs)
            t0 = _time.monotonic()
            with _safe_span(lead, "verify.readback", **tags):
                got = self._fetch(mod, greqs, items, dev, finish)
            if got is None:
                continue
            fetched, finish = got
            t1 = _time.monotonic()
            with _safe_span(lead, "verify.replay", **tags):
                ok = self._replay(mod, greqs, finish, fetched)
            if not ok:
                continue
            t2 = _time.monotonic()
            self._observe(greqs, t2, getattr(finish, "route", ""))
            for r in others:
                _safe_record(r, "verify.readback", t1 - t0, t0, **tags)
                _safe_record(r, "verify.replay", t2 - t1, t1, **tags)
            # wake waiters LAST: a woken caller immediately contends for
            # the GIL, which would otherwise inflate the replay span with
            # the callers' own post-resolve work
            for r in greqs:
                r.t_done = _time.monotonic()
                r.done.set()

    def _fetch(self, mod, greqs, items, dev, finish):
        """The generation's one blocking readback. -> (fetched, finish), or
        None when a dead device at fetch left nothing but the scalar floor
        (every waiter then already resolved)."""
        if dev is None:
            return None, finish
        try:
            return _readback(dev), finish
        except Exception as e:  # noqa: BLE001 - dead device at fetch
            mod.BREAKER.record_failure(e)
            try:
                _, finish = mod._host_fallback(items, len(items),
                                               route="breaker_fallback")
                return None, finish
            except Exception:  # noqa: BLE001
                self._resolve_scalar(mod, greqs)
                return None

    def _replay(self, mod, greqs, finish, fetched) -> bool:
        """Bitmap -> each request's (all_ok, lanes). False when finish
        itself failed and the scalar floor resolved the waiters."""
        try:
            bitmap = finish(fetched)
        except Exception:  # noqa: BLE001 - finish_cb already fell back
            self._resolve_scalar(mod, greqs)
            return False
        off = 0
        for r in greqs:
            n = len(r.items)
            lanes = [bool(b) for b in bitmap[off:off + n]]
            off += n
            r.result = (all(lanes), lanes)
        return True

    # --- degradation floors -------------------------------------------------

    def _resolve_scalar(self, mod, greqs: list[_Request]) -> None:
        """Last-rung fallback: resolve every waiter via the kind's host
        fallback (C verifier when loaded, else the pure-Python scalar
        loop). Never raises into the executor loop; a request whose scalar
        replay itself fails gets the error (resolve() re-raises it on the
        WAITER's thread, where callers already have serial fallbacks)."""
        self.fallbacks += 1
        for r in greqs:
            if r.done.is_set():
                continue
            try:
                _, fb = mod._host_fallback(r.items, len(r.items))
                lanes = [bool(b) for b in fb(None)]
                r.result = (all(lanes), lanes)
            except Exception as e:  # noqa: BLE001
                r.error = e
            r.t_done = _time.monotonic()
            r.done.set()

    def _resolve_error(self, greqs: list[_Request], e: BaseException) -> None:
        for r in greqs:
            if not r.done.is_set():
                r.error = e
                r.t_done = _time.monotonic()
                r.done.set()

    # --- helpers ------------------------------------------------------------

    @staticmethod
    def _traced(greqs):
        """-> (lead, others, tags). `lead` is the first traced request of
        the launch: the executor works inside real spans of ITS tracer.
        `others` is one request for every further tracer that shares the
        launch; those get a recorded copy, so shared-phase durations land
        ONCE per node per generation and a node with several requests in
        one launch doesn't double-count the shared prep/readback. `tags`
        says how many requests coalesced and, when the launch serves more
        than one decision, which (`decisions`)."""
        lead, others, seen = None, [], set()
        for r in greqs:
            if r.tracer is not None and id(r.tracer) not in seen:
                seen.add(id(r.tracer))
                if lead is None:
                    lead = r
                else:
                    others.append(r)
        tags = {"coalesced": len(greqs)}
        served = sorted({r.decision for r in greqs if r.decision})
        if len(served) > 1:
            tags["decisions"] = served
        return lead, others, tags

    def _observe(self, greqs, t_done: float, route: str) -> None:
        """Per-REQUEST metrics, preserving the direct path's semantics:
        batch_verify_seconds spans dispatch(submit)->resolved — host prep,
        coalescing window, queue, device, and readback included — so the
        histogram's meaning does not silently change with the service on.
        `route` is the one that answered the shared launch."""
        try:
            from tendermint_tpu.utils import metrics as tmmetrics

            m = tmmetrics.GLOBAL_NODE_METRICS
            if m is None:
                return
            for r in greqs:
                m.batch_verify_seconds.observe(t_done - r.t_submit,
                                               route=route)
                m.batch_verify_sigs.add(len(r.items))
        except Exception:  # noqa: BLE001 - metrics must not strand waiters
            pass


_SERVICE: VerifyService | None = None
_SERVICE_LOCK = threading.Lock()


def get() -> VerifyService:
    """The process-wide service (lazy; the executor thread starts on first
    submit)."""
    global _SERVICE
    s = _SERVICE
    if s is None:
        with _SERVICE_LOCK:
            if _SERVICE is None:
                _SERVICE = VerifyService()
            s = _SERVICE
    return s


def reset() -> None:
    """Tests: drop the singleton (a fresh one spins up on next submit; the
    old executor thread drains its queue and then idles forever — daemon,
    so it never blocks teardown)."""
    global _SERVICE
    with _SERVICE_LOCK:
        _SERVICE = None
