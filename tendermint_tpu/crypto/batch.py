"""BatchVerifier: the pluggable batch signature-verification registry.

THE capability the reference lacks entirely (SURVEY.md: v0.34 has no
BatchVerifier interface; every verify path is a serial loop over
crypto.PubKey.VerifySignature, reference crypto/crypto.go:22-28). This module
introduces it: callers accumulate (pubkey, msg, sig) triples and flush them in
one call, which on TPU becomes a single wide Edwards-curve kernel launch
(tendermint_tpu.ops.ed25519_batch).

Semantics contract: `verify()` returns a per-item bitmap whose entries are
byte-identical to what the scalar `pub_key.verify_signature` path returns for
the same item. Callers that need the reference's serial early-exit/error-
attribution behavior (e.g. ValidatorSet.VerifyCommitLight) replay the serial
decision procedure over the bitmap -- verification is batched, the consensus
semantics are not changed.

Deferred contract: `dispatch()` issues all host prep + device work and
returns a :class:`PendingVerify` handle; `PendingVerify.resolve()` performs
the blocking device readback (if any) and returns the same (all_ok, bitmap)
pair `verify()` would. Every blocking fetch pays a host<->device round
trip whatever the batch size, so the point of the split is that callers with
SEVERAL decisions in flight (fast-sync verify-ahead, light range sync, the
consensus vote drain) fetch them in one `jax.device_get` via
:func:`prefetch` / :func:`resolve_all` instead of one round trip per
decision.
"""

from __future__ import annotations

import abc
import logging
import os
import threading
import time as _time

from tendermint_tpu.crypto import keys
from tendermint_tpu.utils import trace as _trace

_log = logging.getLogger(__name__)


def _device_get(tree):
    """THE choke point for blocking D2H readbacks of the deferred verify
    API. Every PendingVerify fetch funnels through here so (a) prefetch can
    batch several pendings' outputs into one call and (b) tests can count
    blocking fetches with a spy (tests/test_perf_gate.py)."""
    import jax

    return jax.device_get(tree)


class PendingVerify:
    """A dispatched-but-unfetched batch verification.

    ``devs`` is the list of device outputs still in flight (None entries are
    sub-batches that already resolved on host); ``resolve_fn(fetched)`` --
    with ``fetched`` parallel to ``devs`` -- replays the per-item bitmap.
    ``resolve()`` is idempotent: the first call fetches and caches, later
    calls return the cached (all_ok, bitmap). ``children`` are sub-handles
    (MixedBatchVerifier's per-key-type pendings, which may be
    service-backed) whose in-flight state counts toward
    has_device_output()."""

    __slots__ = ("_devs", "_resolve", "_result", "_tracer", "_t_disp",
                 "_t_height", "_t_decision", "_t_parent", "_children")

    def __init__(self, devs, resolve_fn, children=()):
        self._devs = list(devs)
        self._resolve = resolve_fn
        self._result: tuple[bool, list[bool]] | None = None
        self._children = tuple(children)
        # flight-recorder context captured at dispatch (utils/trace.py):
        # the dispatching node's tracer, the dispatch timestamp (queue-wait
        # phase = resolve start - dispatch end), and the height, decision id
        # and causing span, so phases land on the right timeline and in the
        # right decision's tree even when resolve happens later, elsewhere
        self._tracer = None
        self._t_disp = 0.0
        self._t_height = None
        self._t_decision = 0
        self._t_parent = 0

    @property
    def resolved(self) -> bool:
        return self._result is not None

    def _devs_pending(self) -> bool:
        """Unfetched device buffers of THIS handle (children excluded):
        exactly the condition under which a _device_get is warranted."""
        return self._result is None and any(d is not None for d in self._devs)

    def has_device_output(self) -> bool:
        """True when resolve() will block — on a device fetch, or on a
        service-backed child whose shared launch is still in flight."""
        if self._result is not None:
            return False
        return (self._devs_pending()
                or any(c.has_device_output() for c in self._children))

    def _finish(self, fetched) -> None:
        self._result = self._resolve(fetched)
        # release device buffers (and the resolve closure's captures)
        self._devs = [None] * len(self._devs)
        self._resolve = None

    def _capture(self, tracer) -> None:
        """Flight-recorder context of the dispatching thread, now."""
        self._tracer = tracer
        self._t_disp = _time.monotonic()
        self._t_height = tracer.current_height()
        self._t_decision = tracer.current_decision()
        self._t_parent = tracer.current_span()

    def _trace_tags(self) -> dict:
        return _trace.handle_tags(self._t_height, self._t_decision)

    def _record_queue(self, tracer, now: float) -> None:
        """verify.queue: dispatch -> now, caused by the dispatching span."""
        if self._t_disp:
            tracer.record("verify.queue", now - self._t_disp,
                          start=self._t_disp, parent=self._t_parent or None,
                          **self._trace_tags())

    def resolve(self) -> tuple[bool, list[bool]]:
        """Fetch (one _device_get when device outputs are pending) and
        return (all_ok, bitmap)."""
        if self._result is None:
            tr = self._tracer
            if tr is not None and tr.enabled:
                tags = self._trace_tags()
                self._record_queue(tr, _time.monotonic())
                # _devs_pending, NOT has_device_output: a handle whose only
                # in-flight work is service-backed children has nothing to
                # fetch itself — a _device_get here would be a pointless
                # trip through the audited choke (and a phantom count on
                # the perf-gate fetch spy)
                if self._devs_pending():
                    with tr.span("verify.readback", **tags):
                        fetched = _device_get(self._devs)
                else:
                    fetched = self._devs
                with tr.span("verify.replay", **tags):
                    self._finish(fetched)
            else:
                fetched = (_device_get(self._devs) if self._devs_pending()
                           else self._devs)
                self._finish(fetched)
        return self._result


class ServicePending(PendingVerify):
    """A dispatch routed through the continuous-batching verify service
    (crypto/verify_service.py). The service executor owns host prep, the
    shared (coalesced) kernel launch, and the single batched readback;
    resolve() therefore waits on the request's completion event instead of
    fetching device buffers itself. Exactly-once: the executor resolves
    every request exactly once (result or error), and resolve() caches."""

    __slots__ = ("_req",)

    def __init__(self, req):
        super().__init__([], None)
        self._req = req

    def has_device_output(self) -> bool:
        """True while the shared launch is still in flight (resolve() would
        block on the service), so async callers (the vote drain, the
        verify-ahead pipeline) keep overlapping exactly as they do with a
        raw device handle."""
        return self._result is None and not self._req.done.is_set()

    def _finish(self, _fetched) -> None:
        req = self._req
        if req.tracer is not None and not req.done.is_set():
            # the caller really sleeps on the executor: time its wake-up
            # (done.set() on the executor -> this thread runs again)
            req.done.wait()
            req.tracer.record("verify.wake", _time.monotonic() - req.t_done,
                              start=req.t_done, parent=req.parent or None,
                              **req.tags())
        else:
            req.done.wait()
        if req.error is not None:
            raise req.error
        self._result = req.result
        self._req = None
        self._resolve = None
        self._devs = []

    def resolve(self) -> tuple[bool, list[bool]]:
        if self._result is None:
            self._finish(None)
        return self._result


def prefetch(pendings) -> None:
    """Fetch every unresolved pending's device outputs in ONE _device_get.

    K sequential resolves cost K host<->device round trips, one batched
    fetch costs one. Results are cached on each handle,
    so the later in-order resolve() calls return instantly. Host-resolved
    pendings are untouched. Service-backed pendings (ServicePending) carry
    no device outputs of their own — the verify service already coalesces
    their readbacks into its single fetch point — so they are simply
    waited on."""
    unres = [p for p in pendings if p.has_device_output()]
    svc = [p for p in unres if not p._devs_pending()]
    unres = [p for p in unres if p._devs_pending()]
    for p in svc:
        p.resolve()
    if not unres:
        return
    if _trace.ENABLED:
        tr = _trace.current()
        if tr.enabled:
            now = _time.monotonic()
            for p in unres:
                p._record_queue(p._tracer if p._tracer is not None else tr, now)
            # one fetch for several decisions: the spans name all of them
            served = sorted({p._t_decision for p in unres if p._t_decision})
            tags = {"decisions": served} if served else {}
            with tr.span("verify.readback", batched=len(unres), **tags):
                fetched = _device_get([p._devs for p in unres])
            with tr.span("verify.replay", batched=len(unres), **tags):
                for p, f in zip(unres, fetched):
                    p._finish(f)
            return
    fetched = _device_get([p._devs for p in unres])
    for p, f in zip(unres, fetched):
        p._finish(f)


def resolve_all(pendings) -> list[tuple[bool, list[bool]]]:
    """prefetch() + in-order resolve() of every handle."""
    prefetch(pendings)
    return [p.resolve() for p in pendings]


class BatchVerifier(abc.ABC):
    @abc.abstractmethod
    def add(self, pub_key: keys.PubKey, msg: bytes, sig: bytes) -> None:
        """Queue one (pubkey, message, signature) item."""

    @abc.abstractmethod
    def verify(self) -> tuple[bool, list[bool]]:
        """Verify everything queued. Returns (all_ok, per-item bitmap) and
        resets the queue."""

    def dispatch(self, force_device: bool = False) -> PendingVerify:
        """Issue host prep + device dispatch without fetching; resets the
        queue. Default (scalar) implementation verifies eagerly and returns
        an already-resolved handle."""
        res = self.verify()
        p = PendingVerify([None], None)
        p._result = res
        return p

    @abc.abstractmethod
    def __len__(self) -> int: ...


class ScalarBatchVerifier(BatchVerifier):
    """Fallback: the reference's serial loop, for key types without a batch
    kernel (and for differential testing)."""

    def __init__(self) -> None:
        self._items: list[tuple[keys.PubKey, bytes, bytes]] = []

    def add(self, pub_key: keys.PubKey, msg: bytes, sig: bytes) -> None:
        self._items.append((pub_key, msg, sig))

    def verify(self) -> tuple[bool, list[bool]]:
        out = [pk.verify_signature(m, s) for (pk, m, s) in self._items]
        self._items = []
        return all(out), out

    def __len__(self) -> int:
        return len(self._items)


def batch_min(default: int = 32) -> int:
    """Batch-size threshold below which the kernel is never launched.

    A 1-vote commit (single-validator chains, gossiped singles) must not pay
    kernel dispatch -- and on a cold process must not pay XLA compilation.
    The crossover depends on the SCALAR path's speed, so each verifier
    passes its own default: ed25519's scalar path is ~1-3 ms/sig (crossover
    in the tens of sigs), sr25519's is pure Python at ~18 ms/sig (crossover
    ~8). TM_TPU_BATCH_MIN overrides both."""
    v = os.environ.get("TM_TPU_BATCH_MIN")
    return int(v) if v else default


class _KernelBatchVerifier(BatchVerifier):
    """Shared body of the TPU-batched verifiers: a scalar fallback below
    batch_min (a kernel launch never pays off for a handful of sigs), the
    kernel dispatch, and metrics. Subclasses name the scalar + ops modules."""

    _scalar_module: str
    _ops_module: str
    _kind: str = ""
    _batch_min_default: int = 32

    def __init__(self) -> None:
        self._items: list[tuple[bytes, bytes, bytes]] = []

    def add(self, pub_key: keys.PubKey, msg: bytes, sig: bytes) -> None:
        self._items.append((pub_key.bytes(), msg, sig))

    @classmethod
    def _module(cls, spec_attr: str) -> object:
        """Resolve + cache cls.<spec_attr> per class: the hot addVote drain
        flushes thousands of times per second, and an importlib round trip
        (sys.modules lookup + lock) per flush is pure overhead."""
        cache_attr = spec_attr + "_cache"
        mod = cls.__dict__.get(cache_attr)
        if mod is None:
            import importlib

            mod = importlib.import_module(getattr(cls, spec_attr))
            setattr(cls, cache_attr, mod)
        return mod

    def dispatch(self, force_device: bool = False) -> PendingVerify:
        """Issue host prep + device dispatch without fetching. Returns a
        PendingVerify whose resolve() -> (all_ok, bitmap). Small batches
        verify scalar immediately (no device output to fetch).
        force_device=True pins the device kernel regardless of the host
        crossover (pipelined callers whose chunks overlap other host
        work)."""
        items, self._items = self._items, []
        # one decision for both key types, kept with the ed25519 kernel
        from tendermint_tpu.ops import ed25519_batch

        route = ed25519_batch.route_batch(
            len(items), force_device, batch_min(self._batch_min_default))
        if route == "scalar":
            # Pure-Python scalar fallback only when the C host verifier is
            # missing: with it, the ops dispatch routes ANY size to the host
            # path below the measured crossover.
            scalar = self._module("_scalar_module")
            out = [scalar.verify(p, m, s) for (p, m, s) in items]
            return PendingVerify([None], lambda _f, _r=(all(out), out): _r)
        # DEVICE-BOUND batches ("device", "sharded": the routes that pay the
        # host<->device sync floor) go through the continuous-batching
        # verify service (crypto/verify_service.py): ONE device-owning
        # executor coalesces concurrent dispatches into shared kernel
        # launches, so N simultaneous callers pay one sync floor, not N.
        # Sub-crossover host batches (inline C verify, no floor) stay direct
        # -- a thread hop + coalescing window per tiny flush is pure loss
        # there; at 50-node-fabric scale (tiny vote drains, thousands of
        # threads on one core) that serialization point measurably stalls
        # consensus. The service calls the same ops dispatch_batch below
        # (same routing on the COALESCED size, fault sites, breaker), so the
        # bitmap is byte-identical; TMTPU_VERIFY_SERVICE=0 restores direct
        # dispatch for everything, =1 forces everything onto the service
        # (tests/bench).
        from tendermint_tpu.crypto import verify_service

        if verify_service.enabled() and (
                verify_service.force_all() or route != "host"):
            return verify_service.get().submit(self._kind, items,
                                               force_device=force_device)
        import time as _t

        from tendermint_tpu.utils import metrics as tmmetrics

        ops = self._module("_ops_module")
        started = _t.monotonic()
        if _trace.ENABLED:  # flight recorder: host-prep phase attribution
            tracer = _trace.current()
            with tracer.span("verify.host_prep", n=len(items)):
                dev, finish = ops.dispatch_batch(items,
                                                 force_device=force_device)
        else:
            tracer = None
            dev, finish = ops.dispatch_batch(items, force_device=force_device)

        def resolve(fetched):
            out = [bool(b) for b in finish(fetched[0])]
            if tmmetrics.GLOBAL_NODE_METRICS is not None:
                m = tmmetrics.GLOBAL_NODE_METRICS
                # the route is read after finish(): a fetch-time device
                # failure turns it into breaker_fallback
                m.batch_verify_seconds.observe(_t.monotonic() - started,
                                               route=finish.route)
                m.batch_verify_sigs.add(len(items))
            return all(out), out

        p = PendingVerify([dev], resolve)
        if tracer is not None and tracer.enabled:
            p._capture(tracer)
        return p

    def verify(self) -> tuple[bool, list[bool]]:
        return self.dispatch().resolve()

    def __len__(self) -> int:
        return len(self._items)


class Ed25519BatchVerifier(_KernelBatchVerifier):
    """TPU-batched ed25519 (tendermint_tpu.ops.ed25519_batch)."""

    _scalar_module = "tendermint_tpu.crypto.ed25519"
    _ops_module = "tendermint_tpu.ops.ed25519_batch"
    _kind = "ed25519"


class Sr25519BatchVerifier(_KernelBatchVerifier):
    """TPU-batched sr25519 (tendermint_tpu.ops.sr25519_batch): the Edwards
    comb kernel with merlin challenges batched in C. The reference verifies
    sr25519 serially through go-schnorrkel (crypto/sr25519/pubkey.go:10)."""

    _scalar_module = "tendermint_tpu.crypto.sr25519"
    _ops_module = "tendermint_tpu.ops.sr25519_batch"
    _kind = "sr25519"
    # Pure-Python scalar fallback costs ~18 ms/sig; the kernel pays off
    # almost immediately.
    _batch_min_default = 8


class MixedBatchVerifier(BatchVerifier):
    """Routes items to a per-key-type verifier, preserving item order in the
    result bitmap. Lets commits with mixed ed25519/sr25519/secp256k1 validator
    sets still batch the ed25519 majority."""

    def __init__(self) -> None:
        self._order: list[tuple[str, int]] = []
        self._subs: dict[str, BatchVerifier] = {}

    def add(self, pub_key: keys.PubKey, msg: bytes, sig: bytes) -> None:
        kt = pub_key.type
        sub = self._subs.get(kt)
        if sub is None:
            sub = create_batch_verifier(kt)
            self._subs[kt] = sub
        self._order.append((kt, len(sub)))
        sub.add(pub_key, msg, sig)

    def dispatch(self, force_device: bool = False) -> PendingVerify:
        """Issue every key type's dispatch without fetching. The returned
        PendingVerify's device-output list is the concatenation of every
        sub-verifier's outputs, so one resolve() (or a cross-decision
        prefetch) fetches a mixed ed25519+sr25519 commit in ONE device_get
        instead of one round trip per key type."""
        spans = []  # (key type, sub PendingVerify, offset into devs, n devs)
        devs: list = []
        for kt, sub in self._subs.items():
            p = sub.dispatch(force_device=force_device)
            spans.append((kt, p, len(devs), len(p._devs)))
            devs.extend(p._devs)
        order = self._order
        self._order = []
        self._subs = {}

        def resolve(fetched):
            results = {}
            for kt, p, off, n in spans:
                if not p.resolved:
                    p._finish(fetched[off:off + n])
                results[kt] = p._result[1]
            out = [results[kt][i] for (kt, i) in order]
            return all(out), out

        # Children make has_device_output() see through to service-backed
        # sub-handles (their shared launch is in flight but they carry no
        # device outputs of their own), so async callers keep overlapping.
        mixed = PendingVerify(devs, resolve,
                              children=[p for (_, p, _, _) in spans])
        if _trace.ENABLED:
            tracer = _trace.current()
            # Own the queue/readback attribution UNLESS a service-backed
            # child is involved: the service executor already records those
            # phases per request, and a second caller-side queue record
            # would double-count the wait. Host-resolved and direct-device
            # mixed batches keep their pre-service span coverage.
            svc_children = any(isinstance(p, ServicePending)
                               for (_, p, _, _) in spans)
            if tracer.enabled and not svc_children:
                mixed._capture(tracer)
        return mixed

    def verify(self) -> tuple[bool, list[bool]]:
        # Dispatch every key type's kernel first, then fetch ALL results in
        # one device_get: a mixed ed25519+sr25519 commit pays one
        # host<->device round trip instead of two.
        return self.dispatch().resolve()

    def __len__(self) -> int:
        return len(self._order)


class WarmupStatus:
    """Outcome of the kernel warm-up, readable by whoever started it (Node,
    chip_smoke.py): ``state`` is idle -> running -> done | failed, ``error``
    the exception of a failed run, ``thread`` the background thread (of the
    newest run: it waits for the one before it), ``key_types`` the key types
    whose kernel a run has warmed or is warming."""

    def __init__(self) -> None:
        self.state = "idle"
        self.error: BaseException | None = None
        self.thread = None
        self.key_types: set[str] = set()

    def join(self, timeout: float | None = None) -> bool:
        """Wait for a background warm-up; True when none is left running.
        A process must not exit while an XLA compile is mid-flight in this
        thread (the C++ runtime aborts at teardown)."""
        t = self.thread
        if t is not None:
            t.join(timeout)
            return not t.is_alive()
        return True


WARMUP = WarmupStatus()
_WARMUP_LOCK = threading.Lock()


def _warm_kernel(kind: str, verify_batch, item, sizes) -> None:
    """One key type's kernel at every warm bucket size, each launch a
    ``startup.warm_kernel`` span of the start-up ring. force_device: the
    point is compiling the kernel buckets, which the host route would
    otherwise absorb."""
    for n in sizes:
        with _trace.STARTUP.span("startup.warm_kernel", kind=kind, sigs=n):
            verify_batch([item] * n, force_device=True)


def _warm_ed25519(sizes) -> None:
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.ops import ed25519_batch

    # Measure the host/kernel crossover first so the warm buckets
    # below compile the path real batches will actually take.
    ed25519_batch.calibrate_host_crossover()
    priv = ed25519.gen_priv_key(b"\x42" * 32)
    pub = priv.pub_key().bytes()
    sig = ed25519.sign(priv.data, b"warmup")
    _warm_kernel("ed25519", ed25519_batch.verify_batch, (pub, b"warmup", sig),
                 sizes)
    _warm_mesh(pub, sig)


def _sr25519_item():
    from tendermint_tpu.crypto import sr25519

    spriv = sr25519.gen_priv_key(b"\x43" * 32)
    return (spriv.pub_key().bytes(), b"warmup", spriv.sign(b"warmup"))


def _warm_sr25519(sizes) -> None:
    """The sr25519 kernel, on one chip as on several: a node whose validators
    hold sr25519 keys must not trace and compile it on its first commit."""
    from tendermint_tpu.ops import sr25519_batch

    _warm_kernel("sr25519", sr25519_batch.verify_batch, _sr25519_item(), sizes)


def _warm_mesh(pub, sig):
    """Warm what the "sharded" route runs on a TPU host with several
    chips, so that no commit of the node's life compiles: one Pallas
    chunk of each key type on EVERY local device (with its table gather
    and its pack). A batch of ndev chunks puts chunk k on device k, and
    placement starts from device 0 in every launch, so these are the
    devices any later batch uses."""
    import jax

    from tendermint_tpu.ops import ed25519_batch

    if not ed25519_batch._use_pallas():
        return  # before the Pallas module is imported for nothing
    from tendermint_tpu.ops import ed25519_pallas, sr25519_batch

    n = (jax.local_device_count() - 1) * ed25519_pallas.CHUNK + 1
    if not ed25519_batch.should_shard(n):
        return
    ed25519_batch.verify_batch([(pub, b"warmup", sig)] * n,
                               force_device=True)
    sr25519_batch.verify_batch([_sr25519_item()] * n)


# key type -> what compiles its kernel at the warm bucket sizes
_WARM_KERNELS = {"ed25519": _warm_ed25519, "sr25519": _warm_sr25519}


def warmup(sizes: tuple[int, ...] = (64,), background: bool = True,
           key_types: tuple[str, ...] = ()):
    """AOT-warm the batch kernels at the given bucket sizes.

    XLA compiles one executable per padded bucket shape, and the first launch
    at a new shape pays tracing + compilation. Nodes call this at start (in a
    background thread by default) so the first real commit at a warm bucket
    size is a cache hit, not a compile. ``key_types`` names the key types the
    caller's validators hold (node/node.py: the genesis validators'): ed25519
    is always warmed, every other type with a batch kernel when it is named,
    each once per process, so a later call warms only what no earlier one did
    and is a no-op when nothing is left (or batching is disabled). Every
    kernel warmed writes a ``startup.warm_kernel`` span in the start-up ring.
    The outcome lands in :data:`WARMUP`; a failure is logged at error level
    and never kills the node. Returns the warmup thread when background,
    else None."""
    if (os.environ.get("TM_TPU_DISABLE_BATCH") == "1"
            or os.environ.get("TM_TPU_SKIP_WARMUP") == "1"):
        # TM_TPU_SKIP_WARMUP: short-lived processes (tests) gain nothing from
        # pre-compiling kernels they may never launch, and would have to
        # wait the compile out before exiting (WarmupStatus.join).
        return None
    with _WARMUP_LOCK:
        kinds = [kt for kt in dict.fromkeys(("ed25519", *key_types))
                 if kt in _WARM_KERNELS and kt not in WARMUP.key_types]
        if not kinds:
            return None
        WARMUP.key_types.update(kinds)
        if WARMUP.state != "failed":
            WARMUP.state = "running"
        earlier = WARMUP.thread

    def _device_failures():
        from tendermint_tpu.ops import ed25519_batch, sr25519_batch

        return (ed25519_batch.BREAKER.failures + sr25519_batch.BREAKER.failures,
                ed25519_batch.BREAKER.last_error
                or sr25519_batch.BREAKER.last_error)

    def _run():
        if earlier is not None:
            earlier.join()      # one warm-up at a time on the device
        try:
            failures, _ = _device_failures()
            for kt in kinds:
                _WARM_KERNELS[kt](sizes)
            now, last_error = _device_failures()
            if now != failures:
                # verify_batch degrades through the breaker instead of
                # raising: the host answered and nothing was warmed
                raise RuntimeError(
                    "warm-up batch fell back to the host") from last_error
            if WARMUP.state != "failed":
                WARMUP.state = "done"
            # where the warm-up went, from the start-up ring (recorded with
            # tracing off too; docs/OBSERVABILITY.md)
            where = ", ".join(
                f"{name} {agg['total_s']:.3f}s x{agg['count']}"
                for name, agg in sorted(_trace.STARTUP.summarize().items()))
            _log.info("kernel warm-up done (%s): %s", ", ".join(kinds), where)
        except Exception as e:  # noqa: BLE001 - warmup must never kill a node
            WARMUP.error = e
            WARMUP.state = "failed"
            _log.error("kernel warm-up failed; the first device batches will "
                       "compile (or degrade) on the hot path: %r", e,
                       exc_info=e)

    if background:
        WARMUP.thread = threading.Thread(target=_run, name="batch-warmup",
                                         daemon=True)
        WARMUP.thread.start()
        return WARMUP.thread
    _run()
    return None


def forget_keys() -> None:
    """Forget every key the device holds tables for: both key types' per-key
    table and their key-sequence memo, each emptied under its lock. The next
    verify builds the tables of its keys again, as the first verify of a
    process that has just started does; a dispatch in flight keeps the
    KeySet it took (ops/ed25519_batch.KeyTable.clear). For callers that
    start a session over in one process: a sync from genesis after a reset,
    a benchmark pass, a test."""
    from tendermint_tpu.ops import ed25519_batch, sr25519_batch

    for mod in (ed25519_batch, sr25519_batch):
        with mod._KS_LOCK:
            mod._KS_CACHE.clear()
            mod._KS_UNIQ_CACHE.clear()


_BATCH_TYPES: dict[str, type] = {}


def register_batch_verifier(key_type: str, cls: type) -> None:
    _BATCH_TYPES[key_type] = cls


def supports_batch(key_type: str) -> bool:
    _ensure()
    return key_type in _BATCH_TYPES


def create_batch_verifier(key_type: str | None = None) -> BatchVerifier:
    """Batch verifier for one key type, or a mixed router when None."""
    _ensure()
    if key_type is None:
        return MixedBatchVerifier()
    cls = _BATCH_TYPES.get(key_type, ScalarBatchVerifier)
    return cls()


def _ensure() -> None:
    if _BATCH_TYPES:
        return
    if os.environ.get("TM_TPU_DISABLE_BATCH") == "1":
        _BATCH_TYPES["_disabled"] = ScalarBatchVerifier
        return
    _BATCH_TYPES["ed25519"] = Ed25519BatchVerifier
    _BATCH_TYPES["sr25519"] = Sr25519BatchVerifier
