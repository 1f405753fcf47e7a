"""Circuit breaker for the accelerator dispatch routes.

Consensus must keep committing with a dead accelerator: when a device
dispatch fails (runtime error, link loss, injected fault), the batch is
re-verified on the host fallback in the same dispatch, the circuit opens,
and every later batch routes straight to the host until a background probe
proves the device answers again. This is the standard degradation shape of
production accelerator serving stacks -- fail fast, fall back, re-probe off
the hot path -- applied to the verify pipeline of ops/ed25519_batch.py and
ops/sr25519_batch.py.

States:
  closed  -- device route allowed (the normal state).
  open    -- device route skipped; after ``cooldown_s`` the next ``allow()``
             launches one background probe. The caller still gets False (the
             probe owns the first device touch), and the circuit re-closes
             only when the probe reports success -- so a flapping device
             costs one probe per cooldown, never a consensus stall.

The degradation is silent to callers by design, so it must be loud to the
operator: the failure that opens the circuit is logged at error level with
its traceback (``logging`` logger ``tendermint_tpu.ops.breaker``), and
``failures``/``trips``/``last_error`` stay readable on the breaker.

TM_TPU_BREAKER_COOLDOWN_S overrides the cooldown (read per trip, so tests
can shrink it without re-importing).
"""

from __future__ import annotations

import logging
import os
import threading
import time

_log = logging.getLogger(__name__)


class CircuitBreaker:
    def __init__(self, name: str, probe=None, cooldown_s: float = 5.0):
        self.name = name
        self.probe = probe  # () -> bool; run in a daemon thread while open
        self.cooldown_default = cooldown_s
        self._lock = threading.Lock()
        self._open = False
        self._open_until = 0.0
        self._probing = False
        self.failures = 0   # lifetime failure count
        self.trips = 0      # closed -> open transitions
        self.last_error: BaseException | None = None
        self.events: list[tuple[float, str]] = []  # (monotonic, event) ring

    def _cooldown(self) -> float:
        v = os.environ.get("TM_TPU_BREAKER_COOLDOWN_S")
        return float(v) if v else self.cooldown_default

    def _event(self, what: str) -> None:
        self.events.append((time.monotonic(), what))
        del self.events[:-64]

    @property
    def is_open(self) -> bool:
        with self._lock:
            return self._open

    def allow(self) -> bool:
        """True when the device route may run. While open, a cooldown-due
        call launches the background probe (once) and still returns False."""
        with self._lock:
            if not self._open:
                return True
            if (self.probe is not None and not self._probing
                    and time.monotonic() >= self._open_until):
                self._probing = True
                threading.Thread(target=self._run_probe, daemon=True,
                                 name=f"breaker-probe-{self.name}").start()
            return False

    def _run_probe(self) -> None:
        try:
            ok = bool(self.probe())
        except Exception as e:  # noqa: BLE001 - a dead device raises freely
            self.last_error = e
            ok = False
        with self._lock:
            self._probing = False
            if ok:
                self._open = False
                self._event("probe ok: closed")
            else:
                self._open_until = time.monotonic() + self._cooldown()
                self._event("probe failed: still open")

    def record_failure(self, exc: BaseException | None = None) -> None:
        with self._lock:
            self.failures += 1
            self.last_error = exc
            tripped = not self._open
            if tripped:
                self.trips += 1
                self._event(f"opened: {exc!r}")
            self._open = True
            self._open_until = time.monotonic() + self._cooldown()
        if tripped:
            # the first failure of each trip; later ones while open repeat it
            _log.error("%s: device route failed, circuit opened, verifying "
                       "on the host: %r", self.name, exc, exc_info=exc)

    def record_success(self) -> None:
        # A success observed on the device route while closed; nothing to
        # change, but keep the hook so dispatch sites stay symmetric.
        pass

    def reset(self) -> None:
        """Force-close (tests)."""
        with self._lock:
            self._open = False
            self._probing = False
            self._open_until = 0.0


# Which route answered a batch, decided where dispatch_batch decides it: the
# `route` tag of the prep.launch / prep.host_verify spans and the label set
# of batch_verify_seconds (utils/metrics.py seeds exactly these).
ROUTES = ("pallas", "jnp", "sharded", "host_c", "host_scalar",
          "breaker_fallback")


def routed(finish, route: str):
    """Name the route on a dispatch's ``finish`` callable, where the caller
    that observes the batch's latency reads it (``finish.route``)."""
    finish.route = route
    return finish


def guarded_dispatch(breaker: CircuitBreaker, dispatch_fn, fallback_fn):
    """The one degradation shape both kernel modules share: run
    ``dispatch_fn() -> (dev, finish)`` behind ``breaker``; any dispatch- or
    finish-time failure records on the breaker and re-verifies via
    ``fallback_fn() -> (None, finish)`` in the same call. The returned
    finish carries the route that answered (``.route``), which a
    finish-time failure turns into the fallback's."""
    if not breaker.allow():
        return fallback_fn()
    try:
        dev, finish = dispatch_fn()
    except Exception as e:  # noqa: BLE001 - any device-route failure degrades
        breaker.record_failure(e)
        return fallback_fn()

    def finish_cb(fetched):
        try:
            out = finish(fetched)
        except Exception as e:  # noqa: BLE001
            breaker.record_failure(e)
            _, fb = fallback_fn()
            finish_cb.route = getattr(fb, "route", "breaker_fallback")
            return fb(None)
        breaker.record_success()
        return out

    return dev, routed(finish_cb, getattr(finish, "route", ""))


def guarded_fetch(breaker: CircuitBreaker, dev, finish, fallback_fn):
    """verify_batch tail: fetch ``dev`` and resolve, degrading a fetch-time
    device failure through ``fallback_fn`` exactly like a dispatch failure."""
    if dev is None:
        return finish(None)
    import jax

    try:
        fetched = jax.device_get(dev)
    except Exception as e:  # noqa: BLE001
        breaker.record_failure(e)
        _, fb = fallback_fn()
        return fb(None)
    return finish(fetched)
