"""Flow-rate monitoring + throttling (reference: libs/flowrate/flowrate.go,
the mxk/go-flowrate vendored by the reference for MConnection send/recv
accounting and rate limiting).

Monitor tracks transfer progress with an exponentially-weighted moving rate;
Limit() tells a caller how many bytes it may move now to stay under a target
rate, sleeping like the reference's blocking mode when nothing is allowed.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass


@dataclass
class Status:
    """reference: flowrate.go Status."""

    bytes_total: int
    duration_s: float
    cur_rate: float  # EWMA bytes/sec
    avg_rate: float
    peak_rate: float


class Monitor:
    """reference: flowrate.go Monitor (sample period 100ms, EWMA)."""

    def __init__(self, sample_period_s: float = 0.1, ewma_window_s: float = 1.0):
        self._period = sample_period_s
        self._alpha = sample_period_s / ewma_window_s
        self._mtx = threading.Lock()
        self._start = time.monotonic()
        self._total = 0
        self._acc = 0  # bytes in the current sample window
        self._last_sample = self._start
        self._rate = 0.0
        self._peak = 0.0
        # token bucket backing limit(): armed on first limit() call;
        # update() then debits it (see limit() docstring)
        self._budget: float | None = None
        self._budget_t = self._start
        # seconds limit() slept because nothing was allowed: whole sample
        # periods counted, no clock read for it
        self.blocked_s = 0.0

    def update(self, n: int) -> int:
        """Record n transferred bytes (reference Update)."""
        with self._mtx:
            self._acc += n
            self._total += n
            if self._budget is not None:
                # debit the limiter's token bucket; going negative (the
                # caller moved more than granted, e.g. a full socket
                # buffer) just forces a longer refill sleep
                self._budget -= n
            self._sample_locked()
        return n

    def _sample_locked(self) -> None:
        now = time.monotonic()
        elapsed = now - self._last_sample
        if elapsed < self._period:
            return
        inst = self._acc / elapsed
        # catch up the EWMA over however many periods elapsed
        k = min(int(elapsed / self._period), 20)
        for _ in range(k):
            self._rate += self._alpha * (inst - self._rate)
        self._peak = max(self._peak, self._rate)
        self._acc = 0
        self._last_sample = now

    def status(self) -> Status:
        with self._mtx:
            self._sample_locked()
            dur = time.monotonic() - self._start
            return Status(
                bytes_total=self._total,
                duration_s=dur,
                cur_rate=self._rate,
                avg_rate=self._total / dur if dur > 0 else 0.0,
                peak_rate=self._peak,
            )

    def limit(self, want: int, rate: int, block: bool = True) -> int:
        """How many of `want` bytes may move now to hold `rate` B/s
        (reference Limit). rate <= 0 means unlimited. In blocking mode,
        sleeps until at least one byte is allowed.

        Implemented as a token bucket refilled at `rate` and capped at
        ONE second of burst credit (update() debits it). A cumulative
        since-start budget would let a peer that idles for an hour bank
        3600×rate of unspent allowance and then flood unthrottled for
        gigabytes — the exact attack the recv-side limiter exists to
        stop (docs/OVERLOAD.md)."""
        if rate <= 0 or want <= 0:
            return want
        while True:
            with self._mtx:
                now = time.monotonic()
                if self._budget is None:
                    self._budget = rate * self._period  # small head start
                else:
                    self._budget = min(
                        self._budget + rate * (now - self._budget_t),
                        float(rate))  # burst cap: 1s of credit
                self._budget_t = now
                allowed = int(self._budget)
            if allowed >= 1 or not block:
                return max(0, min(want, allowed))
            # sleep just long enough for one sample period of budget
            time.sleep(self._period)
            self.blocked_s += self._period
