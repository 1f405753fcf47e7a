"""TimeoutTicker: schedules round timeouts, newer schedules overwrite older
(reference: consensus/ticker.go:17,31-134).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass


@dataclass(frozen=True)
class TimeoutInfo:
    duration_s: float
    height: int
    round: int
    step: int  # RoundStepType

    def __str__(self) -> str:
        return f"{self.duration_s} ; {self.height}/{self.round} {self.step}"


class TimeoutTicker:
    """Fires `callback(TimeoutInfo)` after ti.duration_s, unless overwritten.

    Mirrors timeoutRoutine semantics: scheduling a new timeout stops the
    pending one; stale timeouts (older height/round/step) are ignored at
    schedule time (reference: consensus/ticker.go:100-134)."""

    def __init__(self, callback, clock=None):
        self._callback = callback
        # per-node time source (utils/clock.py): the clock's rate scales
        # every scheduled duration, so a skew-rate nemesis can make one
        # node's round timeouts run fast or slow relative to the mesh
        self._clock = clock
        self._timer: threading.Timer | None = None
        self._current: TimeoutInfo | None = None
        self._mtx = threading.Lock()
        self._stopped = False

    def schedule_timeout(self, ti: TimeoutInfo) -> None:
        with self._mtx:
            if self._stopped:
                return
            cur = self._current
            if cur is not None:
                # ignore timeouts for an older h/r/s than the pending one
                if (ti.height, ti.round, ti.step) < (cur.height, cur.round, cur.step):
                    return
            if self._timer is not None:
                self._timer.cancel()
            self._current = ti
            delay = (ti.duration_s if self._clock is None
                     else self._clock.timer_duration(ti.duration_s))
            self._timer = threading.Timer(delay, self._fire, args=(ti,))
            self._timer.daemon = True
            self._timer.name = "cs-ticker"  # one name in the thread census
            self._timer.start()

    def _fire(self, ti: TimeoutInfo) -> None:
        with self._mtx:
            if self._stopped or self._current is not ti:
                return
            self._current = None
            self._timer = None
        self._callback(ti)

    def stop(self) -> None:
        with self._mtx:
            self._stopped = True
            if self._timer is not None:
                self._timer.cancel()
                self._timer = None
            self._current = None

    def resume(self) -> None:
        """Accept schedules again after stop() (the stall watchdog pauses
        consensus for a fast-sync catchup, then restarts it)."""
        with self._mtx:
            self._stopped = False
