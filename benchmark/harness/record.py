"""The record of one run: what the drivers write and the metric readers read.

A driver calls ``run.decide(fn, sigs)`` for every decision of the measured
window. With ``--trace 0`` that is two clock readings and an append. With
``--trace 1`` the program's flight recorder is on for the whole window (its
ring is drained after every decision), and the profiler wraps one steady
slice of it, each decision and the time between decisions inside a
``jax.profiler.TraceAnnotation`` of the benchmark's own. Starting the
profiler and exporting its trace (half a minute and more where a jnp kernel
runs) is the benchmark's work, not the program's: the window's clock stops
for it, so a traced window holds as many seconds of decisions as an untraced
one.
"""

from __future__ import annotations

import shutil
import time
from dataclasses import dataclass, field

from benchmark.harness import stats, xplane

ANNOTATION_PREFIX = "bench."
VERIFY_SPANS = ("verify.host_prep", "verify.queue", "verify.readback",
                "verify.replay")


@dataclass
class Decision:
    t0: float
    t1: float
    sigs: int            # real (unpadded) signatures this decision verified
    ok: bool
    profiled: bool = False


@dataclass
class Run:
    cell: object                       # spec.Cell
    seed: int
    seconds: float
    traced: bool
    rehearse: bool
    decisions: list = field(default_factory=list)
    passes: list = field(default_factory=list)   # (t0, t1, decisions) whole passes
    spans: list = field(default_factory=list)    # flight-recorder span dicts
    counters: dict = field(default_factory=dict)  # name -> (before, after)
    setup: dict = field(default_factory=dict)
    notes: dict = field(default_factory=dict)
    trace: xplane.Trace | None = None
    trace_dir: str | None = None
    failures: list = field(default_factory=list)  # what made `correct` false
    window: tuple | None = None                  # (t0, t1) of the window

    # profiled slice (traced runs): decisions [skip, skip + count)
    profile_skip: int = 0
    profile_count: int = 0
    _profiling: bool = False
    _between = None
    profiler_s: float = 0.0   # spent starting and stopping the profiler
    _annotation: str = ANNOTATION_PREFIX + "decision"

    # --- the measured window ------------------------------------------------

    def open_window(self, annotation: str = "decision") -> None:
        self._annotation = ANNOTATION_PREFIX + annotation
        if self.traced:
            from tendermint_tpu.utils import trace as flight

            flight.dump(clear=True)
            flight.enable()
        self.window = (time.monotonic(), None)

    def close_window(self) -> None:
        self._stop_profile()
        self.window = (self.window[0], time.monotonic())
        if self.traced:
            from tendermint_tpu.utils import trace as flight

            self._drain()
            flight.disable()

    def elapsed(self) -> float:
        """Seconds of the window so far, the profiler's own time left out."""
        return time.monotonic() - self.window[0] - self.profiler_s

    def decide(self, fn, sigs: int):
        """Run one decision. ``fn`` raises or returns falsy on a wrong
        answer; the decision then counts as failed."""
        if not self.traced:
            t0 = time.monotonic()
            try:
                ok = fn()
            except Exception as e:  # noqa: BLE001 - counted, reported, not fatal
                ok = False
                self._note_failure(e)
            self.decisions.append(Decision(t0, time.monotonic(), sigs,
                                           ok is None or bool(ok)))
            return ok
        return self._decide_traced(fn, sigs)

    def _note_failure(self, e: Exception) -> None:
        if len(self.failures) < 8:
            self.failures.append(f"decision {len(self.decisions)}: "
                                 f"{type(e).__name__}: {e}")

    # --- traced runs --------------------------------------------------------

    def _decide_traced(self, fn, sigs: int):
        import jax

        idx = len(self.decisions)
        if idx == self.profile_skip and self.profile_count > 0:
            self._start_profile()
        if self._between is not None:
            self._between.__exit__(None, None, None)
            self._between = None
        profiled = self._profiling
        ann = jax.profiler.TraceAnnotation(self._annotation) if profiled else None
        if ann is not None:
            ann.__enter__()
        t0 = time.monotonic()
        try:
            ok = fn()
        except Exception as e:  # noqa: BLE001 - counted, reported, not fatal
            ok = False
            self._note_failure(e)
        t1 = time.monotonic()
        if ann is not None:
            ann.__exit__(None, None, None)
        self.decisions.append(Decision(t0, t1, sigs, ok is None or bool(ok),
                                       profiled))
        if profiled:
            if idx + 1 >= self.profile_skip + self.profile_count:
                self._stop_profile()
            else:
                self._between = jax.profiler.TraceAnnotation(
                    ANNOTATION_PREFIX + "between")
                self._between.__enter__()
        self._drain()
        return ok

    def _drain(self) -> None:
        from tendermint_tpu.utils import trace as flight

        self.spans.extend(s.as_dict() for s in flight.dump(clear=True))

    def _start_profile(self) -> None:
        import jax

        t0 = time.monotonic()
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # our annotations only: traces are large
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._profiling = True
        self.profiler_s += time.monotonic() - t0

    def _stop_profile(self) -> None:
        if not self._profiling:
            return
        import jax

        if self._between is not None:
            self._between.__exit__(None, None, None)
            self._between = None
        t0 = time.monotonic()
        jax.profiler.stop_trace()
        self._profiling = False
        self.profiler_s += time.monotonic() - t0

    def load_trace(self) -> None:
        path = xplane.find_trace_file(self.trace_dir) if self.trace_dir else None
        if path is not None:
            self.trace = xplane.load(path, ANNOTATION_PREFIX)

    # --- what the readers use -----------------------------------------------

    def latencies_ms(self) -> list[float]:
        return [(d.t1 - d.t0) * 1e3 for d in self.decisions]

    def profiled_decisions(self) -> list[Decision]:
        return [d for d in self.decisions if d.profiled]

    def span_durations(self, name: str) -> list[float]:
        return [s["duration_s"] for s in self.spans if s["name"] == name]

    def span_ms_per_decision(self, name: str) -> float | None:
        """Total time in spans of this name over the window, per decision."""
        if not self.traced or not self.decisions:
            return None
        return sum(self.span_durations(name)) * 1e3 / len(self.decisions)

    def counter_delta(self, name: str) -> float | None:
        if name not in self.counters:
            return None
        before, after = self.counters[name]
        return after - before

    def clock_offset(self) -> float | None:
        """Profiler clock minus ``time.monotonic()``, from the annotated
        decisions: the k-th annotation began when the k-th profiled decision
        did."""
        if self.trace is None:
            return None
        anns = [s for n, s, _e in self.trace.host_spans
                if n == self._annotation]
        decs = self.profiled_decisions()
        if not anns or len(anns) != len(decs):
            return None
        return stats.median([a - d.t0 for a, d in zip(anns, decs)])

    def trace_window(self) -> tuple[float, float] | None:
        """The steady slice on the profiler's clock: first annotated decision
        to the end of the last."""
        if self.trace is None:
            return None
        return self.trace.window_of(self._annotation)

    def host_intervals(self) -> list:
        """Everything known about the host inside the slice, on the
        profiler's clock: the benchmark's annotations, and the program's
        flight-recorder spans shifted by ``clock_offset``."""
        if self.trace is None:
            return []
        out = list(self.trace.host_spans)
        off = self.clock_offset()
        if off is not None:
            out.extend((s["name"], s["start"] + off,
                        s["start"] + s["duration_s"] + off)
                       for s in self.spans if s["duration_s"] > 0)
        return out
