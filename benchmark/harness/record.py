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
from collections import Counter
from dataclasses import dataclass, field

from benchmark.harness import stats, xplane

ANNOTATION_PREFIX = "bench."
FAILURE_CHARS = 240      # of one message in the result line's `failures`
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
    checks: list = field(default_factory=list)    # fail()'s names, in order
    compared: dict = field(default_factory=dict)  # name -> (number, its limit)
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

    # --- what made `correct` false ----------------------------------------------

    def fail(self, check: str, message: str) -> None:
        """A check that did not hold, under its short name: a guarantee's
        letter where the configuration letters them (``"h"``), else a plain
        word (``"breakers"``). The message goes to ``failures`` as an append
        does; a failure appended without a name counts under ``other``."""
        self.failures.append(message)
        self.checks.append(check)

    def compare(self, name: str, value, limit) -> None:
        """Note a number that a check holds to a limit (a count and the count
        it must equal, seconds and the bound they must stay under): printed
        with every result, correct or not. The check itself calls ``fail``."""
        self.compared[name] = (value, limit)

    def failure_summary(self) -> dict:
        """The ``failures`` key of the result line: how many, how many under
        each check's name, the first three messages and every number
        compared beside its limit, so that a refusal's record says which
        check it was (the driver keeps the end of the last line and nothing
        of the earlier ones)."""
        by_check = Counter(self.checks)
        by_check["other"] += len(self.failures) - len(self.checks)
        return {"n": len(self.failures), "by_check": dict(+by_check),
                "first": [str(m)[:FAILURE_CHARS] for m in self.failures[:3]],
                "compared": {k: list(v) for k, v in self.compared.items()}}

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
