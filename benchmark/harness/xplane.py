"""From a profiler trace (``.xplane.pb``) to numbers: device busy union, idle
share, time per device program, idle gaps and what the host was doing in them.

The arithmetic works on plain ``(name, start_s, end_s)`` tuples so that
tests/benchmark/test_reduction.py can feed it synthetic events; ``load``
turns a trace file into those through ``jax.profiler.ProfileData`` (nothing
but jax). No PR that claims a gain may change this file.

What a TPU trace holds (read by hand on this PR's first chip run, PERF.md):
one plane per chip, ``/device:TPU:<n>``, with a line ``XLA Modules`` (one
event per execution of a jitted program, named ``jit_<fn>(<fingerprint>)``)
and a line ``XLA Ops`` (one event per HLO op inside it, named by the whole
HLO instruction); and ``/host:CPU``, one line per thread, two of them named
``python3``, one of which holds the ``jax.profiler.TraceAnnotation`` spans.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

Event = tuple  # (name, start_s, end_s)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
_PROGRAM_ID = re.compile(r"\(\d+\)$")


def program_name(event_name: str) -> str:
    """``jit__verify_chunk(1234)`` -> ``jit__verify_chunk``."""
    return _PROGRAM_ID.sub("", event_name)


# --- arithmetic on plain intervals -------------------------------------------


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, window: tuple[float, float]):
    w0, w1 = window
    return [(max(s, w0), min(e, w1)) for s, e in intervals
            if min(e, w1) > max(s, w0)]


def busy_seconds(intervals, window: tuple[float, float]) -> float:
    return sum(e - s for s, e in merge(clip(intervals, window)))


def idle_share(intervals, window: tuple[float, float]) -> float:
    """1 - busy union over the window, as a share in [0, 1]."""
    length = window[1] - window[0]
    if length <= 0:
        raise ValueError("empty window")
    return 1.0 - busy_seconds(intervals, window) / length


def gaps(intervals, window: tuple[float, float]) -> list[tuple[float, float]]:
    """The idle stretches of the window, in order."""
    out, at = [], window[0]
    for s, e in merge(clip(intervals, window)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if window[1] > at:
        out.append((at, window[1]))
    return out


def program_totals(events: list[Event], window=None) -> dict[str, dict]:
    """name -> {count, seconds} over events that START inside the window."""
    out: dict[str, dict] = {}
    for name, s, e in events:
        if window is not None and not (window[0] <= s < window[1]):
            continue
        t = out.setdefault(program_name(name), {"count": 0, "seconds": 0.0})
        t["count"] += 1
        t["seconds"] += e - s
    return out


def attribute_gaps(gap_list, host_spans: list[Event],
                   other: str = "unattributed") -> dict[str, float]:
    """Idle seconds by what the host was doing: each gap is cut at the
    boundaries of the host spans, and every piece goes to the INNERMOST
    (shortest) span that covers it."""
    out: dict[str, float] = {}
    spans = sorted(host_spans, key=lambda ev: ev[2] - ev[1])
    for g0, g1 in gap_list:
        cuts = {g0, g1}
        for _n, s, e in spans:
            if g0 < s < g1:
                cuts.add(s)
            if g0 < e < g1:
                cuts.add(e)
        edges = sorted(cuts)
        for a, b in zip(edges, edges[1:]):
            mid = (a + b) / 2.0
            name = next((n for n, s, e in spans if s <= mid < e), other)
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def top(table: dict[str, float], k: int = 10) -> list[list]:
    return [[n, v] for n, v in
            sorted(table.items(), key=lambda kv: -kv[1])[:k]]


# --- a trace file ------------------------------------------------------------


@dataclass
class Trace:
    """One trace, reduced to what the metrics read. Times are seconds on the
    profiler's clock."""

    device_ops: dict[int, list[Event]] = field(default_factory=dict)
    device_programs: dict[int, list[Event]] = field(default_factory=dict)
    host_spans: list[Event] = field(default_factory=list)
    path: str | None = None

    @property
    def chips(self) -> list[int]:
        return sorted(set(self.device_ops) | set(self.device_programs))

    def busy_events(self, chip: int) -> list[Event]:
        """Op-level events when the trace has them, else whole programs."""
        return self.device_ops.get(chip) or self.device_programs.get(chip, [])

    def busy_intervals(self, chip: int):
        return [(s, e) for _n, s, e in self.busy_events(chip)]

    def window_of(self, annotation: str) -> tuple[float, float] | None:
        """First start to last end of the host spans with this name."""
        hits = [(s, e) for n, s, e in self.host_spans if n == annotation]
        if not hits:
            return None
        return min(s for s, _ in hits), max(e for _, e in hits)


def find_trace_file(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load(path: str, annotation_prefix: str = "bench.", ops: bool = False) -> Trace:
    """Read an ``.xplane.pb``. Host spans are kept only when their name
    starts with ``annotation_prefix`` (the benchmark's own annotations).

    The ``XLA Ops`` line is skipped unless ``ops``: a chip runs the ops of a
    program back to back, so the union of its programs is the union of its
    ops (0.30837 s against 0.30830 s over 24 decisions of hub-10k.tip, my
    chip run, PR 22), and the jnp kernels write ~230,000 op events per
    decision, which Python reads at ~12 us each."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    trace = Trace(path=path)
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    dest = trace.device_programs.setdefault(chip, [])
                elif line.name == OPS_LINE and ops:
                    dest = trace.device_ops.setdefault(chip, [])
                else:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    dest.append((ev.name, s, s + ev.duration_ns * 1e-9))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(annotation_prefix):
                        s = ev.start_ns * 1e-9
                        trace.host_spans.append(
                            (ev.name, s, s + ev.duration_ns * 1e-9))
    trace.host_spans.sort(key=lambda ev: ev[1])
    return trace


def describe(path: str, k: int = 12) -> list[str]:
    """Planes, lines, event counts and the commonest names: what one reads
    by hand before trusting a reduction."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            names: dict[str, list] = {}
            n = 0
            for ev in line.events:
                n += 1
                t = names.setdefault(program_name(ev.name), [0, 0.0])
                t[0] += 1
                t[1] += ev.duration_ns * 1e-9
            common = sorted(names.items(), key=lambda kv: -kv[1][1])[:k]
            out.append(f"  line {line.name!r}: {n} events; by time: " + ", ".join(
                f"{nm} x{c} {sec:.4f}s" for nm, (c, sec) in common))
    return out
