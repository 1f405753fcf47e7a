"""What the per-layer readers of PR 23 share: sums over the program's
flight-recorder spans and over its start-up ring.

A reader returns None, and the harness leaves its metric out, where the
program under test has no such span at all (``trace.CANONICAL_SPANS`` lacks
the name, ``trace`` has no ``STARTUP`` ring): that is how a parent commit
from before the span reads. Where the program has the span and none was
written in the window, the sum is a true 0.
"""

from __future__ import annotations

from benchmark.harness import xplane


def _program_has(name: str) -> bool:
    from tendermint_tpu.utils import trace

    return name in trace.CANONICAL_SPANS


def ms_per_decision(run, name: str) -> float | None:
    """Total time in spans of this name over the window, per decision."""
    if not run.traced or not run.decisions or not _program_has(name):
        return None
    return sum(run.span_durations(name)) * 1e3 / len(run.decisions)


def tag_ms_per_decision(run, name: str, tag: str) -> float | None:
    """Sum of a seconds-valued tag over the spans of this name, per decision."""
    if not run.traced or not run.decisions or not _program_has(name):
        return None
    total = sum(s["tags"].get(tag, 0.0) for s in run.spans if s["name"] == name)
    return total * 1e3 / len(run.decisions)


def count_per_decision(run, name: str) -> float | None:
    """Spans of this name written in the window, per decision."""
    if not run.traced or not run.decisions or not _program_has(name):
        return None
    return len(run.span_durations(name)) / len(run.decisions)


def tag_share(run, name: str, tag: str, value) -> float | None:
    """Spans of this name whose ``tag`` reads ``value`` over all its spans
    that carry the tag, %. None where none does (no such span in the window,
    or a program from before the tag)."""
    if not run.traced or not _program_has(name):
        return None
    tagged = [s["tags"][tag] for s in run.spans
              if s["name"] == name and tag in s["tags"]]
    if not tagged:
        return None
    return 100.0 * sum(1 for v in tagged if v == value) / len(tagged)


def lane_fill(run) -> float | None:
    """Real signatures over launched lanes, %, over every ``prep.launch`` of
    the window. None where nothing was launched (the host answered)."""
    if not run.traced or not _program_has("prep.launch"):
        return None
    launches = [s["tags"] for s in run.spans if s["name"] == "prep.launch"]
    lanes = sum(t["lanes"] for t in launches)
    if not lanes:
        return None
    return 100.0 * sum(t["sigs"] for t in launches) / lanes


def _startup_intervals(run, names: tuple) -> list | None:
    """[(start, end)] of the start-up ring's spans with these names that
    began before the window opened, or None without a ring."""
    from tendermint_tpu.utils import trace

    ring = getattr(trace, "STARTUP", None)
    if ring is None or run.window is None:
        return None
    return [(s.start, s.start + s.duration_s) for s in ring.dump()
            if s.name in names and s.start < run.window[0]]


def startup_s(run, name: str, minus: tuple = ()) -> float | None:
    """Seconds before the window that the start-up ring's spans of this name
    cover: their union, because jax reports a nested trace inside its
    caller's. With ``minus``, less what spans of those names cover of it (a
    span's self time: the first table build contains its own compile)."""
    mine = _startup_intervals(run, (name,))
    if mine is None:
        return None
    before = (float("-inf"), run.window[0])
    total = xplane.busy_seconds(mine, before)
    if minus and mine:
        others = _startup_intervals(run, minus)
        inner = [iv for outer in xplane.merge(mine)
                 for iv in xplane.clip(others, outer)]
        total -= xplane.busy_seconds(inner, before)
    return total


def window_ring_ms_per_decision(run, name: str) -> float | None:
    """Time in the start-up ring's spans of this name that began inside the
    window, per decision: the ring is always on, and a key-set miss writes
    its two halves there whenever it happens. None without a ring."""
    from tendermint_tpu.utils import trace

    ring = getattr(trace, "STARTUP", None)
    if ring is None or run.window is None or not run.decisions:
        return None
    t0, t1 = run.window
    inside = [s.duration_s for s in ring.dump()
              if s.name == name and t0 <= s.start and (t1 is None or s.start < t1)]
    return sum(inside) * 1e3 / len(run.decisions)
