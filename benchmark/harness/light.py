"""What the per-layer readers of the light-client cell share: a light.* span's
time over the headers the window's whole sessions verified.

As in ``harness/spans.py``, a reader returns None, and the harness leaves its
metric out, where the program under test has no such span at all
(``trace.CANONICAL_SPANS`` lacks the name): that is how a parent commit from
before the span reads. Where the program has the span and none was written in
the window, the sum is a true 0."""

from __future__ import annotations

from benchmark.harness import spans


def headers(run) -> int:
    """Headers verified and stored by the window's whole sessions."""
    return sum(n for _t0, _t1, n in run.passes)


def us_per_header(run, name: str) -> float | None:
    """Total time in spans of this name over the window, per header."""
    if not run.traced or not headers(run) or not spans._program_has(name):
        return None
    return sum(run.span_durations(name)) * 1e6 / headers(run)


def tagged_per_decision(run, name: str, tag: str) -> float | None:
    """Spans of this name that carry ``tag``, per decision (a session)."""
    if not run.traced or not run.decisions or not spans._program_has(name):
        return None
    return (sum(1 for s in run.spans if s["name"] == name and s["tags"].get(tag))
            / len(run.decisions))
