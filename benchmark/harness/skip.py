"""What the per-layer readers of the skipping cell share: counts and sums
over the light.skip.* spans of the window.

As in ``harness/spans.py``, a reader returns None, and the harness leaves its
metric out, where the program under test has no such span at all
(``trace.CANONICAL_SPANS`` lacks ``light.skip.hop``): that is how a parent
commit from before the spans reads. Where the program has the span and none
was written in the window, a count is a true 0."""

from __future__ import annotations

from benchmark.harness import spans


def _hops(run, accepted: int | None = None) -> list | None:
    """The window's light.skip.hop spans (every attempt of every
    bisection), or those tagged ``accepted`` so; None without the span."""
    if not run.traced or not spans._program_has("light.skip.hop"):
        return None
    return [s for s in run.spans if s["name"] == "light.skip.hop"
            and (accepted is None or s["tags"].get("accepted") == accepted)]


def attempts_per_sync(run, accepted: int) -> float | None:
    """Attempts that verified (1) or were refused (0), per session."""
    hops = _hops(run, accepted)
    if hops is None or not run.decisions:
        return None
    return len(hops) / len(run.decisions)


def ms_per(run, name: str, accepted: int | None) -> float | None:
    """Time in spans of this name per attempt of the window (None), or, of
    those written inside an accepted hop (their parent is its
    light.skip.hop), per accepted hop (1)."""
    hops = _hops(run, accepted)
    if not hops or not spans._program_has(name):
        return None
    inside = {h["span_id"] for h in hops}
    return sum(s["duration_s"] for s in run.spans if s["name"] == name
               and (accepted is None or s["parent_id"] in inside)
               ) * 1e3 / len(hops)


def keyset_tag_per_sync(run, tag: str) -> float | None:
    """Sum of an integer tag of prep.keyset over the window, per session;
    None where no prep.keyset of the window carries the tag."""
    if _hops(run) is None or not run.decisions:
        return None
    tagged = [s["tags"][tag] for s in run.spans
              if s["name"] == "prep.keyset" and tag in s["tags"]]
    if not tagged:
        return None
    return sum(tagged) / len(run.decisions)
