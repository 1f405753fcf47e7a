"""What the per-layer readers of the vote-drain cell share: sums over the
state machine's ``consensus.*`` spans of the window and over what the driver
noted (``drivers/votedrain.py``).

As in ``harness/spans.py``, a reader returns None, and the harness leaves its
metric out, where the program under test has no such span at all
(``trace.CANONICAL_SPANS`` lacks the name): that is how a parent commit from
before the span reads. Where the program has the span and none was written in
the window, a sum is a true 0 and a ratio over nothing is None."""

from __future__ import annotations

from benchmark.harness import spans


def _spans(run, name: str) -> list | None:
    if not run.traced or not run.decisions or not spans._program_has(name):
        return None
    return [s for s in run.spans if s["name"] == name]


def tag_sum(run, name: str, tag: str) -> float | None:
    got = _spans(run, name)
    return None if got is None else sum(s["tags"].get(tag, 0) for s in got)


def us_per(run, name: str, tag: str) -> float | None:
    """Total time in spans of this name over the sum of their ``tag``."""
    got = _spans(run, name)
    n = tag_sum(run, name, tag)
    if not n:
        return None
    return sum(s["duration_s"] for s in got) * 1e6 / n


def drain_build_us_per_vote(run) -> float | None:
    """A drain's own work: ``consensus.vote_drain`` less the wait for and the
    apply of the flush before it, which run inside it as its children."""
    drains = _spans(run, "consensus.vote_drain")
    votes = tag_sum(run, "consensus.vote_drain", "votes")
    if not votes or not spans._program_has("consensus.vote_apply"):
        return None
    inside: dict = {}
    for s in run.spans:
        if s["name"] in ("consensus.flush_wait", "consensus.vote_apply"):
            inside[s["parent_id"]] = inside.get(s["parent_id"], 0.0) + s["duration_s"]
    own = sum(s["duration_s"] - inside.get(s["span_id"], 0.0) for s in drains)
    return own * 1e6 / votes


def votes_per_flush(run) -> float | None:
    """Signatures a drain hands the verifier, over the drains that dispatch."""
    drains = _spans(run, "consensus.vote_drain")
    if drains is None:
        return None
    queued = [s["tags"]["queued"] for s in drains if s["tags"].get("queued")]
    return sum(queued) / len(queued) if queued else None


def sigcache_hit_share(run) -> float | None:
    hits = tag_sum(run, "consensus.vote_drain", "cache_hits")
    queued = tag_sum(run, "consensus.vote_drain", "queued")
    if hits is None or not hits + queued:
        return None
    return 100.0 * hits / (hits + queued)


def serial_share(run) -> float | None:
    """Deliveries that took the serial path (alone through ``_handle_msg``,
    or left out of their drain's batch: another height, index or address)
    over all the deliveries the state machine handled."""
    serial = tag_sum(run, "consensus.vote_serial", "votes")
    queued = tag_sum(run, "consensus.vote_drain", "queued")
    hits = tag_sum(run, "consensus.vote_drain", "cache_hits")
    if serial is None or queued is None or not serial + queued + hits:
        return None
    return 100.0 * serial / (serial + queued + hits)


def host_route_share(run) -> float | None:
    """Signatures the C / scalar host verifier answered over all that went
    through the registry (``prep.host_verify`` against ``prep.launch``)."""
    host = tag_sum(run, "prep.host_verify", "sigs")
    device = tag_sum(run, "prep.launch", "sigs")
    if host is None or device is None or not host + device:
        return None
    return 100.0 * host / (host + device)


def shed_share(run) -> float | None:
    """Messages of the live height the peer queue shed over the deliveries
    made in the window, %: 0 when healthy."""
    shed = run.notes.get("shed_in_window")
    made = run.notes.get("deliveries", {}).get("made")
    if shed is None or not made:
        return None
    return 100.0 * sum(shed["live"].values()) / made


def recv_us_per_msg(run) -> float | None:
    """``ConsensusReactor.recv_stats``: seconds in ``receive`` over messages,
    every channel, on the receiving thread."""
    recv = run.notes.get("recv")
    if not recv:
        return None
    msgs = sum(c["msgs"] for c in recv.values())
    return sum(c["seconds"] for c in recv.values()) * 1e6 / msgs if msgs else None
