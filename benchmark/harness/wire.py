"""What the per-layer readers of the node-sync cell share: sums over the spans
and marks the wire writes (``blockchain.recv_block``, ``fastsync.pool_wait``,
``fastsync.first_block``, the ``p2p.wire`` mark beside the
``fastsync.thread_cpu`` census) and over the window's whole passes.

As in ``harness/spans.py``, a reader returns None, and the harness leaves its
metric out, where the program under test has nothing to read:
``trace.CANONICAL_SPANS`` lacks the name, or the run is not traced. That is
how a parent commit from before the spans reads. Where the program has the
span and none was written in the window, a sum is a true 0 and a ratio over
nothing is None."""

from __future__ import annotations

from benchmark.harness import drain, fullsync, spans

WIRE, RECV_THREADS = "p2p.wire", "mconn-recv"
CHANNEL = "0x40"


def block_recv_ms(run) -> float | None:
    """``blockchain.recv_block`` per decision: a BlockResponse's envelope,
    ``Block.unmarshal`` and the pool's ``add_block``, on the connection's
    receive thread."""
    return spans.ms_per_decision(run, "blockchain.recv_block")


def pool_wait_share(run) -> float | None:
    """The sync loop's sleeps with no next pair in the pool over the wall
    time of the window's whole passes, %: near 0 the apply sets the pace,
    high the wire or the peers do."""
    got = drain._spans(run, "fastsync.pool_wait")
    wall = sum(t1 - t0 for t0, t1, _n in run.passes)
    if got is None or not wall:
        return None
    inside = sum(s["duration_s"] for s in got
                 if any(t0 <= s["start"] < t1 for t0, t1, _n in run.passes))
    return 100.0 * inside / wall


def first_block_ms(run) -> float | None:
    """``fastsync.first_block``: start_sync to the first block in the pool,
    mean over the window's passes."""
    marks = drain._spans(run, "fastsync.first_block")
    if not marks:
        return None
    return sum(m["tags"]["seconds"] for m in marks) * 1e3 / len(marks)


def _wire(run) -> list | None:
    marks = drain._spans(run, WIRE)
    return marks or None


def recv_cpu_s(run) -> float | None:
    """CPU seconds of the connections' receive threads over the census."""
    got = fullsync.census(run)
    if got is None:
        return None
    return sum(s for name, s in got["threads"].items()
               if name.startswith(RECV_THREADS))


def cpu_recv_share(run) -> float | None:
    """``mconn-recv*`` threads' CPU over the wall seconds the census marks
    cover, % (``harness/cpu.py``'s way)."""
    got, mine = fullsync.census(run), recv_cpu_s(run)
    return None if mine is None else 100.0 * mine / got["wall_s"]


def recv_cpu_us_per_packet(run) -> float | None:
    """The same CPU over the packets the ``p2p.wire`` marks counted as
    received: both marks are written at the same heights."""
    marks, mine = _wire(run), recv_cpu_s(run)
    packets = sum(m["tags"]["packets_recv"] for m in marks) if marks else 0
    return mine * 1e6 / packets if mine is not None and packets else None


def throttle_wait_ms(run) -> float | None:
    """Seconds the receive side's flow-rate limiter slept, per decision the
    marks cover."""
    marks = _wire(run)
    heights = _heights(run, marks)
    if not heights:
        return None
    return sum(m["tags"]["recv_blocked_s"] for m in marks) * 1e3 / heights


def bytes_per_block(run) -> float | None:
    """Message bytes received on channel 0x40 per decision the marks cover."""
    marks = _wire(run)
    heights = _heights(run, marks)
    if not heights:
        return None
    got = sum(m["tags"]["channels"].get(CHANNEL, {}).get("bytes_recv", 0)
              for m in marks)
    return got / heights


def _heights(run, marks) -> int:
    """Heights the marks cover: each is written every
    ``pipeline.CENSUS_EVERY`` heights a pipeline applied."""
    if not marks:
        return 0
    from tendermint_tpu.blockchain import pipeline

    return len(marks) * pipeline.CENSUS_EVERY
