"""What the per-layer readers of the full-sync cell share: sums over the
spans a full block's body writes (``fastsync.part_set``, ``abci.deliver_txs``,
``store.save_block``, ``state.save_responses``, ``block.data_hash``,
``apply.backlog_wait``, ``apply.post_commit``, ``indexer.height``), the
every-ten-heights census of the process's threads (``fastsync.thread_cpu``),
and what the driver noted of each pass (``drivers/fullsync.py``:
``run.notes["full"]``).

``harness/cpu.census`` sums the ``consensus.thread_cpu`` marks of the
vote-drain cell; this file sums the fast-sync mark the same way. As in
``harness/spans.py``, a reader returns None, and the harness leaves its metric
out, where the program under test has nothing to read: ``trace.CANONICAL_SPANS``
lacks the span, or the run is not traced. That is how a parent commit from
before the spans reads. Where the program has the span and none was written in
the window, a sum is a true 0 and a ratio over nothing is None."""

from __future__ import annotations

from benchmark.harness import drain

CENSUS = "fastsync.thread_cpu"
POST_COMMIT_THREAD, INDEXER_THREAD = "post-commit", "indexer"
# what a block's body causes on the thread that applies it
BODY_SPANS = ("fastsync.part_set", "abci.deliver_txs", "store.save_block",
              "state.save_responses", "block.data_hash", "apply.backlog_wait")


def sync_thread(run) -> str | None:
    """The thread that called ``process_next``: who wrote ``fastsync.apply``."""
    for s in run.spans:
        if s["name"] == "fastsync.apply":
            return s.get("thread")
    return None


def body_share(run) -> float | None:
    """Wall time of the decisions spent, on the thread that applies, inside
    the spans a block's body causes, %. The spans do not nest in one
    another, so their sum counts nothing twice."""
    if drain._spans(run, "store.save_block") is None:
        return None
    wall = sum(d.t1 - d.t0 for d in run.decisions)
    me = sync_thread(run)
    if not wall or me is None:
        return None
    body = sum(s["duration_s"] for s in run.spans
               if s["name"] in BODY_SPANS and s.get("thread") == me)
    return 100.0 * body / wall


def note(run, key: str):
    """What the driver noted of the window's passes, or None."""
    return run.notes.get("full", {}).get(key)


def index_lag_ms(run) -> float | None:
    """From a pass's last ``process_next`` to the index holding its last
    height, mean over the window's passes."""
    lags = note(run, "index_lag_s")
    return sum(lags) * 1e3 / len(lags) if lags else None


def census(run) -> dict | None:
    """The window's ``fastsync.thread_cpu`` marks summed -> {wall_s,
    process_s, threads: name -> s, sync: the sync thread's s}."""
    marks = drain._spans(run, CENSUS)
    if not marks:
        return None
    out = {"wall_s": 0.0, "process_s": 0.0, "threads": {}, "sync": 0.0}
    for m in marks:
        tags = m["tags"]
        out["wall_s"] += tags["wall_s"]
        out["process_s"] += tags["process_s"]
        out["sync"] += tags["threads"].get(tags["sync_thread"], 0.0)
        for name, s in tags["threads"].items():
            out["threads"][name] = out["threads"].get(name, 0.0) + s
    return out if out["wall_s"] > 0 else None


def cpu_share(run, who: str) -> float | None:
    """CPU seconds of one thread (``sync``, ``post-commit``, ``indexer``) or
    of the whole ``process`` over the wall seconds the marks cover, %."""
    got = census(run)
    if got is None:
        return None
    if who == "process":
        mine = got["process_s"]
    elif who == "sync":
        mine = got["sync"]
    else:
        mine = got["threads"].get(who, 0.0)
    return 100.0 * mine / got["wall_s"]
