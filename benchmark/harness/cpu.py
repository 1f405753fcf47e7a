"""What the per-layer readers of PR 35 share: the CPU seconds a span's own
thread got (``cpu_s``, ``time.thread_time()`` over the region) beside the
wall seconds the accepted readers sum, and the once-a-height census of the
process's threads (the ``consensus.thread_cpu`` and ``consensus.recv`` marks).

Under one interpreter lock a span's wall time holds every other thread's turn
too; its CPU time holds only its own. Wall less CPU, on a thread that blocks
on nothing, is time spent waiting for the lock.

As in ``harness/spans.py``, a reader returns None, and the harness leaves its
metric out, where the program under test has nothing to read: its spans carry
no ``cpu_s`` or it writes no census mark. That is how a parent commit from
before PR 35 reads. Where the program has the field and no such span was
written in the window, a sum is a true 0 and a ratio over nothing is None."""

from __future__ import annotations

from benchmark.harness import drain

CENSUS, RECV = "consensus.thread_cpu", "consensus.recv"
CONSENSUS_THREAD, VERIFY_THREAD = "cs-receive", "verify-service"
PEER_THREADS = ("cs-gossip", "mconn-")


def _program_has_cpu() -> bool:
    from tendermint_tpu.utils import trace

    return "cpu_s" in getattr(trace.Span, "__dataclass_fields__", {})


def _spans(run, name: str) -> list | None:
    """The window's spans of this name, or None where there is nothing to
    read a CPU time from."""
    return drain._spans(run, name) if _program_has_cpu() else None


def _cpu(got: list) -> float:
    return sum(s.get("cpu_s") or 0.0 for s in got)


def cpu_ms_per_decision(run, name: str) -> float | None:
    """CPU time of the spans of this name over the window, per decision."""
    got = _spans(run, name)
    return None if got is None else _cpu(got) * 1e3 / len(run.decisions)


def cpu_us_per(run, name: str, tag: str) -> float | None:
    """CPU time of the spans of this name over the sum of their ``tag``."""
    got = _spans(run, name)
    n = sum(s["tags"].get(tag, 0) for s in got) if got else 0
    return _cpu(got) * 1e6 / n if n else None


def recv_cpu_us_per_msg(run) -> float | None:
    """The ``consensus.recv`` marks of the window: CPU seconds the threads
    that called ``ConsensusReactor.receive`` got, over the messages they
    brought (a mark has no ``cpu_s`` where the platform has no thread clock)."""
    marks = [s for s in _spans(run, RECV) or ()
             if s["tags"].get("cpu_s") is not None]
    msgs = sum(s["tags"]["msgs"] for s in marks)
    return sum(s["tags"]["cpu_s"] for s in marks) * 1e6 / msgs if msgs else None


def startup_cpu_s(run, name: str) -> float | None:
    """CPU seconds of the start-up ring's spans of this name that began before
    the window. jax reports a nested region inside its caller's, on the
    caller's thread, so only the outermost of a thread count. None without a
    ring, and where the program's spans carry no ``cpu_s``."""
    from tendermint_tpu.utils import trace

    ring = getattr(trace, "STARTUP", None)
    if ring is None or run.window is None or not _program_has_cpu():
        return None
    mine = sorted((s for s in ring.dump() if s.name == name
                   and s.start < run.window[0] and s.cpu_s is not None),
                  key=lambda s: (s.start, -s.duration_s))
    total, covered = 0.0, {}     # thread -> end of its last outermost span
    for s in mine:
        if s.start >= covered.get(s.thread, float("-inf")):
            total += s.cpu_s
            covered[s.thread] = s.start + s.duration_s
    return total


# --- the census ----------------------------------------------------------------


def census(run) -> dict | None:
    """The window's ``consensus.thread_cpu`` marks summed -> {wall_s,
    process_s, rest_s, threads: name -> s}, or None where none was written."""
    marks = _spans(run, CENSUS)
    if not marks:
        return None
    out = {"wall_s": 0.0, "process_s": 0.0, "rest_s": 0.0, "threads": {}}
    for m in marks:
        tags = m["tags"]
        for key in ("wall_s", "process_s", "rest_s"):
            out[key] += tags[key]
        for name, s in tags["threads"].items():
            out["threads"][name] = out["threads"].get(name, 0.0) + s
    return out if out["wall_s"] > 0 else None


def receivers(run) -> set:
    """The threads the window's ``consensus.recv`` marks name."""
    return {n for s in _spans(run, RECV) or () for n in s["tags"]["threads"]}


def group_of(thread: str, recv: set) -> str:
    """Which share of a height a thread's CPU time belongs to. Each thread
    has one: a connection's receive thread that calls ``receive`` is the
    receive side's, not the peers'."""
    if thread == CONSENSUS_THREAD:
        return "consensus"
    if thread == VERIFY_THREAD:
        return "verify"
    if thread in recv:
        return "recv"
    if thread.startswith(PEER_THREADS):
        return "peers"
    return "other"


def share(run, group: str) -> float | None:
    """CPU seconds of one group of threads over the wall seconds of the
    window's heights, %. ``process``: the whole process's. ``other``: every
    thread no other group claims, and ``rest_s`` (what the process got that
    no live Python thread accounts for: the runtime's and the compiler's
    threads, threads that died), so the five groups add up to ``process``."""
    got = census(run)
    if got is None:
        return None
    if group == "process":
        return 100.0 * got["process_s"] / got["wall_s"]
    recv = receivers(run)
    mine = sum(s for name, s in got["threads"].items()
               if group_of(name, recv) == group)
    if group == "other":
        mine += got["rest_s"]
    return 100.0 * mine / got["wall_s"]
