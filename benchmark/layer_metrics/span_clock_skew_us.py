"""How far the benchmark's clock alignment is off, us: the program bridges
every flight-recorder span to a profiler annotation of the same name, so a
profiled decision's commit.assemble is in the trace on the profiler's own
clock and in the ring on the host's. The median of |annotation start -
(ring start + Run.clock_offset())| over the slice is the error of the
median-offset alignment that the idle-gap attribution rests on."""

from benchmark.harness import stats, xplane

SPAN = "commit.assemble"


def read(run):
    off = run.clock_offset()
    if off is None:
        return None
    profiled = [(d.t0, d.t1) for d in run.profiled_decisions()]
    ring = sorted(s["start"] for s in run.spans if s["name"] == SPAN
                  and any(t0 <= s["start"] < t1 for t0, t1 in profiled))
    bridged = sorted(start for name, start, _end in
                     xplane.load(run.trace.path, "commit.").host_spans
                     if name == SPAN)
    if not ring or len(ring) != len(bridged):
        return None
    return stats.median([abs(a - (s + off)) for a, s in zip(bridged, ring)]) * 1e6
