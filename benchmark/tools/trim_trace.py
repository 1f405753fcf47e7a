#!/usr/bin/env python3
"""Cut an .xplane.pb down to a test fixture: the device planes' ``XLA
Modules`` and ``XLA Ops`` lines and the host's ``bench.*`` annotations, for
the first N annotated decisions, with every statistic dropped.

    python3 benchmark/tools/trim_trace.py IN.xplane.pb OUT.xplane.pb [N]

Needs the xplane protobuf classes that tensorflow ships (a tool, run by
hand; the harness itself reads traces with jax alone).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import xplane  # noqa: E402


def trim(src: str, dst: str, decisions: int, prefix: str = "bench.") -> None:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    host = next(p for p in space.planes if p.name == xplane.HOST_PLANE)
    names = {i: m.name for i, m in host.event_metadata.items()}
    marks = sorted(
        (line.timestamp_ns * 1000 + ev.offset_ps,
         line.timestamp_ns * 1000 + ev.offset_ps + ev.duration_ps)
        for line in host.lines for ev in line.events
        if names[ev.metadata_id] in (prefix + "decision", prefix + "process_next"))
    t0, t1 = marks[0][0], marks[min(decisions, len(marks)) - 1][1]
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        device = xplane.DEVICE_PLANE.match(plane.name)
        if not device and plane.name != xplane.HOST_PLANE:
            continue
        new = out.planes.add(id=plane.id, name=plane.name)
        for line in plane.lines:
            if device and line.name not in (xplane.MODULES_LINE, xplane.OPS_LINE):
                continue
            kept = []
            for ev in line.events:
                at = line.timestamp_ns * 1000 + ev.offset_ps
                name = plane.event_metadata[ev.metadata_id].name
                if not (t0 <= at <= t1) or (not device
                                            and not name.startswith(prefix)):
                    continue
                kept.append(ev)
            if not kept:
                continue
            nl = new.lines.add(id=line.id, name=line.name,
                               timestamp_ns=line.timestamp_ns)
            for ev in kept:
                nl.events.add(metadata_id=ev.metadata_id,
                              offset_ps=ev.offset_ps, duration_ps=ev.duration_ps)
                meta = plane.event_metadata[ev.metadata_id]
                new.event_metadata[ev.metadata_id].id = meta.id
                new.event_metadata[ev.metadata_id].name = meta.name
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())
    print(dst, os.path.getsize(dst), "bytes")


if __name__ == "__main__":
    trim(sys.argv[1], sys.argv[2], int(sys.argv[3]) if len(sys.argv) > 3 else 3)
