#!/usr/bin/env python3
"""Print what an .xplane.pb holds: planes, lines, event counts, the names
that took most time. Read a trace by hand with this before trusting the
reduction (benchmark/harness/xplane.py).

    python3 benchmark/tools/describe_trace.py benchmark/.trace/<cell>-<seed>
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import xplane  # noqa: E402

if __name__ == "__main__":
    path = sys.argv[1]
    if os.path.isdir(path):
        path = xplane.find_trace_file(path)
    print(path, os.path.getsize(path), "bytes")
    print("\n".join(xplane.describe(path)))
