"""BENCHMARK.json and the files it names. A cell is data: its configuration,
its traffic mix, the driver the traffic names and every metric reader are
found by name, so a later PR adds a cell, a mix or a metric as new files and
edits none that is there. There is no registry and no ``if name ==`` here.
"""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SpecError(f"{path}: {e}") from e


def _module(path: str, attr: str):
    if not os.path.exists(path):
        raise SpecError(f"{path} does not exist")
    name = "benchmark_file_" + os.path.relpath(path, ROOT).replace(
        os.sep, "_").replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not hasattr(mod, attr):
        raise SpecError(f"{path} defines no `{attr}`")
    return mod


class Cell:
    """One entry of ``workloads`` with everything it names loaded."""

    def __init__(self, workload: str):
        self.benchmark = _read_json(os.path.join(ROOT, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.benchmark["workloads"]}
        if workload not in cells:
            raise SpecError(f"no workload {workload!r} in BENCHMARK.json; "
                            f"it has {sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        self.config_name = self.entry["config"]
        self.traffic_name = self.entry["traffic"]
        configs = {c["name"]: c for c in self.benchmark["configs"]}
        if self.config_name not in configs:
            raise SpecError(f"workload {workload!r} names config "
                            f"{self.config_name!r}, which `configs` lacks")
        self.config = _read_json(os.path.join(
            ROOT, configs[self.config_name]["file"]))
        self.traffic = _read_json(os.path.join(
            BENCH_DIR, "traffic", self.traffic_name + ".json"))
        self.driver = _module(os.path.join(
            BENCH_DIR, "drivers", self.traffic["driver"] + ".py"), "Driver")

    def _listed(self, group: str) -> list[dict]:
        return [m for m in self.benchmark[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def _readers(self, group: str, subdir: str) -> list[tuple[dict, object]]:
        return [(m, _module(os.path.join(BENCH_DIR, subdir,
                                         m["name"] + ".py"), "read").read)
                for m in self._listed(group)]

    def end_to_end(self):
        """[(metric entry, read(run))] for this cell's end-to-end metrics."""
        return self._readers("end_to_end", "end_to_end")

    def per_layer(self):
        return self._readers("per_layer", "layer_metrics")
