"""The arithmetic the per-layer readers share. A reader
(benchmark/layer_metrics/<name>.py) is a few lines that call into here; a
reader that finds nothing to read returns None and the harness leaves its
metric out of the line."""

from __future__ import annotations

import glob
import json
import os

from benchmark.harness import opmodel, record, spec, stats, xplane

PROGRAMS_DIR = os.path.join(spec.BENCH_DIR, "programs")
PEAKS_FILE = os.path.join(spec.BENCH_DIR, "harness", "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmark/harness/peaks.json: add it with its source")
    return table[device_kind]


def known_programs() -> dict[str, dict]:
    """XLA module name -> its description (benchmark/programs/<name>.json)."""
    out = {}
    for path in sorted(glob.glob(os.path.join(PROGRAMS_DIR, "*.json"))):
        with open(path) as f:
            out[os.path.splitext(os.path.basename(path))[0]] = json.load(f)
    return out


# --- flight-recorder spans ---------------------------------------------------


def decision_self_ms(run) -> float | None:
    """The entry point's own time: each decision's wall time minus the part
    of it that the program's verify.* spans cover (their union, so a span
    recorded on the service's thread is not counted twice)."""
    if not run.traced or not run.decisions:
        return None
    spans = sorted((s["start"], s["start"] + s["duration_s"])
                   for s in run.spans if s["name"] in record.VERIFY_SPANS)
    own = []
    at = 0
    for d in run.decisions:
        while at < len(spans) and spans[at][1] <= d.t0:
            at += 1
        inside = []
        k = at
        while k < len(spans) and spans[k][0] < d.t1:
            inside.append(spans[k])
            k += 1
        own.append((d.t1 - d.t0) - xplane.busy_seconds(inside, (d.t0, d.t1)))
    return stats.mean(own) * 1e3


# --- the device trace ----------------------------------------------------------


def _slice(run):
    """(trace, window, profiled decisions) or None when nothing was traced."""
    if run.trace is None or not run.trace.chips:
        return None
    window = run.trace_window()
    decs = run.profiled_decisions()
    if window is None or not decs:
        return None
    return run.trace, window, decs


def busy_and_window(run) -> tuple[float, float] | None:
    """(busy seconds, mean over the chips used; seconds of the slice)."""
    got = _slice(run)
    if got is None:
        return None
    trace, window, _ = got
    busy = [xplane.busy_seconds(trace.busy_intervals(c), window)
            for c in trace.chips]
    return stats.mean(busy), window[1] - window[0]


def idle_share_per_chip(run) -> dict[int, float] | None:
    got = _slice(run)
    if got is None:
        return None
    trace, window, _ = got
    return {c: 100.0 * xplane.idle_share(trace.busy_intervals(c), window)
            for c in trace.chips}


def device_idle_share(run) -> float | None:
    per_chip = idle_share_per_chip(run)
    return None if per_chip is None else stats.mean(list(per_chip.values()))


def program_totals(run) -> dict[str, dict] | None:
    """Device programs that started inside the slice: name -> {count,
    seconds}, both as a mean over the chips."""
    got = _slice(run)
    if got is None:
        return None
    trace, window, _ = got
    out: dict[str, dict] = {}
    for c in trace.chips:
        for name, t in xplane.program_totals(
                trace.device_programs.get(c, []), window).items():
            o = out.setdefault(name, {"count": 0.0, "seconds": 0.0})
            o["count"] += t["count"] / len(trace.chips)
            o["seconds"] += t["seconds"] / len(trace.chips)
    return out


def kernel_us_per_sig(run) -> float | None:
    """Device time of every program that ran in the slice over the real
    (unpadded) signatures its decisions verified."""
    totals = program_totals(run)
    if not totals:
        return None
    sigs = sum(d.sigs for d in run.profiled_decisions())
    return sum(t["seconds"] for t in totals.values()) * 1e6 / sigs


def device_launches_per_decision(run) -> float | None:
    """Executions of the verify kernels (benchmark/programs/*.json with role
    verify_kernel) per decision; 0 means the host answered."""
    totals = program_totals(run)
    if totals is None:
        return None
    kernels = {n for n, p in known_programs().items()
               if p["role"] == "verify_kernel"}
    return (sum(t["count"] for n, t in totals.items() if n in kernels)
            / len(run.profiled_decisions()))


def kernel_roofline(run, device_kind: str) -> dict | None:
    """Least time the chip could take for the verify kernels' calls over the
    time they took. -> {share_pct, bound, compute_s, hbm_s, kernel_s}."""
    totals = program_totals(run)
    if not totals:
        return None
    peak = peaks(device_kind)
    if not peak.get("int32_mul_add_per_s"):
        return None  # the compute peak of this device was never measured
    least = {"compute": 0.0, "hbm": 0.0}
    kernel_s = 0.0
    for name, prog in known_programs().items():
        if prog["role"] != "verify_kernel" or name not in totals:
            continue
        work = opmodel.call_work(prog)
        calls = totals[name]["count"]
        least["compute"] += calls * work["mul_adds"] / peak["int32_mul_add_per_s"]
        least["hbm"] += calls * work["bytes"] / peak["hbm_bytes_per_s"]
        kernel_s += totals[name]["seconds"]
    if kernel_s <= 0:
        return None
    bound = max(least, key=least.get)
    return {"share_pct": 100.0 * least[bound] / kernel_s, "bound": bound,
            "compute_s": least["compute"], "hbm_s": least["hbm"],
            "kernel_s": kernel_s}


def breakdown(run) -> dict | None:
    """The contract's ``breakdown``: the device programs that took most time
    and the longest idle gaps by what the host was doing (mean over chips)."""
    got = _slice(run)
    if got is None:
        return None
    trace, window, _ = got
    totals = program_totals(run) or {}
    host = run.host_intervals()
    idle: dict[str, float] = {}
    for c in trace.chips:
        by = xplane.attribute_gaps(
            xplane.gaps(trace.busy_intervals(c), window), host)
        for name, sec in by.items():
            idle[name] = idle.get(name, 0.0) + sec / len(trace.chips)
    return {"device_ops": xplane.top({n: t["seconds"] for n, t in totals.items()}),
            "idle_gaps": xplane.top(idle)}
