#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, in a new process:

    python3 benchmark/run.py --workload NAME --seed N --seconds S --trace 0|1

loads or generates the cell's data from the seed, warms up as a node does,
measures for S seconds, checks the answers against the benchmark's plain
reference, and prints the contract's one JSON object as the last line of its
standard output. Everything else worth saying goes on earlier lines.

It refuses (non-zero exit, no result line) unless JAX's backend is a TPU
with the cell's chip count, and whenever a TM_TPU_* / TMTPU_* variable is
set. ``--rehearse`` alone may run on the CPU: the configuration's
``rehearse`` sizes, for tests/benchmark; its output names the CPU and is
never a measurement. See benchmark/README.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_PROCESS_START = time.monotonic()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

EXIT_REFUSED = 2


def say(**fields) -> None:
    """An earlier line: one JSON object, never the last."""
    print(json.dumps(fields, default=str), flush=True)


def refuse(why: str) -> int:
    print(f"benchmark/run.py: refusing to run: {why}", file=sys.stderr)
    return EXIT_REFUSED


def _service_counters() -> dict:
    from tendermint_tpu.crypto import verify_service

    svc = verify_service.get()
    return {k: getattr(svc, k) for k in
            ("launches", "requests", "coalesced_items", "max_coalesced",
             "fallbacks")}


def run_cell(args) -> int:
    from benchmark.harness import datagen, device, record, spec

    cell = spec.Cell(args.workload)
    found = device.program_env(args.rehearse)
    if found:
        return refuse(f"{', '.join(found)} set; a cell measures what a user "
                      f"gets by default")
    cfg = dict(cell.config)
    if args.rehearse:
        cfg["dataset"] = {**cfg["dataset"], **cfg["rehearse"]}

    import jax

    dev = device.device_info()
    if args.rehearse:
        if dev["platform"] != "cpu":
            return refuse("--rehearse is for the CPU; run the cell itself on "
                          "a chip")
    elif dev["platform"] != "tpu" or dev["count"] != cell.chips:
        return refuse(f"cell {cell.name!r} needs {cell.chips} TPU chip(s); "
                      f"jax has {dev['count']} x {dev['platform']} "
                      f"({dev['kind']})")
    say(device=dev, jax=jax.__version__, rehearse=args.rehearse)

    # --- data: signed by children that never import jax ------------------------
    ds = datagen.load_or_generate(
        cell.config_name + ("-rehearse" if args.rehearse else ""), cfg,
        args.seed)
    say(dataset=ds.meta, validators=ds.vals.size(), heights=len(ds.commits))

    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.ops import ed25519_batch

    cache = device.CacheWatch()
    run = record.Run(cell=cell, seed=args.seed, seconds=args.seconds,
                     traced=bool(args.trace), rehearse=args.rehearse)
    run.trace_dir = os.path.join(ROOT, "benchmark", ".trace",
                                 f"{cell.name}-{args.seed}")
    run.profile_skip = cell.traffic["profile_skip"]
    run.profile_count = min(cell.traffic["profile_decisions"],
                            cell.config.get("profile_decisions_max", 1 << 30))
    if args.rehearse:
        run.profile_skip, run.profile_count = 2, 4

    # --- warm up as a node does (node/node.py: crypto.batch.warmup), then
    # the cell's own first decisions ------------------------------------------
    t0 = time.monotonic()
    crypto_batch.warmup(background=False)
    say(node_warmup=crypto_batch.WARMUP.state,
        calibration=dict(ed25519_batch._HOST_CAL),
        host_crossover=ed25519_batch.host_crossover())
    if crypto_batch.WARMUP.state == "failed":
        run.fail("warmup",
                 f"node warm-up failed: {crypto_batch.WARMUP.error!r}")
    driver = cell.driver.Driver(run, ds, cell.traffic)
    driver.warm_up()
    misses_before_window = cache.misses
    run.setup.update(device=dev, datagen_s=ds.meta["seconds"],
                     warmup_s=time.monotonic() - t0)

    # --- the measured window -----------------------------------------------
    before = _service_counters()
    run.setup["setup_s"] = time.monotonic() - T_PROCESS_START
    driver.measure()
    after = _service_counters()
    run.counters = {k: (before[k], after[k]) for k in before}
    compiled_in_window = cache.misses - misses_before_window
    run.setup["memory_peak_bytes"] = device.memory_peak_bytes()
    run.compare("compiled_in_window", compiled_in_window, 0)
    if compiled_in_window:
        run.fail("compiled_in_window", f"{compiled_in_window} program(s) "
                 f"compiled inside the measured window")

    # --- correctness, outside the window -------------------------------------
    driver.check()
    run.setup["compile_cache_misses"] = cache.misses
    attempted = len(run.decisions)
    failed = sum(1 for d in run.decisions if not d.ok)
    run.compare("decisions_failed", failed, 0)
    if attempted == 0:
        run.failures.append("no decision was attempted in the window")
    say(window_s=run.window[1] - run.window[0] - run.profiler_s,
        profiler_s=run.profiler_s, attempted=attempted,
        failed=failed, passes=len(run.passes), counters=run.counters,
        compile_cache=cache.snapshot(), compiled_in_window=compiled_in_window,
        setup={k: v for k, v in run.setup.items() if k != "device"},
        notes=run.notes, failures=run.failures)

    # --- metrics ---------------------------------------------------------------
    device_out = {**dev, "memory_peak_bytes": run.setup["memory_peak_bytes"]}
    result = {"correct": not run.failures and failed == 0,
              "attempted": attempted, "failed": failed}
    if args.trace:
        from benchmark.harness import layers

        run.load_trace()
        readers = cell.per_layer()
        busy = layers.busy_and_window(run)
        if busy is not None:
            device_out["busy_s"], device_out["window_s"] = busy
            say(trace=run.trace.path,
                profiled_decisions=len(run.profiled_decisions()),
                idle_share_per_chip=layers.idle_share_per_chip(run),
                programs=layers.program_totals(run),
                roofline=layers.kernel_roofline(run, dev["kind"]),
                span_totals_ms={n: sum(run.span_durations(n)) * 1e3
                                for n in sorted({s["name"] for s in run.spans})})
            result["breakdown"] = layers.breakdown(run)
        elif not args.rehearse:
            return refuse("the traced run found no device operation in its "
                          "trace: every cell drives the device path")
    else:
        readers = cell.end_to_end()
    metrics = {}
    for entry, read in readers:
        value = read(run)
        if value is None:
            if not args.trace and not args.rehearse:
                return refuse(f"end-to-end metric {entry['name']} has no value")
            continue
        metrics[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    result["metrics"] = metrics
    result["device"] = device_out
    # last in the line, and as the last lines of standard error: which checks
    # failed and every number that was held to a limit, beside that limit
    result["failures"] = run.failure_summary()
    print(json.dumps(result), flush=True)
    for name, (value, limit) in run.compared.items():
        print(f"compared {name}: {value} (limit {limit})", file=sys.stderr)
    print(f"failures {json.dumps(result['failures'])}", file=sys.stderr,
          flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (BENCHMARK.json's "
                         "run_seconds when left out)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU, for tests; never a measurement")
    args = ap.parse_args(argv)
    from benchmark.harness import spec

    try:
        if args.seconds is None:
            with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
                args.seconds = float(json.load(f)["run_seconds"])
        return run_cell(args)
    except spec.SpecError as e:
        return refuse(str(e))


if __name__ == "__main__":
    sys.exit(main())
