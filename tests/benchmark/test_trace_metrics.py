"""PR 23's per-layer metrics: the arithmetic of benchmark/harness/spans.py on
synthetic spans, that every entry of BENCHMARK.json finds its reader, that a
program without the spans (a parent commit) reads as nothing and not as
zero, and a traced rehearsal that prints every new metric the CPU can carry."""

import json
import os
import shutil
import types

import pytest

from benchmark.harness import record, spans, spec
from tendermint_tpu.utils import trace
from tests.benchmark.test_harness import _last_line, _run

NEW_TIP = {"assemble_ms", "sign_bytes_ms", "tally_ms", "wake_ms",
           "prep_keyset_ms", "prep_hash_ms", "prep_launch_ms"}
NEW_REPLAY = {"dispatch_ms", "head_wait_ms"}
NEW_SETUP = {"key_decode_s", "table_build_s", "jit_trace_s", "jit_compile_s"}
TIP_CELLS = ["hub-10k.tip", "hub-10k.tip-4chip", "fastsync-1k-mixed.tip"]


def _span(name, start, dur, **tags):
    return {"name": name, "start": start, "duration_s": dur, "span_id": 1,
            "parent_id": 0, "tags": tags}


def _synthetic_run(span_dicts, decisions=2):
    run = record.Run(cell=types.SimpleNamespace(name="t"), seed=1, seconds=1.0,
                     traced=True, rehearse=True)
    run.decisions = [record.Decision(10.0 + k, 10.5 + k, 100, True)
                     for k in range(decisions)]
    run.window = (10.0, 10.0 + decisions)
    run.spans = span_dicts
    return run


def test_span_sums_are_per_decision_and_tags_are_summed():
    run = _synthetic_run([
        _span("commit.assemble", 10.0, 0.060, sign_bytes_s=0.035, sigs=100),
        _span("commit.assemble", 11.0, 0.070, sign_bytes_s=0.045, sigs=100),
        _span("commit.tally", 10.4, 0.002),
        _span("prep.launch", 10.1, 0.001, sigs=4096, lanes=4096),
        _span("prep.launch", 10.2, 0.001, sigs=4096, lanes=4096),
        _span("prep.launch", 10.3, 0.001, sigs=1807, lanes=4096),
    ])
    assert spans.ms_per_decision(run, "commit.assemble") == pytest.approx(65.0)
    assert spans.tag_ms_per_decision(
        run, "commit.assemble", "sign_bytes_s") == pytest.approx(40.0)
    assert spans.ms_per_decision(run, "commit.tally") == pytest.approx(1.0)
    # the program has the span and wrote none: a true zero, not "nothing"
    assert spans.ms_per_decision(run, "verify.wake") == 0.0
    # 9,999 real signatures in three 4,096-lane chunks: ISSUE 23's 81.4%
    assert spans.lane_fill(run) == pytest.approx(100 * 9999 / 12288)
    assert round(spans.lane_fill(run), 1) == 81.4


def test_a_program_without_the_span_reads_as_nothing(monkeypatch):
    """Laid over a parent commit, the readers must leave their metric out:
    no such name in CANONICAL_SPANS, no STARTUP ring."""
    run = _synthetic_run([_span("fastsync.dispatch", 10.0, 0.004)])
    monkeypatch.setattr(trace, "CANONICAL_SPANS",
                        {"fastsync.dispatch": "", "verify.queue": ""})
    monkeypatch.delattr(trace, "STARTUP")
    cell = spec.Cell("fastsync-1k-mixed.replay")
    got = {entry["name"]: read(run) for entry, read in cell.per_layer()
           if entry["name"] in NEW_REPLAY | NEW_SETUP | {"sync_lane_fill"}}
    # the one span the parent has always written now has a reader
    assert got.pop("dispatch_ms") == pytest.approx(2.0)
    assert set(got) == {"head_wait_ms", "sync_lane_fill"} | NEW_SETUP
    assert all(v is None for v in got.values())
    cell = spec.Cell("hub-10k.tip")
    got = {entry["name"]: read(run) for entry, read in cell.per_layer()
           if entry["name"] in NEW_TIP | {"lane_fill", "span_clock_skew_us"}}
    assert len(got) == len(NEW_TIP) + 2 and all(v is None for v in got.values())


def test_no_launch_means_no_lane_fill():
    assert spans.lane_fill(_synthetic_run([])) is None


def test_startup_seconds_take_the_union_before_the_window(monkeypatch):
    ring = trace.Tracer("ring", cap=64, cold=True)
    monkeypatch.setattr(trace, "STARTUP", ring)
    # jax reports a nested trace inside its caller's: counted once
    ring.record("startup.jit_trace", 4.0, start=1.0, fun="outer")
    ring.record("startup.jit_trace", 1.5, start=2.0, fun="inner")
    ring.record("startup.jit_trace", 1.0, start=6.0)
    ring.record("startup.jit_trace", 9.0, start=10.5)       # in the window
    # the first table build holds its own trace and compile
    ring.record("startup.table_build", 3.0, start=5.5, keys=8)
    ring.record("startup.jit_compile", 0.5, start=7.0)
    ring.record("startup.jit_compile", 2.0, start=20.0)      # after it
    run = _synthetic_run([])
    assert spans.startup_s(run, "startup.jit_trace") == pytest.approx(5.0)
    assert spans.startup_s(run, "startup.jit_compile") == pytest.approx(0.5)
    assert spans.startup_s(run, "startup.table_build") == pytest.approx(3.0)
    assert spans.startup_s(
        run, "startup.table_build",
        minus=("startup.jit_trace", "startup.jit_compile")) == pytest.approx(1.5)
    # the ring is there and holds none of these: a true zero
    assert spans.startup_s(run, "startup.key_decode") == 0.0


def test_every_listed_metric_has_a_reader_and_new_entries_only_append():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    first_new = names.index("assemble_ms")
    assert names[:first_new][-1] == "compile_cache_misses"   # PR 22's last
    assert set(names[first_new:]) == (
        NEW_TIP | NEW_REPLAY | NEW_SETUP
        | {"lane_fill", "sync_lane_fill", "span_clock_skew_us"})
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_TIP | {"lane_fill"}:
        assert by_name[name]["workloads"] == TIP_CELLS, name
    for name in NEW_SETUP:
        assert "workloads" not in by_name[name] and by_name[name]["moves"] == "setup_s"
    for cell in [w["name"] for w in bench["workloads"]]:
        listed = {entry["name"] for entry, _read in spec.Cell(cell).per_layer()}
        assert NEW_SETUP <= listed
        assert ({"lane_fill"} | NEW_TIP <= listed) == (cell in TIP_CELLS)


@pytest.mark.parametrize("workload, new", [
    ("fastsync-1k-mixed.tip", NEW_TIP),
    ("fastsync-1k-mixed.replay", NEW_REPLAY),
])
def test_traced_rehearsal_prints_every_new_metric_the_cpu_can_carry(
        workload, new, tmp_path):
    """From a temp copy of the benchmark (test_harness.py's pattern): the
    new entries of BENCHMARK.json load, and every new span-read metric is
    on the line. lane_fill is not: on the CPU the C verifier answers, so
    nothing is launched."""
    root = str(tmp_path / "copy")
    shutil.copytree(os.path.join(spec.ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns(".data", ".trace", "__pycache__"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    line = _last_line(_run(["--workload", workload, "--seed", "23",
                            "--seconds", "1", "--trace", "1", "--rehearse"],
                           root=root))
    assert line["correct"] is True
    got = line["metrics"]
    assert new | NEW_SETUP <= set(got)
    assert not {"lane_fill", "sync_lane_fill"} & set(got)
    if "assemble_ms" in got:
        assert 0 < got["sign_bytes_ms"]["value"] < got["assemble_ms"]["value"]
        # the bridged commit.assemble annotations reached the host plane
        assert got["span_clock_skew_us"]["value"] < 1000
        assert got["span_clock_skew_us"]["unit"] == "us"
    for name in NEW_SETUP:
        assert got[name]["unit"] == "s" and got[name]["value"] >= 0
