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


def _benchmark(path=os.path.join(spec.ROOT, "BENCHMARK.json")):
    with open(path) as f:
        return json.load(f)


OLD_CELLS = TIP_CELLS + ["fastsync-1k-mixed.replay"]       # PR 22's four
# BENCHMARK.json as PR 25 left it. A later PR appends to the file and edits
# nothing in it, so this copy is what it must still start with.
PINNED = _benchmark(os.path.join(os.path.dirname(__file__), "data",
                                 "BENCHMARK.pr25.json"))


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end",
                                   "per_layer"])
def test_new_entries_only_append(group):
    """The append-only rule: every entry PR 25 knew is there, in its place
    and unchanged, and everything else comes after; only a metric's
    ``workloads`` list may have grown, and only at its end."""
    today, pinned = _benchmark()[group], PINNED[group]
    assert [e["name"] for e in today[:len(pinned)]] == [e["name"] for e in pinned]
    for now, then in zip(today, pinned):
        was = then.get("workloads")
        assert ("workloads" in now) == (was is not None), now["name"]
        if was is not None:
            assert now["workloads"][:len(was)] == was, now["name"]
        assert ({k: v for k, v in now.items() if k != "workloads"}
                == {k: v for k, v in then.items() if k != "workloads"}), now["name"]


def test_the_pinned_copy_keeps_what_prs_22_and_23_listed():
    names = [m["name"] for m in PINNED["per_layer"]]
    first_new = names.index("assemble_ms")
    assert names[:first_new][-1] == "compile_cache_misses"   # PR 22's last
    pr23 = (NEW_TIP | NEW_REPLAY | NEW_SETUP
            | {"lane_fill", "sync_lane_fill", "span_clock_skew_us"})
    assert set(names[first_new:first_new + len(pr23)]) == pr23
    by_name = {m["name"]: m for m in PINNED["per_layer"]}
    for name in NEW_TIP | {"lane_fill"}:
        assert by_name[name]["workloads"][:3] == TIP_CELLS, name
    for name in NEW_SETUP:
        assert "workloads" not in by_name[name] and by_name[name]["moves"] == "setup_s"


def test_every_listed_metric_has_a_reader():
    bench = _benchmark()
    for cell in [w["name"] for w in bench["workloads"]]:
        loaded = spec.Cell(cell)
        for entry, read in loaded.per_layer() + loaded.end_to_end():
            assert callable(read), (cell, entry["name"])
        assert NEW_SETUP <= {e["name"] for e, _read in loaded.per_layer()}


CATCHUP = [m["name"] for m in PINNED["per_layer"]
           if m["moves"] == "catchup_blocks_per_s"]


@pytest.mark.parametrize("twin", ["catchup_blocks_per_s"] + CATCHUP)
def test_a_catchup_twin_is_the_reader_of_the_metric_it_is_named_after(twin):
    """hub-150.fastsync reports fast sync's quantities under names of its
    own, because a name carries one bound and one ``moves``: each twin is the
    original's reader, and the original no longer lists the cell."""
    import importlib

    fast = {m["name"]: m for m in PINNED["per_layer"] + PINNED["end_to_end"]
            if "fastsync-1k-mixed.replay" in m.get("workloads", [])}
    base = twin[len("catchup_"):]
    orig = next(n for n in (base, "sync_" + base, "decisions_per_s")
                if n in fast and (n != "decisions_per_s" or base == "blocks_per_s"))
    group = "end_to_end" if base == "blocks_per_s" else "layer_metrics"
    assert (importlib.import_module(f"benchmark.{group}.{twin}").read
            is importlib.import_module(f"benchmark.{group}.{orig}").read)
    assert "hub-150.fastsync" not in fast[orig]["workloads"]
    assert len(CATCHUP) == 12


@pytest.mark.parametrize("cell", OLD_CELLS)
def test_a_cell_lists_lane_fill_and_the_tip_seven_iff_it_is_a_tip_cell(cell):
    listed = {entry["name"] for entry, _read in spec.Cell(cell).per_layer()}
    assert ({"lane_fill"} | NEW_TIP <= listed) == (cell in TIP_CELLS)


def test_keyset_miss_share_reads_the_programs_own_hit_tag(monkeypatch):
    """The reader on spans the program itself writes: two signer sets never
    seen, then the first again in its order and in another. The table build
    is replaced (no XLA in the quick tier); the tag is build_keyset's."""
    import numpy as np

    from benchmark.layer_metrics import keyset_miss_share, sync_keyset_miss_share
    from tendermint_tpu.ops import ed25519_batch as edb

    class Tables(np.ndarray):
        def block_until_ready(self):
            return self

    monkeypatch.setattr(edb, "_build_comb_tables_tiled",
                        lambda a_neg: np.zeros((256, 16, 4, 20), np.int32).view(Tables))
    monkeypatch.setattr(edb, "_KS_CACHE", type(edb._KS_CACHE)())
    monkeypatch.setattr(edb, "_KS_UNIQ_CACHE", type(edb._KS_UNIQ_CACHE)())
    pubs = [bytes([i]) * 32 for i in range(1, 13)]
    rec = trace.Tracer("t", cap=64, enabled=True)
    try:
        with rec.activate():
            for batch in (pubs[:10], pubs[1:11], pubs[:10], pubs[9::-1]):
                edb.build_keyset(batch, edb._KS_CACHE, edb._KS_LOCK,
                                 lambda p: None, uniq_cache=edb._KS_UNIQ_CACHE)
    finally:
        rec.disable()
    run = _synthetic_run([s.as_dict() for s in rec.dump()])
    assert [s["tags"]["hit"] for s in run.spans] == ["miss", "miss",
                                                    "sequence", "set"]
    assert keyset_miss_share.read(run) == pytest.approx(50.0)
    assert sync_keyset_miss_share.read(run) == pytest.approx(50.0)
    # no lookup in the window (the host answered): nothing, not zero
    assert keyset_miss_share.read(_synthetic_run([])) is None


def test_a_rebuild_inside_the_window_is_read_from_the_ring(monkeypatch):
    from benchmark.layer_metrics import rebuild_decode_ms, rebuild_tables_ms

    ring = trace.Tracer("ring", cap=64, cold=True)
    monkeypatch.setattr(trace, "STARTUP", ring)
    run = _synthetic_run([])                       # window 10.0 .. 12.0
    assert rebuild_tables_ms.read(run) == 0.0      # a ring and no miss: zero
    ring.record("startup.table_build", 0.5, start=3.0, keys=9)    # set-up's
    ring.record("startup.key_decode", 0.020, start=10.1, keys=9)
    ring.record("startup.table_build", 0.300, start=10.2, keys=9)
    ring.record("startup.table_build", 0.500, start=11.2, keys=9)
    ring.record("startup.table_build", 0.7, start=12.5, keys=9)   # the check's
    assert rebuild_decode_ms.read(run) == pytest.approx(10.0)
    assert rebuild_tables_ms.read(run) == pytest.approx(400.0)
    monkeypatch.delattr(trace, "STARTUP")          # a program without a ring
    assert rebuild_tables_ms.read(run) is None


def test_dispatches_per_decision_counts_spans_over_decisions():
    from benchmark.layer_metrics import dispatches_per_decision

    run = _synthetic_run([_span("fastsync.dispatch", 10.0 + 0.1 * k, 0.002)
                          for k in range(5)], decisions=4)
    assert dispatches_per_decision.read(run) == pytest.approx(1.25)


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
