"""The arithmetic between a trace and a metric: busy union, idle share, time
per program, gap attribution (on synthetic events and on a small trace
recorded on the chip), the percentile rule, and the operation model."""

import os
import time
import types

import pytest

from benchmark.harness import layers, opmodel, record, stats, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


# --- synthetic events ----------------------------------------------------------


def test_merge_and_busy_union_count_overlap_once():
    iv = [(0.0, 2.0), (1.0, 3.0), (5.0, 6.0), (5.5, 5.75), (9.0, 9.0)]
    assert xplane.merge(iv) == [(0.0, 3.0), (5.0, 6.0)]
    assert xplane.busy_seconds(iv, (0.0, 10.0)) == pytest.approx(4.0)
    # the window clips: half of the first stretch, none of the second
    assert xplane.busy_seconds(iv, (1.5, 4.0)) == pytest.approx(1.5)


def test_idle_share_and_gaps():
    iv = [(1.0, 2.0), (3.0, 4.0)]
    assert xplane.idle_share(iv, (0.0, 5.0)) == pytest.approx(0.6)
    assert xplane.gaps(iv, (0.0, 5.0)) == [(0.0, 1.0), (2.0, 3.0), (4.0, 5.0)]
    assert xplane.gaps([], (0.0, 1.0)) == [(0.0, 1.0)]
    assert xplane.idle_share([(0.0, 9.0)], (2.0, 4.0)) == 0.0
    with pytest.raises(ValueError):
        xplane.idle_share(iv, (1.0, 1.0))


def test_time_per_program_strips_the_fingerprint_and_honours_the_window():
    events = [("jit__verify_chunk(123)", 0.0, 0.010),
              ("jit__verify_chunk(123)", 0.020, 0.031),
              ("jit_pack_bitmap(9)", 0.031, 0.032),
              ("jit__verify_chunk(123)", 5.0, 5.5)]      # starts outside
    got = xplane.program_totals(events, (0.0, 1.0))
    assert got["jit__verify_chunk"]["count"] == 2
    assert got["jit__verify_chunk"]["seconds"] == pytest.approx(0.021)
    assert got["jit_pack_bitmap"] == {"count": 1, "seconds": pytest.approx(0.001)}
    assert xplane.program_totals(events)["jit__verify_chunk"]["count"] == 3


def test_gaps_go_to_the_innermost_host_span_and_are_cut_at_its_edges():
    host = [("bench.decision", 0.0, 10.0), ("verify.host_prep", 1.0, 3.0),
            ("bench.between", 10.0, 11.0)]
    got = xplane.attribute_gaps([(0.5, 4.0), (9.75, 10.5), (20.0, 21.0)], host)
    assert got["verify.host_prep"] == pytest.approx(2.0)
    assert got["bench.decision"] == pytest.approx(0.5 + 1.0 + 0.25)
    assert got["bench.between"] == pytest.approx(0.5)
    assert got["unattributed"] == pytest.approx(1.0)
    assert [name for name, _sec in xplane.top(got, 2)] == [
        "verify.host_prep", "bench.decision"]


def _synthetic_run():
    """Two profiled decisions of 100 signatures on two chips."""
    cell = types.SimpleNamespace(name="x")
    run = record.Run(cell=cell, seed=0, seconds=1.0, traced=True, rehearse=False)
    run.decisions = [record.Decision(100.0, 100.4, 100, True, True),
                     record.Decision(100.5, 100.9, 100, True, True),
                     record.Decision(101.0, 101.4, 100, True, False)]
    trace = xplane.Trace()
    off = 1000.0    # the profiler's clock is not time.monotonic()
    trace.host_spans = [("bench.decision", off + 100.0, off + 100.4),
                        ("bench.between", off + 100.4, off + 100.5),
                        ("bench.decision", off + 100.5, off + 100.9)]
    for chip, busy in ((0, 0.3), (1, 0.1)):
        trace.device_programs[chip] = [
            ("jit__verify_chunk(1)", off + 100.05, off + 100.05 + busy),
            ("jit__verify_chunk(1)", off + 100.55, off + 100.55 + busy)]
    run.trace = trace
    run.spans = [{"name": "verify.host_prep", "start": 100.0, "duration_s": 0.05},
                 {"name": "verify.readback", "start": 100.05, "duration_s": 0.3},
                 {"name": "verify.host_prep", "start": 100.5, "duration_s": 0.05},
                 {"name": "verify.readback", "start": 100.55, "duration_s": 0.3},
                 {"name": "verify.host_prep", "start": 101.0, "duration_s": 0.05}]
    return run


def test_layer_arithmetic_on_a_synthetic_run():
    run = _synthetic_run()
    assert run.clock_offset() == pytest.approx(1000.0)
    assert run.trace_window() == (pytest.approx(1100.0), pytest.approx(1100.9))
    busy, window = layers.busy_and_window(run)
    assert window == pytest.approx(0.9) and busy == pytest.approx((0.6 + 0.2) / 2)
    per_chip = layers.idle_share_per_chip(run)
    assert per_chip[0] == pytest.approx(100 * (1 - 0.6 / 0.9))
    assert per_chip[1] == pytest.approx(100 * (1 - 0.2 / 0.9))
    assert layers.device_idle_share(run) == pytest.approx(
        (per_chip[0] + per_chip[1]) / 2)
    # device time per real signature: mean over chips, 200 signatures
    assert layers.kernel_us_per_sig(run) == pytest.approx(0.4e6 / 200)
    # 0.05 s of each 0.4 s decision is outside the verify.* spans
    assert layers.decision_self_ms(run) == pytest.approx(
        (0.05 + 0.05 + 0.35) / 3 * 1e3)
    assert run.span_ms_per_decision("verify.host_prep") == pytest.approx(50.0)
    gaps = dict(layers.breakdown(run)["idle_gaps"])
    assert gaps["bench.between"] == pytest.approx(0.1)
    assert gaps["verify.host_prep"] == pytest.approx(0.1)   # aligned by the offset
    assert dict(layers.breakdown(run)["device_ops"])["jit__verify_chunk"] == \
        pytest.approx(0.4)


def test_the_windows_clock_stops_while_the_profiler_starts_and_exports(
        monkeypatch, tmp_path):
    """A traced window holds --seconds of decisions whatever the export of
    the trace costs: half a minute where a jnp kernel runs, in a 30 s
    window (fastsync-1k-mixed.tip, PR 22)."""
    import jax

    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: time.sleep(0.05))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: time.sleep(0.25))
    run = record.Run(cell=types.SimpleNamespace(name="x"), seed=0, seconds=1.0,
                     traced=True, rehearse=False)
    run.trace_dir = str(tmp_path / "trace")
    run.profile_skip, run.profile_count = 1, 2
    run.open_window("decision")
    for _ in range(4):
        run.decide(lambda: True, 1)
    wall, inside = time.monotonic() - run.window[0], run.elapsed()
    run.close_window()
    assert [d.profiled for d in run.decisions] == [False, True, True, False]
    assert run.profiler_s >= 0.3
    assert inside == pytest.approx(wall - run.profiler_s, abs=0.02)


def test_a_run_with_no_device_trace_reads_nothing():
    run = _synthetic_run()
    run.trace = xplane.Trace()
    assert layers.busy_and_window(run) is None
    assert layers.kernel_us_per_sig(run) is None
    assert layers.device_idle_share(run) is None
    assert layers.breakdown(run) is None


# --- a trace recorded on the chip ------------------------------------------------

FIXTURE = os.path.join(DATA, "hub-10k.tip.2-decisions.xplane.pb")


def test_reduction_of_a_trace_recorded_on_the_chip():
    """Two decisions of hub-10k.tip on one TPU v5 lite (PR 22's first chip
    run, cut down by benchmark/tools/trim_trace.py): 9,999 signatures each,
    three 4,096-lane Pallas chunks, a bitmap pack and a concatenate."""
    assert os.path.getsize(FIXTURE) <= 200_000
    trace = xplane.load(FIXTURE, ops=True)
    assert trace.chips == [0]
    assert [n for n, _s, _e in trace.host_spans] == [
        "bench.decision", "bench.between", "bench.decision"]
    window = trace.window_of("bench.decision")
    assert window[1] - window[0] == pytest.approx(0.196636, abs=1e-6)
    programs = xplane.program_totals(trace.device_programs[0], window)
    assert {n: t["count"] for n, t in programs.items()} == {
        "jit__verify_chunk": 6, "jit_pack_bitmap": 2, "jit_concatenate": 2}
    assert programs["jit__verify_chunk"]["seconds"] == pytest.approx(0.0256897, abs=1e-6)
    # the ops of a program run back to back: both unions agree to 0.03%
    by_ops = xplane.busy_seconds(trace.busy_intervals(0), window)
    by_programs = xplane.busy_seconds(
        [(s, e) for _n, s, e in trace.device_programs[0]], window)
    assert by_ops == pytest.approx(0.0256878, abs=1e-6)
    assert by_programs == pytest.approx(by_ops, rel=1e-3)
    assert xplane.idle_share(trace.busy_intervals(0), window) == pytest.approx(
        0.86936, abs=1e-4)
    # every idle second lies inside one of the benchmark's annotations
    by_host = xplane.attribute_gaps(
        xplane.gaps(trace.busy_intervals(0), window), trace.host_spans)
    assert set(by_host) == {"bench.decision", "bench.between", "unattributed"}
    assert by_host["unattributed"] < 50e-6   # between two annotations
    assert by_host["bench.decision"] > 0.16
    assert sum(by_host.values()) == pytest.approx(
        (window[1] - window[0]) - by_ops, rel=1e-9)
    # what the harness loads by default leaves the op events out
    assert not xplane.load(FIXTURE).device_ops


def test_layer_metrics_on_the_recorded_trace():
    run = record.Run(cell=types.SimpleNamespace(name="hub-10k.tip"), seed=0,
                     seconds=1.0, traced=True, rehearse=False)
    run.trace = xplane.load(FIXTURE)
    w = run.trace.window_of("bench.decision")
    run.decisions = [record.Decision(w[0], w[0] + 0.0955, 9999, True, True),
                     record.Decision(w[1] - 0.1002, w[1], 9999, True, True)]
    assert layers.kernel_us_per_sig(run) == pytest.approx(1.2848, abs=1e-3)
    assert layers.device_launches_per_decision(run) == 3.0
    roof = layers.kernel_roofline(run, "TPU v5 lite")
    assert roof["bound"] == "compute"
    # 6 chunks x 2,347,417,600 multiply-adds at the measured 2.5694e12 / s
    assert roof["compute_s"] == pytest.approx(6 * 2_347_417_600 / 2.5694e12)
    assert roof["share_pct"] == pytest.approx(21.34, abs=0.05)
    assert roof["hbm_s"] < roof["compute_s"] / 10


# --- percentiles -----------------------------------------------------------------


def test_percentile_refuses_a_tail_the_sample_cannot_carry():
    assert stats.min_samples(95) == 200 and stats.min_samples(50) == 20
    with pytest.raises(ValueError, match="p95 needs >= 200"):
        stats.percentile([1.0] * 199, 95)
    s = [float(i) for i in range(1, 201)]
    assert stats.percentile(s, 95) == 190.0
    assert stats.percentile(s, 50) == 100.0
    assert stats.median(s) == 100.5 and stats.median([3.0, 1.0, 2.0]) == 2.0
    with pytest.raises(ValueError):
        stats.median([])


# --- the operation model ------------------------------------------------------------


def test_operation_counts_of_one_4096_lane_pallas_chunk_are_pinned():
    prog = layers.known_programs()["jit__verify_chunk"]
    assert prog["role"] == "verify_kernel" and prog["lanes_per_call"] == 4096
    lane = opmodel.lane_counts(prog)
    # 64 x (double 4M+4S, two niels additions 7M each), one inversion
    # (11M + 254S), x and y out of the inversion (2M)
    assert lane == {"field_mul": 1165, "field_sq": 510,
                    "mul_adds": 1165 * 400 + 510 * 210}
    work = opmodel.call_work(prog)
    assert work["mul_adds"] == 4096 * 573_100 == 2_347_417_600
    # 960 int32 table rows + 64 + 32 + 32 + 1 input bytes + 4 output bytes
    assert work["bytes"] == 4096 * (3840 + 129 + 4)


def test_every_described_program_has_a_computable_cost():
    for name, prog in layers.known_programs().items():
        assert prog["role"] in ("verify_kernel", "helper"), name
        if prog["role"] == "verify_kernel":
            work = opmodel.call_work(prog)
            assert work["mul_adds"] > 0 and work["bytes"] > 0, name


def test_peaks_table_knows_the_chip_and_refuses_any_other():
    v5e = layers.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks for device kind"):
        layers.peaks("TPU v9 imaginary")
