"""benchmark/run.py end to end on the CPU, tiny (--rehearse): the contract's
last line, the refusals, and that a later PR's cell, traffic mix and layer
metric are picked up as new files."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.harness.spec import ROOT

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
# PR 48: which checks failed and what was compared, last in the line
LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device", "failures"]


def _run(args, root=ROOT, env_extra=None, unset=()):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "TM_TPU_SKIP_WARMUP": "1",
                "PYTHONPATH": ROOT})
    env.update(env_extra or {})
    for name in unset:
        env.pop(name)
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        capture_output=True, text=True, timeout=300, env=env, cwd=root)


def _last_line(out) -> dict:
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload, metric, live", [
    ("hub-10k.tip", "commit_p50_ms", False),
    ("fastsync-1k-mixed.replay", "decisions_per_s", False),
    ("hub-10k-live.tip", "commit_p50_ms", True),
    ("hub-150.fastsync", "catchup_blocks_per_s", True),
])
def test_rehearsal_prints_the_contracts_last_line(workload, metric, live):
    out = _run(["--workload", workload, "--seed", "11",
                "--seconds", "1", "--trace", "0", "--rehearse"])
    line = _last_line(out)
    if live:
        # the rehearsal shares (10% absent, 5% nil) take the new paths: a nil
        # vote among the corrupted lanes, signer sets that differ
        notes = json.loads(out.stdout.strip().splitlines()[-2])["notes"]
        assert notes["corrupted_nil_votes"] >= 1
        assert notes["signer_sets"]["distinct"] > 1
        assert notes["light_prefix_sigs"][0] >= 9
    assert list(line) == LINE_KEYS
    assert line["failures"]["n"] == 0 and line["failures"]["by_check"] == {}
    assert line["failures"]["first"] == []
    assert line["failures"]["compared"]["decisions_failed"] == [0, 0]
    # standard error ends with the same numbers, one a line
    assert out.stderr.strip().splitlines()[-1] == \
        "failures " + json.dumps(line["failures"])
    assert "compared decisions_failed: 0 (limit 0)" in out.stderr
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(line["metrics"]) >= {metric, "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0


def test_traced_rehearsal_reports_per_layer_metrics_only():
    line = _last_line(_run(["--workload", "fastsync-1k-mixed.tip", "--seed", "11",
                            "--seconds", "1", "--trace", "1", "--rehearse"]))
    assert line["correct"] is True
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"] for m in bench["per_layer"]}
    assert set(line["metrics"]) <= per_layer
    assert {"decision_self_ms", "host_prep_ms", "datagen_s"} <= set(line["metrics"])
    # no device ran, so no device-trace number may appear under any name
    assert not {"kernel_us_per_sig", "device_idle_share",
                "verify_kernel_roofline"} & set(line["metrics"])
    assert "busy_s" not in line["device"]


def test_traced_rehearsal_of_a_live_commit_carries_the_new_metrics():
    """Nil votes take the per-index sign bytes, so the spliced share falls
    below 100; hub-150.fastsync's twin reports its dispatch count. keyset_miss_share
    is not on a CPU line: the C verifier answers and looks no key set up
    (tests/benchmark/test_trace_metrics.py reads the program's own tag)."""
    args = ["--seed", "11", "--seconds", "1", "--trace", "1", "--rehearse"]
    line = _last_line(_run(["--workload", "hub-10k-live.tip"] + args))
    assert line["correct"] is True
    assert 50 < line["metrics"]["sign_bytes_spliced"]["value"] < 100
    assert "keyset_miss_share" not in line["metrics"]
    line = _last_line(_run(["--workload", "hub-150.fastsync"] + args))
    assert line["correct"] is True
    assert line["metrics"]["catchup_dispatches_per_decision"]["value"] == 1.0
    assert line["metrics"]["catchup_prep_keyset_ms"]["value"] == 0.0


def test_without_rehearse_the_cpu_is_refused():
    out = _run(["--workload", "hub-10k.tip", "--seed", "1", "--seconds", "1",
                "--trace", "0"], unset=["TM_TPU_SKIP_WARMUP"])
    assert out.returncode != 0
    assert "needs 1 TPU chip" in out.stderr
    assert '"correct"' not in out.stdout


def test_a_program_variable_is_refused():
    out = _run(["--workload", "hub-10k.tip", "--seed", "1", "--seconds", "1",
                "--trace", "0", "--rehearse"], env_extra={"TM_TPU_SHARD": "0"})
    assert out.returncode != 0
    assert "TM_TPU_SHARD" in out.stderr
    assert '"correct"' not in out.stdout


def test_a_later_prs_cell_traffic_and_metric_are_only_new_files(tmp_path):
    root = str(tmp_path / "copy")
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns(".data", ".trace", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    before = {p: os.path.getmtime(os.path.join(d, p))
              for d, _dirs, files in os.walk(root) for p in files}
    # the later PR: two new files and three new entries
    with open(os.path.join(root, "benchmark", "traffic", "tip-light.json"), "w") as f:
        json.dump({"driver": "tip", "entry_point": "verify_commit_light",
                   "warmup_decisions": 1, "profile_skip": 1,
                   "profile_decisions": 2}, f)
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "decisions_in_window.py"), "w") as f:
        f.write("def read(run):\n    return len(run.decisions)\n")
    bench["workloads"].append({"name": "hub-10k.light", "config": "hub-10k",
                               "traffic": "tip-light", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("commit_p50_ms", "lane_fill", "assemble_ms"):
            m["workloads"].append("hub-10k.light")
    bench["per_layer"].append(
        {"name": "decisions_in_window", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "decision",
         "moves": "commit_p50_ms", "workloads": ["hub-10k.light"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    args = ["--workload", "hub-10k.light", "--seed", "11", "--seconds", "1",
            "--rehearse"]
    line = _last_line(_run(args + ["--trace", "0"], root=root))
    assert line["correct"] is True and "commit_p50_ms" in line["metrics"]
    line = _last_line(_run(args + ["--trace", "1"], root=root))
    assert line["metrics"]["decisions_in_window"]["value"] == line["attempted"]
    assert line["metrics"]["assemble_ms"]["value"] > 0
    after = {p: os.path.getmtime(os.path.join(d, p))
             for d, _dirs, files in os.walk(root) for p in files if p in before}
    assert after == before      # no file that was there was touched


def test_an_unknown_workload_is_refused():
    out = _run(["--workload", "no-such.cell", "--rehearse"])
    assert out.returncode != 0 and "no workload" in out.stderr


def _accept_every_signature(monkeypatch):
    """An answer altered where it is produced: the ed25519 route's bitmap."""
    import numpy as np

    from tendermint_tpu.ops import ed25519_batch

    real = ed25519_batch.dispatch_batch

    def broken(items, force_device=False):
        dev, finish = real(items, force_device=force_device)
        return dev, lambda fetched: np.ones_like(finish(fetched))

    monkeypatch.setattr(ed25519_batch, "dispatch_batch", broken)


def _count_nil_votes_for_the_block(monkeypatch):
    """A guarantee of the configuration broken: the light prefix takes every
    vote that is not Absent, votes for nil included."""
    from tendermint_tpu.types.validator_set import ValidatorSet

    def broken(self, commit, needed):
        prefix, tallied = [], 0
        for idx, cs in enumerate(commit.signatures):
            if cs.absent():
                continue
            prefix.append(idx)
            tallied += self.validators[idx].voting_power
            if tallied > needed:
                break
        return prefix

    monkeypatch.setattr(ValidatorSet, "commit_light_prefix", broken)


@pytest.mark.parametrize("break_it, correct", [
    (None, True),
    (_accept_every_signature, False),
    (_count_nil_votes_for_the_block, False),
], ids=["sound", "accepts_every_signature", "nil_votes_in_the_light_prefix"])
def test_a_broken_timed_path_comes_out_not_correct(break_it, correct,
                                                   monkeypatch, capsys):
    """The whole of a run but the look for a chip, in this process, with the
    program broken underneath: `correct` must say so."""
    from benchmark.harness import spec

    bench_run = spec._module(os.path.join(ROOT, "benchmark", "run.py"), "main")
    if break_it is not None:
        break_it(monkeypatch)
    rc = bench_run.main(["--workload", "hub-10k-live.tip", "--seed", "11",
                         "--seconds", "0.3", "--trace", "0", "--rehearse"])
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    line = json.loads(lines[-1])
    assert line["correct"] is correct, lines[-2]
    if not correct:
        assert json.loads(lines[-2])["failures"]
