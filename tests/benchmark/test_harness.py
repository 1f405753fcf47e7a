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


@pytest.mark.parametrize("workload, metric", [
    ("hub-10k.tip", "commit_p50_ms"),
    ("fastsync-1k-mixed.replay", "decisions_per_s"),
])
def test_rehearsal_prints_the_contracts_last_line(workload, metric):
    line = _last_line(_run(["--workload", workload, "--seed", "11",
                            "--seconds", "1", "--trace", "0", "--rehearse"]))
    assert set(line) == CONTRACT_KEYS
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
    for m in bench["end_to_end"]:
        if m["name"] == "commit_p50_ms":
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
    after = {p: os.path.getmtime(os.path.join(d, p))
             for d, _dirs, files in os.walk(root) for p in files if p in before}
    assert after == before      # no file that was there was touched


def test_an_unknown_workload_is_refused():
    out = _run(["--workload", "no-such.cell", "--rehearse"])
    assert out.returncode != 0 and "no workload" in out.stderr
