"""``run.py --rehearse`` for every cell of BENCHMARK.json (the toy-size
CPU path the harness is debugged on), the refusal to measure without a
chip, and -- with the timed path broken underneath, or the program's own
lower-precision path switched on -- ``correct`` coming out false."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from fmbench import harness

BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]
KIND = {w["name"]: harness.load_cell(w["name"])["traffic"]["driver"]
        for w in BENCH["workloads"]}
# the faults each kind of cell can have (no cell spans chips yet, so no
# exchange to leave out)
FAULTS = {"train": ["state_unchanged", "half_batch"],
          "serve": ["answer_altered"]}


def run(workload, *extra, root=harness.ROOT, rehearse=True):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, os.path.join(root, "benchmarks", "run.py"),
           "--workload", workload, "--seed", str(2**31 + 11),
           "--seconds", "1", "--trace", "0", *extra]
    if rehearse:
        cmd.append("--rehearse")
    return subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                          text=True, timeout=600)


def last_line(proc) -> dict:
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_is_correct_and_carries_no_device_metric(workload):
    proc = run(workload)
    line = last_line(proc)
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["metrics"] == {} and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    assert "memory_peak_bytes" not in line["device"]
    assert list(line)[-1] == "checks" and line["checks"]
    for name, c in line["checks"].items():  # each number beside its limit
        assert f"check {name} = " in proc.stderr
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("workload", CELLS)
def test_lower_precision_control_is_not_correct(workload):
    line = last_line(run(workload, "--control", "bf16"))
    assert line["correct"] is False and line["control"] == "bf16"


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w in CELLS for f in FAULTS[KIND[w]]])
def test_planted_fault_is_not_correct(workload, fault):
    line = last_line(run(workload, "--fault", fault))
    assert line["correct"] is False and line["fault"] == fault


@pytest.mark.parametrize("workload", [w for w in CELLS if KIND[w] == "serve"])
def test_model_restored_by_the_programs_own_load_model(workload):
    line = last_line(run(workload, "--via-checkpoint"))
    assert line["correct"] is True and line["via_checkpoint"] is True


def test_without_a_chip_nothing_is_measured():
    proc = run(CELLS[0], rehearse=False)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_bare_directory_prints_no_result(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH_DIR, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    proc = run(CELLS[0], root=str(tmp_path), rehearse=False)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
