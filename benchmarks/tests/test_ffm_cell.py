"""The field-aware cell (``criteo-ffm-train``, PR 33) on the CPU: its
``run.py --rehearse`` at toy size -- a sound run correct, the
lower-precision control and each planted fault not -- its roofline
count against a batch counted by hand, and its configuration against the
example cfg a user would run."""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from fmbench import harness, roofline_ffm

CELL = "criteo-ffm-train"


def run(*extra):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 11), "--seconds", "1",
         "--trace", "0", "--rehearse", *extra],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


def test_rehearsal_is_correct_and_carries_no_device_metric():
    line, err = run()
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["metrics"] == {} and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    assert {"fields_not_in_file", "rows_not_in_file", "loss_gap", "grad_gap",
            "change_gap", "score_gap"} == set(line["checks"])
    for name, c in line["checks"].items():  # each number beside its limit
        assert f"check {name} = " in err and c["value"] <= c["limit"]
    # the run says which row it trained: 1 + 39 * 4 floats
    assert line["info"]["row_floats"] == 157
    # ... and how much the sort merged (the gauge is set at a health
    # readback, one dispatch late: a window of a second may close first)
    frac = line["info"]["apply_unique_frac"]
    assert frac is None or 0 < frac < 1
    assert all(0 < u < 128 * 39 for u in line["info"]["unique_rows_per_step"])


@pytest.mark.parametrize("flag,value,over", [
    ("--control", "bf16", "score_gap"),
    ("--fault", "half_batch", "grad_gap"),
    ("--fault", "fields_zeroed", "score_gap"),
])
def test_control_and_planted_faults_are_not_correct(flag, value, over):
    line, _ = run(flag, value)
    assert line["correct"] is False and line[flag.strip("-")] == value
    c = line["checks"]
    assert c[over]["value"] > c[over]["limit"]
    assert c["rows_not_in_file"]["value"] == 0  # the feed was sound
    assert c["fields_not_in_file"]["value"] == 0


def test_roofline_counts_by_hand():
    # 2 examples x 3 features, 3 fields, k = 2, touching 4 distinct rows:
    # a row is 1 + 3 * 2 = 7 floats = 28 B; 3 pairs an example
    n, f, p, k, u = 2, 3, 3, 2, 4
    assert roofline_ffm.row_bytes(p, k) == 28 and roofline_ffm.pairs(f) == 3
    fwd = n * (2 * f + 3 * (2 * k + 2))
    bwd = n * (3 * (2 * k + 2) + f + 4)
    assert roofline_ffm.ffm_forward_flops(n, f, k) == fwd
    assert roofline_ffm.ffm_backward_flops(n, f, k) == bwd
    step = roofline_ffm.train_step_needed(n, f, p, k, u)
    # gather 4 rows; table and accumulator read and written; ids, values
    # and fields of 6 occurrences; labels and weights
    assert step["bytes"] == u * 28 + u * 28 * 4 + n * f * 12 + n * 8
    assert step["flops"] == fwd + bwd + n * f * 7 * 3 + u * 7 * 5
    # the cell's own shapes: 628 B a row, 741 pairs of 39 features
    assert roofline_ffm.row_bytes(39, 4) == 628
    assert roofline_ffm.pairs(39) == 741
    big = roofline_ffm.train_step_needed(16384, 39, 39, 4, 300000)
    assert big["bytes"] == 300000 * 628 * 5 + 16384 * 39 * 12 + 16384 * 8


def test_cell_cfg_and_the_example_cfg_agree_key_by_key(tmp_path):
    from fast_tffm_tpu.config import load_config

    cell = harness.load_cell(CELL)
    assert cell["cell"]["chips"] == 1 and cell["config"]["reduced"] == []
    assert cell["config_entry"]["source"] == cell["config"]["source"]
    path = str(tmp_path / "cell.cfg")
    harness.write_cfg(path, cell["config"]["cfg"])
    mine = dataclasses.asdict(load_config(path))
    theirs = dataclasses.asdict(load_config(os.path.join(
        harness.ROOT, "examples", "criteo_kaggle_ffm.cfg")))
    paths = {"train_files", "validation_files", "predict_files",
             "model_file", "score_path"}
    differ = {k: (mine[k], theirs[k]) for k in mine
              if k not in paths and mine[k] != theirs[k]}
    assert not differ, differ
    assert mine["field_num"] == 39 and mine["factor_num"] == 4
    assert load_config(path).embedding_dim == 157
