"""The FTRL cell (``criteo1tb-ftrl-train-shard``) on the CPU: its
``run.py --rehearse`` at toy size -- a sound run correct, the
lower-precision control and each planted fault not, ``l1_dropped`` by
the numbers FTRL's L1 moves -- the driver's two new numbers on arrays
made by hand, its roofline counts against a batch counted by hand, the
writer's roofline reader, and its configuration against both the
Adagrad shard it pairs with and the example cfg a user would run."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from fmbench import harness, roofline, roofline_ftrl

CELL = "criteo1tb-ftrl-train-shard"


@pytest.fixture(scope="module")
def driver():
    return harness.load_by_path("drivers", "train_ftrl")


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
    assert {"rows_not_in_file", "loss_gap", "grad_gap", "change_gap",
            "score_gap", "untouched_changed", "l1_zero_gap"} == set(
                line["checks"])
    for name, c in line["checks"].items():  # each number beside its limit
        assert f"check {name} = " in err and c["value"] <= c["limit"]
    detail = line["info"]["detail"]
    # every leaf of FTRL's state is compared, both accumulators too
    assert set(detail["prog"]["change"]) == {
        "params.w0", "params.table", "opt_state.z.w0", "opt_state.z.table",
        "opt_state.n.w0", "opt_state.n.table"}
    assert detail["init_gap"] < 1e-6
    assert 0 < detail["l1_zeros"]["coordinates"]
    # the program's sparsity gauge, read at a log step
    frac = line["info"]["ftrl_zero_frac"]
    assert frac is not None and 0 <= frac < 0.5


@pytest.mark.parametrize("flag,value,over", [
    ("--control", "bf16", "grad_gap"),
    ("--fault", "half_batch", "grad_gap"),
    ("--fault", "state_unchanged", "change_gap"),
    ("--fault", "l1_dropped", "l1_zero_gap"),
])
def test_control_and_planted_faults_are_not_correct(flag, value, over):
    line, _ = run(flag, value)
    assert line["correct"] is False and line[flag.strip("-")] == value
    c = line["checks"]
    assert c[over]["value"] > c[over]["limit"]
    assert c["rows_not_in_file"]["value"] == 0  # the feed was sound
    assert c["untouched_changed"]["value"] == 0  # no stray row written
    if value == "l1_dropped":
        # a dropped L1 moves no gradient: the first one still agrees
        assert c["grad_gap"]["value"] <= c["grad_gap"]["limit"]
        zeros = line["info"]["detail"]["l1_zeros"]
        assert zeros["prog"] == 0 < zeros["ref"]


def test_untouched_changed_counts_rows_outside_the_batches(driver):
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    tables = [rng.normal(size=(64, 9)).astype(np.float32) for _ in range(3)]
    before = driver.fingerprint([jnp.asarray(t) for t in tables])
    assert before.shape == (64,) and before.dtype == np.uint32
    after_tables = [t.copy() for t in tables]
    after_tables[0][5, 3] += 1e-3  # touched: a step's id
    after_tables[2][7, 8] = np.nextafter(np.float32(after_tables[2][7, 8]),
                                         np.float32(1.0))  # one ulp
    after_tables[1][40, 0] = -after_tables[1][40, 0]  # a sign
    after = driver.fingerprint([jnp.asarray(t) for t in after_tables])
    ids = [np.array([[5, 6], [6, 9]]), np.array([[1, 2]])]
    assert driver.untouched_changed(before, after, ids) == 2  # 7 and 40
    assert driver.untouched_changed(before, before, ids) == 0
    # the same rows' bits give the same prints; the tables' order counts
    assert (driver.fingerprint([jnp.asarray(t) for t in tables])
            == before).all()
    swapped = driver.fingerprint([jnp.asarray(t) for t in tables[::-1]])
    assert (swapped != before).any()


def test_l1_zero_gap_by_hand(driver):
    prog = np.array([[0.0, 1.0, 0.0], [2.0, 0.0, 3.0]], np.float32)
    ref = np.array([[0.0, 0.0, 0.0], [-2.0, 0.0, 0.0]], np.float32)
    # (0, 1) is 0 in the reference alone, (1, 2) likewise; (1, 0) differs
    # in sign and value, not in being 0
    assert driver.l1_zero_gap(prog, ref) == pytest.approx(2 / 6)
    assert driver.l1_zero_gap(prog, prog) == 0.0


def test_roofline_counts_by_hand():
    # 2 examples x 3 features, k = 2, touching 4 distinct rows: a row is
    # 1 + 2 = 3 floats = 12 B
    n, f, k, u = 2, 3, 2, 4
    apply_ = roofline_ftrl.apply_needed(k, u)
    # w, z and n of each unique row read and written once; FTRL's 18
    # operations on each of its 3 coordinates
    assert apply_ == {"bytes": u * 12 * 6, "flops": u * 3 * 18}
    step = roofline_ftrl.train_step_needed(n, f, k, u)
    assert step["bytes"] == u * 12 + u * 12 * 6 + n * f * 8 + n * 8
    assert step["flops"] == (roofline.fm_forward_flops(n, f, k)
                             + roofline.fm_backward_flops(n, f, k)
                             + n * f * 3 * 3 + u * 3 * 18)
    # against Adagrad's count at the cell's shapes: 2 row-bytes more a
    # unique row (the third table read and written)
    ada = roofline.train_step_needed(65536, 39, 8, 700000)
    ftrl = roofline_ftrl.train_step_needed(65536, 39, 8, 700000)
    assert ftrl["bytes"] - ada["bytes"] == 700000 * 36 * 2


def test_writer_roofline_reader(monkeypatch):
    reader = harness.load_by_path("metrics", "train_apply_write_roofline_pct")
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run_ = {"counters": {"apply_needed_bytes": 819e6,
                         "apply_needed_flops": 1e9}, "peaks": peaks}
    monkeypatch.setattr(reader._scopes, "ms", lambda run, scopes: 4.0)
    # 819 MB at 819 GB/s is 1 ms of a 4 ms write
    assert reader.read(run_) == pytest.approx(25.0)
    monkeypatch.setattr(reader._scopes, "ms", lambda run, scopes: None)
    assert reader.read(run_) is None  # no scope table, no trace
    assert reader.read({"counters": {}, "peaks": peaks}) is None


def _cfg_fields(path):
    from fast_tffm_tpu.config import load_config

    return dataclasses.asdict(load_config(path))


def test_cell_cfg_against_the_adagrad_shard_and_the_example(tmp_path):
    cell = harness.load_cell(CELL)
    pair = harness.load_cell("criteo1tb-train-shard")
    conf, other = cell["config"], pair["config"]
    assert cell["cell"]["chips"] == 1 and conf["reference"] == "ftrl"
    assert cell["config_entry"]["source"] == conf["source"]
    assert conf["reduced"] == other["reduced"]
    assert conf["published"] == other["published"]
    assert cell["traffic"]["warm"] == pair["traffic"]["warm"]
    assert conf["harness"] == other["harness"]
    assert conf["rehearse"] == other["rehearse"]
    # the pair differs by the optimizer alone, key for key
    ftrl_keys = {"ftrl.l1", "ftrl.l2", "ftrl.beta"}
    mine, theirs = conf["cfg"], other["cfg"]
    assert set(mine) == set(theirs) | ftrl_keys
    assert {k for k in theirs if mine[k] != theirs[k]} == {"optimizer"}
    assert mine["optimizer"] == "ftrl" and theirs["optimizer"] == "adagrad"
    assert (mine["ftrl.l1"], mine["ftrl.l2"], mine["ftrl.beta"]) == (
        1e-3, 1e-3, 1.0)
    # ... and it is the example cfg a user runs, file paths aside
    path = str(tmp_path / "cell.cfg")
    harness.write_cfg(path, mine)
    got = _cfg_fields(path)
    want = _cfg_fields(os.path.join(harness.ROOT, "examples",
                                    "criteo_1tb_ftrl.cfg"))
    paths = {"train_files", "validation_files", "predict_files",
             "model_file", "score_path"}
    differ = {k: (got[k], want[k]) for k in got
              if k not in paths and got[k] != want[k]}
    assert not differ, differ
    assert got["optimizer"] == "ftrl" and got["ftrl_l1"] == 1e-3
