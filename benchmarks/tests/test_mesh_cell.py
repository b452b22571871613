"""The four-chip cell (``criteo1tb-train-2x2``, PR 35) on the CPU: its
``run.py --rehearse`` in a process that is told no device count (the
driver gets its four CPU devices itself and keeps the 2x2 mesh), the
lower-precision control and the planted faults, a chip's roofline count
against a batch counted by hand, the collectives reducer against a
hand-made trace, and the configuration against the example cfg a user
would run."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from fmbench import harness, roofline, roofline_mesh, xplane_collectives

CELL = "criteo1tb-train-2x2"
MS = 1e6  # ns


def run(*extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 11), "--seconds", "1",
         "--trace", "0", "--rehearse", *extra],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


def test_rehearsal_keeps_the_2x2_mesh_without_being_told_a_device_count():
    line, err = run()
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["metrics"] == {} and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 4}
    # the driver said that it started over, once
    assert err.count("starting the same command again") == 1
    assert "xla_force_host_platform_device_count=4" in err
    info = line["info"]
    assert info["mesh"] == {"data": 2, "model": 2}
    assert info["batch_size"] == 128
    # the compiled step exchanges entries (interpreted kernels keep K2;
    # the fill is read back one dispatch late: a short window may miss it)
    g = info["gauges"]
    assert g["exchange_mode"] == 1 and g["apply_stream"] == 0
    assert g["exchange_fill"] is None or 0 < g["exchange_fill"] < 1
    c = info["chip_counts"]
    assert 0 < c["gather_unique"] <= c["apply_unique"] < 128 * 39
    assert {"rows_not_in_file", "loss_gap", "grad_gap", "change_gap",
            "score_gap"} == set(line["checks"])
    for name, chk in line["checks"].items():
        assert f"check {name} = " in err and chk["value"] <= chk["limit"]


@pytest.mark.parametrize("flag,value,over", [
    ("--control", "bf16", "score_gap"),
    ("--fault", "half_batch", "grad_gap"),
    ("--fault", "state_unchanged", "change_gap"),
])
def test_control_and_planted_faults_are_not_correct(flag, value, over):
    line, _ = run(flag, value)
    assert line["correct"] is False and line[flag.strip("-")] == value
    c = line["checks"]
    assert c[over]["value"] > c[over]["limit"]
    assert c["rows_not_in_file"]["value"] == 0  # the feed was sound
    if value == "state_unchanged":
        # the rows were put back to the bit: nothing moved at all
        assert c["change_gap"]["value"] == 1 and c["grad_gap"]["value"] == 1


def test_a_chips_roofline_count_by_hand():
    # 4 examples x 2 features over 8 rows, 2 data x 2 model shards:
    # data shard 0 = examples 0-1, model shard 0 = rows 0-3
    ids = np.array([[0, 5], [0, 1], [1, 6], [7, 7]])
    c = roofline_mesh.shard_counts(ids, 8, 2, 2)
    # chip (d0,m0) gathers {0,1}, (d1,m0) {1}, (d0,m1) {5}, (d1,m1) {6,7}
    assert c["gather_unique"] == (2 + 1 + 1 + 2) / 4
    # shard 0 holds {0,1}, shard 1 {5,6,7}: each on two chips
    assert c["apply_unique"] == (2 + 2 + 3 + 3) / 4
    # what the other data shard brings: {1} to (d0,m0), {0,1} to (d1,m0),
    # {6,7} to (d0,m1), {5} to (d1,m1)
    assert c["foreign_entries"] == (1 + 2 + 2 + 1) / 4
    k, f, n = 2, 2, 4
    rb = roofline.row_bytes(k)
    need = roofline_mesh.train_step_needed(n, f, k, c, 2, 2)
    assert need["bytes"] == (1.5 * rb + 2.5 * rb * 4
                             + 2 * f * 8 + 2 * 2 * 4)
    whole = (roofline.fm_forward_flops(n, f, k)
             + roofline.fm_backward_flops(n, f, k))
    assert need["flops"] == whole / 4 + 2.5 * (1 + k) * 6
    # an entry of the exchange: a row id and the row's two sums
    assert roofline_mesh.exchange_needed_bytes(c, k) == 1.5 * (4 + 2 * rb)
    # one chip on a 1x1 mesh is the one-device count
    one = roofline_mesh.shard_counts(ids, 8, 1, 1)
    assert one == {"gather_unique": 5.0, "apply_unique": 5.0,
                   "foreign_entries": 0.0}
    assert roofline_mesh.train_step_needed(n, f, k, one, 1, 1) == \
        roofline.train_step_needed(n, f, k, 5)
    # 1,600 Gbit/s a chip (Google Cloud, "TPU v5e")
    assert roofline_mesh.least_exchange_seconds(
        200e9, "TPU v5 lite") == pytest.approx(1.0)
    with pytest.raises(KeyError):
        roofline_mesh.least_exchange_seconds(1.0, "TPU v9")


def _planes():
    def chip(n, shift):
        ops = [
            ("%fusion.1 = f32[8] fusion(...)", 0 * MS, 10 * MS),
            # a synchronous collective
            ("%all-reduce.3 = f32[8] all-reduce(...)", (10 + shift) * MS,
             2 * MS),
            # an asynchronous pair with compute under it: one interval,
            # start to done
            ("%all-gather-start.1 = (s32[4], s32[8]) all-gather-start(...)",
             20 * MS, 1 * MS),
            ("%fusion.2 = f32[8] fusion(...)", 21 * MS, 5 * MS),
            ("%all-gather-done.1 = s32[8] all-gather-done(...)", 26 * MS,
             3 * MS),
            # another kind inside the pair's flight: the union counts the
            # overlap once
            ("%collective-permute.2 = f32[8] collective-permute(...)",
             27 * MS, 4 * MS),
            # outside the window
            ("%all-reduce.3 = f32[8] all-reduce(...)", 150 * MS, 2 * MS),
            # the rule is how a name STARTS
            ("%convert_all-gather_fusion = f32[8] fusion(...)", 40 * MS,
             1 * MS),
        ]
        return {"name": f"/device:TPU:{n}", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [
                ("jit_scan_health_step(1)", 0.0, 50 * MS)]}]}

    host = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        ("bench:window", 0.0, 100 * MS)]}]}
    return [chip(0, 0), chip(1, 1), host]


def test_collectives_reducer_on_a_hand_made_trace():
    assert xplane_collectives.kind_of(
        "%all-gather-start.1 = (s32[4]) all-gather-start(...)") == (
        "all-gather", "start")
    assert xplane_collectives.kind_of("%all-to-all.7 = ...") == (
        "all-to-all", "")
    assert xplane_collectives.kind_of("%fusion.9 = ...") is None
    r = xplane_collectives.reduce(_planes())
    assert r["chips"] == 2
    # a chip: all-reduce 2 ms + [20, 29) the pair + [27, 31) the permute
    # = 2 + 11 ms
    assert r["seconds"] == pytest.approx(0.013)
    assert r["by_kind"]["all-reduce"] == pytest.approx(0.002)
    assert r["by_kind"]["all-gather"] == pytest.approx(0.009)
    assert r["by_kind"]["collective-permute"] == pytest.approx(0.004)
    assert r["count"] == 3
    no_device = [p for p in _planes() if p["name"].startswith("/host")]
    assert xplane_collectives.reduce(no_device) is None
    # a done without its start, a start without its done: each alone
    alone = xplane_collectives.intervals([
        ("%all-gather-done.5 = ...", 5.0, 2.0),
        ("%all-reduce-start.6 = ...", 9.0, 1.0)])
    assert sorted(alone) == [("all-gather", 5.0, 7.0),
                             ("all-reduce", 9.0, 10.0)]


def test_readers_of_the_two_exchange_metrics():
    ms = harness.load_by_path("metrics", "train_exchange_ms")
    pct = harness.load_by_path("metrics", "train_exchange_ici_pct")
    # the program of another cell (or of the parent) has no such counter
    assert ms.read({"counters": {}}) is None
    assert pct.read({"counters": {}}) is None
    run_ = {"counters": {"collective_s_per_step": 0.010,
                         "exchange_needed_bytes": 200e6,
                         "device_kind": "TPU v5 lite"}}
    assert ms.read(run_) == pytest.approx(10.0)
    # 200 MB at 200 GB/s = 1 ms of the 10 ms spent
    assert pct.read(run_) == pytest.approx(10.0)


def test_cell_cfg_is_the_example_cfg_but_for_the_cut(tmp_path):
    from fast_tffm_tpu.config import load_config

    cell = harness.load_cell(CELL)
    config = cell["config"]
    assert cell["cell"]["chips"] == 4
    assert config["reduced"] == ["batch_size", "mesh_data", "mesh_model"]
    assert cell["config_entry"]["source"] == config["source"]
    assert config["reference"] == "fm"
    assert cell["traffic"]["driver"] == "train_mesh"
    # the traffic is libsvm-epochs', word for word, but for the driver
    plain = harness.load_json(os.path.join(
        harness.BENCH_DIR, "traffic", "libsvm-epochs.json"))
    assert {**cell["traffic"], "driver": "train"} == plain
    path = str(tmp_path / "cell.cfg")
    harness.write_cfg(path, config["cfg"])
    mine = dataclasses.asdict(load_config(path))
    theirs = dataclasses.asdict(load_config(os.path.join(
        harness.ROOT, "examples", "criteo_1tb_dist.cfg")))
    paths = {"train_files", "validation_files", "predict_files",
             "model_file", "score_path"}
    differ = {k: (mine[k], theirs[k]) for k in mine
              if k not in paths and mine[k] != theirs[k]}
    assert differ == {"batch_size": (131072, 262144),
                      "mesh_data": (2, 4), "mesh_model": (2, 4)}
    assert config["published"] == {k: v[1] for k, v in differ.items()}
    # the table is whole: two shards of 2^25 rows, every width the cfg's
    assert mine["vocabulary_size"] == 1 << 26 and mine["factor_num"] == 8
    assert mine["lookup"] == "shardmap" and mine["sparse_exchange"] == "auto"
    # the rehearsal cuts sizes, never the mesh
    assert not {"mesh_data", "mesh_model"} & set(config["rehearse"])
