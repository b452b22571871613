"""What a later PR adds as files and never as code: a traffic mix of
another shape (bursts, an open loop judged on throughput), a cell's
limits, a configuration's reference.  Each is tried here at toy size on
the CPU, through the same drivers the cells use."""

import copy
import glob
import json
import os
import time

import numpy as np
import pytest

from fmbench import compare, harness, traffic

BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]
BURSTS = {"period_s": 0.5, "on_s": 0.1, "on_factor": 4.0, "off_factor": 0.25}


def _open_mix(**over) -> dict:
    mix = copy.deepcopy(harness.load_cell("criteo1tb-serve-steady")["traffic"])
    mix.update(mix.pop("rehearse"))
    mix.update(over)
    return mix


def test_burst_mix_is_a_data_file():
    mix = _open_mix(rate_per_s=200, bursts=BURSTS)
    plans = [traffic.make_plan(mix, seconds=5, seed=s, vocab=4096, features=5)
             for s in (3, 2**31 + 7)]
    due = plans[0]["due"]
    # 0.1 s * 4 + 0.4 s * 0.25 = 0.5 s of the base rate a period: its mean
    assert len(due) == 1000 and 0 <= due.min() and due.max() < 5
    assert np.all(np.diff(due) >= 0)
    in_burst = (due % BURSTS["period_s"]) < BURSTS["on_s"]
    assert 0.7 < in_burst.mean() < 0.9  # four fifths of the arrivals
    # every seed gets the same sizes, kinds and gaps, in another order
    assert sorted(plans[0]["n"]) == sorted(plans[1]["n"])
    assert np.allclose(np.sort(plans[0]["gaps"]), np.sort(plans[1]["gaps"]))
    assert not np.array_equal(plans[0]["n"], plans[1]["n"])
    steady = traffic.make_plan(_open_mix(rate_per_s=200), seconds=5, seed=3,
                               vocab=4096, features=5)
    assert len(steady["due"]) == 1000


@pytest.mark.parametrize("cell_name,over", [
    ("criteo1tb-serve-steady", {"bursts": BURSTS}),   # Open-question row 3
    ("criteo1tb-serve-steady", {"rate_per_s": 400}),  # row 4: judged on ex/s
    ("criteo1tb-serve-bulk", {}),
])
def test_serve_driver_measures_every_metric_whatever_the_loop(
        tmp_path, cell_name, over):
    serve = harness.load_by_path("drivers", "serve")
    cell = copy.deepcopy(harness.load_cell(cell_name))
    cell["traffic"]["rehearse"].update(over)
    out = serve.run(cell=cell, seed=2**31 + 3, seconds=1.0, trace=False,
                    rehearse=True, control="", fault="", rate=0.0,
                    via_checkpoint=False, work=str(tmp_path), t0=time.time())
    assert out["checks"].correct, out["checks"].as_dict()
    e2e = out["e2e"]
    assert {"setup_s", "serve_ex_per_s", "serve_p50_ms",
            "serve_p99_ms"} <= set(e2e)
    assert e2e["serve_ex_per_s"] > 0 and 0 < e2e["serve_p50_ms"] < np.inf


def test_a_metric_the_driver_lacks_is_named_not_a_keyerror(capsys):
    cell = copy.deepcopy(harness.load_cell(CELLS[0]))
    result = {"checks": compare.Checks(), "attempted": 1, "failed": 0,
              "e2e": {"setup_s": 1.0}, "memory_peak_bytes": 0}
    with pytest.raises(SystemExit, match="does not measure"):
        harness.emit(cell=cell, device={"platform": "cpu", "kind": "cpu",
                                        "count": 1},
                     trace=False, rehearse=False, result=result, labels={})
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("workload", CELLS)
def test_every_cell_has_limits_between_its_readings(workload):
    limits = harness.load_cell(workload)["limits"]
    numbers = {k: v for k, v in limits.items() if isinstance(v, dict)}
    assert numbers
    for name, r in numbers.items():
        assert r["sound_max"] < r["limit"] < r["must_fail_min"], name
        assert r["must_fail_min"] >= 3 * r["sound_max"], name
        # room on both sides
        assert r["limit"] >= 2 * r["sound_max"], name
        assert r["must_fail_min"] >= 2 * r["limit"], name
    names = {os.path.basename(p)[:-5] for p in glob.glob(
        os.path.join(harness.BENCH_DIR, "limits", "*.json"))}
    assert set(CELLS) <= names


def test_a_number_without_a_limit_is_an_error_not_a_pass():
    checks = compare.Checks()
    with pytest.raises(KeyError):
        checks.add_limited("new_gap", 0.0, {"loss_gap": {"limit": 1.0}})
    checks.add_limited("loss_gap", 2.0, {"loss_gap": {"limit": 1.0}})
    checks.add_limited("loose", 9.0, {"loose": {"limit": None}})
    assert [r[0] for r in checks.rows] == ["loss_gap"] and not checks.correct


def test_reference_states_the_optimizer_it_follows():
    from reference import fm as ref

    keys = dict(harness.load_cell("criteo1tb-train-shard")["config"]["cfg"])
    leaves = ref.program_leaves(keys)
    assert leaves["params"] == ["params.w0", "params.table"]
    with pytest.raises(NotImplementedError):
        ref.make_step({**keys, "optimizer": "ftrl"})
    # Adagrad's move, undone: g = -dw * sqrt(acc_new + eps) / lr
    pre = {"params.w0": np.float64(0.0), "params.table": np.zeros((2, 3))}
    g = np.array([[3.0, 0.0, 4.0], [0.0, 0.0, 0.0]])
    acc = 0.1 + g * g
    post = {"params.table": -0.05 * g / np.sqrt(acc + ref.ADAGRAD_EPS),
            "params.w0": np.float64(-0.05 * 2.0 / np.sqrt(4.1 + 1e-7)),
            "opt_state.acc.table": acc, "opt_state.acc.w0": np.float64(4.1)}
    got = ref.first_gradient(keys, pre, post)
    assert got["params.table"] == pytest.approx(5.0)
    assert got["params.w0"] == pytest.approx(2.0)


def test_train_driver_reads_leaves_by_path_and_caches_the_text(tmp_path):
    from typing import NamedTuple

    train = harness.load_by_path("drivers", "train")

    class P(NamedTuple):
        w0: float
        table: float

    class Opt(NamedTuple):
        z: P
        n: P

    class State(NamedTuple):
        params: P
        opt_state: Opt

    leaves = train.named_leaves(State(P(1, 2), Opt(P(3, 4), P(5, 6))))
    assert leaves["params.table"] == 2 and leaves["opt_state.n.w0"] == 5
    config = harness.load_cell("criteo1tb-train-shard")["config"]
    paths = []
    for k in range(2):
        work = tmp_path / str(k)
        work.mkdir()
        _, keys, _ = train.make_inputs(str(work), config, 2**31 + 77, True, "")
        paths.append((keys["train_files"],
                      os.stat(keys["train_files"]).st_mtime_ns))
    assert paths[0] == paths[1]  # written once, found again
    assert json.dumps(paths[0][0]).count("seed2147483725") == 1
