"""The yardstick against hand-built inputs: the trace reduction, the
peaks table, the ops/bytes functions, the hash, and BENCHMARK.json's
entries against the files they name."""

import json
import os
import re

import numpy as np
import pytest

from fmbench import harness, peaks, roofline, traffic, xplane
from reference import fm as ref

MS = 1e6  # ns


def _trace():
    ops = [("%fusion.1 = f32[8] fusion(...)", 10 * MS, 20 * MS),
           ("%fusion.2 = f32[8] fusion(...)", 25 * MS, 15 * MS),  # overlaps
           ("%copy.3 = f32[8] copy(...)", 60 * MS, 10 * MS),
           ("%fusion.1 = f32[8] fusion(...)", 120 * MS, 5 * MS)]  # outside
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [
                ("jit_step(123)", 10 * MS, 30 * MS),
                ("jit_step(123)", 60 * MS, 10 * MS),
                ("jit_unpack(9)", 5 * MS, 1 * MS)]},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ("bench:window", 0.0, 100 * MS),
            ("tffm:h2d", 41 * MS, 18 * MS),       # covers the 40..60 gap
            ("tffm:dispatch", 71 * MS, 500 * MS),  # cut by the window
            ("PjitFunction(step)", 1 * MS, 1 * MS)]}]},
    ]


def test_reduce_hand_built_trace():
    r = xplane.reduce(_trace())
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(0.100)
    # busy: [10,40) and [60,70) = 40 ms; the op at 120 ms is outside
    assert r["busy_s"] == pytest.approx(0.040)
    assert r["programs"]["jit_step"] == {"seconds": pytest.approx(0.040),
                                         "runs": 2}
    assert r["programs"]["jit_unpack"]["runs"] == 1
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.020)
    assert ops["fusion.2"] == pytest.approx(0.015)
    gaps = dict(r["idle_gaps"])
    # gaps: [0,10) nobody, [40,60) under h2d, [70,100) -- the span the
    # window cuts does not count
    assert gaps["tffm:h2d"] == pytest.approx(0.020)
    assert gaps["unattributed"] == pytest.approx(0.040)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_reduce_without_device_plane_reads_nothing():
    planes = [p for p in _trace() if p["name"].startswith("/host")]
    assert xplane.reduce(planes) is None


def test_recorded_cpu_trace_loads(tmp_path):
    import jax
    import jax.numpy as jnp

    win = harness.TraceWindow(str(tmp_path), True)
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    win.start()
    with jax.profiler.TraceAnnotation("tffm:dispatch"):
        f(x).block_until_ready()
    win.stop()
    planes = xplane.load(xplane.find_xplane(win.dir))
    names = {e[0] for p in planes for ln in p["lines"] for e in ln["events"]}
    assert {"bench:window", "tffm:dispatch"} <= names
    assert xplane.reduce(planes) is None  # a CPU run has no device plane


def test_roofline_hand_counts():
    # 2 examples x 3 features, k=2, touching 4 distinct rows of 12 B
    need = roofline.train_step_needed(2, 3, 2, 4)
    assert roofline.row_bytes(2) == 12
    assert need["bytes"] == 4 * 12 + 4 * 12 * 4 + 2 * 3 * 8 + 2 * 8
    fwd = 2 * (3 * (2 + 8) + 6 + 2)
    bwd = 2 * (3 * (1 + 8) + 4)
    assert need["flops"] == fwd + bwd + 4 * 3 * 6
    serve = roofline.serve_needed(2, 3, 2, 4)
    assert serve["bytes"] == 4 * 12 + 2 * 3 * 8 + 2 * 4
    # the cell's own shapes: 180 B a unique row, as PERF.md says
    big = roofline.train_step_needed(65536, 39, 8, 800000)
    assert big["bytes"] == 800000 * 180 + 65536 * 39 * 8 + 65536 * 8


def test_peaks_table():
    p = peaks.peaks_for("TPU v5 lite")
    least = peaks.least_seconds(1e9, 819e9, p)
    assert least == {"seconds": pytest.approx(1.0), "bound": "bytes"}
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def test_hash_matches_the_programs():
    from fast_tffm_tpu.data import libsvm

    xs = np.array([0, 7, 1234567, 12345678, 99999999, 123456789,
                   2147483647, 33554431, 10**14 + 3])
    got = ref.murmur64a_decimal(xs)
    assert [int(g) for g in got] == [
        libsvm.murmur64(str(int(x)).encode()) for x in xs]
    assert ref.hash_bucket_decimal(xs, 1 << 25).tolist() == [
        libsvm.hash_bucket(str(int(x)), 1 << 25) for x in xs]


def test_traffic_same_work_for_every_seed():
    mix = harness.load_json(os.path.join(harness.BENCH_DIR, "traffic",
                                         "steady.json"))
    a = traffic.make_plan(mix, seconds=2, seed=1, vocab=1 << 20, features=39)
    b = traffic.make_plan(mix, seconds=2, seed=2**31 + 7, vocab=1 << 20,
                          features=39)
    assert sorted(a["n"]) == sorted(b["n"]) and a["n"].tolist() != b[
        "n"].tolist()
    assert a["text"].sum() == b["text"].sum()
    assert np.allclose(np.sort(a["gaps"]), np.sort(b["gaps"]))
    assert not np.allclose(a["gaps"], b["gaps"])
    assert not np.array_equal(a["raw_ids"], b["raw_ids"])
    again = traffic.make_plan(mix, seconds=2, seed=1, vocab=1 << 20,
                              features=39)
    assert np.array_equal(a["raw_ids"], again["raw_ids"])
    path, body = traffic.encode_bodies(a)[int(np.flatnonzero(~a["text"])[0])]
    assert path == "/score_bin" and body[:4] == b"TFB1"


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_names_files_that_exist():
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and all(m["bound"] <= 0.1 for m in e2e.values())
    layers = {m["name"] for m in bench["per_layer"]}
    for name in list(e2e) + list(layers):
        assert NAME.match(name)
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        harness.load_by_path("metrics", m["name"]).read  # one reader each
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])
    used = set()
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        used.add(w["config"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        harness.load_by_path("drivers", cell["traffic"]["driver"]).run
        harness.load_by_path("reference", cell["config"]["reference"])
        assert cell["config_entry"]["reduced"] == cell["config"]["reduced"]
        # every cell reports set-up, another end-to-end metric, a layer
        assert len(harness.metrics_for(bench, "end_to_end", w["name"])) >= 2
        assert harness.metrics_for(bench, "per_layer", w["name"],
                                   set(e2e))
    assert used == {c["name"] for c in bench["configs"]}
    assert len(json.dumps(bench)) < 64 * 1024
