"""The span readers against a hand-built trace: ``metrics/_spans.py``
and the six readers over it, a phase the window cuts, a run with no
trace, and a recorded CPU session whose stats must come back."""

import os

import pytest

from fmbench import harness, xplane

import _spans  # noqa: E402 - benchmarks/metrics, on the path with harness
MS = 1e6  # ns

READERS = ("serve_queue_wait_ms", "serve_coalesce_ms",
           "serve_dispatcher_busy_pct.serve_lat",
           "serve_dispatcher_busy_pct.serve_tput",
           "serve_launch_ms", "serve_readback_ms")


def _ev(phase, start_ms, dur_ms, **stats):
    return ("tffm:serve." + phase, start_ms * MS, dur_ms * MS, stats)


def _planes():
    dispatcher = [
        # dispatch 1: two requests, 9 examples
        _ev("coalesce", 10, 2.0, reqs=2, n=9),
        _ev("fill", 12, 0.5, reqs=2, n=9, rung=64, qwait_us=600),
        _ev("launch", 12.5, 1.0, rung=64),
        _ev("readback", 13.5, 0.5, rung=64),
        _ev("deliver", 14, 0.25, reqs=2),
        _ev("quality", 14.25, 0.75, n=9),
        # dispatch 2: one request, 1024 examples
        _ev("coalesce", 40, 1.0, reqs=1, n=1024),
        _ev("fill", 41, 1.5, reqs=1, n=1024, rung=1024, qwait_us=300),
        _ev("launch", 42.5, 2.0, rung=1024),
        _ev("readback", 44.5, 1.5, rung=1024),
        _ev("deliver", 46, 0.25, reqs=1),
        _ev("quality", 46.25, 4.0, n=1024),
        # dispatch 3 straddles the window's end: its coalesce and fill
        # lie inside, its launch is cut and does not count
        _ev("coalesce", 95, 2.0, reqs=1, n=8),
        _ev("fill", 97, 0.5, reqs=1, n=8, rung=64, qwait_us=100),
        _ev("launch", 99, 3.0, rung=64),
    ]
    worker = [
        _ev("read_body", 8, 0.25, bytes=4096),
        _ev("parse", 8.25, 1.0, n=5, text=1),
        _ev("respond", 14.5, 0.5, n=5),
        _ev("parse", -2, 1.0, n=3, text=0),  # before the window opened
        ("PjitFunction(score_fn)", 12 * MS, 1 * MS, {}),
    ]
    return [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ("%fusion.1", 12.6 * MS, 0.1 * MS, {})]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [("bench:window", 0.0, 100 * MS, {})]},
            {"name": "python", "events": dispatcher},
            {"name": "python", "events": worker},
        ]},
    ]


def test_reduce_hand_built_spans():
    r = _spans.reduce(_planes())
    assert r["window_s"] == pytest.approx(0.100)
    ph = r["phases"]
    assert ph["coalesce"]["count"] == 3
    assert ph["coalesce"]["seconds"] == pytest.approx(0.005)
    assert ph["fill"]["stats"] == {"reqs": 4, "n": 1041, "rung": 1152,
                                   "qwait_us": 1000}
    # the launch the window cuts is not there, nor the parse before it
    assert ph["launch"]["count"] == 2
    assert ph["launch"]["seconds"] == pytest.approx(0.003)
    assert ph["parse"]["count"] == 1 and ph["parse"]["stats"]["text"] == 1
    assert ph["read_body"]["stats"] == {"bytes": 4096}
    assert ph["quality"]["longest_s"] == pytest.approx(0.004)
    # the dispatcher's line is the one that holds coalesce: the HTTP
    # worker's phases are not on it
    assert set(r["dispatcher"]) == {"coalesce", "fill", "launch", "readback",
                                    "deliver", "quality"}
    assert r["dispatcher"]["fill"] == pytest.approx(0.0025)


def test_readers_over_hand_built_spans(monkeypatch):
    reduced = _spans.reduce(_planes())
    monkeypatch.setattr(_spans, "for_run", lambda run: reduced)
    run = {"trace": {"window_s": 0.1}, "workload": "w", "counters": {}}
    got = {name: harness.load_by_path("metrics", name).read(run)
           for name in READERS}
    assert got["serve_queue_wait_ms"] == pytest.approx(1.0 / 4)
    assert got["serve_coalesce_ms"] == pytest.approx(5.0 / 3)
    assert got["serve_launch_ms"] == pytest.approx(1.5)
    assert got["serve_readback_ms"] == pytest.approx(1.0)
    # fill 2.5 + launch 3 + readback 2 + deliver 0.5 + quality 4.75 ms
    # of a 100 ms window; coalesce (5 ms) is the batcher's wait
    busy = 12.75
    assert got["serve_dispatcher_busy_pct.serve_lat"] == pytest.approx(busy)
    assert got["serve_dispatcher_busy_pct.serve_tput"] == pytest.approx(busy)


def test_a_program_without_serve_spans_reads_nothing(monkeypatch):
    planes = _planes()
    planes[1]["lines"] = planes[1]["lines"][:1] + [
        {"name": "python", "events": [("tffm:h2d", 5 * MS, 1 * MS, {})]}]
    assert _spans.reduce(planes) is None  # the parent: window, no phases
    assert _spans.reduce([planes[0]]) is None  # no window either
    monkeypatch.setattr(_spans, "for_run", lambda run: None)
    run = {"trace": {"window_s": 0.1}, "workload": "w", "counters": {}}
    for name in READERS:
        assert harness.load_by_path("metrics", name).read(run) is None


def test_a_run_with_no_trace_reads_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "WORK_ROOT", str(tmp_path))
    untraced = {"trace": None, "workload": "w", "counters": {}}
    traced_but_gone = {"trace": {"window_s": 5.0}, "workload": "w",
                       "counters": {}}
    for run in (untraced, traced_but_gone):
        assert _spans.for_run(run) is None
        for name in READERS:
            assert harness.load_by_path("metrics", name).read(run) is None


def test_recorded_cpu_session_gives_the_stats_back(tmp_path, monkeypatch):
    import time

    import jax

    monkeypatch.setattr(harness, "WORK_ROOT", str(tmp_path))
    work = tmp_path / "cell"
    work.mkdir()
    win = harness.TraceWindow(str(work), True)
    win.start()
    with jax.profiler.TraceAnnotation("tffm:serve.coalesce") as span:
        time.sleep(0.002)
        span.set_metadata(reqs=3, n=17)
    with jax.profiler.TraceAnnotation("tffm:serve.fill", reqs=3,
                                      qwait_us=420):
        time.sleep(0.001)
    with jax.profiler.TraceAnnotation("tffm:serve.launch", rung=64):
        time.sleep(0.001)
    win.stop()
    assert os.path.isfile(xplane.find_xplane(win.dir))
    run = {"trace": {"window_s": 1.0}, "workload": "cell", "counters": {}}
    spans = _spans.for_run(run)
    assert spans["phases"]["fill"]["stats"] == {"reqs": 3, "qwait_us": 420}
    assert spans["phases"]["coalesce"]["stats"] == {"reqs": 3, "n": 17}
    assert spans["phases"]["launch"]["stats"] == {"rung": 64}
    assert spans["phases"]["coalesce"]["seconds"] >= 0.002
    read = {name: harness.load_by_path("metrics", name).read(run)
            for name in READERS}
    assert read["serve_queue_wait_ms"] == pytest.approx(0.140)
    assert read["serve_coalesce_ms"] >= 2.0
    assert read["serve_launch_ms"] >= 1.0
    assert read["serve_readback_ms"] is None  # no such span in the session
    assert 0 < read["serve_dispatcher_busy_pct.serve_tput"] < 100
    # the old reduction still reads the same file (no device plane: None)
    assert xplane.reduce(xplane.load(xplane.find_xplane(win.dir))) is None
