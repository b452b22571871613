"""The scope readers against a hand-built trace: ``metrics/_scopes.py``
and the nine readers over it -- self time, not the time of a container
and its children twice; another program's operations and those outside
the window left out; two chips averaged; what is mixed, moved and
unnamed; the sum of the parts equal to the whole; a raise when the table
and the trace have nothing in common, or when the program could not make
the table -- and the rehearsal of a train cell through
``run.py``, whose ``record: compile`` carries the table and whose CPU
trace (no device plane) reads None in every reader."""

import importlib.util
import os

import pytest

from fmbench import harness

import _scopes  # noqa: E402 - benchmarks/metrics, on the path with harness
MS = 1e6  # ns
PREFIX = "jit_scan_health_step"

READERS = ("train_gather_ms", "train_interaction_ms", "train_apply_prep_ms",
           "train_apply_write_ms", "train_exchange_work_ms",
           "train_health_ms", "train_scope_unnamed_pct",
           "train_scope_mixed_pct", "train_scope_moved_pct")
TABLE = {
    "scopes": {"tffm.row_gather": ["fusion.1"],
               "tffm.fm_interaction_bwd": ["fusion.7"],
               "tffm.apply_write": ["tffm.apply_write.3"],
               "tffm.exchange_merge": ["fusion.9"],
               "tffm.health": ["fusion.3"],
               "tffm.loss": ["fusion.4"]},
    "moved": {"tffm.row_gather": ["copy.6"]},
    "mixed": {"fusion.2": ["tffm.fm_interaction_fwd", "tffm.row_gather"]},
    "containers": ["conditional.2"],
}


def _op(name, start_ms, dur_ms):
    # a TPU trace names an operation by its instruction's text
    return (f"%{name} = f32[8]{{0}} fusion(%p), kind=kLoop", start_ms * MS,
            dur_ms * MS)


def _step(t, gather_ms):
    """One run of the step from ``t``: the gather's layout copy and the
    gather, a mixed fusion, the interaction's backward, a conditional
    that holds the writer and the merge, an unnamed copy, the health and
    the loss."""
    ops, c = [], t
    for name, d in (("copy.6", 1), ("fusion.1", gather_ms), ("fusion.2", 6),
                    ("fusion.7", 2)):
        ops.append(_op(name, c, d))
        c += d
    ops += [_op("conditional.2", c, 10), _op("tffm.apply_write.3", c + 1, 4),
            _op("fusion.9", c + 6, 3)]
    c += 10
    for name, d in (("copy.5", 1), ("fusion.3", 2), ("fusion.4", 1)):
        ops.append(_op(name, c, d))
        c += d
    return ops, c - t


def _chip(n, gather_ms):
    ops, modules = [], []
    for t in (10, 60):  # two runs of the step inside the window
        step, d = _step(t, gather_ms)
        ops += step
        modules.append((f"{PREFIX}(7)", t * MS, d * MS))
    # another program's run, with an operation of the same name
    modules.append(("jit_unpack(3)", 100 * MS, 5 * MS))
    ops.append(_op("fusion.1", 101, 3))
    # a run of the step after the window closed
    step, d = _step(130, gather_ms)
    modules.append((f"{PREFIX}(7)", 130 * MS, d * MS))
    ops += step
    return {"name": f"/device:TPU:{n}", "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops}]}


def _planes():
    return [_chip(0, 4), _chip(1, 6),
            {"name": "/host:CPU", "lines": [
                {"name": "python", "events": [
                    ("bench:window", 0.0, 120 * MS)]}]}]


def test_self_time_by_scope_averaged_over_chips():
    r = _scopes.reduce(_planes(), TABLE, PREFIX)
    assert r["runs"] == 2  # per chip; jit_unpack and the late run left out
    per_run = {k: 1e3 * v / r["runs"] for k, v in r["scopes"].items()}
    assert per_run == pytest.approx({
        "tffm.row_gather": 6.0,  # 4 and 6 ms on the two chips + the copy
        "tffm.fm_interaction_bwd": 2.0, "tffm.apply_write": 4.0,
        "tffm.exchange_merge": 3.0, "tffm.health": 2.0, "tffm.loss": 1.0})
    # the conditional's own time, less the writer and the merge it holds
    assert 1e3 * r["containers_s"] / r["runs"] == pytest.approx(3.0)
    assert 1e3 * r["mixed_s"] / r["runs"] == pytest.approx(6.0)
    assert 1e3 * r["unnamed_s"] / r["runs"] == pytest.approx(1.0)
    # the copy the table gives the gather by inference, also apart
    assert {k: 1e3 * v / r["runs"] for k, v in r["moved"].items()} == \
        pytest.approx({"tffm.row_gather": 1.0})
    assert 1e3 * r["moved_s"] / r["runs"] == pytest.approx(1.0)
    # the parts add up to the step's summed self time, which is the
    # step's busy time: nothing counted twice
    parts = (sum(r["scopes"].values()) + r["containers_s"] + r["mixed_s"]
             + r["unnamed_s"])
    assert parts == pytest.approx(r["total_s"])
    assert 1e3 * r["total_s"] / r["runs"] == pytest.approx(28.0)
    assert r["ops"]["conditional.2"] == ["containers", pytest.approx(6e-3)]


def test_nested_events_give_self_time():
    events = [("a", 0.0, 10.0), ("b", 1.0, 4.0), ("c", 2.0, 1.0),
              ("d", 6.0, 2.0), ("e", 12.0, 1.0)]
    got = {name: own for name, _, own in _scopes._self_times(events)}
    assert got == {"a": 4.0, "b": 3.0, "c": 1.0, "d": 2.0, "e": 1.0}


def _run(tmp_path, monkeypatch, table=TABLE, planes=None):
    path = tmp_path / "run.xplane.pb"
    path.write_bytes(b"")
    monkeypatch.setattr(_scopes.xplane, "load",
                        lambda p: planes if planes is not None else _planes())
    monkeypatch.setattr(_scopes, "_cache", {})
    older = {"record": "compile", "k": 4, "scopes": {"tffm.loss": ["x"]}}
    tail = {"record": "compile", "k": 1, "scopes": {"tffm.loss": ["y"]}}
    records = [{"record": "train"}, older, {"record": "compile", "k": 4,
                                            **table}, tail]
    return {"trace": {"window_s": 0.12}, "trace_file": str(path),
            "records": records, "workload": "w",
            "counters": {"step_program_prefix": PREFIX}}


def test_readers_over_hand_built_trace(tmp_path, monkeypatch):
    run = _run(tmp_path, monkeypatch)
    # the newest table of the largest k, not the epoch tail's
    assert _scopes.table(run["records"]) == TABLE
    got = {name: harness.load_by_path("metrics", name).read(run)
           for name in READERS}
    assert got == pytest.approx({
        "train_gather_ms": 6.0, "train_interaction_ms": 2.0,
        "train_apply_prep_ms": None,  # the table names no such scope
        "train_apply_write_ms": 4.0, "train_exchange_work_ms": 3.0,
        "train_health_ms": 2.0,
        "train_scope_unnamed_pct": 100.0 * 1 / 28,
        "train_scope_mixed_pct": 100.0 * 6 / 28,
        "train_scope_moved_pct": 100.0 * 1 / 28})


def test_a_table_that_joins_nothing_raises(tmp_path, monkeypatch):
    renamed = {"scopes": {"tffm.loss": ["fusion.404"]}, "mixed": {},
               "containers": ["while.404"]}
    run = _run(tmp_path, monkeypatch, table=renamed)
    with pytest.raises(RuntimeError, match="naming broke"):
        harness.load_by_path("metrics", "train_gather_ms").read(run)


def test_a_table_the_program_could_not_make_raises(tmp_path, monkeypatch):
    """The newest compile of the largest k says why it has no table: a
    traced run raises rather than read a step with no names; an
    untraced one reads None, as every untraced run does."""
    run = _run(tmp_path, monkeypatch)
    run["records"].append({"record": "compile", "k": 4,
                           "scope_table_error": "ValueError: broken"})
    with pytest.raises(RuntimeError, match="ValueError: broken"):
        harness.load_by_path("metrics", "train_health_ms").read(run)
    untraced = {**run, "trace": None, "trace_file": None}
    for name in READERS:
        assert harness.load_by_path("metrics", name).read(untraced) is None


def test_no_table_no_trace_no_device_read_nothing(tmp_path, monkeypatch):
    run = _run(tmp_path, monkeypatch)
    parent = {**run, "records": [r for r in run["records"]
                                 if "scopes" not in r]}
    untraced = {**run, "trace": None, "trace_file": None}
    gone = {**run, "trace_file": str(tmp_path / "gone.xplane.pb")}
    for r in (parent, untraced, gone):
        for name in READERS:
            assert harness.load_by_path("metrics", name).read(r) is None
    # a CPU trace: a host plane and no device plane
    cpu = _run(tmp_path, monkeypatch, planes=_planes()[2:])
    for name in READERS:
        assert harness.load_by_path("metrics", name).read(cpu) is None


def test_the_rehearsal_records_the_table(tmp_path, monkeypatch):
    """``run.py --rehearse --trace 1`` of a train cell in this process:
    the program's ``record: compile`` carries the scope table, and on the
    CPU's trace, which holds no device plane, every reader reads None."""
    monkeypatch.setattr(harness, "WORK_ROOT", str(tmp_path / "work"))
    seen = {}

    def as_on_a_chip(*, cell, device, trace, rehearse, result, labels):
        run = {"trace": result["trace"], "trace_file": result["trace_file"],
               "records": result["records"], "counters": result["counters"],
               "telemetry": result["telemetry"], "workload": labels}
        seen["table"] = _scopes.table(result["records"])
        seen["read"] = {name: harness.load_by_path("metrics", name).read(run)
                        for name in READERS}
        return 0

    monkeypatch.setattr(harness, "emit", as_on_a_chip)
    spec = importlib.util.spec_from_file_location(
        "bench_run_py", os.path.join(harness.BENCH_DIR, "run.py"))
    run_py = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run_py)
    assert run_py.main(["--workload", "criteo1tb-train-shard",
                        "--seed", str(2**31 + 38), "--seconds", "1",
                        "--trace", "1", "--rehearse"]) == 0
    tab = seen["table"]
    assert tab is not None
    assert {"tffm.row_gather", "tffm.apply_write",
            "tffm.health"} <= set(tab["scopes"]).union(*tab["mixed"].values())
    assert seen["read"] == {name: None for name in READERS}
