"""What every driver shares: the cell's files found by name, the look
for a chip, the compile cache, the profiler window, the result line."""

from __future__ import annotations

import configparser
import importlib.util
import json
import os
import shutil
import sys

from fmbench import peaks as peaks_lib
from fmbench import xplane

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
WORK_ROOT = os.path.join(BENCH_DIR, ".work")
# jax's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR is
# set, there; else one fixed path inside the checkout (the path is part
# of the cache's key, so it must never move).
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
# Readers and drivers are loaded by path; what they share (metrics/_trace,
# the fmbench package) is importable from these two directories.
for _p in (BENCH_DIR, os.path.join(BENCH_DIR, "metrics")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    """BENCHMARK.json's entry for ``workload`` with its configuration and
    traffic files, all found by name."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    traffic_path = os.path.join(BENCH_DIR, "traffic",
                                cell["traffic"] + ".json")
    return {
        "bench": bench,
        "cell": cell,
        "config_entry": config,
        "config": load_json(os.path.join(ROOT, config["file"])),
        "traffic": load_json(traffic_path),
        # the limits of the numbers that decide ``correct``, set from this
        # cell's own readings (PERF.md section 2)
        "limits": load_json(os.path.join(BENCH_DIR, "limits",
                                         workload + ".json")),
    }


def metrics_for(bench: dict, group: str, workload: str,
                reports: set | None = None) -> list:
    """The metrics of ``group`` this cell reports: those that list it
    under ``workloads``, and those with no such key whose end-to-end
    metric (for per-layer ones) the cell reports."""
    out = []
    for m in bench[group]:
        cells = m.get("workloads")
        if cells is not None:
            if workload in cells:
                out.append(m)
        elif reports is None or m.get("moves", m["name"]) in reports \
                or m["name"] == "setup_s":
            out.append(m)
    return out


def load_by_path(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` as a module (names may hold dots
    and dashes, so this is not an import statement)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + "".join(c if c.isalnum() else "_" for c in name),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def work_dir(workload: str) -> str:
    path = os.path.join(WORK_ROOT, workload)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def cache_dir(key: str) -> str:
    """A directory under the git-ignored work root that outlives a run:
    what is a pure function of ``key`` is made once per checkout."""
    path = os.path.join(WORK_ROOT, ".cache", key)
    os.makedirs(path, exist_ok=True)
    return path


def write_cfg(path: str, keys: dict) -> None:
    """The .cfg the program reads, from a configuration's flat keys."""
    cp = configparser.ConfigParser()
    cp["General"] = {k: (str(v).lower() if isinstance(v, bool) else str(v))
                     for k, v in keys.items()}
    with open(path, "w") as f:
        cp.write(f)


def fold_seed(seed: int) -> int:
    """--seed may exceed 32 signed bits; jax keys and cfg seeds take the
    folded value (numpy generators take the seed whole)."""
    return int(seed) % (2**31 - 1)


# ------------------------------------------------------------------ device


def look_for_chip(chips: int, rehearse: bool) -> dict:
    """What jax reports.  Without --rehearse anything but ``chips`` TPU
    devices ends the process with a code other than 0 and no result."""
    if rehearse:
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax

    devs = jax.devices()
    plat = devs[0].platform
    if not rehearse and (plat != "tpu" or len(devs) < chips):
        print(f"benchmark: jax found {len(devs)} {plat!r} device(s), the "
              f"cell needs {chips} TPU chip(s); refusing to measure "
              "(--rehearse runs the toy-size CPU rehearsal)",
              file=sys.stderr)
        raise SystemExit(2)
    return {"platform": plat, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_compile_cache() -> str:
    import jax

    want = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    os.makedirs(want, exist_ok=True)
    if jax.config.jax_compilation_cache_dir != want:
        jax.config.update("jax_compilation_cache_dir", want)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return want


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def free_device() -> None:
    import gc

    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


# ---------------------------------------------------------------- profiler


class TraceWindow:
    """The profiler around (part of) the measured window.  Python's own
    tracer is off: it would slow the host it is meant to watch."""

    def __init__(self, work: str, on: bool):
        self.dir = os.path.join(work, "trace")
        self.on = on
        self._span = None
        self.started = False

    def start(self) -> None:
        if not self.on or self.started:
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN)
        self._span.__enter__()
        self.started = True

    def stop(self) -> None:
        if not self.started:
            return
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.started = False

    def reduce(self) -> dict | None:
        if not self.on:
            return None
        return xplane.reduce(xplane.load(xplane.find_xplane(self.dir)))


# ------------------------------------------------------------------ result


def emit(*, cell: dict, device: dict, trace: bool, rehearse: bool,
         result: dict, labels: dict) -> int:
    """Print the checks (stderr) and the result line (stdout, last).
    Returns the process's exit code."""
    bench, workload = cell["bench"], cell["cell"]["name"]
    checks = result["checks"]
    e2e = {m["name"] for m in metrics_for(bench, "end_to_end", workload)}
    line = {
        "correct": checks.correct,
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
    }
    metrics = {}
    dev = dict(device)
    if rehearse:
        # A rehearsal carries no device metric and says what it is.
        line["rehearsal"] = True
    else:
        dev["memory_peak_bytes"] = int(result["memory_peak_bytes"])
        if trace:
            reduced = result.get("trace")
            if reduced is None:
                raise RuntimeError("traced run without a device trace")
            dev["busy_s"] = reduced["busy_s"]
            dev["window_s"] = reduced["window_s"]
            run = {"trace": reduced, "counters": result["counters"],
                   "peaks": peaks_lib.peaks_for(device["kind"]),
                   "workload": workload}
            for m in metrics_for(bench, "per_layer", workload, e2e):
                value = load_by_path("metrics", m["name"]).read(run)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            line["breakdown"] = {"device_ops": reduced["device_ops"],
                                 "idle_gaps": reduced["idle_gaps"]}
        else:
            for m in metrics_for(bench, "end_to_end", workload):
                if m["name"] not in result["e2e"]:
                    raise SystemExit(
                        f"the driver of {workload!r} does not measure "
                        f"{m['name']!r}; it measures {sorted(result['e2e'])}")
                metrics[m["name"]] = {"value": result["e2e"][m["name"]],
                                      "unit": m["unit"]}
    line["metrics"] = metrics
    line["device"] = dev
    line.update(labels)
    line["info"] = result.get("info", {})
    line["checks"] = checks.as_dict()  # each number beside its limit, last
    sys.stderr.flush()
    checks.print_stderr()
    print(json.dumps(line), flush=True)
    return 0
