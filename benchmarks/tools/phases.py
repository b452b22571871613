#!/usr/bin/env python3
"""One run of a serve cell with the program's request phases kept: what
PERF.md section 5 reports per phase, traced against untraced.

    python3 benchmarks/tools/phases.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Runs the cell as ``run.py`` does, but reads while the run's work
directory still stands (``run.py`` removes it before the readers are
called, so the span readers of ``metrics/_spans.py`` find no trace
there: PERF.md section 7).  Prints one JSON line: the end-to-end numbers,
the ``serve.*`` timers of the program's final record (whole run, warm-up
requests included), and from a traced run the idle gaps, every phase of
``_spans.reduce`` and the six span readers.
"""

import time

T0 = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

SPAN_READERS = ("serve_queue_wait_ms", "serve_coalesce_ms",
                "serve_dispatcher_busy_pct.serve_lat",
                "serve_launch_ms", "serve_readback_ms")


def final_timers(work: str) -> dict:
    """``serve.*`` timers of the last record the program wrote."""
    path = os.path.join(work, "serve_metrics.jsonl")
    if not os.path.isfile(path):
        return {}
    with open(path) as f:
        last = json.loads(f.readlines()[-1])
    timers = (last.get("stages") or {}).get("timers") or {}
    return {k: {"count": v["count"], "mean_ms": v.get("mean_ms"),
                "total_s": v["total_s"]}
            for k, v in sorted(timers.items())
            if k.startswith("serve.") and v.get("count")}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from fmbench import harness

    cell = harness.load_cell(args.workload)
    harness.look_for_chip(cell["cell"]["chips"], args.rehearse)
    if not args.rehearse:
        harness.enable_compile_cache()
    driver = harness.load_by_path("drivers", cell["traffic"]["driver"])
    work = harness.work_dir(args.workload)
    line = {"workload": args.workload, "seed": args.seed,
            "trace": args.trace}
    try:
        result = driver.run(
            cell=cell, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), rehearse=args.rehearse, control="",
            fault="", rate=0.0, via_checkpoint=False, work=work, t0=T0)
        line.update({
            "correct": result["checks"].correct,
            "failed": int(result["failed"]), "e2e": result["e2e"],
            "latency_ms": result["info"].get("latency_ms"),
            "dispatches": result["info"].get("dispatches"),
            "timers": final_timers(work),
        })
        reduced = result.get("trace")
        if reduced:
            line["busy_s"] = reduced["busy_s"]
            line["window_s"] = reduced["window_s"]
            line["idle_gaps"] = reduced["idle_gaps"]
        if args.trace:
            import _spans

            # (a CPU rehearsal's trace has no device plane to reduce,
            # but its host spans are there for the readers)
            run = {"trace": reduced or {"rehearsal": True},
                   "counters": result["counters"],
                   "workload": args.workload}
            spans = _spans.for_run(run)
            if spans:
                line["dispatcher_s"] = spans["dispatcher"]
                line["phases"] = {
                    k: {"count": v["count"], "total_s": v["seconds"],
                        "mean_ms": 1e3 * v["seconds"] / v["count"],
                        "longest_ms": 1e3 * v["longest_s"],
                        "stats": v["stats"]}
                    for k, v in sorted(spans["phases"].items())}
            line["readers"] = {
                name: harness.load_by_path("metrics", name).read(run)
                for name in SPAN_READERS}
            c = result["counters"]
            if c.get("serve_dispatch_count"):
                line["readers"]["serve_dispatch_ms"] = (
                    1e3 * c["serve_dispatch_s"] / c["serve_dispatch_count"])
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps(line), flush=True)
    return 0 if line.get("correct") else 1


if __name__ == "__main__":
    sys.exit(main())
