#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once: load, warm up, measure, check.

    python3 benchmarks/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

This file only looks names up: the cell's configuration
(``configs/<config>.json``, which names its plain reference under
``reference/``), its traffic mix (``traffic/<traffic>.json``, which names
the driver under ``drivers/``), the limits that decide ``correct``
(``limits/<workload>.json``), and one reader per per-layer metric
(``metrics/<metric>.py``).  A later PR adds a cell by adding such
files and an entry in BENCHMARK.json, never by editing one that is here.

Without a TPU (or in a directory that lacks the program) the process
ends with a code other than 0 and prints no result.  ``--rehearse`` runs
the same path at toy size on the CPU to debug the harness; its last line
says so and carries no device metric.  ``--control``, ``--fault``,
``--rate`` and ``--via-checkpoint`` are for the readings PERF.md reports
(lower precision, a planted fault, the rate sweep, a served model
restored by the program's own ``load_model``); the driver of a check
never passes them and every line they produce is labelled.
"""

import time

T0 = time.time()  # set-up counts from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", default="",
                    help="run the cell's lower-precision control")
    ap.add_argument("--fault", default="",
                    help="plant a fault under the timed path")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="override an open-loop mix's rate (sweeps)")
    ap.add_argument("--via-checkpoint", action="store_true",
                    help="serve cells: write the seed's table through the "
                         "program's checkpoint writer and let its own "
                         "load_model restore it")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "fast_tffm_tpu")):
        print("benchmark: the program (fast_tffm_tpu/) is not in this "
              "checkout; nothing to measure", file=sys.stderr)
        return 3
    sys.path.insert(0, ROOT)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from fmbench import harness

    cell = harness.load_cell(args.workload)
    device = harness.look_for_chip(cell["cell"]["chips"], args.rehearse)
    if not args.rehearse:  # a rehearsal shares no cache with a chip run
        harness.enable_compile_cache()
    driver = harness.load_by_path("drivers", cell["traffic"]["driver"])
    work = harness.work_dir(args.workload)
    try:
        result = driver.run(
            cell=cell, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace) and not args.rehearse,
            rehearse=args.rehearse, control=args.control,
            fault=args.fault, rate=args.rate,
            via_checkpoint=args.via_checkpoint, work=work, t0=T0,
        )
    finally:
        import shutil

        shutil.rmtree(work, ignore_errors=True)
    labels = {k: v for k, v in (("control", args.control),
                                ("fault", args.fault),
                                ("rate_override", args.rate),
                                ("via_checkpoint", args.via_checkpoint))
              if v}
    return harness.emit(cell=cell, device=device, trace=bool(args.trace),
                        rehearse=args.rehearse, result=result,
                        labels=labels)


if __name__ == "__main__":
    sys.exit(main())
