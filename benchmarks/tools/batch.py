#!/usr/bin/env python3
"""Run several cells one after another (one process each: a chip belongs
to one process at a time) and keep every result line.

    python3 benchmarks/tools/batch.py <tag> "<run.py args>" ["<args>" ...]

Writes ``chiprun_out/<tag>.jsonl``: per run its arguments, exit code,
wall seconds, the result line and the end of stderr.  Used for the
readings PERF.md reports (the dozen seeds, the controls and faults, the
rate sweep, the two sets of six) -- the driver of a check runs the
benchmark's command itself.
"""

import json
import os
import shlex
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    tag, runs = sys.argv[1], sys.argv[2:]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    bad = 0
    with open(os.path.join(out_dir, tag + ".jsonl"), "a") as out:
        for args in runs:
            argv = shlex.split(args)
            cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py")]
            if argv and argv[0].endswith(".py"):
                cmd = [sys.executable, os.path.join(ROOT, argv.pop(0))]
            t0 = time.time()
            p = subprocess.run(cmd + argv, cwd=ROOT,
                               capture_output=True, text=True)
            wall = time.time() - t0
            lines = p.stdout.strip().splitlines()
            try:
                result = json.loads(lines[-1]) if lines else None
            except ValueError:
                result = lines[-1]
            rec = {"args": args, "rc": p.returncode, "wall_s": wall,
                   "result": result, "stderr_tail": p.stderr[-3000:]}
            out.write(json.dumps(rec) + "\n")
            out.flush()
            ok = isinstance(result, dict) and result.get("correct")
            bad += p.returncode != 0
            brief = {}
            if isinstance(result, dict):
                brief = {k: v.get("value") for k, v in
                         result.get("metrics", {}).items()}
                brief["checks"] = {k: v["value"] for k, v in
                                   result.get("checks", {}).items()}
                brief["mem"] = result.get("device", {}).get(
                    "memory_peak_bytes")
            print(f"[{tag}] rc={p.returncode} correct={ok} "
                  f"wall={wall:.1f}s {args} :: {json.dumps(brief)}",
                  flush=True)
            if p.returncode != 0:
                print(p.stderr[-1500:], flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
