#!/usr/bin/env python3
"""Spreads of a cell's two sets of runs, as the bounds are set from them.

    python3 benchmarks/tools/spread.py chiprun_out/<tag>.jsonl [runs per set]

Reads what ``tools/batch.py`` wrote: the untraced runs in order, split
into two sets.  Per end-to-end metric: each set's median and spread (first
to third quartile of ``statistics.quantiles(n=4)`` over the median), the
wider spread, five times it, and the second median against the first.
"""

import json
import statistics
import sys


def spread(values: list) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    path = sys.argv[1]
    per_set = int(sys.argv[2]) if len(sys.argv) > 2 else 6
    runs = [json.loads(line) for line in open(path)]
    plain = [r["result"] for r in runs
             if isinstance(r["result"], dict) and "--trace 0" in r["args"]]
    wrong = [r["args"] for r in runs if not (
        isinstance(r["result"], dict) and r["result"].get("correct"))]
    sets = [plain[:per_set], plain[per_set:2 * per_set]]
    for name in plain[0]["metrics"]:
        cols = [[r["metrics"][name]["value"] for r in s] for s in sets]
        meds = [statistics.median(c) for c in cols if c]
        sp = [spread(c) for c in cols if len(c) >= 2]
        print(json.dumps({
            "metric": name, "medians": meds, "spreads": sp,
            "five_times_widest": 5 * max(sp),
            "second_vs_first": meds[-1] / meds[0] - 1,
            "values": cols}))
    print(json.dumps({"runs": len(runs), "not_correct": wrong}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
