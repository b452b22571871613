"""The comparison that decides ``correct``: every number compared is
printed beside its limit, and a run is correct when none is over."""

from __future__ import annotations

import math
import statistics
import sys


class Checks:
    def __init__(self):
        self.rows = []  # (name, value, limit)

    def add(self, name: str, value: float, limit: float) -> None:
        self.rows.append((name, float(value), float(limit)))

    def add_limited(self, name: str, value: float, limits: dict) -> None:
        """Compare ``value`` under the limit the cell's own file
        (``limits/<workload>.json``) gives ``name``.  A number the file
        does not name is an error, not a number left out: a cell leaves
        one uncompared by stating ``"limit": null`` with its readings."""
        if name not in limits:
            raise KeyError(f"the cell's limits file names no {name!r}")
        if limits[name]["limit"] is not None:
            self.add(name, value, limits[name]["limit"])

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(
            math.isfinite(v) and v <= lim for _, v, lim in self.rows)

    def as_dict(self) -> dict:
        return {n: {"value": v, "limit": lim} for n, v, lim in self.rows}

    def print_stderr(self) -> None:
        for n, v, lim in self.rows:
            mark = "ok" if math.isfinite(v) and v <= lim else "OVER"
            print(f"check {n} = {v:.6g} limit {lim:.6g} {mark}",
                  file=sys.stderr)
        print(f"correct = {self.correct}", file=sys.stderr, flush=True)


def worst_leaf_gap(prog: dict, ref: dict, skip=()) -> float:
    """Largest gap between the program's and the reference's norm of a
    leaf (NOT the norm of their difference), against the reference's norm
    of that leaf or of the median leaf, whichever is larger."""
    med = statistics.median(ref.values())
    worst = 0.0
    for leaf, r in ref.items():
        if leaf in skip:
            continue
        denom = max(r, med)
        gap = abs(prog[leaf] - r) / denom if denom > 0 else math.inf
        worst = max(worst, gap)
    return worst


def nought_leaves(ref_grad: dict) -> set:
    """Leaves whose gradient is nought to rounding in the reference: under
    a thousandth of the median leaf's.  Under Adagrad they move by
    round-off alone and are left out of the change comparison."""
    med = statistics.median(ref_grad.values())
    return {k for k, g in ref_grad.items() if g < 1e-3 * med}
